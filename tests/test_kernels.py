"""Per-kernel shape/dtype sweeps against the ref.py oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d.ops import conv2d
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.flash_attention.ops import (
    block_size,
    flash_attention,
    flash_attention_reference,
)
from repro.kernels.icp.ops import icp_align, icp_correspondences
from repro.kernels.icp.ref import correspondences_ref, rigid_transform_ref
from repro.kernels.ssd.ops import ssd_chunk_scan
from repro.kernels.ssd.ref import ssd_sequential_ref
from repro.models.attention import sdpa


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, S, Hq, Hkv, D, causal, dtype
    (2, 128, 4, 2, 64, True, jnp.float32),
    (1, 256, 8, 8, 32, True, jnp.float32),
    (2, 128, 4, 1, 64, False, jnp.float32),
    (1, 384, 6, 2, 128, True, jnp.float32),
    (1, 256, 2, 2, 64, True, jnp.bfloat16),
    (2, 512, 4, 4, 64, False, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,Hq,Hkv,D,causal,dtype", FLASH_CASES)
def test_flash_attention_matches_ref(B, S, Hq, Hkv, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal)
    ref = flash_attention_reference(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_block_sizes():
    """Each length tiles with the largest of 512, 256 and 128 that divides it."""
    for S, block in [(384, 128), (256, 256), (1024, 512)]:
        assert block_size(S) == block
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, S, 2, 64))
        k = jax.random.normal(ks[1], (1, S, 2, 64))
        v = jax.random.normal(ks[2], (1, S, 2, 64))
        ref = flash_attention_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_kernel_is_reused_across_traces():
    """The kernel a first trace builds serves later traces of the shape:
    its cached mask arrays are constants, not the first trace's tracers."""
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 1, 64))
    first = jax.jit(lambda q, k: flash_attention(q, k, k))(q, k)
    again = jax.jit(lambda q, k: 2.0 * flash_attention(q, k, k))(q, k)
    np.testing.assert_allclose(np.asarray(again), 2.0 * np.asarray(first), rtol=1e-6)


@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("G", [1, 7])
def test_flash_attention_grads_match_sdpa(G, D, causal, S):
    """Output and q/k/v gradients of the flash path against the einsum core."""
    B, Hkv = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, S, Hkv * G, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    ct = jax.random.normal(ks[3], q.shape)  # a cotangent that weighs every output
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    def einsum(q, k, v):
        return sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=causal)

    (o1, vjp1), (o2, vjp2) = jax.vjp(flash, q, k, v), jax.vjp(einsum, q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=2e-5)
    for name, g1, g2 in zip("qkv", vjp1(ct), vjp2(ct)):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

SSD_CASES = [
    # B, S, H, P, G, N, Q
    (2, 128, 4, 16, 1, 32, 32),
    (1, 256, 8, 32, 2, 16, 64),
    (2, 64, 2, 8, 1, 8, 64),
    (1, 128, 6, 16, 3, 8, 32),
]


@pytest.mark.parametrize("B,S,H,P,G,N,Q", SSD_CASES)
def test_ssd_kernel_matches_sequential(B, S, H, P, G, N, Q):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=1.0))
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y, st = ssd_chunk_scan(x, dt, A, Bm, Cm, chunk_size=Q)
    yr, str_ = ssd_sequential_ref(x, dt, A, Bm, Cm)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-6
    assert float(jnp.max(jnp.abs(y - yr))) / scale < 1e-4
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), atol=1e-3, rtol=1e-3)


def test_ssd_chunk_size_invariance():
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    B, S, H, P, G, N = 1, 128, 2, 8, 1, 16
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    outs = [ssd_chunk_scan(x, dt, A, Bm, Cm, chunk_size=q)[0] for q in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# ICP
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,N", [(100, 200), (256, 256), (300, 500), (64, 1000)])
def test_icp_correspondences_match_bruteforce(M, N):
    src = jax.random.normal(jax.random.PRNGKey(0), (M, 3)) * 4
    tgt = jax.random.normal(jax.random.PRNGKey(1), (N, 3)) * 4
    idx, d2 = icp_correspondences(src, tgt)
    ridx, rd2 = correspondences_ref(src, tgt)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(rd2), atol=1e-3, rtol=1e-4)


def test_icp_recovers_rigid_transform():
    ang = 0.25
    R_true = jnp.array(
        [[np.cos(ang), -np.sin(ang), 0], [np.sin(ang), np.cos(ang), 0], [0, 0, 1]],
        jnp.float32,
    )
    t_true = jnp.array([0.4, -0.3, 0.2])
    cloud = jax.random.normal(jax.random.PRNGKey(2), (600, 3)) * 2
    R, t, err = icp_align(cloud, cloud @ R_true.T + t_true, iters=15)
    assert float(err) < 1e-5
    np.testing.assert_allclose(np.asarray(R), np.asarray(R_true), atol=1e-4)
    np.testing.assert_allclose(np.asarray(t), np.asarray(t_true), atol=1e-4)


def test_rigid_transform_weighted_ignores_outliers():
    src = jax.random.normal(jax.random.PRNGKey(3), (100, 3))
    t_true = jnp.array([1.0, 2.0, 3.0])
    matched = src + t_true
    matched = matched.at[0].set(jnp.array([100.0, 100.0, 100.0]))  # outlier
    w = jnp.ones((100,)).at[0].set(0.0)
    R, t = rigid_transform_ref(src, matched, w)
    np.testing.assert_allclose(np.asarray(t), np.asarray(t_true), atol=1e-4)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

CONV_CASES = [
    (2, 16, 16, 8, 3, 16, jnp.float32),
    (1, 32, 32, 3, 5, 32, jnp.float32),
    (2, 8, 8, 4, 1, 8, jnp.float32),
    (1, 16, 16, 8, 3, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("N,H,W,CI,K,CO,dtype", CONV_CASES)
def test_conv2d_matches_ref(N, H, W, CI, K, CO, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (N, H, W, CI), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (K, K, CI, CO), jnp.float32) * 0.1).astype(dtype)
    b = jax.random.normal(ks[2], (CO,), jnp.float32).astype(dtype)
    out = conv2d(x, w, b, block_co=min(16, CO))
    ref = conv2d_ref(x, w, b)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )
