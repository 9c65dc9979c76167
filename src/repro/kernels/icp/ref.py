"""Pure-jnp oracle for ICP correspondence + the rigid-alignment math."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def correspondences_ref(src: jax.Array, tgt: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Brute-force nearest neighbors. src (M,3), tgt (N,3) -> (idx, d2)."""
    d2 = jnp.sum(
        (src[:, None, :].astype(jnp.float32) - tgt[None, :, :].astype(jnp.float32)) ** 2,
        axis=-1,
    )
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def rigid_transform_ref(
    src: jax.Array, matched: jax.Array, weights: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Least-squares rigid transform (Horn/Umeyama): returns (R (3,3), t (3,))
    minimizing ||R src + t - matched||^2."""
    src = src.astype(jnp.float32)
    matched = matched.astype(jnp.float32)
    if weights is None:
        weights = jnp.ones((src.shape[0],), jnp.float32)
    w = weights / jnp.maximum(weights.sum(), 1e-9)
    cs = jnp.sum(src * w[:, None], axis=0)
    cm = jnp.sum(matched * w[:, None], axis=0)
    # f32 products throughout: the chip's default matmul pass is bf16
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    H = mm((src - cs).T, (matched - cm) * w[:, None])
    U, _, Vt = jnp.linalg.svd(H)
    det = jnp.linalg.det(mm(Vt.T, U.T))
    S = jnp.diag(jnp.array([1.0, 1.0, 1.0]) * jnp.where(
        jnp.arange(3) == 2, det, 1.0
    ))
    R = mm(mm(Vt.T, S), U.T)
    t = cm - mm(R, cs)
    return R, t


def icp_update(
    src: jax.Array, tgt: jax.Array, idx: jax.Array, d2: jax.Array,
    trim_quantile: float = 0.9,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Given correspondences, trim outliers and solve the rigid update.
    Returns (R, t, mean_sq_err) over the kept pairs."""
    thresh = jnp.quantile(d2, trim_quantile)
    w = (d2 <= thresh).astype(jnp.float32)
    R, t = rigid_transform_ref(src, tgt[idx], w)
    err = jnp.sum(d2 * w) / jnp.maximum(w.sum(), 1.0)
    return R, t, err


@jax.jit
def icp_step_ref(src: jax.Array, tgt: jax.Array, trim_quantile: float = 0.9):
    """One ICP iteration with brute-force correspondences (the kernel-free
    path and the oracle for ``ops.icp_step``)."""
    idx, d2 = correspondences_ref(src, tgt)
    return icp_update(src, tgt, idx, d2, trim_quantile)
