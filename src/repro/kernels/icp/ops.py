"""Jitted ICP: Pallas correspondence kernel + closed-form rigid update.

``icp_align`` is the full point-cloud-alignment primitive the map-generation
pipeline calls (paper: "the most expensive operation for the map generation
stage is the iterative closest point alignment ... accelerated 30x on GPU").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.icp.kernel import icp_correspondences_fwd
from repro.kernels.icp.ref import icp_update

COORD_PAD = 8  # pad xyz -> 8 lanes for the MXU distance matmul


def _pad_points(pts: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    n = pts.shape[0]
    m = ((n + multiple - 1) // multiple) * multiple
    padded = jnp.zeros((m, COORD_PAD), jnp.float32)
    padded = padded.at[:n, :3].set(pts.astype(jnp.float32))
    return padded, n


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def icp_correspondences(
    src: jax.Array,  # (M, 3)
    tgt: jax.Array,  # (N, 3)
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Nearest target index + squared distance for every source point."""
    M = src.shape[0]
    srcp, _ = _pad_points(src, block_m)
    tgtp, n_tgt = _pad_points(tgt, block_n)
    idx, d2 = icp_correspondences_fwd(
        srcp, tgtp, n_valid_tgt=n_tgt, block_m=block_m, block_n=block_n,
        interpret=interpret,
    )
    return idx[:M, 0], d2[:M, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def icp_step(
    src: jax.Array,  # (M, 3) current source cloud
    tgt: jax.Array,  # (N, 3)
    *,
    trim_quantile: float = 0.9,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One ICP iteration: correspond -> trim outliers -> closed-form (R, t).

    Returns (R, t, mean_sq_err)."""
    idx, d2 = icp_correspondences(src, tgt, interpret=interpret)
    return icp_update(src, tgt, idx, d2, trim_quantile)


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def icp_align(
    src: jax.Array,  # (M, 3)
    tgt: jax.Array,  # (N, 3)
    *,
    iters: int = 10,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full ICP: iterate correspond+solve. Returns (R, t, final mean_sq_err)
    with ``R src + t ~ tgt``."""

    def body(carry, _):
        R, t, _ = carry
        cur = src @ R.T + t
        dR, dt, err = icp_step(cur, tgt, interpret=interpret)
        return (dR @ R, dR @ t + dt, err), err

    init = (jnp.eye(3, dtype=jnp.float32), jnp.zeros((3,), jnp.float32), jnp.inf)
    (R, t, err), _ = jax.lax.scan(body, init, None, length=iters)
    return R, t, err
