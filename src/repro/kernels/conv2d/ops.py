"""Jitted SAME-conv wrapper around the Pallas direct-conv kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conv2d.kernel import conv2d_fwd


@functools.partial(jax.jit, static_argnames=("block_co", "interpret"))
def conv2d(
    x: jax.Array,  # (N, H, W, CI)
    w: jax.Array,  # (KH, KW, CI, CO)
    b: jax.Array | None = None,
    *,
    block_co: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """SAME convolution, stride 1 (odd kernel sizes)."""
    N, H, W, CI = x.shape
    KH, KW, CI2, CO = w.shape
    assert CI == CI2 and KH % 2 == 1 and KW % 2 == 1
    if b is None:
        b = jnp.zeros((CO,), x.dtype)
    ph, pw = KH // 2, KW // 2
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    return conv2d_fwd(
        xp, w, b, out_h=H, out_w=W, block_co=block_co, interpret=interpret
    )
