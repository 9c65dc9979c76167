"""Jitted wrapper: model-layout SSD via the Pallas chunk kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_chunk_scan_fwd


@functools.partial(jax.jit, static_argnames=("chunk_size", "interpret"))
def ssd_chunk_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) — softplus'd
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    *,
    chunk_size: int = 64,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    B_, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk_size, S)
    assert S % Q == 0
    NC = S // Q

    xdt = (x.astype(jnp.float32) * dt[..., None].astype(jnp.float32))
    dA = dt.astype(jnp.float32) * A  # (B,S,H)

    # layouts: xdt (B,S,H,P) -> (B,H,NC,Q,P); dA (B,S,H) -> (B,H,NC,Q)
    xdt_c = jnp.transpose(xdt.reshape(B_, NC, Q, H, P), (0, 3, 1, 2, 4))
    dA_c = jnp.transpose(dA.reshape(B_, NC, Q, H), (0, 3, 1, 2))
    B_c = jnp.transpose(Bm.reshape(B_, NC, Q, G, N), (0, 3, 1, 2, 4)).astype(jnp.float32)
    C_c = jnp.transpose(Cm.reshape(B_, NC, Q, G, N), (0, 3, 1, 2, 4)).astype(jnp.float32)

    y, st = ssd_chunk_scan_fwd(xdt_c, dA_c, B_c, C_c, interpret=interpret)
    y = jnp.transpose(y, (0, 2, 3, 1, 4)).reshape(B_, S, H, P).astype(x.dtype)
    return y, st
