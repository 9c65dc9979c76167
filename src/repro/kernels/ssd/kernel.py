"""Mamba-2 SSD chunk-scan Pallas kernel.

Grid = (batch, heads, chunks) with the chunk dimension sequential; the
(P, N) inter-chunk state lives in VMEM scratch and is carried across the
chunk grid steps — the whole recurrence never leaves VMEM.  Per chunk the
kernel computes, entirely in registers/VMEM:

  intra-chunk:  L = exp(segsum(dA));  Y_diag = (C B^T ⊙ L) @ (x·dt)
  state input:  Y_off  = (C @ state^T) ⊙ exp(cumsum dA)
  state update: state' = state·exp(Σ dA) + (x·dt)^T @ (B ⊙ decay_tail)

B/C group tensors are shared across the heads of a group via the BlockSpec
index_map (h -> h * G // H), as a GQA kernel shares a KV head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret


def _ssd_kernel(
    xdt_ref,  # (1, 1, 1, Q, P)
    dA_ref,  # (1, 1, 1, Q)
    B_ref,  # (1, 1, 1, Q, N)
    C_ref,  # (1, 1, 1, Q, N)
    y_ref,  # (1, 1, 1, Q, P) out
    st_ref,  # (1, 1, P, N) out (final state)
    state_scr,  # (P, N) fp32 scratch
    *,
    nc: int,
    Q: int,
):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    dA = dA_ref[0, 0, 0].astype(jnp.float32)  # (Q,)
    Bm = B_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Cm = C_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)

    cs = jnp.cumsum(dA)  # (Q,)
    seg = cs[:, None] - cs[None, :]  # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (Q, Q), 1
    )
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    y_diag = jax.lax.dot((scores * L), xdt, preferred_element_type=jnp.float32)

    state = state_scr[...]
    y_off = jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cs)[:, None]  # (Q, P)

    decay_tail = jnp.exp(cs[-1] - cs)  # (Q,)
    new_state = state * jnp.exp(cs[-1]) + jax.lax.dot_general(
        xdt, Bm * decay_tail[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    state_scr[...] = new_state

    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _final():
        st_ref[0, 0] = new_state.astype(st_ref.dtype)


def ssd_chunk_scan_fwd(
    xdt: jax.Array,  # (B, H, NC, Q, P) — x pre-multiplied by dt
    dA: jax.Array,  # (B, H, NC, Q)
    Bm: jax.Array,  # (B, G, NC, Q, N)
    Cm: jax.Array,  # (B, G, NC, Q, N)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    B_, H, NC, Q, P = xdt.shape
    G, N = Bm.shape[1], Bm.shape[4]
    assert H % G == 0

    kernel = functools.partial(_ssd_kernel, nc=NC, Q=Q)
    y, st = pl.pallas_call(
        kernel,
        grid=(B_, H, NC),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h * G // H, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h * G // H, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_, H, NC, Q, P), xdt.dtype),
            jax.ShapeDtypeStruct((B_, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, dA, Bm, Cm)
    return y, st
