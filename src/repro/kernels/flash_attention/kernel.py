"""Flash attention (online softmax) Pallas kernel, GQA-aware.

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks), kv innermost and
sequential.  Running row-max / row-sum / output accumulator live in VMEM
scratch and are rescaled per kv block (Dao et al., FlashAttention-2
schedule adapted to MXU tile shapes).  GQA never materializes repeated K/V:
the K/V BlockSpec index_map folds the query head onto its kv head
(``h // group``), so the same VMEM block serves the whole query group.

Causal masking is positional; fully-masked kv blocks are skipped via
``pl.when`` on the block index (upper-triangle blocks cost nothing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, tpu_compiler_params

NEG_INF = -1e30


def _attn_kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bk, d)
    v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, 1, bq, d)
    m_scr,  # (bq,) fp32
    l_scr,  # (bq,) fp32
    acc_scr,  # (bq, d) fp32
    *,
    scale: float,
    causal: bool,
    bq: int,
    bk: int,
    nk: int,
):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-masked (strictly upper-triangular) blocks
    run = (not causal) or (iq * bq + bq - 1 >= ik * bk)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, T, D)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    nq, nk = S // bq, T // bk
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk
    )
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            # running max / sum / output accumulator, persistent across the
            # sequential kv-block grid dimension
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        # batch / head / q-block axes are embarrassingly parallel; the kv
        # axis carries the online-softmax scratch and must stay sequential
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
