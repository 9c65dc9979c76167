"""Paged GQA decode attention (online softmax over block-table pages).

A window of query tokens per sequence attends over a KV cache stored as
fixed-size pages in a shared pool; the per-sequence page list (block
table) and the live length arrive as *scalar-prefetch* operands, so the
K/V BlockSpec index maps gather pages straight from HBM — the kernel never
sees a dense ``(B, max_len)`` cache and HBM traffic scales with live tokens.

Tiling: grid = (batch, pages); the page axis is innermost and sequential,
with running max / sum / output accumulator in VMEM scratch
(FlashAttention-2 decode schedule).  Each grid step fetches one whole page
``(page, Hkv, hd)`` — every KV head at once — so each page is read once
and every block's last two dims equal the array's (the TPU tiling rule);
GQA is native: kv head ``h`` serves its query group's rows.

The window tokens' K/V are separate operands merged analytically at the
final page step — mirroring ``attention.sdpa_decode_readonly``, the cache
stays read-only and is written once by the caller, outside the layer scan.

Pages past ``seq_len`` are skipped via ``pl.when`` (their block-table
entries point at the allocator's null page, so the prefetched index is
always in range); positions past ``seq_len`` inside the last live page
are masked positionally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, tpu_compiler_params

NEG_INF = -1e30


def _online_update(m_scr, l_scr, acc_scr, h, s, v):
    """One online-softmax step for kv head ``h``: scores ``s`` (R, n) over
    values ``v`` (n, hd) folded into the (R, 1) max/sum and (R, hd) acc."""
    m_prev = m_scr[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[h] = m_new


def _paged_decode_kernel(
    tables_ref,  # scalar prefetch: (B, n_pages) int32 page ids
    lens_ref,  # scalar prefetch: (B,) int32 cached tokens (window starts here)
    q_ref,  # (1, Hkv, R, hd) — R = Q*G rows per kv head, row r = j*G + g
    k_ref,  # (1, page, Hkv, hd) — page tables_ref[b, ip], every kv head
    v_ref,  # (1, page, Hkv, hd)
    kn_ref,  # (1, Q, Hkv, hd) window tokens' keys
    vn_ref,  # (1, Q, Hkv, hd)
    o_ref,  # (1, Hkv, R, hd)
    m_scr,  # (Hkv, R, 1) fp32 running max
    l_scr,  # (Hkv, R, 1) fp32 running sum
    acc_scr,  # (Hkv, R, hd) fp32 output accumulator
    *,
    scale: float,
    page_size: int,
    n_pages: int,
    group: int,
):
    """Window token ``j`` sits at position ``seq_len + j``, so every window
    row sees the whole cache (the mask ``pos < seq_len`` holds for all of
    them) and the finalize step merges the Q window keys under an
    intra-window causal mask (row ``j`` attends cols ``j' <= j``).  Q == 1
    is classic decode; Q > 1 serves speculative k-token verification
    (Q = 1 + drafts) and chunked prefill (Q = chunk) with one schedule."""
    b, ip = pl.program_id(0), pl.program_id(1)
    seq_len = lens_ref[b]
    n_kv = q_ref.shape[1]

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ip * page_size < seq_len)
    def _body():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)  # (R, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (page, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (R, page)
            pos = ip * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=1
            )
            _online_update(
                m_scr, l_scr, acc_scr, h, jnp.where(pos < seq_len, s, NEG_INF), v
            )

    @pl.when(ip == n_pages - 1)
    def _finalize():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)  # (R, hd)
            kn = kn_ref[0, :, h, :].astype(jnp.float32)  # (Q, hd)
            vn = vn_ref[0, :, h, :].astype(jnp.float32)
            sn = jax.lax.dot_general(
                q, kn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (R, Q)
            row_tok = (
                jax.lax.broadcasted_iota(jnp.int32, sn.shape, dimension=0) // group
            )
            col_tok = jax.lax.broadcasted_iota(jnp.int32, sn.shape, dimension=1)
            _online_update(
                m_scr, l_scr, acc_scr, h, jnp.where(col_tok <= row_tok, sn, NEG_INF),
                vn,
            )
            o_ref[0, h] = (acc_scr[h] / l_scr[h]).astype(o_ref.dtype)


def paged_decode_qtok_fwd(
    q: jax.Array,  # (B, Hkv, Q*G, hd) — window-major rows: r = j*G + g
    k_pages: jax.Array,  # (P, page, Hkv, hd) shared page pool (last page = null)
    v_pages: jax.Array,
    k_new: jax.Array,  # (B, Q, Hkv, hd) window tokens
    v_new: jax.Array,
    block_tables: jax.Array,  # (B, n_pages) int32, null-page-padded
    seq_lens: jax.Array,  # (B,) int32 cached tokens (window begins here)
    *,
    group: int,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    B, Hkv, R, hd = q.shape
    Q = k_new.shape[1]
    assert R == Q * group
    page_size = k_pages.shape[1]
    n_pages = block_tables.shape[1]

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / (hd ** 0.5),
        page_size=page_size,
        n_pages=n_pages,
        group=group,
    )
    page_spec = pl.BlockSpec(
        (1, page_size, Hkv, hd), lambda b, ip, tr, lr: (tr[b, ip], 0, 0, 0)
    )
    row_spec = pl.BlockSpec((1, Hkv, R, hd), lambda b, ip, tr, lr: (b, 0, 0, 0))
    win_spec = pl.BlockSpec((1, Q, Hkv, hd), lambda b, ip, tr, lr: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[row_spec, page_spec, page_spec, win_spec, win_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, hd), q.dtype),
        interpret=interpret,
        compiler_params=tpu_compiler_params(("parallel", "arbitrary")),
    )(block_tables, seq_lens, q, k_pages, v_pages, k_new, v_new)


def paged_decode_fwd(
    q: jax.Array,  # (B, Hkv, G, hd) — query heads grouped under their kv head
    k_pages: jax.Array,  # (P, page, Hkv, hd) shared page pool (last page = null)
    v_pages: jax.Array,
    k_new: jax.Array,  # (B, Hkv, hd) current token
    v_new: jax.Array,
    block_tables: jax.Array,  # (B, n_pages) int32, null-page-padded
    seq_lens: jax.Array,  # (B,) int32 tokens already in cache (< q_pos)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token decode: the Q == 1 window."""
    return paged_decode_qtok_fwd(
        q, k_pages, v_pages, k_new[:, None], v_new[:, None], block_tables,
        seq_lens, group=q.shape[2], interpret=interpret,
    )
