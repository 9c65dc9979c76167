"""Grouped-query attention: one implementation for train / prefill / decode.

The default ``attention_impl='flash'`` sends full-sequence self-attention
on one TPU through the flash kernel (``kernels.flash_attention``: forward
and backward, scores kept in VMEM) where :func:`attend` can see that it
applies, and every other call through the einsum core :func:`sdpa`
(GSPMD-friendly; non-divisible head counts are padded by the partitioner).
``'einsum'``, ``'blocked'`` and ``'hd_sharded'`` pick their core always.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import constrain, get_abstract_mesh
from repro.models import layers
from repro.models.params import ParamDef


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, n_kv, hd)
    v: jax.Array  # (B, S_max, n_kv, hd)


def attention_plan(cfg: ModelConfig, d_in: int | None = None, lora_rank: int = 0) -> dict:
    d = d_in or cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    plan = {
        "q": layers.linear_plan(d, nq * hd, ("embed", "heads"), bias=cfg.qkv_bias),
        "k": layers.linear_plan(d, nkv * hd, ("embed", "heads"), bias=cfg.qkv_bias),
        "v": layers.linear_plan(d, nkv * hd, ("embed", "heads"), bias=cfg.qkv_bias),
        "o": layers.linear_plan(nq * hd, d, ("heads", "embed")),
    }
    if cfg.qk_norm:
        plan["q_norm"] = {"scale": ParamDef((hd,), (None,), init="ones", dtype=jnp.float32)}
        plan["k_norm"] = {"scale": ParamDef((hd,), (None,), init="ones", dtype=jnp.float32)}
    if lora_rank:
        for name in ("q", "k", "v"):
            out_dim = (nq if name == "q" else nkv) * hd
            plan[f"{name}_lora_a"] = ParamDef((d, lora_rank), ("embed", "lora"), scale=0.02)
            plan[f"{name}_lora_b"] = ParamDef((lora_rank, out_dim), ("lora", "heads"), init="zeros")
    return plan


def _project(cfg: ModelConfig, p: dict, x: jax.Array, name: str, n_heads: int) -> jax.Array:
    y = layers.apply_linear(p[name], x)
    if f"{name}_lora_a" in p:
        y = y + (x @ p[f"{name}_lora_a"].astype(x.dtype)) @ p[f"{name}_lora_b"].astype(x.dtype)
    B, S = x.shape[:2]
    return y.reshape(B, S, n_heads, cfg.resolved_head_dim)


def qkv(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    angles: Optional[jax.Array],
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Project + (qk-norm) + rotary.  x (B,S,D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd)."""
    hd = cfg.resolved_head_dim
    q = _project(cfg, p, x, "q", cfg.num_heads)
    k = _project(cfg, p, x, "k", cfg.num_kv_heads)
    v = _project(cfg, p, x, "v", cfg.num_kv_heads)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    if angles is not None:
        q = layers.apply_rotary(q, angles, hd)
        k = layers.apply_rotary(k, angles, hd)
    q = constrain(q, ("batch", "seq", "act_heads", None))
    k = constrain(k, ("batch", "seq", "act_heads", None))
    v = constrain(v, ("batch", "seq", "act_heads", None))
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, T, Hkv, hd) -> (B, T, Hq, hd).  Explicit repeat keeps the head dim
    shardable over 'model' by *query* heads (kv-head counts in the pool are
    often << 16, which would waste most of the model axis)."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def sdpa(
    q: jax.Array,  # (B, S, Hq, hd)
    k: jax.Array,  # (B, T, Hkv, hd)
    v: jax.Array,  # (B, T, Hkv, hd)
    *,
    q_pos: jax.Array,  # (B, S) absolute positions of queries
    kv_pos: jax.Array,  # (B, T) absolute positions of keys
    causal: bool = True,
    hd_sharded: bool = False,
    scores_dtype=jnp.float32,
) -> jax.Array:
    """Einsum GQA attention, fp32 softmax. Returns (B, S, Hq, hd).

    ``hd_sharded=True`` keeps K/V (and the cache they came from) sharded on
    head_dim and contracts QKᵀ over that sharded axis — the partial logits
    all-reduce is (B,H,S,T) fp32, tiny at decode, instead of all-gathering
    the whole cache to re-shard it by heads (the baseline's behaviour when
    kv_heads doesn't divide the model axis; see EXPERIMENTS.md §Perf)."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if hd_sharded:
        kv_lg = ("batch", "kv_seq", None, "cache_hd")
        q_lg = ("batch", "seq", None, "cache_hd")
        score_lg = ("batch", None, "seq", "kv_seq")
        out_lg = ("batch", "seq", None, "cache_hd")
    else:
        kv_lg = ("batch", "kv_seq", "act_heads", None)
        q_lg = ("batch", "seq", "act_heads", None)
        score_lg = ("batch", "act_heads", "seq", "kv_seq")
        out_lg = ("batch", "seq", "act_heads", None)
    q = constrain(q, q_lg)
    kr = constrain(_repeat_kv(k, Hq // Hkv), kv_lg)
    vr = constrain(_repeat_kv(v, Hq // Hkv), kv_lg)
    scale = jnp.asarray(1.0 / hd ** 0.5, scores_dtype)
    neg = jnp.finfo(scores_dtype).min / 2
    logits = jnp.einsum("bshd,bthd->bhst", q, kr, preferred_element_type=scores_dtype)
    logits = constrain(logits * scale, score_lg)
    valid = kv_pos[:, None, None, :] <= q_pos[:, None, :, None] if causal else (
        kv_pos[:, None, None, :] >= 0
    )
    logits = jnp.where(valid, logits, neg)
    probs = constrain(jax.nn.softmax(logits, axis=-1), score_lg)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), vr)
    return constrain(out, out_lg)


def sdpa_decode_readonly(
    q: jax.Array,  # (B, 1, Hq, hd)
    ck: jax.Array,  # (B, T, Hkv, hd) cache — read-only, holds tokens < pos
    cv: jax.Array,
    k_new: jax.Array,  # (B, 1, Hkv, hd) current token
    v_new: jax.Array,
    *,
    q_pos: jax.Array,  # (B, 1)
    kv_pos: jax.Array,  # (B, T)
    scores_dtype=jnp.float32,
) -> jax.Array:
    """Decode attention without writing the cache inside the layer scan.

    The merged softmax runs over [cache logits | current-token logit]; the
    cache participates strictly below ``q_pos`` (its slot for the current
    token is written *after* the scan, once, in place).  Keeping the cache a
    read-only scan input removes GSPMD's replicate-repartition of the whole
    cache at the scan ys boundary (EXPERIMENTS.md §Perf, decode cells)."""
    B, _, Hq, hd = q.shape
    T, Hkv = ck.shape[1], ck.shape[2]
    G = Hq // Hkv
    # grouped einsum: the cache is contracted directly per kv head — the
    # G-times-repeated K/V tensors are never materialized (they were ~half
    # the remaining decode HBM traffic; §Perf iteration 4)
    qg = q.reshape(B, 1, Hkv, G, hd)
    score_lg = ("batch", "cache_heads", None, "seq", "kv_seq")
    scale = jnp.asarray(1.0 / hd ** 0.5, scores_dtype)
    neg = jnp.finfo(scores_dtype).min / 2

    lc = jnp.einsum("bskgd,btkd->bkgst", qg, ck, preferred_element_type=scores_dtype)
    lc = constrain(lc * scale, score_lg)
    valid = kv_pos[:, None, None, None, :] < q_pos[:, None, None, :, None]
    lc = jnp.where(valid, lc, neg)
    ln = jnp.einsum("bskgd,btkd->bkgst", qg, k_new, preferred_element_type=scores_dtype)
    ln = ln * scale  # (B, kv, G, 1, 1) — self-attention of the current token
    m = jnp.maximum(jnp.max(lc, axis=-1, keepdims=True), ln)
    ec = jnp.exp(lc - m)
    en = jnp.exp(ln - m)
    denom = jnp.sum(ec, axis=-1, keepdims=True) + en
    pv_c = jnp.einsum("bkgst,btkd->bskgd", (ec / denom).astype(cv.dtype), cv)
    pv_n = jnp.einsum("bkgst,btkd->bskgd", (en / denom).astype(cv.dtype), v_new)
    out = (pv_c + pv_n).reshape(B, 1, Hq, hd)
    return constrain(out, ("batch", "seq", "act_heads", None))


def paged_decode(
    q: jax.Array,  # (B, 1, Hq, hd)
    k_pages: jax.Array,  # (P, page, Hkv, hd) shared pool (last page = null)
    v_pages: jax.Array,
    k_new: jax.Array,  # (B, 1, Hkv, hd) current token
    v_new: jax.Array,
    *,
    block_tables: jax.Array,  # (B, n_pages) int32
    seq_lens: jax.Array,  # (B,) int32 tokens already cached (< query position)
    use_kernel: bool | None = None,
) -> jax.Array:
    """Decode attention over a block-table paged cache.

    The paged counterpart of :func:`sdpa_decode_readonly`: the pool is
    read-only inside the layer scan and the current token is merged
    analytically; the caller writes each layer's new (k, v) into its page
    slot once, after the scan.  Routes to the Pallas paged kernel on TPU
    and to the gather + einsum path elsewhere (kernels.decode_attention)."""
    from repro.kernels.decode_attention import ops as pd_ops

    return pd_ops.paged_decode_attention(
        q, k_pages, v_pages, k_new, v_new, block_tables, seq_lens,
        use_kernel=use_kernel,
    )


def blocked_sdpa(
    q: jax.Array,  # (B, S, Hq, hd)
    k: jax.Array,
    v: jax.Array,
    *,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    causal: bool = True,
    block_q: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """Q-block-chunked attention: the S x T score matrix is never fully
    materialized — peak temp is (B, block_q, Hq, T) per step.  Pure XLA
    (GSPMD-shardable on batch/heads); the memory move that stands in for the
    Pallas flash kernel on backends where Pallas doesn't compile."""
    B, S, Hq, hd = q.shape
    bq = min(block_q, S)
    if S % bq != 0:
        return sdpa(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal)
    nq = S // bq
    qb = jnp.moveaxis(q.reshape(B, nq, bq, Hq, hd), 1, 0)  # (nq, B, bq, Hq, hd)
    pb = jnp.moveaxis(q_pos.reshape(B, nq, bq), 1, 0)  # (nq, B, bq)

    def one_block(args):
        qi, pi = args
        return sdpa(qi, k, v, q_pos=pi, kv_pos=kv_pos, causal=causal)

    if unroll:
        outs = jnp.stack([one_block((qb[i], pb[i])) for i in range(nq)])
    else:
        outs = jax.lax.map(one_block, (qb, pb))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, hd)


def flash_sdpa(q, k, v, *, q_pos, kv_pos, causal=True):
    """Pallas flash-attention path (full self-attention, positions 0..S-1)."""
    from repro.kernels.flash_attention import ops as fa_ops

    return fa_ops.flash_attention(q, k, v, causal=causal)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_applies(q: jax.Array, k: jax.Array) -> bool:
    """Self-attention with no cache, a length the kernel tiles, on one TPU:
    a ``pallas_call`` is not partitioned by GSPMD, so a mesh of several
    devices keeps the einsum core."""
    if q.shape[1] != k.shape[1] or q.shape[1] % 128 != 0 or not _on_tpu():
        return False
    mesh = get_abstract_mesh()
    return mesh is None or mesh.size == 1


def attend(
    cfg: ModelConfig,
    q,
    k,
    v,
    *,
    q_pos,
    kv_pos,
    causal=True,
) -> jax.Array:
    """Attention core of a train or prefill call: q (B,S,Hq,hd), k/v
    (B,T,Hkv,hd) -> (B,S,Hq,hd).

    Under ``attention_impl='flash'`` (the default) a call goes to the flash
    kernel when its input shows that the kernel applies (``_flash_applies``:
    S == T, S a multiple of 128, a TPU backend, no mesh of several devices)
    and to :func:`sdpa` otherwise: decode, short or odd prefill buckets, the
    CPU, sharded meshes.  The kernel masks by index and not by position:
    a causal call passes ``q_pos == kv_pos == arange(S)`` per row, and a
    non-causal one attends to every key, as every caller does."""
    if cfg.attention_impl == "flash" and _flash_applies(q, k):
        return flash_sdpa(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal)
    if cfg.attention_impl == "blocked" and q.shape[1] > 1024:
        return blocked_sdpa(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
            unroll=not cfg.scan_layers,
        )
    hd_sharded = cfg.attention_impl == "hd_sharded" and q.shape[1] == 1
    scores_dtype = jnp.bfloat16 if cfg.attn_scores_bf16 else jnp.float32
    return sdpa(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                hd_sharded=hd_sharded, scores_dtype=scores_dtype)


def out_proj(cfg: ModelConfig, p: dict, attn_out: jax.Array) -> jax.Array:
    B, S = attn_out.shape[:2]
    y = attn_out.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim)
    return layers.apply_linear(p["o"], y)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> KVCache:
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def cache_logical(long_context: bool = False) -> KVCache:
    """Logical axes for cache sharding; long-context shards seq over data."""
    if long_context:
        lg = ("batch_rep", "kv_seq_data", "cache_heads", "cache_hd")
    else:
        lg = ("batch", "kv_seq", "cache_heads", "cache_hd")
    return KVCache(k=lg, v=lg)


def update_cache(cache: KVCache, k_new: jax.Array, v_new: jax.Array, pos: jax.Array) -> KVCache:
    """Write S_new positions starting at scalar position `pos` (same per batch)."""
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), (0, pos, 0, 0))
    return KVCache(k=k, v=v)
