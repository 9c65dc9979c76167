"""Decoder-only transformer LM (dense / MoE / VLM families).

Layers are stacked along a leading axis and executed with ``lax.scan`` so the
HLO (and compile time) is independent of depth; remat wraps the per-layer body
for training.  One block implementation serves train, prefill and decode.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.distributed.sharding import constrain
from repro.models import attention, layers, mlp, moe
from repro.models.attention import KVCache
from repro.models.params import ParamDef, stack_plan
from repro.models.scan_utils import scan_or_unroll


class DecodeState(NamedTuple):
    cache: KVCache  # stacked (L, B, S_max, n_kv, hd)
    pos: jax.Array  # scalar int32: next write position


class PagedKVState(NamedTuple):
    """Paged KV pool shared by all sequences (serving path).

    Pages are (page_size, n_kv, hd) token slabs; a sequence owns an
    arbitrary set of pages named by its block-table row, so HBM scales
    with live tokens instead of batch x max_len.  The last page of the
    pool is the allocator's *null page*: unused block-table entries point
    at it, and writes for inactive slots land there harmlessly."""

    k_pages: jax.Array  # (L, P, page_size, n_kv, hd)
    v_pages: jax.Array


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


def block_plan(cfg: ModelConfig) -> dict:
    plan = {
        "ln1": layers.norm_plan(cfg),
        "attn": attention.attention_plan(cfg),
        "ln2": layers.norm_plan(cfg),
    }
    if cfg.family == "moe":
        plan["moe"] = moe.moe_plan(cfg)
    else:
        plan["mlp"] = mlp.mlp_plan(cfg)
    return plan


def _zero_metrics() -> moe.MoEMetrics:
    z = jnp.zeros((), jnp.float32)
    return moe.MoEMetrics(z, z, z)


def block_apply(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    angles: Optional[jax.Array],
    q_pos: jax.Array,
    kv_pos: jax.Array,
    cache: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    paged: Optional[tuple] = None,
):
    """Returns (x_out, (k, v), metrics).

    * train/prefill: (k, v) are the full-sequence keys/values.
    * decode: ``cache`` is this layer's (k, v) buffers, READ-ONLY; the new
      token's (k, v) are merged analytically into the softmax
      (sdpa_decode_readonly) and returned so the caller writes the cache
      once, outside the layer scan — keeping the cache a scan constant
      avoids GSPMD's replicate-repartition at the ys boundary.
    * paged decode: ``paged`` is (k_pages, v_pages, block_tables, seq_lens)
      for this layer; same read-only contract through the paged kernel.

    The ``attention`` and ``mlp`` named scopes ride into every device op's
    HLO ``op_name``, forward, recomputed and backward alike, so a profile
    buckets the chip's time by layer.
    """
    with jax.named_scope("attention"):
        h = layers.apply_norm(cfg, p["ln1"], x)
        q, k, v = attention.qkv(cfg, p["attn"], h, angles)
        if paged is not None:
            kp, vp, block_tables, seq_lens = paged
            o = attention.paged_decode(
                q, kp, vp, k, v, block_tables=block_tables, seq_lens=seq_lens
            )
        elif cache is not None:
            ck, cv = cache
            o = attention.sdpa_decode_readonly(
                q, ck, cv, k, v, q_pos=q_pos, kv_pos=kv_pos,
                scores_dtype=jnp.bfloat16 if cfg.attn_scores_bf16 else jnp.float32,
            )
        else:
            o = attention.attend(cfg, q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True)
        kv_out = (k, v)
        x = x + attention.out_proj(cfg, p["attn"], o)

    with jax.named_scope("mlp"):
        h2 = layers.apply_norm(cfg, p["ln2"], x)
        if "moe" in p:
            y, metrics = moe.apply_moe(cfg, p["moe"], h2)
        else:
            y = mlp.apply_mlp(cfg, p["mlp"], h2)
            metrics = _zero_metrics()
        x = x + y
    x = constrain(x, ("batch", "seq", "act_embed"))
    return x, kv_out, metrics


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        # the flash kernel's output and logsumexp are a pallas_call's, which
        # the dots policy alone would recompute by rerunning the forward
        # kernel in the backward; only the flash route names them
        from repro.kernels.flash_attention.ops import RESIDUALS

        cp = jax.checkpoint_policies
        policy = cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, cp.save_only_these_names(RESIDUALS))
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Layer stack runners
# ---------------------------------------------------------------------------


def run_layers_train(cfg: ModelConfig, stacked: Any, x: jax.Array, angles, q_pos, kv_pos):
    def body(h, lp):
        h, _, metrics = block_apply(cfg, lp, h, angles, q_pos, kv_pos)
        return h, metrics

    body = _maybe_remat(body, cfg)
    with jax.named_scope("layers"):
        x, metrics = scan_or_unroll(body, x, stacked, cfg.scan_layers)
    return x, jax.tree.map(jnp.mean, metrics)


def run_layers_prefill(cfg: ModelConfig, stacked: Any, x, angles, q_pos, kv_pos, max_len: int):
    """Prefill: returns hidden states and a (L, B, max_len, kv, hd) cache."""

    def body(h, lp):
        h, (k, v), _ = block_apply(cfg, lp, h, angles, q_pos, kv_pos)
        return h, (k, v)

    x, (ks, vs) = scan_or_unroll(body, x, stacked, cfg.scan_layers)
    B, S = ks.shape[1], ks.shape[2]
    pad = max_len - S
    if pad > 0:
        padding = [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)]
        ks = jnp.pad(ks, padding)
        vs = jnp.pad(vs, padding)
    return x, KVCache(k=ks, v=vs)


def run_layers_decode(cfg: ModelConfig, stacked: Any, x, angles, q_pos, kv_pos, cache: KVCache, pos):
    def body(h, xs):
        lp, ck, cv = xs
        h, (nk, nv), _ = block_apply(cfg, lp, h, angles, q_pos, kv_pos, cache=(ck, cv), cache_pos=pos)
        return h, (nk, nv)

    # ys are the per-layer NEW (k, v) slices (L, B, 1, kv, hd) — tiny; the
    # cache is a read-only scan input and is updated in place once here
    x, (nk, nv) = scan_or_unroll(body, x, (stacked, cache.k, cache.v), cfg.scan_layers)
    new_k = jax.lax.dynamic_update_slice(cache.k, nk.astype(cache.k.dtype), (0, 0, pos, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache.v, nv.astype(cache.v.dtype), (0, 0, pos, 0, 0))
    return x, KVCache(k=new_k, v=new_v)


def run_layers_decode_paged(
    cfg: ModelConfig, stacked: Any, x, angles, q_pos, block_tables, seq_lens,
    pages: PagedKVState,
):
    """Paged decode over the layer stack.  The page pool is a read-only scan
    input; ys are the per-layer new (k, v) slices, written into their page
    slots once by the caller."""

    def body(h, xs):
        lp, kp, vp = xs
        h, (nk, nv), _ = block_apply(
            cfg, lp, h, angles, q_pos, None,
            paged=(kp, vp, block_tables, seq_lens),
        )
        return h, (nk, nv)

    x, (nk, nv) = scan_or_unroll(
        body, x, (stacked, pages.k_pages, pages.v_pages), cfg.scan_layers
    )
    return x, nk, nv


# ---------------------------------------------------------------------------
# Model (dense / moe / vlm)
# ---------------------------------------------------------------------------


class TransformerLM:
    """Decoder-only LM. VLM family prepends projected patch embeddings."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- parameters ----
    def plan(self) -> dict:
        cfg = self.cfg
        plan = {
            "embed": layers.embed_plan(cfg),
            "layers": stack_plan(block_plan(cfg), cfg.num_layers),
            "final_norm": layers.norm_plan(cfg),
        }
        if cfg.frontend == "vision_patches":
            plan["patch_proj"] = layers.linear_plan(
                cfg.frontend_dim, cfg.d_model, ("frontend", "embed"), bias=True
            )
        return plan

    # ---- embedding ----
    def _embed(self, params, batch) -> tuple[jax.Array, Optional[jax.Array]]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope("embed"):
            x = layers.embed_tokens(params["embed"], batch["tokens"], dtype)
            if cfg.frontend == "vision_patches" and "patches" in batch:
                pe = layers.apply_linear(params["patch_proj"], batch["patches"].astype(dtype))
                x = jnp.concatenate([pe, x], axis=1)
        x = constrain(x, ("batch", "seq", "act_embed"))
        return x

    def _angles(self, batch, positions: jax.Array):
        cfg = self.cfg
        if cfg.rope_mode == "none":
            return None
        if cfg.rope_mode == "mrope":
            return layers.mrope_angles(cfg, batch["positions3"], layers.mrope_sections(cfg))
        return layers.rope_angles(cfg, positions)

    # ---- forward modes ----
    def forward(self, params, batch) -> tuple[jax.Array, moe.MoEMetrics]:
        """Full-sequence causal forward -> (logits (B,S,Vpad), metrics)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        angles = self._angles(batch, pos)
        x, metrics = run_layers_train(cfg, params["layers"], x, angles, pos, pos)
        with jax.named_scope("lm_head"):
            x = layers.apply_norm(cfg, params["final_norm"], x)
            logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        logits = constrain(logits, ("batch", "seq", "vocab_act"))
        return logits, metrics

    def prefill(self, params, batch, max_len: int) -> tuple[jax.Array, DecodeState]:
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        angles = self._angles(batch, pos)
        x, cache = run_layers_prefill(cfg, params["layers"], x, angles, pos, pos, max_len)
        x = layers.apply_norm(cfg, params["final_norm"], x[:, -1:])
        logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        return logits, DecodeState(cache=cache, pos=jnp.asarray(S, jnp.int32))

    def decode_step(self, params, state: DecodeState, batch) -> tuple[jax.Array, DecodeState]:
        """One token for every sequence. batch['tokens'] (B, 1)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = layers.embed_tokens(params["embed"], batch["tokens"], dtype)
        x = constrain(x, ("batch", "seq", "act_embed"))
        B = x.shape[0]
        pos = jnp.broadcast_to(state.pos.astype(jnp.int32), (B, 1))
        if cfg.rope_mode == "mrope":
            angles = layers.mrope_angles(
                self.cfg, batch["positions3"], layers.mrope_sections(cfg)
            )
        elif cfg.rope_mode == "none":
            angles = None
        else:
            angles = layers.rope_angles(cfg, pos)
        S_max = state.cache.k.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32), (B, S_max))
        x, cache = run_layers_decode(
            cfg, params["layers"], x, angles, pos, kv_pos, state.cache, state.pos
        )
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        return logits, DecodeState(cache=cache, pos=state.pos + 1)

    def decode_step_paged(
        self, params, pages: PagedKVState, batch
    ) -> tuple[jax.Array, PagedKVState]:
        """A Q-token window per slot against the paged pool.

        ``batch``: tokens (B, Q); block_tables (B, n_pages) int32;
        seq_lens (B,) int32 — the number of cached tokens per slot; window
        token ``j`` sits at position ``seq_lens + j``.  ``Q == 1`` is
        classic decode; ``Q > 1`` carries speculative drafts and/or a
        chunked-prefill slab (intra-window causal).  Inactive slots carry
        all-null block-table rows, so their cache writes land in the null
        page and their logits are ignored by the engine; window positions
        past a slot's allocated pages scatter to the null page too (never
        clamped onto a real page)."""
        cfg = self.cfg
        if cfg.rope_mode == "mrope":
            raise NotImplementedError("paged decode supports standard/none rope")
        dtype = jnp.dtype(cfg.dtype)
        x = layers.embed_tokens(params["embed"], batch["tokens"], dtype)
        x = constrain(x, ("batch", "seq", "act_embed"))
        block_tables = batch["block_tables"].astype(jnp.int32)
        seq_lens = batch["seq_lens"].astype(jnp.int32)
        Q = batch["tokens"].shape[1]
        q_pos = seq_lens[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]
        angles = None if cfg.rope_mode == "none" else layers.rope_angles(cfg, q_pos)
        x, nk, nv = run_layers_decode_paged(
            cfg, params["layers"], x, angles, q_pos, block_tables, seq_lens, pages
        )
        # write every layer's new (k, v) into its page slot in one scatter;
        # jnp.take_along_axis clips out-of-range indices, which would alias a
        # real page — mask overflowing window positions to the null page
        # explicitly (the pool's last page, by init_paged_state convention)
        page_size = pages.k_pages.shape[2]
        width = block_tables.shape[1]
        null_page = pages.k_pages.shape[1] - 1
        page_idx = q_pos // page_size  # (B, Q)
        page_ids = jnp.take_along_axis(
            block_tables, jnp.minimum(page_idx, width - 1), axis=1
        )
        page_ids = jnp.where(page_idx < width, page_ids, null_page)
        offs = q_pos % page_size
        nk = nk.astype(pages.k_pages.dtype)  # (L, B, Q, kv, hd)
        nv = nv.astype(pages.v_pages.dtype)
        new_pages = PagedKVState(
            k_pages=pages.k_pages.at[:, page_ids, offs].set(nk),
            v_pages=pages.v_pages.at[:, page_ids, offs].set(nv),
        )
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        return logits, new_pages

    # ---- decode state construction ----
    def init_paged_state(self, num_pages: int, page_size: int) -> PagedKVState:
        """``num_pages`` INCLUDES the null page (allocators pass pool+1)."""
        cfg = self.cfg
        shape = (
            cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
            cfg.resolved_head_dim,
        )
        dtype = jnp.dtype(cfg.dtype)
        return PagedKVState(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def init_decode_state(self, batch_size: int, max_len: int) -> DecodeState:
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads, hd)
        dtype = jnp.dtype(cfg.dtype)
        return DecodeState(
            cache=KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype)),
            pos=jnp.zeros((), jnp.int32),
        )

    def decode_state_logical(self, long_context: bool = False) -> DecodeState:
        if long_context:
            lg = ("layers", "batch_rep", "kv_seq_data", "cache_heads", "cache_hd")
        else:
            lg = ("layers", "batch", "kv_seq", "cache_heads", "cache_hd")
        return DecodeState(cache=KVCache(k=lg, v=lg), pos=None)
