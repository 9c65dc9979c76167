"""Dense Qwen decoders (Qwen2, Qwen3): grouped-query attention and a SwiGLU
MLP in every layer, the embedding tied to the head.

Written from the published descriptions (Qwen2: arXiv:2407.10671 and
``Qwen/Qwen2-0.5B`` config.json; Qwen3: ``Qwen/Qwen3-4B`` config.json) with
the shared helpers of ``reference``.

Per layer: RMSNorm, q/k/v projections (biases where the config has
``attention_bias``), RMSNorm over head_dim of q and k where it has
``qk_norm``, rotary position embedding, causal grouped-query attention with a
softmax scaled by head_dim ** -0.5, output projection, residual; RMSNorm and
a SwiGLU MLP, residual.  A final RMSNorm and the tied embedding give the
logits over ``vocab_size``.

Departures, each deliberate:

* Rotary pairs dimensions (2i, 2i+1), as the RoPE paper (arXiv:2104.09864)
  defines it.  The published checkpoints pair (i, i + head_dim/2)
  (``rotate_half`` in transformers), which is the same map after a fixed
  permutation of q and k's output columns; with seeded random weights the
  two are equally the model.
* Attention is computed in blocks of query rows (``reference.attention``),
  so that the scores of a long sequence fit; the mathematics is unchanged.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, attention, mm, rms, rope, scan_layers


def layer_specs(cfg: dict, prefix: str, n: int) -> dict[str, tuple[tuple, str]]:
    """The leaves of ``n`` dense layers stacked under ``prefix``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    s = {
        "ln1.scale": ((n, d), "norm"),
        "ln2.scale": ((n, d), "norm"),
        "attn.q.w": ((n, d, hq * hd), "matrix"),
        "attn.k.w": ((n, d, hkv * hd), "matrix"),
        "attn.v.w": ((n, d, hkv * hd), "matrix"),
        "attn.o.w": ((n, hq * hd, d), "matrix"),
        "mlp.gate.w": ((n, d, f), "matrix"),
        "mlp.up.w": ((n, d, f), "matrix"),
        "mlp.down.w": ((n, f, d), "matrix"),
    }
    if cfg["attention_bias"]:
        s["attn.q.b"] = ((n, hq * hd), "bias")
        s["attn.k.b"] = ((n, hkv * hd), "bias")
        s["attn.v.b"] = ((n, hkv * hd), "bias")
    if cfg["qk_norm"]:
        s["attn.q_norm.scale"] = ((n, hd), "norm")
        s["attn.k_norm.scale"] = ((n, hd), "norm")
    return {f"{prefix}.{k}": v for k, v in s.items()}


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple, str]]:
    """name -> (shape, kind); kind is matrix | bias | norm | embed."""
    d = cfg["hidden_size"]
    return {
        "embed.embedding": ((cfg["vocab_size"], d), "embed"),
        "final_norm.scale": ((d,), "norm"),
        **layer_specs(cfg, "layers", cfg["num_hidden_layers"]),
    }


def model_config(cfg: dict):
    """The program's ModelConfig for ``cfg``: its registered arch, with every
    number the benchmark's file states."""
    from repro.config import get_arch

    base = get_arch(cfg["arch"])
    return dataclasses.replace(
        base,
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        qkv_bias=cfg["attention_bias"],
        qk_norm=cfg["qk_norm"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        **cfg.get("program", {}),
    )


def layer(cfg: dict, quant, x: jax.Array, lw: dict) -> jax.Array:
    """One decoder layer over one sequence x (S, d); lw: this layer's leaves."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    S = x.shape[0]
    lw = {k: v.astype(F32) for k, v in lw.items()}
    h = rms(x, lw["ln1.scale"], eps)

    def proj(name, heads):
        y = mm(h, lw[f"attn.{name}.w"], quant)
        if f"attn.{name}.b" in lw:
            y = y + lw[f"attn.{name}.b"]
        return y.reshape(S, heads, hd)

    q, k, v = proj("q", hq), proj("k", hkv), proj("v", hkv)
    if cfg["qk_norm"]:
        q = rms(q, lw["attn.q_norm.scale"], eps)
        k = rms(k, lw["attn.k_norm.scale"], eps)
    pos = jnp.arange(S)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    o = attention(q, k, v, quant).reshape(S, hq * hd)
    x = x + mm(o, lw["attn.o.w"], quant)
    h = rms(x, lw["ln2.scale"], eps)
    g = jax.nn.silu(mm(h, lw["mlp.gate.w"], quant)) * mm(h, lw["mlp.up.w"], quant)
    return x + mm(g, lw["mlp.down.w"], quant)


def hidden(cfg: dict, w: dict, tokens: jax.Array, quant=None) -> jax.Array:
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    x = w["embed.embedding"].astype(F32)[tokens]
    x = scan_layers(functools.partial(layer, cfg, quant), x, w, "layers")
    return rms(x, w["final_norm.scale"].astype(F32), cfg["rms_norm_eps"])


def logits(cfg: dict, w: dict, h: jax.Array, quant=None) -> jax.Array:
    """Tied-embedding logits (n, vocab_size) of hidden rows h (n, d)."""
    return mm(h, w["embed.embedding"].astype(F32).T, quant)


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights a token multiplies by in one layer (q, k, v, o and the MLP)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def layer_flops(cfg: dict, ctx: np.ndarray) -> float:
    """Forward operations of every layer for tokens that attend over ``ctx``
    earlier-or-own positions each: the projections and MLP, the attention
    scores and weighted sum."""
    L = cfg["num_hidden_layers"]
    hq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    per_tok = 2.0 * L * matmul_params_per_layer(cfg)
    return float(ctx.size * per_tok + (4.0 * L * hq * hd) * ctx.sum())


def head_flops(cfg: dict) -> float:
    """Forward operations of the LM head for one token."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
