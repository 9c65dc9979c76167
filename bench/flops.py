"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no change to the program can change how its
work is counted.  A multiply-add counts as two operations.
"""

from __future__ import annotations

import numpy as np

BF16 = 2


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights a token multiplies by in one layer (q, k, v, o and the MLP)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def token_flops(cfg: dict, ctx: np.ndarray, head: bool = True) -> float:
    """Forward operations of tokens that attend over ``ctx`` earlier-or-own
    positions each: the projections and MLP of every layer, the attention
    scores and weighted sum, and the LM head where ``head``."""
    ctx = np.asarray(ctx, np.float64)
    L = cfg["num_hidden_layers"]
    hq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    per_tok = 2.0 * L * matmul_params_per_layer(cfg)
    if head:
        per_tok += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return float(ctx.size * per_tok + (4.0 * L * hq * hd) * ctx.sum())


def prefill_flops(cfg: dict, plen: int) -> float:
    """One prompt of ``plen`` tokens, causal, logits for its last token only."""
    ctx = np.arange(1, plen + 1)
    return (token_flops(cfg, ctx, head=False)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (three times the forward), causal attention over
    ``seq_len``, no recomputation counted."""
    ctx = np.arange(1, seq_len + 1)
    return 3.0 * token_flops(cfg, ctx) / seq_len


def paged_decode_call(cfg: dict, seq_lens: np.ndarray, page: int) -> tuple[float, float]:
    """(operations, bytes) of one paged decode attention call (one layer,
    one new token per slot) over slots with ``seq_lens`` cached tokens:
    scores and weighted sum over the cache and the new token; the live
    pages of K and V read once, q, the new K/V and the output moved once."""
    s = np.asarray(seq_lens, np.int64)
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 4.0 * hq * hd * float((s + 1).sum())
    live = float((-(-s // page) * page).sum())
    b = s.size
    nbytes = BF16 * (2 * live * hkv * hd + 2 * b * hq * hd + 2 * b * hkv * hd)
    return flops, nbytes
