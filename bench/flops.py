"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no change to the program can change how its
work is counted.  A multiply-add counts as two operations.  What a token
costs in the model's layers and head is its family's to count
(``bench/families/<family>.py``); ``paged_decode_call`` counts the program's
GQA paged decode kernel.
"""

from __future__ import annotations

import numpy as np

BF16 = 2


def token_flops(fam, cfg: dict, ctx: np.ndarray, head: bool = True) -> float:
    """Forward operations of tokens that attend over ``ctx`` earlier-or-own
    positions each: every layer (the family's ``layer_flops``), and the LM
    head where ``head``."""
    ctx = np.asarray(ctx, np.float64)
    total = fam.layer_flops(cfg, ctx)
    if head:
        total += ctx.size * fam.head_flops(cfg)
    return float(total)


def prefill_flops(fam, cfg: dict, plen: int) -> float:
    """One prompt of ``plen`` tokens, causal, logits for its last token only."""
    return token_flops(fam, cfg, np.arange(1, plen + 1), head=False) + fam.head_flops(cfg)


def train_flops_per_token(fam, cfg: dict, seq_len: int) -> float:
    """Forward and backward (three times the forward), causal attention over
    ``seq_len``, no recomputation counted."""
    ctx = np.arange(1, seq_len + 1)
    return 3.0 * token_flops(fam, cfg, ctx) / seq_len


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
