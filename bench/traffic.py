"""The one traffic generator.  Every mix is a data file in ``bench/traffic/``.

Serving mixes (``"kind": "serve"``):

* ``arrival: "backlog"``: ``requests`` queued at t=0.
* ``arrival: "gamma"``: open loop at ``rate_per_s`` with Gamma gaps of
  coefficient of variation ``cv``; the gaps are rescaled to span the window
  exactly, so every seed offers the same load.
* ``prompt`` / ``output``: lognormal lengths (``median``, ``sigma``) clipped to
  ``[min, max]``.

Lengths come from quantiles of the distribution and gaps from one fixed draw,
so every seed gets the same set of sizes and gaps; the seed only orders them
and draws the token ids.  A backlog is dealt in rounds of ``round`` requests,
each round a permutation of the same ``round`` (prompt, output) pairs, so any
prefix of the queue has the same mix whatever the seed.

Training mixes (``"kind": "train"``): rows of ``seq_len + 1`` uniform token
ids, one partition at a time.  The program masks no document boundaries, so
every row of the same length is the same work.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_FIXED = 20240417  # the draw that fixes sizes and gaps for every seed


def lognormal_quantiles(d: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a clipped lognormal."""
    nd = NormalDist()
    mu = math.log(d["median"])
    out = [
        math.exp(mu + d["sigma"] * nd.inv_cdf((i + 0.5) / n)) for i in range(n)
    ]
    return np.clip(np.rint(out), d["min"], d["max"]).astype(np.int64)


def size_pairs(mix: dict, n: int) -> np.ndarray:
    """(n, 2) fixed (prompt, output) pairs; the pairing is one fixed
    permutation, so prompt and output lengths are independent."""
    p = lognormal_quantiles(mix["prompt"], n)
    o = lognormal_quantiles(mix["output"], n)
    o = o[np.random.default_rng(_FIXED).permutation(n)]
    return np.stack([p, o], axis=1)


def mean_tokens(mix: dict) -> float:
    """Mean prompt + output tokens of a request of this mix."""
    n = mix.get("round", 256)
    return float(size_pairs(mix, n).sum(axis=1).mean())


def _gaps(mix: dict, n: int, seconds: float) -> np.ndarray:
    cv = mix["cv"]
    shape = 1.0 / (cv * cv)
    g = np.random.default_rng(_FIXED).gamma(shape, 1.0, size=n)
    return g * (seconds / g.sum())


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """The requests of one run: ``rid``, ``prompt`` (int32 ids),
    ``max_new``, ``arrival`` (seconds from the window's start), in arrival
    order."""
    rng = np.random.default_rng(seed)
    if mix["arrival"] == "backlog":
        r = mix["round"]
        pairs = size_pairs(mix, r)
        rounds = -(-mix["requests"] // r)
        sizes = np.concatenate([pairs[rng.permutation(r)] for _ in range(rounds)])
        sizes = sizes[: mix["requests"]]
        arrivals = np.zeros(len(sizes))
    elif mix["arrival"] == "gamma":
        n = max(int(round(mix["rate_per_s"] * seconds)), 1)
        sizes = size_pairs(mix, n)[rng.permutation(n)]
        gaps = _gaps(mix, n, seconds)[rng.permutation(n)]
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    ids = rng.integers(0, vocab, size=int(sizes[:, 0].sum()), dtype=np.int32)
    cuts = np.concatenate([[0], np.cumsum(sizes[:, 0])])
    return [
        {
            "rid": i,
            "prompt": ids[cuts[i]:cuts[i + 1]],
            "max_new": int(sizes[i, 1]),
            "arrival": float(arrivals[i]),
        }
        for i in range(len(sizes))
    ]


def train_rows(seed: int, part: int, rows: int, seq_len: int, vocab: int) -> np.ndarray:
    """(rows, seq_len + 1) int32 token ids of partition ``part``.
    Every (seed, part) gives different rows."""
    rng = np.random.default_rng([seed, part])
    return rng.integers(0, vocab, size=(rows, seq_len + 1), dtype=np.int32)
