#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell: the highest offered rate the
program sustains with no growing queue.  One process, one rate after another.

    python3 bench/sweep.py --workload qwen2-0.5b.serve-chat --seed 7 \
        --seconds 30 --rates 2,4,8,16

For each rate the cell's mix runs with ``rate_per_s`` replaced, for
``--seconds``, with its usual drain.  A rate is sustained when the requests
due in the last third of the window waited for admission no longer, at the
95th percentile, than twice those due in the first third plus 100 ms, and
every request due in the window got its first token within the drain.  One
JSON line per rate; the cell file takes 0.8 x the highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness
from stats import percentile


def thirds(rec, seconds: float) -> tuple[float, float]:
    """p95 time to first token of the requests due in the first and the
    last third of the window."""
    by_due = sorted(zip(rec.due.values(), (rec.first.get(r, rec.end_s) - d
                                           for r, d in rec.due.items())))
    first = sorted(v for d, v in by_due if d < seconds / 3)
    last = sorted(v for d, v in by_due if d >= 2 * seconds / 3)
    return percentile(first, 0.95), percentile(last, 0.95)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.find_devices(1)
    import serve

    for rate in [float(r) for r in args.rates.split(",")]:
        ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                                seconds=args.seconds, trace=0, trace_dir="")
        ctx, _ = harness.build_ctx(ns)
        ctx.t_start = time.perf_counter()
        ctx.mix = dict(ctx.mix, rate_per_s=rate)
        rec = serve.run(ctx)
        p95_first, p95_last = thirds(rec, args.seconds)
        all_t = sorted(rec.first.get(r, rec.end_s) - d for r, d in rec.due.items())
        waits = sorted(p[3] for s in rec.steps for p in s["prefills"] if p[3] is not None)
        missing = sum(1 for r in rec.due if r not in rec.first)
        sustained = missing == 0 and p95_last <= 2 * p95_first + 0.1
        print(json.dumps({
            "rate_per_s": rate, "requests": len(rec.due), "missing_first": missing,
            "ttft_p50_s": percentile(all_t, 0.5), "ttft_p95_s": percentile(all_t, 0.95),
            "ttft_p95_first_third_s": p95_first, "ttft_p95_last_third_s": p95_last,
            "queue_wait_p95_s": percentile(waits, 0.95) if waits else None,
            "drain_s": rec.end_s - rec.window_s, "sustained": sustained,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
