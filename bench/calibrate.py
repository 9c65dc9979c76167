#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--control-seeds 1,2,3]

For each seed: one run of the cell at its own load for ``--seconds``, and the
numbers its check compares, with ``correct`` against the committed limits.  On ``--control-seeds`` also the control (the
reference put in the program's place at float8, one step below the
configurations' bfloat16) and, for training, the half-batch fault.  One JSON
line per seed on stdout.  ``run.py`` never runs any of this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    from repro.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.find_devices(1)
    import serve
    import train

    for seed in seeds:
        t0 = time.perf_counter()
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0, trace_dir="")
        ctx, _ = harness.build_ctx(ns)
        ctx.t_start = t0
        out = {"seed": seed}
        if ctx.mix["kind"] == "serve":
            rec = serve.run(ctx)
            quant = "fp8" if seed in controls else None
            worst, ctrl, count = serve.gaps(ctx, rec, quant)
            out.update(program_gap=worst, tokens=count, finished=len(rec.served),
                       compiles=rec.compiles)
            if quant:
                out["control_gap"] = ctrl
        else:
            rec = train.run(ctx)
            ref = train.reference_readings(ctx)

            def judged(readings):
                c = train.compare(readings, ref)
                c["correct"] = all(harness.passes(x) for x in
                                   train.limit_checks(c, ctx.limits).values())
                return c

            out["program"] = judged(rec.extra["program"])
            out["loss"] = rec.extra["program"]["loss"]
            out["ref_loss"] = ref["loss"]
            if seed in controls:
                out["control"] = judged(train.reference_readings(ctx, "fp8"))
                out["half_batch"] = judged(train.reference_readings(ctx, half=True))
        out["setup_s"] = rec.setup_s
        out["total_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        del rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
