"""Benchmark harness (deliverable d): one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json OUT]

Prints ``name,us_per_call,derived`` CSV; the derived column carries the
paper-claim analog (speedups / efficiencies) next to the paper's number.
``--json OUT`` additionally writes the rows as machine-readable JSON
(e.g. ``BENCH_serving.json``) so the perf trajectory is tracked across
PRs.  The JSON carries a ``meta`` provenance header (git sha, UTC date,
platform string, JAX device count) so a snapshot is attributable to the
commit and machine that produced it.
"""

from __future__ import annotations

import argparse
import datetime
import json
import platform as _platform
import subprocess
import sys
import traceback

from repro.compile_cache import enable_compilation_cache


def snapshot_meta() -> dict:
    """Provenance header for a ``--json`` snapshot.  Every field degrades
    to ``"unknown"`` rather than failing the run (e.g. a tarball checkout
    with no git)."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    try:
        import jax

        devices = jax.device_count()
    except Exception:
        devices = 0
    return {
        "git_sha": sha,
        "date": datetime.datetime.now(datetime.timezone.utc)
        .strftime("%Y-%m-%dT%H:%M:%SZ"),
        "platform": _platform.platform(),
        "python": _platform.python_version(),
        "devices": devices,
    }

MODULES = [
    ("pipeline_fusion", "§2.1 Spark-vs-MapReduce 5x (in-memory pipeline)"),
    ("tiered_store_bench", "§2.2 Alluxio-vs-HDFS 30x (tiered cache)"),
    ("param_server_bench", "§4.2 Alluxio parameter server 5x I/O"),
    ("scheduler_overhead", "§2.3 LXC container overhead <5%"),
    ("sim_scaling", "Fig.6 simulation scalability 2k->10k cores"),
    ("heterogeneous", "§2.3/§4.3 GPU offload 10-20x + mixed tenants, one platform"),
    ("train_pipeline", "Fig.7 unified training pipeline ~2x"),
    ("train_scaling", "Fig.9 near-linear distributed training scaling"),
    ("mapgen_bench", "§5.2 fused map job 5x; ICP offload 30x"),
    ("serving_bench", "§4.3 serving: continuous batching + paged KV >=3x"),
    ("scenario_bench", "§3 closed-loop scenario sweeps: scenarios/sec vs batch"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows as JSON to this path")
    args = ap.parse_args()
    enable_compilation_cache()

    print("name,us_per_call,derived")
    failed = []
    for name, claim in MODULES:
        if args.only and args.only != name:
            continue
        print(f"# {name}: {claim}", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if args.json:
        from benchmarks.common import RESULTS

        meta = snapshot_meta()
        with open(args.json, "w") as f:
            json.dump(
                {"meta": meta, "results": RESULTS, "failed": failed},
                f, indent=2,
            )
        print(
            f"# wrote {len(RESULTS)} rows to {args.json} "
            f"(sha={meta['git_sha'][:12]} devices={meta['devices']})"
        )
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
