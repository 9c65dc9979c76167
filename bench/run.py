#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in BENCHMARK.json from its configuration and traffic
files, warms every shape it will use (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON line last on stdout.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` traces the window and reports its per-layer metrics.
The compared numbers and their limits are the last lines on stderr.

Exits non-zero without a result when JAX finds no TPU, fewer chips than the
cell asks for, or a device kind missing from bench/peaks.json.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

# tokens of served output the correctness sample covers, at least
CHECK_TOKENS = 512


class NoAccelerator(RuntimeError):
    """The process found no TPU, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Ctx:
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    trace_dir: Path
    limits: dict
    root: Path = ROOT
    check_tokens: int = CHECK_TOKENS
    start_trace: Callable[[], None] = lambda: None
    stop_trace: Callable[[], None] = lambda: None

    @property
    def family(self):
        """The configuration's family module (``spec.family``)."""
        return spec.family(self.config, self.root / "bench")


def find_devices(chips: int) -> dict:
    """The device JAX gives this process; a TPU with ``chips`` chips or an
    error."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=str(ROOT / ".bench_trace"),
                    help="where the traced run's profile goes (replaced)")
    return ap.parse_args(argv)


def passes(c: dict) -> bool:
    """A check is {"value", "max"} or {"value", "min"}."""
    v = c["value"]
    if isinstance(v, float) and math.isnan(v):
        return False
    return v >= c["min"] if "min" in c else v <= c["max"]


def build_ctx(args, root: Path = ROOT) -> tuple[Ctx, dict]:
    bm = spec.load_benchmark(root)
    cell = spec.cell(bm, args.workload)
    cfg = spec.config(bm, cell["config"], root)
    mix = spec.traffic(cell["traffic"], root / "bench")
    spec.family(cfg, root / "bench")  # a missing family fails before set-up
    return Ctx(cell=cell, config=cfg, mix=mix, seed=args.seed,
               seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
               trace_dir=Path(args.trace_dir),
               limits=cfg.get("limits", {}).get(mix["kind"], {}), root=root), bm


def attach_tracer(ctx: Ctx) -> None:
    import jax

    def start():
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(ctx.trace_dir), profiler_options=opts)

    ctx.start_trace = start
    ctx.stop_trace = jax.profiler.stop_trace


def result(bm, ctx: Ctx, rec, checks: dict, device: dict) -> dict:
    metrics = {}
    for m in spec.metrics_for(bm, ctx.cell["name"], ctx.trace):
        value: Optional[float] = spec.reader(m["name"], ctx.root / "bench")(rec)
        if value is None:
            if not ctx.trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=rec.memory_peak_bytes)
    out: dict[str, Any] = {
        "correct": all(passes(c) for c in checks.values()),
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metrics,
        "device": dev,
    }
    if ctx.trace:
        dev["busy_s"] = rec.trace["busy_s"]
        dev["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def run_cell(ctx: Ctx, bm: dict, device: dict) -> dict:
    """Set-up, window, check; the result line as a dict."""
    import serve
    import train

    module = {"serve": serve, "train": train}[ctx.mix["kind"]]
    if ctx.trace:
        attach_tracer(ctx)
    rec = module.run(ctx)
    print(f"sizing {json.dumps(rec.sizing)} extra {json.dumps(rec.extra)}",
          file=sys.stderr)
    rec.peaks = spec.peaks(device["kind"]) if device["platform"] == "tpu" else {}
    if ctx.trace:
        import trace_reduce

        rec.trace = trace_reduce.reduce(
            trace_reduce.extract(trace_reduce.latest_xplane(str(ctx.trace_dir))))
    t = time.perf_counter()
    checks = module.check(ctx, rec)
    print(f"check took {time.perf_counter() - t:.1f} s", file=sys.stderr)
    return result(bm, ctx, rec, checks, device)


def main(argv=None) -> int:
    args = parse(argv)
    ctx, bm = build_ctx(args)
    from repro.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        device = find_devices(ctx.cell["chips"])
        spec.peaks(device["kind"])
    except (NoAccelerator, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(ctx, bm, device)
    for name, c in out["checks"].items():
        bound = f"max {c['max']}" if "max" in c else f"min {c['min']}"
        print(f"check {name}: {c['value']} {bound}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
