"""Runs of the flash forward kernel per run of its dkv backward kernel, on
device 0 inside ``bench.window``: 2 where the remat reruns the forward
kernel in the backward, 1 where the forward's output and logsumexp are
saved.  None where no dkv kernel ran (untraced, or the route did not
engage)."""

import functools
import os

import layer_trace
import trace_reduce


@functools.lru_cache(maxsize=1)
def _extract(path: str, mtime_ns: int) -> dict:
    return layer_trace.extract(path)


def per_bwd(ex: dict) -> float | None:
    """Forward kernel events (op family ``splash_mqa_fwd*``) over dkv ones
    (``splash_mqa_dkv*``) overlapping the window, on device 0."""
    (lo, hi), = [(s, e) for n, s, e, _ in ex["host"] if n == trace_reduce.WINDOW]
    families = [trace_reduce.op_family(n)
                for n, s, e, _ in ex["devices"][min(ex["devices"])] if e > lo and s < hi]
    fwd = sum(f.startswith("splash_mqa_fwd") for f in families)
    dkv = sum(f.startswith("splash_mqa_dkv") for f in families)
    return fwd / dkv if dkv else None


def read(run):
    if layer_trace.for_run(run) is None:
        return None  # untraced, or the profile is not this run's
    path = trace_reduce.latest_xplane(str(layer_trace.TRACE_DIR))
    return per_bwd(_extract(path, os.stat(path).st_mtime_ns))
