"""Device ms per window step of the ops whose scope path holds
``flash_attention``: the flash kernel's route inside the ``attention``
scope (forward, recomputed and backward kernels and their layout copies),
from the traced window's op metadata.  None where no op carries the name,
as in a program without that route or where the route did not engage."""

import functools
import os

import layer_trace
import trace_reduce

SCOPE = "flash_attention"


@functools.lru_cache(maxsize=1)
def _extract(path: str, mtime_ns: int) -> dict:
    return layer_trace.extract(path)


def kernel_ns(ex: dict) -> float:
    """Device 0's self time, inside ``bench.window``, of the ops under
    ``SCOPE``."""
    (lo, hi), = [(s, e) for n, s, e, _ in ex["host"] if n == trace_reduce.WINDOW]
    ops = [(SCOPE in layer_trace._TOKEN.split(p), max(s, lo), min(e, hi))
           for _, s, e, p in ex["devices"][min(ex["devices"])] if e > lo and s < hi]
    return sum(ns for inside, ns in trace_reduce._self_times(ops) if inside)


def read(run):
    if layer_trace.for_run(run) is None or not run.steps:
        return None  # untraced, or the profile is not this run's
    path = trace_reduce.latest_xplane(str(layer_trace.TRACE_DIR))
    ns = kernel_ns(_extract(path, os.stat(path).st_mtime_ns))
    return 1e3 * ns / 1e9 / len(run.steps) if ns else None
