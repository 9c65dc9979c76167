"""Reduce a profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of (name, start_ns, end_ns): the operations of each TPU (the "XLA Ops"
line of each ``/device:TPU:<n>`` plane) and the harness's own host spans
(``TraceAnnotation`` names starting ``bench.``).  ``reduce`` then computes,
inside the host span ``bench.window``:

* busy: the union of operation intervals, averaged over the devices;
* self time by operation name (an op's time less the ops nested in it),
  numeric suffixes folded (``%fusion.12 = ...`` -> ``fusion``);
* idle gaps of device 0, attributed to the host span that covered them
  (``(none)`` where no span did).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
_SUFFIX = re.compile(r"([.:_-]\d+)+$")


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def extract(path: str) -> dict:
    """{"devices": {plane: [(name, start, end)]}, "host": [(name, start, end)]}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.end_ns)
                    for e in line.events if e.name.startswith("bench.")
                )
    return {"devices": devices, "host": host}


def op_family(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` (the TPU's op events carry the
    HLO instruction) -> ``fusion``."""
    if name.startswith("%"):
        name = name[1:].split(" = ", 1)[0]
    return _SUFFIX.sub("", name)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _clip_named(ops, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]


def _self_times(ops):
    """(name, self ns) of each op: its duration less the ops nested in it
    (a ``while`` holds its body's ops on the same line)."""
    out, stack = [], []  # stack of [name, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append((stack[-1][0], stack[-1][2]))
            stack.pop()
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((n, ns) for n, _, ns in stack)
    return out


def window_of(ex: dict) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in ex["host"] if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(spans)}")
    return spans[0]


def reduce(ex: dict, top: int = 10) -> dict:
    lo, hi = window_of(ex)
    if not ex["devices"]:
        raise ValueError("the trace holds no TPU plane")
    busy, op_ns, dev0_busy = [], defaultdict(float), None
    for plane in sorted(ex["devices"]):
        ops = ex["devices"][plane]
        iv = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in iv))
        if dev0_busy is None:
            dev0_busy = iv
            for name, ns in _self_times(_clip_named(ops, lo, hi)):
                op_ns[op_family(name)] += ns
    gaps, edge = [], lo
    for s, e in dev0_busy:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    spans = sorted((s, e, n) for n, s, e in ex["host"] if n != WINDOW)
    gap_ns = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(spans) and spans[j][1] <= gs:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < ge:
            s, e, n = spans[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                gap_ns[n] += ov
                covered += ov
            k += 1
        if ge - gs - covered > 0:
            gap_ns["(none)"] += ge - gs - covered
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "device_ops": rank(op_ns),
        "idle_gaps": rank(gap_ns),
    }
