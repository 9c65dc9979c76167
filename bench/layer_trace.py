"""Device time by the program's own layer names, and the program's own host
spans, from the traced run's profile.

The program marks the train step's layers with ``jax.named_scope``; XLA
keeps the scope path in each HLO instruction's ``op_name`` metadata.  The
TPU's op events name only their instruction (``%fusion.12 = ...``) and the
module running them (the ``XLA Modules`` line), so the paths come from the
optimized HLO that the profile keeps of every module (``/host:metadata``,
stat ``Hlo Proto``).  The program's host spans are ``TraceAnnotation``
names starting ``repro.`` (``repro.obs.annotate``).

``extract`` reads the ``.xplane.pb`` into plain lists: each TPU's ops as
(name, start_ns, end_ns, scope path) and the host spans named ``bench.`` or
``repro.`` as (name, start_ns, end_ns, thread).  ``reduce`` then computes,
inside the host span ``bench.window``:

* ``scope_s``: device 0's self time by the innermost of ``SCOPES`` found
  anywhere in an op's path (``(unscoped)`` for none), so the buckets sum to
  device 0's busy time.  The backward pass wraps a scope as
  ``transpose(jvp(layers))/...`` and recomputation sits under
  ``checkpoint/rematted_computation/``; both keep the inner names.  None
  where no op carries any of the names (a program without the scopes);
* ``spans``: the durations of each ``repro.*`` span;
* ``idle_gaps``: device 0's idle time by the innermost host span over it
  (``bench.*`` or ``repro.*``), on the thread that opened ``bench.window``
  only, so the attributed gaps sum to the idle time.

``for_run`` reduces the profile a traced run of ``run.py`` wrote, once.
"""

from __future__ import annotations

import functools
import os
import re
import sys
from collections import defaultdict
from pathlib import Path

import trace_reduce

SCOPES = ("embed", "attention", "mlp", "lm_head", "layers", "microbatches",
          "grad_accum", "optimizer")
UNSCOPED = "(unscoped)"
TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_trace"  # run.py's default
_TOKEN = re.compile(r"[/()]")


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one serialized protobuf
    message; a length-delimited value is a memoryview of its bytes."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _get(msg, field):
    return [v for f, v in _fields(msg) if f == field]


def hlo_op_names(path: str) -> dict:
    """{module: {instruction: op_name}} from the optimized HLO of every
    module, as the profile keeps it (``XSpace`` > plane ``/host:metadata`` >
    event metadata named ``<module>(<program id>)`` > stat ``Hlo Proto`` >
    ``HloProto.hlo_module`` > computations > instructions > metadata)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _get(space, 1):                       # XSpace.planes
        fields = _fields(plane)
        name = next((bytes(v).decode() for f, v in fields if f == 2), "")
        if name != "/host:metadata":
            continue
        events, stat_names = [], {}
        for f, v in fields:
            if f == 4:                                 # XPlane.event_metadata
                events.append(_get(v, 2)[0])
            elif f == 5:                               # XPlane.stat_metadata
                md = _get(v, 2)[0]
                stat_names[_get(md, 1)[0]] = bytes(_get(md, 2)[0]).decode()
        for ev in events:
            module = bytes(_get(ev, 2)[0]).decode()
            for stat in _get(ev, 5):                   # XEventMetadata.stats
                if stat_names.get(_get(stat, 1)[0]) != "Hlo Proto":
                    continue
                ops = out.setdefault(module, {})
                for mod in _get(bytes(_get(stat, 6)[0]), 1):       # HloProto.hlo_module
                    for comp in _get(mod, 3):                      # .computations
                        for ins in _get(comp, 2):                  # .instructions
                            md = _get(ins, 7)                      # .metadata
                            op = _get(md[0], 2) if md else []      # OpMetadata.op_name
                            ops[bytes(_get(ins, 1)[0]).decode()] = (
                                bytes(op[0]).decode() if op else "")
    return out


def _instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name[1:].split(" = ", 1)[0] if event_name.startswith("%") else event_name


def _op_paths(ops, modules, names):
    """(name, start, end, op_name) of each op, its instruction looked up in
    the module whose ``XLA Modules`` event covers its start."""
    out, j = [], 0
    modules = sorted(modules)
    for n, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(modules) and modules[j][1] <= s:
            j += 1
        inside = j < len(modules) and modules[j][0] <= s
        table = names.get(modules[j][2], {}) if inside else {}
        out.append((n, s, e, table.get(_instruction(n), "")))
    return out


def extract(path: str) -> dict:
    """{"devices": {plane: [(name, start, end, op_name)]},
    "host": [(name, start, end, thread)]}"""
    from jax.profiler import ProfileData

    names = hlo_op_names(path)
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            devices[plane.name] = _op_paths(ops, modules, names)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}#{i}"
                host.extend((e.name, e.start_ns, e.end_ns, thread) for e in line.events
                            if e.name.startswith(("bench.", "repro.")))
    return {"devices": devices, "host": host}


def scope_of(path: str) -> str:
    """The innermost (rightmost) of ``SCOPES`` in an op's scope path."""
    for tok in reversed(_TOKEN.split(path)):
        if tok in SCOPES:
            return tok
    return UNSCOPED


def _innermost(spans):
    """Cut the nested spans (start, end, name) of one thread into disjoint
    (start, end, name) pieces, each named by the innermost span over it."""
    out, stack, t = [], [], None
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            out.append((t, end, name))
            t = end
        if stack:
            out.append((t, s, stack[-1][1]))
        t = s
        stack.append((e, n))
    while stack:
        end, name = stack.pop()
        out.append((t, end, name))
        t = end
    return [p for p in out if p[1] > p[0]]


def _idle_by_span(gaps, pieces):
    """Each gap's nanoseconds by the piece over it, ``(none)`` for the rest."""
    out, j = defaultdict(float), 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e, n = pieces[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[n] += ov
                covered += ov
            k += 1
        if ge - gs - covered > 0:
            out["(none)"] += ge - gs - covered
    return out


def reduce(ex: dict) -> dict:
    windows = [(s, e, th) for n, s, e, th in ex["host"] if n == trace_reduce.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW} span, found {len(windows)}")
    lo, hi, main = windows[0]
    if not ex["devices"]:
        raise ValueError("the trace holds no TPU plane")
    ops = [(scope_of(p), max(s, lo), min(e, hi))
           for _, s, e, p in ex["devices"][min(ex["devices"])] if e > lo and s < hi]

    scope_ns = defaultdict(float)
    for scope, ns in trace_reduce._self_times(ops):
        scope_ns[scope] += ns
    scoped = any(k != UNSCOPED for k in scope_ns)

    busy = trace_reduce._union([(s, e) for _, s, e in ops])
    gaps, edge = [], lo
    for s, e in busy:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    pieces = _innermost([(max(s, lo), min(e, hi), n) for n, s, e, th in ex["host"]
                         if th == main and n != trace_reduce.WINDOW and e > lo and s < hi])
    gap_ns = _idle_by_span(gaps, pieces)

    spans = defaultdict(list)
    for n, s, e, _ in ex["host"]:
        if n.startswith("repro.") and s >= lo and e <= hi:
            spans[n].append((e - s) / 1e9)
    return {
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "scope_s": {k: v / 1e9 for k, v in scope_ns.items()} if scoped else None,
        "spans": dict(spans),
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(gap_ns.items(), key=lambda kv: -kv[1])],
    }


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    r = reduce(extract(path))
    print(f"layer_trace: busy {r['busy_s']:.6f} s, scope_s {r['scope_s']}, "
          f"idle_gaps {r['idle_gaps']}", file=sys.stderr)
    return r


def for_run(run):
    """``reduce`` of the profile the traced ``run`` wrote to ``run.py``'s
    default trace directory; None for an untraced run, or where the profile
    there is not this run's (its window differs from ``run.trace``'s)."""
    if not run.trace:
        return None
    try:
        path = trace_reduce.latest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    r = _reduce_file(path, os.stat(path).st_mtime_ns)
    return r if r["window_s"] == run.trace["window_s"] else None


def device_ms_per_step(run, scopes) -> float | None:
    """Device ms per window step in the given scope buckets."""
    r = for_run(run)
    if r is None or r["scope_s"] is None or not run.steps:
        return None
    return 1e3 * sum(r["scope_s"].get(k, 0.0) for k in scopes) / len(run.steps)
