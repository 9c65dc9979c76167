"""Hierarchical spans with ``(job, attempt, span)`` ids.

A span is one timed unit of platform work — a job's lifecycle, one
attempt on a container, one ``CheckpointToken.checkpoint()`` round-trip,
a SIGTERM→SIGKILL enforcement ladder, one served request.  Spans nest
via parent ids rather than thread-local context because platform work
hops threads (dispatcher → worker) and processes (supervisor → isolated
child); the id triple is stable across both.

The tracer's clock is pluggable: production uses ``time.monotonic``,
the deterministic concurrency tier injects its ``VirtualClock`` so two
seeded runs produce *identical* traces (``sequence()`` renders the
timestamp-free canonical form that the byte-identity proof compares).

Cross-process spans: the isolation supervisor stamps the parent span id
and clock origin into the bootstrap frame; the child builds its own
tracer with ``seq0=CHILD_SPAN_BASE`` so its span ids can never collide
with parent-side ids for the same (job, attempt), and ships its span
dicts back on the terminal IPC frame for ``merge()``.

``annotate`` is the other kind of span: a host span written into the JAX
profiler's own trace, so it lands on the device trace's clock.  It is the
program's one way to put a host span beside the device's operations.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Iterable, Optional

# Child-process tracers number spans from here so supervisor-side spans
# (bounded by checkpoint count, far below 2**20) never collide with
# child-side spans for the same (job, attempt).  Fixed, so numbering
# stays deterministic.
CHILD_SPAN_BASE = 1 << 20


def annotate(name: str):
    """Context manager: a host span ``name`` in the JAX profiler's trace
    (``jax.profiler.TraceAnnotation``), on the clock of the device
    operations.  About a microsecond when no profile runs.  jax is imported
    here, not at module import, so ``repro.obs`` stays stdlib-only."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


@dataclasses.dataclass
class Span:
    """One timed unit of work.  Identified by ``(job, attempt, span)``."""

    job: str
    attempt: int
    span: int
    name: str
    t0: float
    t1: Optional[float] = None
    parent: Optional[tuple] = None  # (job, attempt, span) of enclosing span
    tags: dict = dataclasses.field(default_factory=dict)
    # (t, name, tags) point-in-time annotations, e.g. chaos injections
    events: list = dataclasses.field(default_factory=list)

    @property
    def span_id(self) -> tuple:
        return (self.job, self.attempt, self.span)

    @property
    def duration_s(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def to_dict(self) -> dict:
        return {
            "job": self.job,
            "attempt": self.attempt,
            "span": self.span,
            "name": self.name,
            "t0": self.t0,
            "t1": self.t1,
            "parent": list(self.parent) if self.parent is not None else None,
            "tags": dict(self.tags),
            "events": [[t, n, dict(tags)] for (t, n, tags) in self.events],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        return cls(
            job=d["job"],
            attempt=int(d["attempt"]),
            span=int(d["span"]),
            name=d["name"],
            t0=float(d["t0"]),
            t1=None if d.get("t1") is None else float(d["t1"]),
            parent=tuple(d["parent"]) if d.get("parent") else None,
            tags=dict(d.get("tags") or {}),
            events=[(float(t), n, dict(tags)) for t, n, tags in d.get("events") or []],
        )

    def canonical(self) -> str:
        """Timestamp-free rendering for determinism proofs.

        Includes structure (id, name, parent), non-float tags, and event
        names — everything that must match bit-for-bit across two seeded
        runs — and excludes wall-clock-derived values (timestamps,
        duration tags) that legitimately vary.
        """
        tags = ",".join(
            f"{k}={self.tags[k]}"
            for k in sorted(self.tags)
            if isinstance(self.tags[k], (str, int, bool))
            and not isinstance(self.tags[k], float)
        )
        evs = ",".join(n for (_, n, _) in self.events)
        par = "-" if self.parent is None else "/".join(map(str, self.parent))
        return (
            f"{self.job}/{self.attempt}/{self.span} {self.name}"
            f" <- {par} {{{tags}}} [{evs}]"
        )


class Tracer:
    """Thread-safe span factory and store.

    When ``enabled=False`` every method is a cheap no-op (``start``
    returns ``None`` and the mutators tolerate ``None``), so hot paths
    can call unconditionally — this is the tracing-off benchmark leg.
    """

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        *,
        enabled: bool = True,
        seq0: int = 1,
    ):
        self._clock = clock
        self.enabled = enabled
        self._seq0 = seq0
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._seq: dict[tuple, int] = {}  # (job, attempt) -> next span seq

    def now(self) -> float:
        return self._clock()

    def start(
        self,
        name: str,
        *,
        job: str,
        attempt: int = 0,
        parent: Any = None,
        t: Optional[float] = None,
        **tags: Any,
    ) -> Optional[Span]:
        if not self.enabled:
            return None
        if isinstance(parent, Span):
            parent = parent.span_id
        elif parent is not None:
            parent = tuple(parent)
        t0 = self._clock() if t is None else t
        with self._lock:
            key = (job, attempt)
            seq = self._seq.get(key, self._seq0)
            self._seq[key] = seq + 1
            sp = Span(
                job=job, attempt=attempt, span=seq, name=name,
                t0=t0, parent=parent, tags=dict(tags),
            )
            self._spans.append(sp)
        return sp

    def end(self, span: Optional[Span], t: Optional[float] = None) -> None:
        if span is None or not self.enabled:
            return
        t1 = self._clock() if t is None else t
        with self._lock:
            span.t1 = t1

    def event(
        self,
        span: Optional[Span],
        name: str,
        t: Optional[float] = None,
        **tags: Any,
    ) -> None:
        if span is None or not self.enabled:
            return
        te = self._clock() if t is None else t
        with self._lock:
            span.events.append((te, name, tags))

    def tag(self, span: Optional[Span], **tags: Any) -> None:
        if span is None or not self.enabled:
            return
        with self._lock:
            span.tags.update(tags)

    @contextlib.contextmanager
    def span(self, name: str, **kw: Any):
        sp = self.start(name, **kw)
        try:
            yield sp
        finally:
            self.end(sp)

    def spans(self, job: Optional[str] = None) -> list[Span]:
        with self._lock:
            if job is None:
                return list(self._spans)
            return [s for s in self._spans if s.job == job]

    def to_dicts(self, job: Optional[str] = None) -> list[dict]:
        return [s.to_dict() for s in self.spans(job)]

    def merge(self, records: Iterable[dict]) -> None:
        """Ingest span dicts from another tracer (an isolated child)."""
        if not self.enabled:
            return
        with self._lock:
            for r in records:
                sp = Span.from_dict(r)
                self._spans.append(sp)
                key = (sp.job, sp.attempt)
                nxt = self._seq.get(key, self._seq0)
                if sp.span >= nxt:
                    self._seq[key] = sp.span + 1

    def sequence(self, job: Optional[str] = None) -> list[str]:
        """Canonical timestamp-free span sequence, sorted by id.

        Sorting by ``(job, attempt, span)`` makes the rendering
        independent of thread interleaving in span *storage* order;
        with a deterministic executor two seeded runs are byte-equal.
        """
        spans = sorted(self.spans(job), key=lambda s: (s.job, s.attempt, s.span))
        return [s.canonical() for s in spans]
