"""What every cell shares: the run record, compile counting, host spans."""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass
class Record:
    """Everything a run measured; the metric readers take their numbers
    from it.  Times are seconds on the harness's clock from the window's
    start."""

    kind: str
    cfg: dict
    mix: dict
    family: Any = None  # the configuration's family module (spec.family)
    setup_s: float = 0.0
    window_s: float = 0.0
    end_s: float = 0.0
    steps: list = dataclasses.field(default_factory=list)
    sizing: dict = dataclasses.field(default_factory=dict)
    due: dict = dataclasses.field(default_factory=dict)
    first: dict = dataclasses.field(default_factory=dict)
    token_times: dict = dataclasses.field(default_factory=dict)
    served: dict = dataclasses.field(default_factory=dict)
    prompts: dict = dataclasses.field(default_factory=dict)
    compiles: int = 0
    backlog_left: int = 0
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace: Any = None  # trace_reduce.reduce(...) of the traced run
    peaks: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)


class Compiles:
    """Counts traces and compiles (persistent-cache hits included) while
    active: every one inside the window means a shape was not warmed."""

    _active: list = []
    _registered = False

    def __init__(self):
        self.count = 0
        self.names: list[str] = []

    @classmethod
    def _listen(cls, event, duration, **kw):
        if event in COMPILE_EVENTS:
            for c in cls._active:
                c.count += 1
                c.names.append(kw.get("fun_name", "?"))

    def __enter__(self):
        if not Compiles._registered:
            import jax

            jax.monitoring.register_event_duration_secs_listener(Compiles._listen)
            Compiles._registered = True
        Compiles._active.append(self)
        return self

    def __exit__(self, *exc):
        Compiles._active.remove(self)
        return False


@contextlib.contextmanager
def host_span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(name):
        yield


def peak_bytes(dev) -> int:
    """Peak device bytes in use so far (0 where the backend keeps no count)."""
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
