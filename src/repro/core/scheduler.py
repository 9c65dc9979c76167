"""Resource manager — the YARN + LXC role (paper §2.3).

The cluster's device pool is sliced into *containers* (contiguous sub-meshes)
that jobs are scheduled onto.  The paper used YARN for queueing/allocation
and LXC for isolation; on a TPU pod the isolation boundary is the sub-mesh
(a job only sees its own devices), and this module is the queue + allocator +
elasticity logic:

* FIFO-with-priority job queue over a shared device pool,
* allocation of power-of-two device blocks (sub-mesh "containers"),
* preemption of lower-priority jobs when a higher-priority job can't fit,
* elastic resize: a job may shrink to its ``min_devices`` under pressure and
  grow back when the pool frees up,
* failure handling: a dead container's devices are quarantined and the job
  is resubmitted (to be resumed from its checkpoint by the training driver),
* speculative re-execution of straggler partitions (Spark-style backup
  tasks) at the data-partition level.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from typing import Callable, Optional


def _locked(fn):
    """Run a ResourceManager method under the pool lock."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)

    return wrapper


def _locked_notify(fn):
    """Run under the pool lock, then wake registered listeners *after* the
    lock is released.  Listeners (e.g. a Platform condition) may take their
    own locks; notifying outside the pool lock keeps the global lock order
    acyclic (platform -> ResourceManager, never the reverse)."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            out = fn(self, *args, **kwargs)
        self._notify_listeners()
        return out

    return wrapper

JOB_PENDING = "PENDING"
JOB_RUNNING = "RUNNING"
JOB_PREEMPTED = "PREEMPTED"
JOB_FAILED = "FAILED"
JOB_DONE = "DONE"


@dataclasses.dataclass
class Job:
    name: str
    kind: str  # train | simulate | scenario | mapgen | serve (validated
    #            against the driver registry by repro.platform at submit)
    devices: int  # desired container size (power of two)
    min_devices: int = 1
    priority: int = 0  # higher wins
    state: str = JOB_PENDING
    container: Optional["Container"] = None
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    preemptions: int = 0
    resumes: int = 0
    resizes: int = 0  # accepted mid-run ResizeOffers (grow or shrink)
    # retry-backoff hold: schedule() skips the job until this monotonic
    # timestamp (0 = no hold); set by fail_container(delay_s=...) so a
    # flapping container can't thrash the queue with immediate retries
    not_before: float = 0.0


@dataclasses.dataclass
class Container:
    """An isolated slice of the device pool (the LXC analog)."""

    cid: int
    device_ids: tuple[int, ...]
    job: Optional[str] = None

    @property
    def size(self) -> int:
        return len(self.device_ids)

    def jax_devices(self) -> list:
        """The JAX devices this container runs on: pool device id ``d`` is
        ``jax.devices()[d % jax.device_count()]`` — the identity on a pool
        sized to the host, and the one device when the pool is larger (a
        logical pool over a single CPU device)."""
        import jax

        devs = jax.devices()
        return [devs[d % len(devs)] for d in self.device_ids]


class ResourceManager:
    def __init__(self, total_devices: int):
        self.total = total_devices
        self.free: set[int] = set(range(total_devices))
        self.quarantined: set[int] = set()
        # device id -> monotonic timestamp of its quarantine, for healing
        # probes (heal_expired); healed/never-quarantined ids are absent
        self.quarantined_at: dict[int, float] = {}
        self.containers: dict[int, Container] = {}
        self.jobs: dict[str, Job] = {}
        self._cid = itertools.count(1)
        self.events: list[str] = []
        # one pool, many tenants: submit/complete may race from worker
        # threads (e.g. a sweep runner waiting out a train job); RLock
        # because complete() -> schedule() re-enters
        self._lock = threading.RLock()
        # completion/reschedule listeners: executors register a callback so
        # a foreign tenant's complete() wakes their wait loop instead of the
        # loop polling job states on a timer
        self._listeners: list[Callable[[], None]] = []

    # ------------------------------------------------------------------
    def _log(self, msg: str) -> None:
        self.events.append(msg)

    def add_listener(self, fn: Callable[[], None]) -> None:
        """Register a callback fired after any pool-state mutation (submit /
        complete / failure / heal / resize).  Called *outside* the pool lock;
        implementations must be cheap and non-reentrant into this manager."""
        self._listeners.append(fn)

    def remove_listener(self, fn: Callable[[], None]) -> None:
        try:
            self._listeners.remove(fn)
        except ValueError:
            pass

    def _notify_listeners(self) -> None:
        for fn in list(self._listeners):
            fn()

    @_locked_notify
    def submit(self, job: Job) -> str:
        if job.name in self.jobs:
            # multi-tenant pool: callers race on friendly names, so rename
            # instead of rejecting (the final name is the handle)
            base, i = job.name, 2
            while f"{base}-{i}" in self.jobs:
                i += 1
            job.name = f"{base}-{i}"
            self._log(f"uniquified duplicate job name {base} -> {job.name}")
        self.jobs[job.name] = job
        self._log(f"submit {job.name} kind={job.kind} want={job.devices}")
        self.schedule()
        return job.name

    @staticmethod
    def _runs(ids: set[int]) -> list[tuple[int, int]]:
        """Maximal contiguous runs of device ids as (start, length)."""
        runs = []
        start = prev = None
        for d in sorted(ids):
            if prev is None or d != prev + 1:
                if start is not None:
                    runs.append((start, prev - start + 1))
                start = d
            prev = d
        if start is not None:
            runs.append((start, prev - start + 1))
        return runs

    @classmethod
    def _max_run(cls, ids: set[int]) -> int:
        return max((length for _, length in cls._runs(ids)), default=0)

    @_locked
    def free_runs(self) -> list[tuple[int, int]]:
        """Maximal contiguous free-device runs as (start, length) — the pool
        shape signal elasticity decisions (grow offers, ``--shards auto``)
        are derived from."""
        return self._runs(self.free)

    def _allocate(self, n: int) -> Optional[Container]:
        """Claim a *contiguous* block of n devices (the sub-mesh container
        promise) — best-fit over free runs so preemption churn doesn't
        fragment the pool."""
        if n <= 0 or len(self.free) < n:
            return None
        fits = [(length, start) for start, length in self._runs(self.free) if length >= n]
        if not fits:
            return None
        _, start = min(fits)
        ids = tuple(range(start, start + n))
        self.free.difference_update(ids)
        c = Container(next(self._cid), ids)
        self.containers[c.cid] = c
        return c

    def _allocate_shrinking(self, size: int, min_devices: int) -> Optional[Container]:
        """Try ``size`` first, halving toward ``min_devices`` when
        fragmentation leaves no contiguous run that large."""
        c = None
        while c is None and size >= min_devices:
            c = self._allocate(size)
            if c is None:
                if size == min_devices:
                    break
                size = max(size // 2, min_devices)
        return c

    def _release(self, c: Container) -> None:
        self.free.update(set(c.device_ids) - self.quarantined)
        self.containers.pop(c.cid, None)

    # ------------------------------------------------------------------
    @_locked
    def schedule(self) -> None:
        """Greedy highest-priority-first packing with shrink + preemption.
        Jobs under a retry-backoff hold (``not_before`` in the future) are
        skipped; ``kick_expired`` reschedules them when the hold lapses."""
        now = time.monotonic()
        pending = sorted(
            (j for j in self.jobs.values()
             if j.state in (JOB_PENDING, JOB_PREEMPTED)
             and j.not_before <= now),
            key=lambda j: (-j.priority, j.submitted_at),
        )
        for job in pending:
            size = job.devices
            c = self._allocate(size)
            if c is None and len(self.free) >= job.min_devices:
                # elastic shrink: take what's available (>= min)
                size = 1 << (len(self.free).bit_length() - 1)
                size = max(size, job.min_devices)
                c = self._allocate_shrinking(size, job.min_devices)
                if c is not None:
                    self._log(f"shrink {job.name} -> {c.size}")
            if c is None:
                c = self._preempt_for(job)
            if c is None:
                continue
            c.job = job.name
            job.container = c
            if job.state == JOB_PREEMPTED:
                job.resumes += 1
            job.state = JOB_RUNNING
            self._log(f"run {job.name} on container {c.cid} ({c.size} devices)")

    def _preempt_for(self, job: Job) -> Optional[Container]:
        victims = sorted(
            (j for j in self.jobs.values() if j.state == JOB_RUNNING and j.priority < job.priority),
            key=lambda j: j.priority,
        )
        # dry-run the evictions: only preempt if the resulting free pool has a
        # *contiguous* run big enough — otherwise victims would lose progress
        # for an allocation that still fails on fragmentation
        hypo = set(self.free)
        taken = []
        for v in victims:
            hypo.update(set(v.container.device_ids) - self.quarantined)
            taken.append(v)
            if self._max_run(hypo) >= job.min_devices:
                break
        fits = [(length, start) for start, length in self._runs(hypo)
                if length >= job.min_devices]
        if not fits:
            return None
        # spare victims whose devices don't touch the winning run — evicting
        # them would cost their progress without helping the requester
        length, start = min(fits)
        run_ids = set(range(start, start + length))
        taken = [v for v in taken if set(v.container.device_ids) & run_ids]
        for v in taken:
            self._log(f"preempt {v.name}")
            self._release(v.container)
            v.container = None
            v.state = JOB_PREEMPTED
            v.preemptions += 1
        want = min(job.devices, len(self.free))
        size = 1 << (want.bit_length() - 1) if want else 0
        size = max(size, job.min_devices)
        return self._allocate_shrinking(size, job.min_devices)

    # ------------------------------------------------------------------
    @_locked_notify
    def resize(self, name: str, devices: int) -> Optional[Container]:
        """Re-grant a RUNNING job's container at a new size — the commit half
        of an accepted ResizeOffer.  The old container is released first (so
        a grow can absorb the adjacent free run), then a fresh contiguous
        block of ``devices`` (clamped to [min_devices, job.devices]) is
        claimed, shrinking toward ``min_devices`` if the pool fragmented in
        between.  Returns the new container, or None when the job was not
        resizable (not RUNNING) or nothing could be granted — in which case
        the job is requeued PENDING at its desired size.

        Freed devices are offered to the queue immediately, which is the
        whole point of a shrink offer: a queued tenant starts on them."""
        job = self.jobs[name]
        if job.state != JOB_RUNNING or job.container is None:
            return None
        devices = max(job.min_devices, min(devices, job.devices))
        old = job.container
        if devices == old.size:
            return old
        self._release(old)
        job.container = None
        c = self._allocate_shrinking(devices, job.min_devices)
        if c is None:
            # the pool churned underneath the offer: requeue at desired size
            job.state = JOB_PENDING
            self._log(f"resize {name} -> {devices} failed; requeued")
            self.schedule()
            return None
        c.job = name
        job.container = c
        job.resizes += 1
        self._log(f"resize {name}: {old.size} -> {c.size} devices")
        self.schedule()  # a shrink's freed devices go to queued tenants now
        return c

    @_locked_notify
    def complete(self, name: str, state: str = JOB_DONE) -> None:
        """Terminate a job and free its container.  ``state`` records the
        outcome (JOB_DONE, or JOB_FAILED for driver errors) so co-tenants
        inspecting the shared pool see the real disposition."""
        job = self.jobs[name]
        job.state = state
        if job.container:
            self._release(job.container)
            job.container = None
        self._log(f"{'done' if state == JOB_DONE else state.lower()} {name}")
        self.schedule()

    @_locked
    def running_jobs(self, exclude=()) -> list[str]:
        """Names of RUNNING jobs not in ``exclude`` — how an executor spots
        foreign tenants holding the pool before declaring itself stuck."""
        return [
            j.name
            for j in self.jobs.values()
            if j.state == JOB_RUNNING and j.name not in exclude
        ]

    @_locked_notify
    def fail_container(
        self, name: str, dead_devices: int = 1, delay_s: float = 0.0
    ) -> None:
        """A node in the job's container died: quarantine devices, resubmit.

        ``dead_devices=0`` means the *worker* died but its devices are fine
        (e.g. a killed isolated process): nothing is quarantined, the job is
        just requeued.  ``delay_s > 0`` holds the requeued job out of
        ``schedule()`` until the backoff lapses (``Job.not_before``)."""
        job = self.jobs[name]
        if job.container is None:
            return
        dead = set(job.container.device_ids[:dead_devices])
        if dead:
            now = time.monotonic()
            self.quarantined.update(dead)
            self.quarantined_at.update({d: now for d in dead})
            self._log(f"container failure in {name}: quarantine {sorted(dead)}")
        else:
            self._log(f"container failure in {name}: worker lost, devices kept")
        self._release(job.container)
        job.container = None
        job.state = JOB_PENDING  # driver resumes from checkpoint on reschedule
        job.not_before = time.monotonic() + delay_s if delay_s > 0 else 0.0
        self.schedule()

    @_locked_notify
    def quarantine_devices(self, device_ids) -> None:
        """Mark devices dead without rescheduling their job — used when a
        failing job is abandoned (e.g. retries exhausted) but its devices
        must still be kept out of the pool."""
        dead = set(device_ids)
        if not dead:
            return
        now = time.monotonic()
        self.quarantined.update(dead)
        self.quarantined_at.update({d: now for d in dead})
        self.free.difference_update(dead)
        self._log(f"quarantine {sorted(dead)}")

    @_locked_notify
    def heal(self, device_ids: Optional[list[int]] = None) -> None:
        ids = set(device_ids) if device_ids else set(self.quarantined)
        self.quarantined.difference_update(ids)
        for d in ids:
            self.quarantined_at.pop(d, None)
        self.free.update(ids)
        self.schedule()

    def heal_expired(self, after_s: float, now: Optional[float] = None) -> list[int]:
        """Healing probe: devices quarantined at least ``after_s`` ago are
        probed (trivially healthy in this repro — real pools would run a
        device self-test) and returned to the pool.  Returns the healed ids.
        """
        with self._lock:
            t = time.monotonic() if now is None else now
            due = sorted(
                d for d, at in self.quarantined_at.items()
                if d in self.quarantined and t - at >= after_s
            )
            for d in due:
                self._log(f"healing probe passed: device {d} rejoins the pool")
        if due:
            self.heal(due)  # reschedules + notifies listeners
        return due

    def kick_expired(self) -> list[str]:
        """Re-run the scheduler for jobs whose retry-backoff hold has lapsed;
        returns the names whose hold was cleared.  Called from executor wait
        loops (cheap no-op while every hold is still ticking)."""
        kicked = []
        with self._lock:
            now = time.monotonic()
            for job in self.jobs.values():
                if job.not_before and job.not_before <= now \
                        and job.state in (JOB_PENDING, JOB_PREEMPTED):
                    job.not_before = 0.0
                    kicked.append(job.name)
            if kicked:
                self.schedule()
        if kicked:
            self._notify_listeners()
        return kicked

    @_locked
    def earliest_hold(self) -> Optional[float]:
        """The soonest ``not_before`` among held queued jobs (monotonic
        timestamp), or None — bounds the executor's condition-wait so a
        backoff retry fires on time."""
        now = time.monotonic()
        holds = [
            j.not_before for j in self.jobs.values()
            if j.not_before > now and j.state in (JOB_PENDING, JOB_PREEMPTED)
        ]
        return min(holds) if holds else None

    def utilization(self) -> float:
        busy = sum(c.size for c in self.containers.values())
        return busy / max(self.total, 1)


# ---------------------------------------------------------------------------
# Straggler mitigation: Spark-style speculative backup tasks
# ---------------------------------------------------------------------------


def run_with_speculation(
    task_fn: Callable[[int], object],
    partitions: list[int],
    runtimes: dict[int, float],
    speculation_multiple: float = 1.5,
) -> tuple[dict[int, object], list[int]]:
    """Execute `task_fn` per partition; partitions whose (simulated or
    measured) runtime exceeds ``speculation_multiple`` x median get a backup
    execution, and the fastest copy wins — Spark speculative execution, which
    is where straggler mitigation lives when the inner step is SPMD.

    ``runtimes`` carries observed/estimated per-partition runtimes; the
    return includes which partitions were speculatively re-executed.
    """
    times = sorted(runtimes.get(p, 1.0) for p in partitions)
    median = times[len(times) // 2] if times else 1.0
    results: dict[int, object] = {}
    speculated: list[int] = []
    for p in partitions:
        results[p] = task_fn(p)
        if runtimes.get(p, 1.0) > speculation_multiple * median:
            backup = task_fn(p)  # deterministic tasks: either copy is valid
            results[p] = backup
            speculated.append(p)
    return results, speculated
