"""Platform client: submit / status / wait / cancel / results over all services.

The one front door to the paper's unified infrastructure.  ``submit``
validates the spec's kind against the driver registry, uniquifies the job
name, coerces the config payload (fail-fast), and queues the job on the
shared :class:`~repro.core.scheduler.ResourceManager` pool.  ``wait`` drives
the executor until the named jobs are terminal.

Two executors share one lifecycle state machine:

* **Concurrent (default)** — every granted container gets a worker thread
  running its driver, so co-scheduled tenants overlap on wall clock.  A
  worker holds a *device claim* for its container: a newly scheduled job
  whose container overlaps a still-running worker (e.g. the preemption
  victim hasn't yielded yet) waits until that worker exits, preserving the
  one-worker-per-device isolation story.  Drivers that accept a
  :class:`~repro.platform.driver.CheckpointToken` are interruptible
  *between checkpoints*: preemption and cancel stop a running driver at its
  next ``token.checkpoint()`` instead of only between jobs.
* **Serial** (``concurrent=False``) — the PR-3 in-process loop, retained as
  the benchmark baseline: one scheduled job at a time, highest priority
  first, preemption only between jobs.

Job lifecycle (bridged from the ResourceManager's container states, with
per-job events surfaced):

    PENDING -> RUNNING -> DONE
       ^        |  | \\-> FAILED (driver error, or retries exhausted)
       |        |  \\--> RUNNING (resized: an accepted ResizeOffer yields
       |        v        at a checkpoint and is re-granted grown/shrunk)
       +---- PREEMPTED          (higher-priority tenant took the devices;
       |          |              a running driver yields at its next
       |          v              checkpoint)
       +--    (resumed)         RUNNING again, possibly shrunk (elastic)
    any non-terminal -> CANCELLED

A :class:`~repro.platform.driver.ContainerFailure` raised by a driver
quarantines the dead devices and resubmits the job (up to
``JobSpec.max_retries``) — the paper's node-failure story, now uniform
across all five services.

Determinism hooks: ``ExecutorHooks`` lets tests inject barriers/gates at
worker start/exit and at every driver checkpoint, and ``clock`` swaps the
event-timestamp clock for a virtual one — the concurrency test harness
drives preempt-mid-run, cancel-mid-run and racing submit/complete paths
without sleeps.

Elastic control plane: ``platform.elastic`` (an
:class:`~repro.platform.elastic.ElasticController`) issues load-driven
``ResizeOffer``s onto running tokens; ``elastic_poll_s`` makes the wait
loop step it.  Wait loops are event-driven — worker exits, submits, and
*foreign-tenant* completions (via a ``ResourceManager`` listener) all
notify the platform condition — and ``wait(deadline_s=...)`` adds a hard
bound that raises :class:`JobTimeout` with each stuck job's last
lifecycle event.
"""

from __future__ import annotations

import dataclasses
import inspect
import random
import threading
import time
import weakref
from typing import Any, Callable, Optional, Sequence, Union

from repro.core.scheduler import (
    JOB_DONE,
    JOB_FAILED,
    JOB_PENDING,
    JOB_PREEMPTED,
    JOB_RUNNING,
    Job,
    ResourceManager,
)
from repro.platform.driver import (
    CANCEL,
    PREEMPT,
    RESIZE,
    CheckpointToken,
    ContainerFailure,
    JobInterrupted,
    ResizeOffer,
    ServiceDriver,
    get_driver,
)
from repro.obs.metrics import MetricsRegistry, stage_summary
from repro.obs.trace import Tracer
from repro.platform.chaos import ChaosController, FaultPlan
from repro.platform.elastic import ElasticController
from repro.platform.spec import JobReport, JobSpec

# platform-level job states: the scheduler's, plus CANCELLED
DONE = "DONE"
FAILED = "FAILED"
CANCELLED = "CANCELLED"
TERMINAL = (DONE, FAILED, CANCELLED)


class JobTimeout(RuntimeError):
    """``wait(deadline_s=...)`` expired with jobs still live.  Carries each
    unfinished job's last lifecycle event so the caller sees *where* it was
    stuck instead of a bare timeout."""

    def __init__(self, pending: dict[str, str], deadline_s: float):
        self.pending = dict(pending)
        detail = "; ".join(f"{n}: {ev}" for n, ev in self.pending.items())
        super().__init__(
            f"jobs not terminal after {deadline_s:.1f}s deadline: {detail}"
        )


def _noop(*args: Any) -> None:
    return None


@dataclasses.dataclass
class ExecutorHooks:
    """Executor observation points for the deterministic test harness.

    All hooks run on the worker thread (never under the platform lock), so
    blocking inside one stalls exactly that worker — which is the point:
    tests park a driver at a checkpoint, change the world, then release it.
    """

    worker_start: Callable[[str], None] = _noop  # name — before driver.run
    checkpoint: Callable[[str, CheckpointToken], None] = _noop  # each checkpoint()
    worker_exit: Callable[[str, str], None] = _noop  # name, platform state


@dataclasses.dataclass
class _Worker:
    token: CheckpointToken
    devices: frozenset[int]  # claim held until the thread exits
    thread: Optional[threading.Thread] = None


@dataclasses.dataclass
class _JobRecord:
    spec: JobSpec
    driver: ServiceDriver
    ctx: Any  # driver.prepare() output
    accepts_token: bool = False
    state: str = JOB_PENDING
    last_rm_state: str = JOB_PENDING
    submitted_at: float = 0.0
    first_run_at: Optional[float] = None
    finished_at: Optional[float] = None
    run_time_s: float = 0.0
    devices_used: int = 0
    retries: int = 0
    checkpoints: int = 0  # cancellation points passed (all attempts)
    cancel_requested: bool = False
    driver_state: dict = dataclasses.field(default_factory=dict)
    metrics: dict = dataclasses.field(default_factory=dict)
    # structured event stream: (absolute clock timestamp, message).  The
    # legacy "+N.NNs msg" strings are a rendered view (``events``), so
    # concurrent tenants' records merge onto one absolute timeline.
    records: list[tuple[float, str]] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    # observability: the platform tracer, this job's root span, attempts
    tracer: Optional[Tracer] = None
    root: Any = None
    attempts: int = 0
    enqueued_at: float = 0.0  # last submit/requeue time (queue-wait origin)

    def log(self, msg: str, now: float) -> None:
        self.records.append((now, msg))
        if self.tracer is not None:
            self.tracer.event(self.root, "log", t=now, msg=msg)

    @property
    def events(self) -> list[str]:
        """Rendered view over ``records`` — byte-identical to the
        pre-structured format (offsets from ``submitted_at``)."""
        return [f"+{t - self.submitted_at:.2f}s {m}" for t, m in self.records]


def _wants_token(driver: ServiceDriver) -> bool:
    try:
        return "token" in inspect.signature(driver.run).parameters
    except (TypeError, ValueError):  # builtins / exotic callables: assume not
        return False


class Platform:
    """Unified client over the shared device pool: every service is a job."""

    def __init__(
        self,
        rm: Optional[ResourceManager] = None,
        total_devices: int = 8,
        *,
        concurrent: bool = True,
        hooks: Optional[ExecutorHooks] = None,
        clock: Callable[[], float] = time.monotonic,
        elastic_poll_s: Optional[float] = None,
        retry_backoff_s: float = 0.05,
        retry_backoff_cap_s: float = 2.0,
        backoff_seed: int = 0,
        heal_after_s: Optional[float] = None,
        chaos_plan: Optional[FaultPlan] = None,
        chaos_poll_s: float = 0.02,
        trace: bool = True,
    ):
        self.rm = rm if rm is not None else ResourceManager(total_devices)
        self.concurrent = concurrent
        self.hooks = hooks if hooks is not None else ExecutorHooks()
        self._clock = clock
        # structured observability: one tracer + one metrics registry per
        # platform.  ``trace=False`` disables span recording entirely (the
        # benchmark overhead-off leg); the event log and metrics stay on.
        self.tracer = Tracer(clock=clock, enabled=trace)
        self.obs = MetricsRegistry()
        # container-failure resubmission: exponential backoff with jitter
        # (delay = min(cap, base * 2^(retry-1)) * U[0.5, 1.5)); base <= 0
        # disables the hold entirely (immediate requeue, the PR-4 behavior)
        self.retry_backoff_s = retry_backoff_s
        self.retry_backoff_cap_s = retry_backoff_cap_s
        self._backoff_rng = random.Random(backoff_seed)
        # quarantine healing probe: devices quarantined longer than this
        # rejoin the pool from the wait loop (None = quarantine is forever)
        self.heal_after_s = heal_after_s
        self._records: dict[str, _JobRecord] = {}
        self._active: dict[str, _Worker] = {}
        # guards _records/_active/record fields; workers notify on exit.
        # lock order is always platform -> ResourceManager, never reversed.
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        # a foreign tenant completing on the shared pool (e.g. a FleetRunner
        # calling rm.complete) wakes our wait loops through this listener.
        # Registered through a weakref so a long-lived shared manager never
        # pins finished Platform instances alive.
        self_ref = weakref.ref(self)

        def _pool_listener() -> None:
            p = self_ref()
            if p is not None:
                p._pool_changed()

        self.rm.add_listener(_pool_listener)
        # the elastic control plane: load-driven ResizeOffers.  Always
        # constructed (so tests/benchmarks can force offers); only stepped
        # from the wait loops when ``elastic_poll_s`` is set.  Offers need a
        # live worker to land on, so under the serial executor the policy
        # only bites when another thread is mid-step (forced offers always
        # work).
        self.elastic = ElasticController(self, poll_s=elastic_poll_s)
        # the chaos layer: armed only when built with a FaultPlan; stepped
        # from the wait loops right next to the elastic controller so fault
        # injection rides the same cadence machinery as elasticity
        self.chaos = ChaosController(self, chaos_plan, poll_s=chaos_poll_s)

    def _pool_changed(self) -> None:
        # Never block here: the notifying thread may hold *another*
        # platform's lock (two Platforms sharing one ResourceManager), and a
        # blocking acquire would close an A->B/B->A lock cycle.  If the lock
        # is contended the holder is awake and will re-check its predicate;
        # waiters are covered by the wait-timeout safety net.  The acquire
        # still succeeds reentrantly for this platform's own mutations.
        if self._lock.acquire(blocking=False):
            try:
                self._cond.notify_all()
            finally:
                self._lock.release()

    # -- submission ----------------------------------------------------
    def submit(self, spec: JobSpec) -> str:
        """Validate, uniquify, queue; returns the (possibly renamed) job name."""
        spec.validate()  # isolation/grace/elasticity sanity, fail-fast
        if spec.isolation == "process":
            from repro.platform import isolation

            isolation.check_backend()
        driver = get_driver(spec.kind)  # raises UnknownServiceKind on typos
        ctx = driver.prepare(spec)  # bad config payloads fail here, not in queue
        with self._cond:
            rec = _JobRecord(
                spec=spec, driver=driver, ctx=ctx,
                accepts_token=_wants_token(driver),
                submitted_at=self._clock(),
            )
            job = Job(
                spec.name or spec.kind,
                spec.kind,
                devices=spec.devices,
                min_devices=spec.resolved_min_devices(),
                priority=spec.priority,
            )
            name = self.rm.submit(job)  # auto-uniquifies duplicate names
            self._records[name] = rec
            rec.tracer = self.tracer
            rec.enqueued_at = rec.submitted_at
            rec.root = self.tracer.start(
                "job", job=name, t=rec.submitted_at, kind=spec.kind,
                devices=spec.devices, priority=spec.priority,
                isolation=spec.isolation,
                **{f"label_{k}": v for k, v in sorted(spec.labels.items())},
            )
            rec.log(f"submitted kind={spec.kind} want={spec.devices} "
                    f"priority={spec.priority}", self._clock())
            # the submit may have preempted running tenants: flag their tokens
            self._observe()
            self._cond.notify_all()
        return name

    def submit_batch(self, specs: Sequence[JobSpec]) -> list[str]:
        """Heterogeneous batch submission: a mixed tenant set onto one pool."""
        return [self.submit(s) for s in specs]

    # -- lifecycle bridging --------------------------------------------
    def _observe(self) -> None:
        """Diff ResourceManager job states into per-job lifecycle events.

        Must hold the platform lock.  A RUNNING->PREEMPTED transition with a
        live worker also requests a cooperative stop, so the driver yields at
        its next checkpoint.
        """
        for name, rec in self._records.items():
            if rec.state in TERMINAL:
                continue
            job = self.rm.jobs[name]
            prev, cur = rec.last_rm_state, job.state
            if cur == prev:
                continue
            now = self._clock()
            if cur == JOB_RUNNING:
                c = job.container
                verb = "resumed" if prev == JOB_PREEMPTED else "scheduled"
                if prev == JOB_PREEMPTED:
                    self.obs.inc("resumes")
                rec.log(f"{verb} on container {c.cid} ({c.size} devices)", now)
            elif cur == JOB_PREEMPTED:
                rec.log("preempted (devices reclaimed by higher priority)", now)
                self.obs.inc("preempts")
                rec.enqueued_at = now  # queue-wait clock restarts here
                worker = self._active.get(name)
                if worker is not None:
                    worker.token.request_stop(PREEMPT)
            elif cur == JOB_PENDING:
                rec.log("requeued", now)
                rec.enqueued_at = now
            rec.last_rm_state = cur
            rec.state = cur

    # -- shared completion paths ---------------------------------------
    def _finish(self, name: str, state: str, error: Optional[str] = None) -> None:
        """Terminal transition (platform lock held)."""
        rec = self._records[name]
        now = self._clock()
        rec.state = state
        rec.error = error
        rec.finished_at = now
        rec.log(state.lower() if not error else f"failed: {error}", now)
        self.obs.inc(f"jobs_{state.lower()}")
        self.tracer.tag(rec.root, state=state)
        self.tracer.end(rec.root, t=now)
        # frees the container, reschedules the queue; co-tenants sharing the
        # ResourceManager see the real outcome, not a blanket "done"
        self.rm.complete(name, state=JOB_FAILED if state == FAILED else JOB_DONE)
        # rescheduling can preempt lower-priority tenants mid-run: flag them
        self._observe()
        self._cond.notify_all()

    def _handle_container_failure(
        self, name: str, container, e: ContainerFailure
    ) -> None:
        """ContainerFailure from a driver (platform lock held).  ``container``
        is the one the driver actually ran on — the job may have been
        preempted (and even rescheduled onto a fresh container) since."""
        rec = self._records[name]
        rec.log(f"container failure: {e}", self._clock())
        if rec.state in TERMINAL:
            # cancelled while dying: no retry, but the dead devices still
            # must leave the pool
            self.rm.quarantine_devices(container.device_ids[: e.dead_devices])
            return
        if rec.retries >= rec.spec.max_retries:
            # abandoned, but its dead devices still leave the pool
            self.rm.quarantine_devices(container.device_ids[: e.dead_devices])
            self._finish(name, FAILED, error=str(e))
            return
        rec.retries += 1
        self.obs.inc("retries")
        delay = self._retry_delay(rec.retries)
        if delay > 0:
            rec.log(
                f"resubmitting in {delay:.3f}s "
                f"(retry {rec.retries}/{rec.spec.max_retries}, "
                "exponential backoff + jitter)", self._clock())
        else:
            rec.log(f"resubmitting (retry {rec.retries}/{rec.spec.max_retries})",
                    self._clock())
        job = self.rm.jobs[name]
        if job.container is container:
            self.rm.fail_container(
                name, dead_devices=e.dead_devices, delay_s=delay)
        else:
            # preempted while dying (maybe already rescheduled elsewhere):
            # quarantine the devices of the container that actually died,
            # not whatever the job holds now
            self.rm.quarantine_devices(container.device_ids[: e.dead_devices])
        # fail_container reschedules synchronously, so the requeued job may
        # already hold a fresh container — _observe would see the stale
        # RUNNING->RUNNING as no transition; log it here
        job = self.rm.jobs[name]
        rec.state = rec.last_rm_state = job.state
        if job.state == JOB_RUNNING:
            rec.log(f"rescheduled on container {job.container.cid} "
                    f"({job.container.size} devices)", self._clock())
        self._observe()
        self._cond.notify_all()

    def _retry_delay(self, retries: int) -> float:
        """Resubmission hold for the ``retries``-th container-failure retry:
        exponential backoff with jitter, so a flapping container doesn't
        thrash the scheduler (and correlated failures don't resubmit in
        lockstep)."""
        base = self.retry_backoff_s
        if base <= 0:
            return 0.0
        delay = min(self.retry_backoff_cap_s, base * (2 ** (retries - 1)))
        return delay * (0.5 + self._backoff_rng.random())

    def _log_event(self, name: str, msg: str) -> None:
        """Append to a job's event log from outside the lock (the isolation
        supervisor reports spawn/enforcement milestones through this)."""
        with self._cond:
            rec = self._records.get(name)
            if rec is not None:
                rec.log(msg, self._clock())

    # -- concurrent executor -------------------------------------------
    def _dispatch(self) -> int:
        """Spawn workers for scheduled jobs whose devices are unclaimed.

        Platform lock held.  Returns how many workers were started.  A job
        whose container overlaps a live worker's claim (a preemption victim
        that hasn't reached a checkpoint yet) is skipped until that worker
        exits — one worker per device at all times.
        """
        claimed: set[int] = set()
        for w in self._active.values():
            claimed |= w.devices
        runnable = [
            name
            for name, rec in self._records.items()
            if rec.state not in TERMINAL
            and name not in self._active
            and self.rm.jobs[name].state == JOB_RUNNING
            and self.rm.jobs[name].container is not None
        ]
        runnable.sort(
            key=lambda n: (-self.rm.jobs[n].priority, self.rm.jobs[n].submitted_at)
        )
        started = 0
        for name in runnable:
            rec = self._records[name]
            container = self.rm.jobs[name].container
            devices = frozenset(container.device_ids)
            if devices & claimed:
                continue
            token = CheckpointToken(
                name, state=rec.driver_state, on_checkpoint=self.hooks.checkpoint
            )
            if rec.cancel_requested:
                token.request_stop(CANCEL)
            rec.devices_used = container.size
            self._note_dispatch(name, rec)
            worker = _Worker(token=token, devices=devices)
            self._active[name] = worker
            worker.thread = threading.Thread(
                target=self._worker_main,
                args=(name, rec, container, token),
                name=f"platform-{name}",
                daemon=True,
            )
            worker.thread.start()
            claimed |= devices
            started += 1
        if started:
            self.obs.gauge(
                "pool_utilization", len(claimed) / max(self.rm.total, 1))
            self.obs.observe(
                "pool_utilization", len(claimed) / max(self.rm.total, 1))
        return started

    def _note_dispatch(self, name: str, rec: _JobRecord) -> None:
        """Record the queue-wait that just ended (platform lock held): a
        closed span from the last submit/requeue to now, plus the per-kind
        queue-wait histogram sample."""
        now = self._clock()
        if rec.first_run_at is None:
            rec.first_run_at = now
        qs = self.tracer.start(
            "queue_wait", job=name, parent=rec.root, t=rec.enqueued_at)
        self.tracer.end(qs, t=now)
        self.obs.observe(
            f"queue_wait_s.{rec.spec.kind}", max(now - rec.enqueued_at, 0.0))
        rec.enqueued_at = now

    def _execute(
        self, name: str, rec: _JobRecord, container, token: CheckpointToken
    ) -> None:
        """Run the driver once and settle the outcome — the shared body of
        both executors (a worker thread, or the serial step).  Settling is
        terminal-state-aware (defense in depth): a job that somehow reached
        a terminal state while the driver ran keeps it instead of being
        overwritten."""
        with self._cond:
            rec.attempts += 1
            attempt = rec.attempts
        span = self.tracer.start(
            "attempt", job=name, attempt=attempt, parent=rec.root,
            container=container.cid, devices=container.size,
            kind=rec.spec.kind, isolation=rec.spec.isolation,
        )
        token.bind_obs(
            tracer=self.tracer, span=span, obs=self.obs,
            kind=rec.spec.kind, attempt=attempt,
        )
        t0 = time.perf_counter()
        try:
            if rec.spec.isolation == "process":
                # enforced isolation: the attempt runs in a subprocess pinned
                # to the container's devices; this thread supervises the IPC
                # and escalates SIGTERM -> SIGKILL when the child blows its
                # grace window.  Exceptions surface identically to the
                # in-thread path, so settling below is shared.
                from repro.platform import isolation

                metrics = isolation.run_isolated(
                    rec.spec, container, token,
                    checkpoint_hook=self.hooks.checkpoint,
                    grace_s=rec.spec.grace_s,
                    log=lambda m: self._log_event(name, m),
                    chaos=self.chaos if self.chaos.armed else None,
                )
            elif rec.accepts_token:
                metrics = rec.driver.run(container, rec.ctx, token=token)
            else:
                metrics = rec.driver.run(container, rec.ctx)
        except JobInterrupted as e:
            self.tracer.tag(span, outcome=e.reason.lower())
            self.tracer.end(span)
            with self._cond:
                rec.run_time_s += time.perf_counter() - t0
                rec.checkpoints += token.checkpoints
                if rec.state in TERMINAL:
                    pass  # already settled (serial immediate cancel)
                elif e.reason == CANCEL or rec.cancel_requested:
                    rec.log(f"cancelled at checkpoint {token.checkpoints}",
                            self._clock())
                    self._finish(name, CANCELLED)
                elif e.reason == RESIZE and e.offer is not None:
                    self._apply_resize(name, rec, token, e.offer)
                else:
                    rec.log(
                        f"yielded at checkpoint {token.checkpoints} "
                        "(preempted mid-run)", self._clock())
                    # the job stays PREEMPTED/RUNNING in the scheduler and is
                    # redispatched once devices (and any worker claim) free
                    self._observe()
        except ContainerFailure as e:
            self.tracer.tag(span, outcome="container_failure")
            self.tracer.end(span)
            with self._cond:
                rec.run_time_s += time.perf_counter() - t0
                rec.checkpoints += token.checkpoints
                self._handle_container_failure(name, container, e)
        except Exception as e:  # driver bug / bad workload: job fails, pool survives
            self.tracer.tag(span, outcome="error")
            self.tracer.end(span)
            with self._cond:
                rec.run_time_s += time.perf_counter() - t0
                rec.checkpoints += token.checkpoints
                if rec.state not in TERMINAL:
                    self._finish(name, FAILED, error=f"{type(e).__name__}: {e}")
        else:
            self.tracer.tag(span, outcome="done")
            self.tracer.end(span)
            with self._cond:
                rec.run_time_s += time.perf_counter() - t0
                rec.checkpoints += token.checkpoints
                rec.metrics = metrics or {}
                if rec.state in TERMINAL:
                    pass  # cancelled mid-run in serial mode; keep its state
                elif rec.cancel_requested:
                    # the driver outran the cancel; record the withdrawal but
                    # keep whatever it computed
                    rec.log("cancel requested; run had already completed",
                            self._clock())
                    self._finish(name, CANCELLED)
                else:
                    self._finish(name, DONE)

    def _apply_resize(
        self, name: str, rec: _JobRecord, token: CheckpointToken,
        offer: ResizeOffer,
    ) -> None:
        """Commit an accepted ResizeOffer (platform lock held): the driver
        has yielded at a checkpoint with its progress persisted in
        ``token.state``; re-grant the container at the offered size and keep
        the job RUNNING so the dispatcher restarts the driver there — the
        same resume path a preemption takes, minus the queueing."""
        job = self.rm.jobs[name]
        old = job.container.size if job.container is not None else 0
        rec.log(
            f"yielded at checkpoint {token.checkpoints} "
            f"(accepted resize offer: {old} -> {offer.target_devices} "
            f"devices, {offer.reason})", self._clock())
        rspan = self.tracer.start(
            "resize_commit", job=name, attempt=rec.attempts, parent=rec.root,
            old=old, new=offer.target_devices, reason=offer.reason,
        )
        c = self.rm.resize(name, offer.target_devices)
        self.tracer.tag(rspan, granted=c is not None)
        self.tracer.end(rspan)
        if c is not None:
            self.obs.inc("resizes_committed")
            rec.log(f"re-granted container {c.cid} ({c.size} devices)",
                    self._clock())
            rec.state = rec.last_rm_state = JOB_RUNNING
        else:
            # the pool churned underneath the offer (or a preemption won the
            # race): the scheduler requeued the job; bridge whatever state
            # it left and let the normal resume path pick it back up
            rec.state = rec.last_rm_state = self.rm.jobs[name].state
            rec.log("resize not granted; awaiting reschedule", self._clock())
        self._observe()
        self._cond.notify_all()

    def _worker_main(
        self, name: str, rec: _JobRecord, container, token: CheckpointToken
    ) -> None:
        """Thread body: run the driver once, feed the outcome back."""
        self.hooks.worker_start(name)
        try:
            self._execute(name, rec, container, token)
        finally:
            with self._cond:
                self._active.pop(name, None)
                self._cond.notify_all()
            self.hooks.worker_exit(name, rec.state)

    # -- serial executor (benchmark baseline) --------------------------
    def _runnable(self) -> list[str]:
        return [
            name
            for name, rec in self._records.items()
            if rec.state not in TERMINAL
            and name not in self._active  # in-flight on another thread
            and self.rm.jobs[name].state == JOB_RUNNING
        ]

    def step(self) -> bool:
        """Serial mode: execute the highest-priority scheduled job in-process
        (to completion); True if any ran."""
        with self._cond:
            self._observe()
            runnable = self._runnable()
            if not runnable:
                return False
            name = min(
                runnable,
                key=lambda n: (-self.rm.jobs[n].priority,
                               self.rm.jobs[n].submitted_at),
            )
            rec = self._records[name]
            job = self.rm.jobs[name]
            container = job.container
            rec.devices_used = container.size
            self._note_dispatch(name, rec)
            token = CheckpointToken(
                name, state=rec.driver_state, on_checkpoint=self.hooks.checkpoint
            )
            # the in-flight claim: a second thread stepping the same platform
            # must not pick this job up, and cancel() goes cooperative
            self._active[name] = _Worker(
                token=token, devices=frozenset(container.device_ids)
            )
        try:
            # the driver runs outside the lock; serial mode never preempts
            # mid-run, and a cross-thread cancel flags the token
            self._execute(name, rec, container, token)
        finally:
            with self._cond:
                self._active.pop(name, None)
                self._observe()
                self._cond.notify_all()
        return True

    # -- client surface ------------------------------------------------
    def status(self, name: str) -> str:
        with self._cond:
            self._observe()
            return self._records[name].state

    def events(self, name: str) -> list[str]:
        with self._cond:
            self._observe()
            return list(self._records[name].events)

    def timeline(self) -> list[str]:
        """All tenants' structured event records merged on one absolute
        timeline (offsets from the earliest record), tagged by job —
        the cross-tenant view the per-job offset rendering can't give."""
        with self._cond:
            self._observe()
            recs = [
                (t, name, msg)
                for name, rec in self._records.items()
                for (t, msg) in rec.records
            ]
        recs.sort(key=lambda r: (r[0], r[1]))
        if not recs:
            return []
        t0 = recs[0][0]
        return [f"+{t - t0:.2f}s [{n}] {m}" for t, n, m in recs]

    def metrics_snapshot(self) -> dict:
        """Platform-wide metrics registry snapshot (counters, gauges,
        histogram percentiles) — see ``repro.obs.metrics`` for the catalog."""
        return self.obs.snapshot()

    def active_workers(self) -> list[str]:
        """Names of jobs a worker thread is currently executing."""
        with self._cond:
            return sorted(self._active)

    def cancel(self, name: str) -> bool:
        """Withdraw a job.  Queued/preempted/unstarted jobs cancel
        immediately; a job mid-run on a worker stops at its next driver
        checkpoint (cooperative), reaching CANCELLED when the worker yields.
        """
        with self._cond:
            self._observe()
            rec = self._records[name]
            if rec.state in TERMINAL or rec.cancel_requested:
                return False
            self.obs.inc("cancels")
            now = self._clock()
            worker = self._active.get(name)
            if worker is not None:
                rec.cancel_requested = True
                worker.token.request_stop(CANCEL)
                rec.log("cancel requested (stops at next checkpoint)", now)
                self._cond.notify_all()
                return True
            rec.state = CANCELLED
            rec.finished_at = now
            rec.log("cancelled", now)
            self.obs.inc("jobs_cancelled")
            self.tracer.tag(rec.root, state=CANCELLED)
            self.tracer.end(rec.root, t=now)
            self.rm.complete(name)
            self._observe()
            self._cond.notify_all()
            return True

    def wait(
        self,
        names: Union[str, Sequence[str], None] = None,
        timeout_s: float = 600.0,
        deadline_s: Optional[float] = None,
    ) -> Union[JobReport, dict[str, JobReport]]:
        """Drive the executor until the named jobs (default: all submitted so
        far) reach a terminal state; returns their JobReports (one, or
        name->report).  ``timeout_s`` bounds *stall* detection (pool held by
        foreign tenants) and ``deadline_s`` is a hard overall bound: on
        expiry a :class:`JobTimeout` is raised carrying each unfinished
        job's last lifecycle event.  Both run on the real clock even under
        an injected virtual clock."""
        single = isinstance(names, str)
        if single:
            targets = [names]
        elif names is None:
            with self._cond:  # snapshot races concurrent submit() otherwise
                targets = list(self._records)
        else:
            targets = list(names)
        deadline = (
            None if deadline_s is None else time.monotonic() + deadline_s
        )
        if self.concurrent:
            self._wait_concurrent(targets, timeout_s, deadline, deadline_s)
        else:
            self._wait_serial(targets, timeout_s, deadline, deadline_s)
        if single:
            return self.results(targets[0])
        return {n: self.results(n) for n in targets}

    def wait_any(
        self,
        names: Sequence[str],
        timeout_s: float = 600.0,
        return_after_s: Optional[float] = None,
    ) -> list[str]:
        """Drive the executor until *any* of ``names`` is terminal; returns
        the terminal subset (possibly several at once).  Unlike :meth:`wait`
        this hands control back as soon as one job settles, which is what a
        DAG driver needs: harvest the finished leg's artifacts and submit its
        dependents while sibling legs keep running.

        ``return_after_s`` bounds the wait: on expiry an empty list is
        returned even though nothing finished — the caller's cue to do
        time-based work (e.g. resubmit a leg whose retry hold lapsed) and
        call back in.  With it set, an empty ``names`` is a bounded sleep
        that still drives dispatch/chaos/elastic; without it, empty
        ``names`` returns immediately.  ``timeout_s`` bounds foreign-tenant
        stall detection exactly as in :meth:`wait`.
        """
        targets = list(names)
        if not targets and return_after_s is None:
            return []
        t0 = time.monotonic()
        if not self.concurrent:
            return self._wait_any_serial(targets, timeout_s, t0, return_after_s)
        with self._cond:
            while True:
                self._observe()
                done = [
                    n for n in targets
                    if self._records[n].state in TERMINAL
                    and n not in self._active
                ]
                if done:
                    return done
                if return_after_s is not None and \
                        time.monotonic() - t0 >= return_after_s:
                    return []
                self._tick_controllers()
                if self._dispatch():
                    continue
                self.elastic.maybe_step()
                timeout = self._wait_timeout(None)
                if return_after_s is not None:
                    timeout = min(
                        timeout,
                        max(return_after_s - (time.monotonic() - t0), 0.001))
                if self._active or self.rm.earliest_hold() is not None:
                    self._cond.wait(timeout=timeout)
                    continue
                foreign = self.rm.running_jobs(exclude=self._records)
                if foreign and time.monotonic() - t0 < timeout_s:
                    self._cond.wait(timeout=timeout)
                    continue
                if return_after_s is not None:
                    # nothing of ours runnable, but the caller polls with a
                    # bound: it may be about to submit more work (a DAG
                    # driver between legs), so this is not a stall yet
                    self._cond.wait(timeout=timeout)
                    continue
                raise self._stall(targets, foreign)

    def _wait_any_serial(
        self, targets: Sequence[str], timeout_s: float, t0: float,
        return_after_s: Optional[float],
    ) -> list[str]:
        while True:
            with self._cond:
                self._observe()
                done = [
                    n for n in targets
                    if self._records[n].state in TERMINAL
                    and n not in self._active
                ]
                if done:
                    return done
                if return_after_s is not None and \
                        time.monotonic() - t0 >= return_after_s:
                    return []
            if self.step():
                continue
            with self._cond:
                if self._tick_controllers():
                    continue
                self.elastic.maybe_step()
                timeout = self._wait_timeout(None)
                if return_after_s is not None:
                    timeout = min(
                        timeout,
                        max(return_after_s - (time.monotonic() - t0), 0.001))
                if self._active or self.rm.earliest_hold() is not None:
                    self._cond.wait(timeout=timeout)
                    continue
                foreign = self.rm.running_jobs(exclude=self._records)
                if foreign and time.monotonic() - t0 < timeout_s:
                    self._cond.wait(timeout=timeout)
                    continue
                if return_after_s is not None:
                    self._cond.wait(timeout=timeout)
                    continue
                raise self._stall(targets, foreign)

    def _stall(self, targets: Sequence[str], foreign: Sequence[str]) -> RuntimeError:
        stuck = [n for n in targets if self._records[n].state not in TERMINAL]
        return RuntimeError(
            f"platform stalled: {stuck} cannot be scheduled "
            f"(pool={self.rm.total}, free={len(self.rm.free)}, "
            f"quarantined={len(self.rm.quarantined)}"
            + (f", held by {foreign})" if foreign else ")")
        )

    def _check_deadline(
        self, targets: Sequence[str], deadline: Optional[float],
        deadline_s: Optional[float],
    ) -> None:
        """Raise JobTimeout when the hard deadline expired (lock held)."""
        if deadline is None or time.monotonic() < deadline:
            return
        pending = {
            n: (self._records[n].events[-1]
                if self._records[n].events else "(no events)")
            for n in targets
            if self._records[n].state not in TERMINAL or n in self._active
        }
        if pending:
            raise JobTimeout(pending, deadline_s or 0.0)

    def _wait_timeout(self, deadline: Optional[float]) -> float:
        """Condition-wait bound: waits are event-driven (worker exits,
        submits, and foreign-tenant completions all notify through the
        ResourceManager listener); this bound only exists so the elastic
        controller gets its poll cadence and a hard deadline fires on time.
        """
        base = 0.5  # safety net, not a poll: notifications do the waking
        if self.elastic.poll_s is not None:
            base = min(base, max(self.elastic.poll_s, 0.02))
        if self.chaos.armed:
            base = min(base, max(self.chaos.poll_s, 0.005))
        hold = self.rm.earliest_hold()
        if hold is not None:  # wake when a backoff hold lapses
            base = min(base, max(hold - time.monotonic(), 0.005))
        if self.heal_after_s is not None and self.rm.quarantined_at:
            base = min(base, max(self.heal_after_s / 4.0, 0.01))
        if deadline is not None:
            base = min(base, max(deadline - time.monotonic(), 0.001))
        return base

    def _tick_controllers(self) -> bool:
        """Per-wait-loop-iteration housekeeping (platform lock held): lapse
        backoff holds, run healing probes, step the chaos schedule.  True if
        pool state changed (something kicked or healed)."""
        changed = bool(self.rm.kick_expired())
        if self.heal_after_s is not None:
            changed = bool(self.rm.heal_expired(self.heal_after_s)) or changed
        self.chaos.maybe_step()
        return changed

    def _wait_concurrent(
        self, targets: Sequence[str], timeout_s: float,
        deadline: Optional[float] = None, deadline_s: Optional[float] = None,
    ) -> None:
        t0 = time.monotonic()
        with self._cond:
            while True:
                self._observe()
                # a finishing worker flips the state terminal just before it
                # leaves _active; wait for both so callers returning from
                # wait() never see their jobs' worker threads still live
                if all(self._records[n].state in TERMINAL for n in targets) \
                        and not any(n in self._active for n in targets):
                    return
                self._check_deadline(targets, deadline, deadline_s)
                self._tick_controllers()
                if self._dispatch():
                    continue
                self.elastic.maybe_step()
                if self._active:
                    # workers run; their exit (or a submit, or a pool-state
                    # change) notifies the condition
                    self._cond.wait(timeout=self._wait_timeout(deadline))
                    continue
                if self.rm.earliest_hold() is not None:
                    # everything runnable is in a backoff hold: not a stall,
                    # the timeout below wakes us when the hold lapses
                    self._cond.wait(timeout=self._wait_timeout(deadline))
                    continue
                foreign = self.rm.running_jobs(exclude=self._records)
                if foreign and time.monotonic() - t0 < timeout_s:
                    # event-driven: the foreign tenant's rm.complete() fires
                    # the manager listener, which notifies this condition
                    self._cond.wait(timeout=self._wait_timeout(deadline))
                    continue
                raise self._stall(targets, foreign)

    def _wait_serial(
        self, targets: Sequence[str], timeout_s: float,
        deadline: Optional[float] = None, deadline_s: Optional[float] = None,
    ) -> None:
        t0 = time.monotonic()
        while True:
            with self._cond:
                self._observe()
                if all(self._records[n].state in TERMINAL for n in targets):
                    return
                self._check_deadline(targets, deadline, deadline_s)
            if self.step():
                continue
            with self._cond:
                # serial mode only has live workers when another thread is
                # mid-step; the controller can still offer to those
                if self._tick_controllers():
                    continue  # a hold lapsed / device healed: retry step()
                self.elastic.maybe_step()
                if self._active:
                    # another thread is mid-step on this platform: its job
                    # wasn't runnable for us, so wait for it to settle
                    self._cond.wait(timeout=self._wait_timeout(deadline))
                    continue
                if self.rm.earliest_hold() is not None:
                    # runnable work is in a backoff hold, not stuck
                    self._cond.wait(timeout=self._wait_timeout(deadline))
                    continue
                # nothing of ours is scheduled: either a foreign tenant
                # (e.g. a FleetRunner on the same pool) holds the devices,
                # or the queue is genuinely stuck (job can never fit / pool
                # quarantined).  Foreign completions notify the condition
                # through the ResourceManager listener.
                foreign = self.rm.running_jobs(exclude=self._records)
                if foreign and time.monotonic() - t0 < timeout_s:
                    self._cond.wait(timeout=self._wait_timeout(deadline))
                    continue
                raise self._stall(targets, foreign)

    def run_batch(
        self,
        specs: Sequence[JobSpec],
        timeout_s: float = 600.0,
        deadline_s: Optional[float] = None,
    ) -> dict[str, JobReport]:
        """submit_batch + wait: the heterogeneous multi-tenant entrypoint."""
        names = self.submit_batch(specs)
        reports = self.wait(names, timeout_s=timeout_s, deadline_s=deadline_s)
        assert isinstance(reports, dict)
        return reports

    def results(self, name: str) -> JobReport:
        """JobReport for a job (a live snapshot if it isn't terminal yet)."""
        with self._cond:
            self._observe()
            rec = self._records[name]
            job = self.rm.jobs[name]
            now = self._clock()
            end = rec.finished_at if rec.finished_at is not None else now
            # a job that never executed queued until it finished (e.g. cancelled)
            first_run = rec.first_run_at if rec.first_run_at is not None else end
            return JobReport(
                name=name,
                kind=rec.spec.kind,
                state=rec.state,
                devices_used=rec.devices_used,
                queue_time_s=max(first_run - rec.submitted_at, 0.0),
                run_time_s=rec.run_time_s,
                wall_time_s=max(end - rec.submitted_at, 0.0),
                preemptions=job.preemptions,
                resumes=job.resumes,
                resizes=job.resizes,
                retries=rec.retries,
                checkpoints=rec.checkpoints,
                metrics=self._report_metrics(name, rec),
                events=list(rec.events),
                error=rec.error,
            )

    def _report_metrics(self, name: str, rec: _JobRecord) -> dict:
        """Driver metrics plus a per-job span-stage summary under "obs"
        (count/total/p50/p99 per stage) when tracing is on.  Serving
        fast-path counters (speculation / prefix sharing / chunked
        prefill) ride the attempt spans as ``serve.fastpath`` events;
        they are summed here into flat ``serve_*`` keys matching the
        registry catalog, so a job report carries its own counts even
        though the registry itself is platform-wide."""
        metrics = dict(rec.metrics)
        if self.tracer.enabled:
            spans = self.tracer.spans(name)
            if spans:
                metrics["obs"] = stage_summary(spans)
                fast: dict = {}
                for sp in spans:
                    for (_, ev_name, tags) in sp.events:
                        if ev_name == "serve.fastpath":
                            for k, v in tags.items():
                                key = f"serve_{k}"
                                fast[key] = fast.get(key, 0) + int(v)
                metrics["obs"].update(fast)
        return metrics
