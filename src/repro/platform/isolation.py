"""Enforced process isolation: one subprocess worker per granted container.

The default executor runs drivers on threads, so every interruption is
*cooperative* — a driver that never reaches ``token.checkpoint()`` can hold
its devices forever, and a simulated ``ContainerFailure`` never actually
loses a process.  ``JobSpec(isolation="process")`` makes the failure domain
real: each run attempt executes in a fresh subprocess pinned to its
container's devices via the ``--xla_force_host_platform_device_count``
idiom (the same fake-device trick ``launch/dryrun.py`` uses), with the
CheckpointToken protocol carried over a pickle-framed pipe pair:

    parent -> child   bootstrap {spec, container, resume state, trace ctx}
    child  -> parent  ("checkpoint", n, state)    at every token.checkpoint()
    parent -> child   ("continue", directives) | ("stop", reason)
                      | ("resize", offer) | ("fault", msg, dead_devices)
    child  -> parent  ("done", metrics, state, spans) | ("interrupted",
                      reason, offer, state, spans) | ("error", kind, msg,
                      dead, state, spans)

The bootstrap's trace context (parent span id + clock origin) lets the
child run its own :class:`~repro.obs.trace.Tracer` whose spans nest under
the supervising worker's attempt span; the child's span dicts ride the
terminal frame (never a droppable checkpoint frame) and are merged into
the parent tracer, so one timeline covers both sides of the boundary.

The child blocks inside ``checkpoint()`` waiting for the reply, so the
parent-side supervisor mirrors the thread executor's semantics exactly: the
``ExecutorHooks.checkpoint`` hook runs on the supervising worker thread
while the child is parked (the deterministic concurrency harness works
unchanged), stops/resizes/faults requested on the *parent* token are
relayed at the next checkpoint, and ``token.state`` is refreshed from the
child's snapshot so resume-after-anything uses the usual driver state.

Only the CPU backend can be split this way: a chip belongs to one process
at a time, and the supervising process holds it once any tenant has
touched JAX, so ``Platform.submit`` refuses ``isolation="process"`` on any
other backend (:func:`check_backend`) instead of starting a child that
would run on the CPU or hang waiting for the chip.

What threads cannot give, processes do — **enforcement**: a stop (preempt /
cancel) the child has not honored within ``JobSpec.grace_s`` escalates to
SIGTERM, then SIGKILL, and the supervisor raises the interruption itself
from the last snapshot.  A child that dies unexpectedly (chaos SIGKILL, a
crash, an OOM) surfaces as ``ContainerFailure(dead_devices=0)`` — the
worker is gone but the devices are fine — and rides the normal
quarantine/backoff/retry path.

Test hook: the child imports the comma-separated modules named by the
``REPRO_ISOLATION_IMPORT`` environment variable before resolving the
driver, so suites can register throwaway driver kinds that exist in the
child too.
"""

from __future__ import annotations

import importlib
import os
import pickle
import select
import struct
import subprocess
import sys
import time
import traceback
from typing import Callable, Optional

from repro.core.scheduler import Container
from repro.platform.driver import (
    CANCEL,
    RESIZE,
    CheckpointToken,
    ContainerFailure,
    JobInterrupted,
    get_driver,
)
from repro.platform.spec import JobSpec

_LEN = struct.Struct(">I")


# ---------------------------------------------------------------------------
# framing: 4-byte big-endian length + pickle, over blocking pipe fds
# ---------------------------------------------------------------------------


def _send(f, obj) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    f.write(_LEN.pack(len(payload)) + payload)
    f.flush()


def _read_exact(f, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            raise EOFError(f"IPC channel closed mid-frame ({len(buf)}/{n} bytes)")
        buf += chunk
    return buf


def _recv(f):
    (n,) = _LEN.unpack(_read_exact(f, _LEN.size))
    return pickle.loads(_read_exact(f, n))


# ---------------------------------------------------------------------------
# parent side: spawn + supervise one isolated attempt
# ---------------------------------------------------------------------------


def check_backend() -> None:
    """Refuse process isolation unless this process runs on the CPU."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise ValueError(
            f"isolation='process' needs the cpu backend, but this process "
            f"runs on {backend!r}: a chip belongs to one process at a time, "
            "so an isolated worker could not reach it; use "
            "isolation='thread'"
        )


def _noop_log(msg: str) -> None:
    return None


def _enforce_kill(proc, token, log, term_wait_s: float = 1.0) -> None:
    """The enforcement ladder: SIGTERM, a short wait, then SIGKILL."""
    reason = (token.reason or CANCEL).lower()
    tr = token.tracer
    sp = None
    if tr is not None:
        sp = tr.start(
            "enforce", job=token.job_name, attempt=token.attempt,
            parent=token.span, reason=reason, pid=proc.pid,
        )
    log(f"grace window expired; enforcing {reason} with SIGTERM "
        f"(pid={proc.pid})")
    if tr is not None:
        tr.event(sp, "sigterm", pid=proc.pid)
    proc.terminate()
    try:
        proc.wait(timeout=term_wait_s)
    except subprocess.TimeoutExpired:
        log(f"SIGTERM ignored; SIGKILL (pid={proc.pid})")
        if tr is not None:
            tr.event(sp, "sigkill", pid=proc.pid)
        proc.kill()
        proc.wait(timeout=10.0)
    log("isolated worker killed (enforced interruption); "
        "resuming from the last checkpoint snapshot")
    if tr is not None:
        tr.end(sp)


def run_isolated(
    spec: JobSpec,
    container: Container,
    token: CheckpointToken,
    *,
    checkpoint_hook: Optional[Callable[[str, CheckpointToken], None]] = None,
    grace_s: float = 5.0,
    log: Callable[[str], None] = _noop_log,
    chaos=None,
    poll_s: float = 0.02,
) -> dict:
    """Run one attempt of ``spec`` in an isolated subprocess; mirrors
    ``driver.run(container, ctx, token=...)`` semantics (returns metrics,
    raises JobInterrupted / ContainerFailure).  ``chaos`` duck-types
    ``take_ipc(job_name) -> None | ("delay", s) | ("drop",)`` — the chaos
    controller's per-message IPC fault hook."""
    c2p_r, c2p_w = os.pipe()
    p2c_r, p2c_w = os.pipe()
    env = dict(os.environ)
    # the container pinning idiom: the child sees exactly its grant as
    # fake host devices (set before the child's first jax import)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={container.size}"
    )
    env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.platform.isolation",
         str(p2c_r), str(c2p_w)],
        env=env, pass_fds=(p2c_r, c2p_w), close_fds=True,
    )
    os.close(p2c_r)
    os.close(c2p_w)
    r = os.fdopen(c2p_r, "rb")
    w = os.fdopen(p2c_w, "wb")
    token.worker_pid = proc.pid
    log(f"isolated worker spawned (pid={proc.pid}, "
        f"{container.size} devices pinned via XLA_FLAGS)")
    stop_deadline: Optional[float] = None

    def send(obj) -> None:
        # a write can hit a just-killed child (chaos SIGKILL mid-boot, an
        # OOM): that is the same worker-death failure a read EOF signals
        try:
            _send(w, obj)
        except BrokenPipeError:
            try:
                proc.wait(timeout=10.0)
            except Exception:
                pass
            raise ContainerFailure(
                f"isolated worker died mid-message (pid={proc.pid}, "
                f"rc={proc.returncode})", dead_devices=0) from None

    # span context crosses the isolation boundary inside the bootstrap
    # frame: the child builds its own tracer on a clock anchored to the
    # parent's, numbers spans from CHILD_SPAN_BASE (no id collisions),
    # and ships its span dicts back on the terminal frame for merge()
    tr = token.tracer
    trace_info = None
    if tr is not None and getattr(tr, "enabled", False):
        trace_info = {
            "enabled": True,
            "job": token.job_name,
            "attempt": token.attempt,
            "parent": (
                list(token.span.span_id) if token.span is not None else None
            ),
            "clock0": tr.now(),
        }

    def merge_spans(frame_spans) -> None:
        if tr is not None and frame_spans:
            tr.merge(frame_spans)

    try:
        send({
            "spec": spec,
            "cid": container.cid,
            "device_ids": container.device_ids,
            "state": token.state,
            "trace": trace_info,
        })
        while True:
            if token.should_stop() and stop_deadline is None:
                stop_deadline = time.monotonic() + grace_s
            if stop_deadline is not None and time.monotonic() >= stop_deadline:
                _enforce_kill(proc, token, log)
                raise JobInterrupted(token.reason or CANCEL)
            ready, _, _ = select.select([r], [], [], poll_s)
            if not ready:
                if proc.poll() is not None:
                    raise ContainerFailure(
                        f"isolated worker died (pid={proc.pid}, "
                        f"rc={proc.returncode})", dead_devices=0)
                continue
            try:
                msg = _recv(r)
            except EOFError:
                proc.wait(timeout=10.0)
                raise ContainerFailure(
                    f"isolated worker died mid-message (pid={proc.pid}, "
                    f"rc={proc.returncode})", dead_devices=0) from None
            kind = msg[0]
            if kind == "checkpoint":
                ipc = chaos.take_ipc(token.job_name) if chaos is not None \
                    else None
                if ipc is not None and ipc[0] == "delay":
                    time.sleep(float(ipc[1]))
                n, snapshot = int(msg[1]), msg[2]
                token.checkpoints = n
                if ipc is not None and ipc[0] == "drop":
                    # one lost state snapshot: the parent keeps the previous
                    # one — chunk-keyed driver state makes the re-run of
                    # anything newer bitwise-identical, never duplicated
                    pass
                else:
                    token.state.clear()
                    token.state.update(snapshot)
                if checkpoint_hook is not None:
                    # same contract as the thread executor: the harness hook
                    # runs on this worker thread while the child is parked
                    # awaiting the reply
                    checkpoint_hook(token.job_name, token)
                if token.should_stop():
                    send(("stop", token.reason or CANCEL))
                    # the child is cooperating now (save may be slow): give
                    # it a fresh grace window to persist and yield
                    stop_deadline = time.monotonic() + grace_s
                    continue
                fault = token.take_fault()
                if fault is not None:
                    send(("fault", fault[0], int(fault[1])))
                    continue
                offer = token.take_resize()
                if offer is not None:
                    send(("resize", offer))
                    continue
                send(("continue", token.drain_directives()))
            elif kind == "done":
                token.state.clear()
                token.state.update(msg[2])
                merge_spans(msg[3] if len(msg) > 3 else None)
                proc.wait(timeout=30.0)
                return msg[1]
            elif kind == "interrupted":
                reason, offer, snapshot = msg[1], msg[2], msg[3]
                token.state.clear()
                token.state.update(snapshot)
                merge_spans(msg[4] if len(msg) > 4 else None)
                proc.wait(timeout=30.0)
                raise JobInterrupted(reason, offer=offer)
            elif kind == "error":
                ekind, emsg, dead, snapshot = msg[1], msg[2], msg[3], msg[4]
                token.state.clear()
                token.state.update(snapshot)
                merge_spans(msg[5] if len(msg) > 5 else None)
                proc.wait(timeout=30.0)
                if ekind == "ContainerFailure":
                    raise ContainerFailure(emsg, dead_devices=int(dead or 0))
                raise RuntimeError(f"isolated worker failed: {ekind}: {emsg}")
            else:  # pragma: no cover — protocol bug
                raise RuntimeError(f"unknown IPC frame {kind!r}")
    finally:
        token.worker_pid = None
        if proc.poll() is None:
            proc.kill()
        try:
            proc.wait(timeout=10.0)
        except Exception:
            pass
        r.close()
        w.close()


# ---------------------------------------------------------------------------
# child side: python -m repro.platform.isolation <read_fd> <write_fd>
# ---------------------------------------------------------------------------


class _ChildToken(CheckpointToken):
    """The driver-facing token inside the isolated worker.  ``checkpoint``
    is a synchronous round-trip to the supervisor: publish the state
    snapshot, block for the verdict, then continue / raise exactly like the
    in-thread token would."""

    def __init__(self, job_name: str, state: dict, rfile, wfile):
        super().__init__(job_name, state=state)
        self._r = rfile
        self._w = wfile

    def checkpoint(self, save=None) -> None:
        self.checkpoints += 1
        tr, sp = self.tracer, None
        if tr is not None:
            sp = tr.start(
                "checkpoint", job=self.job_name, attempt=self.attempt,
                parent=self.span, n=self.checkpoints,
            )
        outcome = "continue"
        try:
            self._consume_stalls()  # stalls shipped with an earlier reply
            _send(self._w, ("checkpoint", self.checkpoints, self.state))
            tv0 = time.perf_counter()
            reply = _recv(self._r)
            if tr is not None:
                # the verdict-wait phase: child parked while the supervisor
                # ran hooks and decided continue/stop/fault/resize
                tr.tag(sp, verdict_wait_s=time.perf_counter() - tv0)
            kind = reply[0]
            if kind == "continue":
                for d in reply[1]:
                    self.post_directive(d)
                # a ("stall_checkpoint", s) directive stalls *this* checkpoint
                self._consume_stalls()
                return
            if kind == "stop":
                outcome = str(reply[1]).lower()
                self._timed_save(save, tr, sp)
                raise JobInterrupted(reply[1])
            if kind == "fault":
                outcome = "fault"
                raise ContainerFailure(reply[1], dead_devices=int(reply[2]))
            if kind == "resize":
                outcome = "resize"
                self._timed_save(save, tr, sp)
                raise JobInterrupted(RESIZE, offer=reply[1])
            raise RuntimeError(f"unknown checkpoint reply {kind!r}")
        finally:
            if tr is not None:
                tr.tag(sp, outcome=outcome)
                tr.end(sp)


def _child_main(argv: list[str]) -> int:
    r = os.fdopen(int(argv[0]), "rb")
    w = os.fdopen(int(argv[1]), "wb")
    boot = _recv(r)
    # test hook: register extra driver kinds in this process too
    for mod in os.environ.get("REPRO_ISOLATION_IMPORT", "").split(","):
        if mod.strip():
            importlib.import_module(mod.strip())
    import repro.platform  # noqa: F401 — registers the built-in drivers
    from repro.platform.client import _wants_token

    spec: JobSpec = boot["spec"]
    container = Container(int(boot["cid"]), tuple(boot["device_ids"]))
    tinfo = boot.get("trace") or {}
    # the supervisor's (uniquified) job name, so child span ids line up
    # with the parent trace after the merge
    job_name = tinfo.get("job") or spec.name or spec.kind
    token = _ChildToken(job_name, boot["state"], r, w)
    tracer = None
    run_span = None
    if tinfo.get("enabled"):
        from repro.obs.trace import CHILD_SPAN_BASE, Tracer

        epoch = time.perf_counter()
        clock0 = float(tinfo.get("clock0", 0.0))
        tracer = Tracer(
            clock=lambda: clock0 + (time.perf_counter() - epoch),
            seq0=CHILD_SPAN_BASE,
        )
        run_span = tracer.start(
            "isolated_run", job=job_name,
            attempt=int(tinfo.get("attempt", 0)),
            parent=tuple(tinfo["parent"]) if tinfo.get("parent") else None,
            pid=os.getpid(), devices=container.size,
        )
        token.bind_obs(
            tracer=tracer, span=run_span, kind=spec.kind,
            attempt=int(tinfo.get("attempt", 0)),
        )

    def spans() -> list:
        if tracer is None:
            return []
        tracer.end(run_span)
        return tracer.to_dicts()

    try:
        driver = get_driver(spec.kind)
        ctx = driver.prepare(spec)
        if _wants_token(driver):
            metrics = driver.run(container, ctx, token=token)
        else:
            metrics = driver.run(container, ctx)
    except JobInterrupted as e:
        # state is sent *after* the driver's finally blocks ran, so wall-
        # clock accumulators etc. survive the yield
        _send(w, ("interrupted", e.reason, e.offer, token.state, spans()))
    except ContainerFailure as e:
        _send(w, ("error", "ContainerFailure", str(e), e.dead_devices,
                  token.state, spans()))
    except BaseException as e:  # noqa: BLE001 — everything must cross the pipe
        _send(w, ("error", type(e).__name__,
                  f"{e}\n{traceback.format_exc()}", None, token.state, spans()))
    else:
        _send(w, ("done", metrics, token.state, spans()))
    w.flush()
    return 0


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv[1:]))
