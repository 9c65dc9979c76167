"""Enforced process isolation: subprocess workers, SIGKILL recovery,
exactly-once accounting, and the SIGTERM/SIGKILL enforcement ladder.

Every test here spawns real isolated workers (fresh pythons importing jax),
hence the ``subprocess`` marker.
"""

from __future__ import annotations

import os
import signal
import time

import numpy as np
import pytest

import chaos_driver_fixture  # noqa: F401 — registers sleeper/crashy kinds
from repro.platform import ExecutorHooks, JobSpec, Platform

pytestmark = pytest.mark.subprocess

SCN = {"per_family": 2, "steps": 5, "chunks": 4}


def _rollout_leaves(report):
    import jax

    return jax.tree.leaves(report.metrics["_rollout"])


def _thread_reference(config=SCN):
    p = Platform(total_devices=4)
    rep = p.wait(
        p.submit(JobSpec(kind="scenario", devices=2, config=dict(config))),
        deadline_s=300,
    )
    assert rep.state == "DONE", rep.error
    return rep


def test_isolated_worker_is_pinned_to_its_container(monkeypatch):
    """The --xla_force_host_platform_device_count idiom: the child sees
    exactly its container's size as devices, whatever the parent has."""
    monkeypatch.setenv("REPRO_ISOLATION_IMPORT", "chaos_driver_fixture")
    p = Platform(total_devices=4)
    rep = p.wait(
        p.submit(JobSpec(
            kind="sleeper", devices=2, isolation="process",
            config={"naps": 2, "report_devices": True},
        )),
        deadline_s=300,
    )
    assert rep.state == "DONE", rep.error
    assert rep.metrics["devices"] == 2
    assert any("pinned via XLA_FLAGS" in e for e in rep.events)


def test_process_isolation_matches_thread_mode_bitwise():
    p = Platform(total_devices=4)
    rep = p.wait(
        p.submit(JobSpec(
            kind="scenario", devices=2, isolation="process",
            config=dict(SCN),
        )),
        deadline_s=300,
    )
    assert rep.state == "DONE", rep.error
    ref = _thread_reference()
    for a, b in zip(_rollout_leaves(rep), _rollout_leaves(ref)):
        np.testing.assert_array_equal(a, b)


def test_sigkill_mid_chunk_exactly_once_and_bitwise_resume():
    """kill -9 the isolated worker mid-unit: the job resumes from the last
    shipped snapshot, every scenario runs exactly once (completed chunk
    ranges partition the shard with no overlap), and the merged result is
    bitwise-equal to a fault-free run."""
    killed: list[int] = []

    def ckpt(name, token):
        if token.checkpoints == 2 and not killed and token.worker_pid:
            killed.append(token.worker_pid)
            os.kill(token.worker_pid, signal.SIGKILL)

    p = Platform(
        total_devices=4, hooks=ExecutorHooks(checkpoint=ckpt),
        retry_backoff_s=0.02,
    )
    name = p.submit(JobSpec(
        kind="scenario", devices=2, isolation="process", max_retries=2,
        config=dict(SCN),
    ))
    rep = p.wait(name, deadline_s=300)
    assert killed, "the hook never saw a live worker pid"
    assert rep.state == "DONE", rep.error
    assert rep.retries == 1
    assert any("rc=-9" in e for e in rep.events)  # the SIGKILL death
    assert any("resubmitting in" in e and "backoff" in e for e in rep.events)
    # exactly-once: the completed (lo, hi) ranges partition [0, n) with no
    # gaps and no overlaps — nothing lost, nothing run twice
    done = p._records[name].driver_state["done"]
    ranges = sorted(done)
    assert ranges[0][0] == 0
    assert ranges[-1][1] == rep.metrics["scenarios"]
    for (_, h1), (l2, _) in zip(ranges, ranges[1:]):
        assert h1 == l2, f"gap/overlap at {h1} vs {l2}"
    ref = _thread_reference()
    for a, b in zip(_rollout_leaves(rep), _rollout_leaves(ref)):
        np.testing.assert_array_equal(a, b)


def test_enforced_cancel_escalates_to_sigkill(monkeypatch, tmp_path):
    """A stuck worker (never checkpoints again, ignores SIGTERM) cannot be
    stopped cooperatively; the supervisor enforces the cancel through the
    full SIGTERM -> SIGKILL ladder within the grace window."""
    import threading

    monkeypatch.setenv("REPRO_ISOLATION_IMPORT", "chaos_driver_fixture")
    ready = tmp_path / "ready"
    p = Platform(total_devices=2)
    name = p.submit(JobSpec(
        kind="sleeper", devices=1, isolation="process", grace_s=0.5,
        config={"stuck": True, "ignore_sigterm": True,
                "ready_file": str(ready)},
    ))
    # the wait loop drives dispatch, so it must run while we watch for the
    # worker to spawn and then cancel from outside
    result = {}
    waiter = threading.Thread(
        target=lambda: result.update(rep=p.wait(name, deadline_s=180)),
        daemon=True,
    )
    waiter.start()
    deadline = time.monotonic() + 120
    # cancel once the worker ignores SIGTERM: a SIGTERM that lands while
    # the child is still importing ends it before the ladder can escalate
    while not ready.exists():
        assert time.monotonic() < deadline, p.events(name)
        time.sleep(0.05)
    assert p.cancel(name)
    waiter.join(timeout=180)
    assert not waiter.is_alive(), "wait() never returned after the cancel"
    rep = result["rep"]
    assert rep.state == "CANCELLED"
    events = "\n".join(rep.events)
    assert "enforcing cancel with SIGTERM" in events
    assert "SIGTERM ignored; SIGKILL" in events
    assert "enforced interruption" in events


def test_flaky_process_worker_retries_with_backoff(monkeypatch):
    """ContainerFailure raised *inside* the child crosses the pipe and
    rides the same backoff/retry path, with driver state persisted."""
    monkeypatch.setenv("REPRO_ISOLATION_IMPORT", "chaos_driver_fixture")
    p = Platform(total_devices=2, retry_backoff_s=0.02)
    rep = p.wait(
        p.submit(JobSpec(
            kind="crashy", devices=1, isolation="process", max_retries=3,
            config={"fail_attempts": 2, "dead_devices": 0},
        )),
        deadline_s=300,
    )
    assert rep.state == "DONE", rep.error
    assert rep.retries == 2
    assert rep.metrics["attempt"] == 3  # state survived both child deaths
    assert sum("resubmitting in" in e for e in rep.events) == 2
    assert len(p.rm.quarantined) == 0  # dead_devices=0: workers, not devices


def test_process_isolation_refused_off_cpu(monkeypatch):
    """On a chip backend the supervising process holds the chip, so an
    isolated child could never reach it: submit refuses, naming why, and
    no worker is started."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = Platform(total_devices=4)
    with pytest.raises(ValueError, match="one process at a time"):
        p.submit(JobSpec(kind="scenario", devices=2, isolation="process",
                         config=dict(SCN)))
    assert p.rm.jobs == {}
