"""ResourceManager: containers, preemption, elasticity, quarantine (paper §2.3)."""

import jax
import pytest

from repro.core.scheduler import (
    JOB_PENDING,
    Container,
    JOB_PREEMPTED,
    JOB_RUNNING,
    Job,
    ResourceManager,
    run_with_speculation,
)


def test_basic_allocation():
    rm = ResourceManager(16)
    rm.submit(Job("train", "train", devices=8))
    rm.submit(Job("sim", "simulate", devices=8))
    assert rm.jobs["train"].state == JOB_RUNNING
    assert rm.jobs["sim"].state == JOB_RUNNING
    assert rm.utilization() == 1.0


@pytest.mark.parametrize("host", [1, 4])
def test_container_device_ids_map_onto_jax_devices(monkeypatch, host):
    """Pool id ``d`` runs on ``jax.devices()[d % count]``: one chip per id
    on a pool sized to the host, everything on the one device otherwise."""
    chips = [f"chip{i}" for i in range(host)]
    monkeypatch.setattr(jax, "devices", lambda: chips)
    got = Container(cid=0, device_ids=(0, 1, 2, 3, 5)).jax_devices()
    assert got == [chips[d % host] for d in (0, 1, 2, 3, 5)]


def test_isolation_no_overlap():
    rm = ResourceManager(16)
    rm.submit(Job("a", "train", devices=8))
    rm.submit(Job("b", "train", devices=8))
    da = set(rm.jobs["a"].container.device_ids)
    db = set(rm.jobs["b"].container.device_ids)
    assert not (da & db)


def test_queueing_when_full():
    rm = ResourceManager(8)
    rm.submit(Job("a", "train", devices=8))
    rm.submit(Job("b", "train", devices=8))
    assert rm.jobs["b"].state == JOB_PENDING
    rm.complete("a")
    assert rm.jobs["b"].state == JOB_RUNNING


def test_elastic_shrink():
    rm = ResourceManager(12)
    rm.submit(Job("a", "train", devices=8))
    rm.submit(Job("b", "train", devices=8, min_devices=2))
    assert rm.jobs["b"].state == JOB_RUNNING
    assert rm.jobs["b"].container.size == 4  # shrank to the available block


def test_priority_preemption():
    rm = ResourceManager(8)
    rm.submit(Job("batch", "simulate", devices=8, priority=0))
    rm.submit(Job("urgent", "train", devices=8, min_devices=4, priority=10))
    assert rm.jobs["batch"].state == JOB_PREEMPTED
    assert rm.jobs["urgent"].state == JOB_RUNNING
    rm.complete("urgent")
    assert rm.jobs["batch"].state == JOB_RUNNING  # resumed
    assert rm.jobs["batch"].resumes == 1


def test_container_failure_quarantines_and_reschedules():
    rm = ResourceManager(8)
    rm.submit(Job("a", "train", devices=8, min_devices=2))
    dead = rm.jobs["a"].container.device_ids[:2]
    rm.fail_container("a", dead_devices=2)
    # rescheduled on the surviving devices (elastic), dead ones quarantined
    assert rm.jobs["a"].state == JOB_RUNNING
    assert set(dead) <= rm.quarantined
    assert not (set(rm.jobs["a"].container.device_ids) & rm.quarantined)
    rm.heal()
    assert not rm.quarantined


def _is_contiguous(ids):
    ids = sorted(ids)
    return ids == list(range(ids[0], ids[0] + len(ids)))


def test_allocation_is_contiguous_after_fragmentation():
    """_allocate must honor the contiguous sub-mesh promise: with holes in
    the pool it must not stitch fragments together, and a queued job runs on
    a truly contiguous block once one frees up."""
    rm = ResourceManager(8)
    for name in ("a", "b", "c", "d"):
        rm.submit(Job(name, "train", devices=2))
    rm.complete("a")  # frees {0,1}
    rm.complete("c")  # frees {4,5} -> free pool {0,1,4,5}, fragmented
    rm.submit(Job("e", "train", devices=4, min_devices=4))
    # 4 devices are free but no contiguous run of 4 exists
    assert rm.jobs["e"].state == JOB_PENDING
    rm.complete("b")  # frees {2,3} -> contiguous run 0..5
    assert rm.jobs["e"].state == JOB_RUNNING
    assert _is_contiguous(rm.jobs["e"].container.device_ids)


def test_elastic_shrink_halves_into_contiguous_hole():
    """A shrinkable job fits the largest contiguous hole even when the total
    free count suggests a bigger (fragmented) block."""
    rm = ResourceManager(8)
    for name in ("a", "b", "c", "d"):
        rm.submit(Job(name, "train", devices=2))
    rm.complete("a")
    rm.complete("c")  # free {0,1,4,5}
    rm.submit(Job("e", "train", devices=4, min_devices=2))
    assert rm.jobs["e"].state == JOB_RUNNING
    assert rm.jobs["e"].container.size == 2
    assert _is_contiguous(rm.jobs["e"].container.device_ids)


def test_all_containers_contiguous_under_churn():
    rm = ResourceManager(16)
    rm.submit(Job("a", "train", devices=4))
    rm.submit(Job("b", "simulate", devices=8))
    rm.submit(Job("c", "serve", devices=2))
    rm.complete("a")
    rm.submit(Job("d", "train", devices=2))
    rm.submit(Job("e", "mapgen", devices=4))
    for job in rm.jobs.values():
        if job.container is not None:
            assert _is_contiguous(job.container.device_ids), job.name


def test_no_wasted_preemption_when_fragmentation_blocks_allocation():
    """Preemption must not evict victims when the freed pool still has no
    contiguous run for the requester (the eviction would be pure loss)."""
    rm = ResourceManager(4)
    rm.submit(Job("a", "train", devices=1, priority=5))   # -> {0}
    rm.submit(Job("b", "train", devices=1, priority=0))   # -> {1}
    rm.submit(Job("c", "train", devices=1, priority=5))   # -> {2}
    rm.submit(Job("d", "train", devices=1, priority=0))   # -> {3}
    # only b and d are evictable (priority < 3); that would free {1, 3} —
    # no contiguous pair, so nobody should be preempted
    rm.submit(Job("e", "train", devices=2, min_devices=2, priority=3))
    assert rm.jobs["e"].state == JOB_PENDING
    assert rm.jobs["b"].state == JOB_RUNNING and rm.jobs["b"].preemptions == 0
    assert rm.jobs["d"].state == JOB_RUNNING and rm.jobs["d"].preemptions == 0
    rm.complete("c")  # frees {2}: evicting b now yields contiguous {1, 2}
    assert rm.jobs["e"].state == JOB_RUNNING
    assert _is_contiguous(rm.jobs["e"].container.device_ids)
    assert rm.jobs["d"].state == JOB_RUNNING  # d was never a useful victim


def test_duplicate_names_auto_uniquified():
    """submit() renames colliding jobs instead of raising; the returned
    name is the handle."""
    rm = ResourceManager(8)
    assert rm.submit(Job("job", "train", devices=2)) == "job"
    second = rm.submit(Job("job", "train", devices=2))
    third = rm.submit(Job("job", "train", devices=2))
    assert second == "job-2" and third == "job-3"
    assert {"job", "job-2", "job-3"} <= set(rm.jobs)
    assert rm.jobs[second].state == JOB_RUNNING


def test_speculative_execution():
    calls = []

    def task(p):
        calls.append(p)
        return p * 10

    runtimes = {0: 1.0, 1: 1.0, 2: 10.0, 3: 1.1}
    results, speculated = run_with_speculation(task, [0, 1, 2, 3], runtimes)
    assert speculated == [2]
    assert results[2] == 20
    assert calls.count(2) == 2  # backup launched for the straggler
