"""Closed-loop scenario sweep CLI — thin wrapper over the platform API (§3).

    PYTHONPATH=src python -m repro.launch.scenario_job --per-family 64 --shards 4
    PYTHONPATH=src python -m repro.launch.scenario_job --shards auto
    PYTHONPATH=src python -m repro.launch.scenario_job --ab-test --policy aeb

A sweep is submitted as ``--shards`` independent ``scenario`` jobs (each
rolling out its slice of the seed-deterministic batch on its own container)
and the per-shard metrics are merged back into one
:class:`~repro.scenario.metrics.ScenarioReport` — heterogeneous batch
submission over the shared pool.  ``--shards auto`` derives the shard count
from the pool's free contiguous device runs (one ``--devices-per-shard``
container per run slice) instead of making the caller guess; the merged
report is identical either way.  ``--ab-test`` runs the deployed and
candidate sweeps through the same path and gates with
:func:`repro.scenario.metrics.qualify`.
"""

from __future__ import annotations

import argparse
import time

from repro.compile_cache import enable_compilation_cache
from repro.platform import JobSpec, Platform, ScenarioJobConfig, aggregate_scenario_metrics
from repro.platform.services import scenario_policies
from repro.scenario.dsl import FAMILIES

POLICIES = tuple(scenario_policies())


def resolve_shards(platform: Platform, shards, devices_per_shard: int) -> int:
    """``--shards`` value -> shard count.  ``auto`` derives it from the
    pool's free contiguous runs — one shard container per
    ``devices_per_shard`` slice of each run, the same plan the serve-cell
    tier uses (:func:`repro.launch.cells.serve_cell_plan`), so the two
    pool-saturation policies can never drift apart."""
    if isinstance(shards, str) and shards.strip().lower() == "auto":
        from repro.launch.cells import serve_cell_plan

        return len(serve_cell_plan(
            platform.rm, devices_per_cell=devices_per_shard
        ))
    n = int(shards)
    if n < 1:
        raise ValueError(f"--shards must be >= 1 or 'auto', got {shards!r}")
    return n


def _sweep(platform: Platform, args, policy: str, prefix: str):
    """Submit one scenario job per shard, wait, merge into a ScenarioReport."""
    t0 = time.perf_counter()
    num_shards = resolve_shards(platform, args.shards, args.devices_per_shard)
    specs = [
        JobSpec(
            kind="scenario",
            name=f"{prefix}-{i}",
            config=ScenarioJobConfig(
                families=args.families, per_family=args.per_family,
                steps=args.steps, dt=args.dt, seed=args.seed, policy=policy,
                use_pallas=args.pallas_collision,
                shard_index=i, num_shards=num_shards,
            ),
            devices=args.devices_per_shard,
            isolation=args.isolation,
        )
        for i in range(num_shards)
    ]
    # key strictly by the *returned* (uniquified) names, in shard order:
    # a concurrent sweep submitting the same shard names on a shared
    # platform gets "-2"-suffixed jobs, and keying by the request-side
    # names would cross-merge the two sweeps' reports
    names = platform.submit_batch(specs)
    reports = platform.wait(names)
    assert isinstance(reports, dict)
    bad = {n: reports[n].error for n in names if reports[n].state != "DONE"}
    if bad:
        raise RuntimeError(f"scenario shards failed: {bad}")
    return aggregate_scenario_metrics(
        [reports[n].metrics for n in names], time.perf_counter() - t0
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--families", nargs="*", default=None, choices=sorted(FAMILIES),
                    help="scenario families to sweep (default: all)")
    ap.add_argument("--per-family", type=int, default=64)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--dt", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="aeb", choices=sorted(POLICIES))
    ap.add_argument("--shards", default="4",
                    help="shard count, or 'auto' to derive one shard per "
                         "--devices-per-shard slice of the pool's free runs")
    ap.add_argument("--devices", type=int, default=8, help="scheduler pool size")
    ap.add_argument("--devices-per-shard", type=int, default=2)
    ap.add_argument("--pallas-collision", action="store_true",
                    help="route collision/TTC through the Pallas kernel")
    ap.add_argument("--isolation", choices=["thread", "process"],
                    default="thread",
                    help="process: each shard attempt runs in a subprocess "
                         "pinned to its container, with enforced (SIGTERM/"
                         "SIGKILL) preemption and cancel")
    ap.add_argument("--ab-test", action="store_true",
                    help="qualify --policy against the deployed baseline")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    platform = Platform(total_devices=args.devices)
    if args.ab_test:
        from repro.scenario.metrics import qualify

        rep_a = _sweep(platform, args, "baseline", "ab-deployed")
        rep_b = _sweep(platform, args, args.policy, "ab-candidate")
        print("[scenario] deployed (baseline):")
        print(rep_a.summary())
        print(f"[scenario] candidate ({args.policy}):")
        print(rep_b.summary())
        print("[scenario] verdict:", qualify(rep_a, rep_b).verdict())
    else:
        rep = _sweep(platform, args, args.policy, "scenario")
        print(rep.summary())


if __name__ == "__main__":
    main()
