"""Campaign CLI — plan and drive the closed-loop qualification campaign (§5).

    PYTHONPATH=src python -m repro.launch.campaign --per-family 8 --fan-out 4
    PYTHONPATH=src python -m repro.launch.campaign --chaos --faults 4
    PYTHONPATH=src python -m repro.launch.campaign --flip-ab  # gate-false leg

Builds the five-leg qualification DAG (scenario sweep -> near-miss mining ->
train -> A/B qualify gate -> conditional serve rollout), drives it on one
shared platform pool, prints the campaign report, and optionally exports the
span stream (``--trace-out``) so the Perfetto timeline shows the DAG
critical path.  ``--chaos`` arms a seeded mid-campaign
:class:`~repro.platform.chaos.FaultPlan`; the campaign must still converge,
and because artifacts are content-addressed the final versions can be
diffed against a fault-free run's.  ``--flip-ab`` swaps baseline and
candidate so the gate rejects and the rollout leg is skipped.  Rerunning
with the same ``--artifacts-dir`` reuses legs whose inputs are unchanged
(``SKIPPED_CACHED``).
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import tempfile
from pathlib import Path

from repro.campaign import ArtifactStore, CampaignDriver, qualification_campaign
from repro.campaign.report import render_report
from repro.compile_cache import enable_compilation_cache
from repro.platform import DONE, FaultPlan, Platform
from repro.platform.chaos import FAIL_DEVICE, KILL_WORKER, STALL_CHECKPOINT

# fault kinds viable for the campaign's thread-isolated tenants (the IPC
# faults need process workers and would defer forever; see repro.platform
# .chaos's in-order determinism)
CHAOS_KINDS = (KILL_WORKER, FAIL_DEVICE, STALL_CHECKPOINT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--devices", type=int, default=8, help="pool size")
    ap.add_argument("--fan-out", default="4",
                    help="sweep shard count (>= 2), or 'auto' to derive "
                         "from the pool's free runs")
    ap.add_argument("--devices-per-shard", type=int, default=2)
    ap.add_argument("--per-family", type=int, default=8)
    ap.add_argument("--scenario-steps", type=int, default=40)
    ap.add_argument("--train-steps", type=int, default=6)
    ap.add_argument("--serve-gen", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--flip-ab", action="store_true",
                    help="swap baseline/candidate: the gate rejects and the "
                         "rollout leg is skipped")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a seeded FaultPlan mid-campaign")
    ap.add_argument("--faults", type=int, default=4)
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--artifacts-dir", default=None,
                    help="artifact + checkpoint root (default: a tempdir; "
                         "pass a fixed dir to get leg reuse across runs)")
    ap.add_argument("--no-reuse", action="store_true",
                    help="disable memoized leg skipping")
    ap.add_argument("--report-out", default=None,
                    help="also write the rendered report to this file")
    ap.add_argument("--trace-out", default=None,
                    help="export the span stream (JSONL) to this file")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    with contextlib.ExitStack() as stack:
        root = args.artifacts_dir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="repro_campaign_"))
        platform = Platform(
            total_devices=args.devices,
            chaos_plan=(FaultPlan(seed=args.chaos_seed, faults=args.faults,
                                  kinds=CHAOS_KINDS)
                        if args.chaos else None),
            retry_backoff_s=0.02,
            heal_after_s=0.5,
            backoff_seed=args.seed,
        )
        base, cand = "baseline", "aeb"
        if args.flip_ab:
            base, cand = cand, base
        spec = qualification_campaign(
            ckpt_root=f"{root}/ckpt",
            arch=args.arch,
            per_family=args.per_family,
            scenario_steps=args.scenario_steps,
            baseline_policy=base,
            candidate_policy=cand,
            fan_out=(args.fan_out if args.fan_out == "auto"
                     else int(args.fan_out)),
            devices_per_shard=args.devices_per_shard,
            train_steps=args.train_steps,
            serve_gen=args.serve_gen,
            seed=args.seed,
        )
        store = ArtifactStore(f"{root}/artifacts")
        driver = CampaignDriver(
            platform, spec, store, reuse=not args.no_reuse,
            backoff_seed=args.seed,
        )
        try:
            report = driver.run()
        finally:
            store.flush()
            store.close()

        text = render_report(report)
        print(text)
        if args.chaos:
            s = platform.chaos.summary()
            print(f"[campaign] chaos: {s['injected']} faults injected "
                  f"({dict(s['by_kind'])}), {s['skipped']} skipped")
        if args.report_out:
            Path(args.report_out).write_text(text + "\n")
            print(f"[campaign] report written to {args.report_out}")
        if args.trace_out:
            from repro.obs import write_jsonl

            spans = platform.tracer.spans()
            write_jsonl(spans, args.trace_out)
            print(f"[campaign] {len(spans)} spans written to {args.trace_out}")
        if report.state != DONE:
            sys.exit(1)


if __name__ == "__main__":
    main()
