"""Training CLI — thin wrapper over the unified platform API (paper §4).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --batch 8 --seq 256 --scale smoke --ckpt-dir /tmp/run1

Parses flags into a ``train`` :class:`~repro.platform.JobSpec` and submits
through :class:`~repro.platform.Platform`; the actual training loop (BinPipe
RDD data path, ZeRO-sharded state, crash-restart from the newest committed
checkpoint, ``--fail-at`` failure injection) lives in
:class:`repro.platform.services.TrainDriver`.
"""

from __future__ import annotations

import argparse
import sys

from repro.compile_cache import enable_compilation_cache
from repro.platform import DONE, JobSpec, Platform, TrainJobConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke",
                    help="smoke: reduced config for CPU; full: the real config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512, help="smoke-scale vocab")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a crash at this step (fault-tolerance test)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--pool-devices", type=int, default=8,
                    help="platform device-pool size")
    ap.add_argument("--job-devices", type=int, default=8,
                    help="container size requested for this job")
    ap.add_argument("--priority", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compilation_cache()

    spec = JobSpec(
        kind="train",
        config=TrainJobConfig(
            arch=args.arch, scale=args.scale, steps=args.steps,
            batch=args.batch, seq=args.seq, vocab=args.vocab, lr=args.lr,
            microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, fail_at=args.fail_at,
            log_every=args.log_every,
        ),
        devices=args.job_devices,
        priority=args.priority,
    )
    platform = Platform(total_devices=args.pool_devices)
    report = platform.wait(platform.submit(spec))
    print(report.summary())
    if report.state != DONE:
        sys.exit(1)


if __name__ == "__main__":
    main()
