"""Replay-simulation CLI — thin wrapper over the unified platform API (§3).

    PYTHONPATH=src python -m repro.launch.simulate --partitions 8 --frames 16

Flags become a ``simulate`` :class:`~repro.platform.JobSpec`; the replay
harness itself lives in :class:`repro.platform.services.SimulateDriver`.
"""

from __future__ import annotations

import argparse
import sys

from repro.compile_cache import enable_compilation_cache
from repro.platform import DONE, JobSpec, Platform, SimulateJobConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--lidar-points", type=int, default=512)
    ap.add_argument("--pallas-conv", action="store_true")
    ap.add_argument("--ab-test", action="store_true")
    ap.add_argument("--pool-devices", type=int, default=8)
    ap.add_argument("--job-devices", type=int, default=4)
    ap.add_argument("--priority", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compilation_cache()

    spec = JobSpec(
        kind="simulate",
        config=SimulateJobConfig(
            partitions=args.partitions, frames=args.frames,
            lidar_points=args.lidar_points, pallas_conv=args.pallas_conv,
            ab_test=args.ab_test,
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
