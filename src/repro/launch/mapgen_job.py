"""HD-map-generation CLI — thin wrapper over the unified platform API (§5).

    PYTHONPATH=src python -m repro.launch.mapgen_job --partitions 4 --frames 16

Flags become a ``mapgen`` :class:`~repro.platform.JobSpec`; the pipeline
lives in :class:`repro.platform.services.MapGenDriver`.
"""

from __future__ import annotations

import argparse
import sys

from repro.compile_cache import enable_compilation_cache
from repro.platform import DONE, JobSpec, MapGenJobConfig, Platform


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--lidar-points", type=int, default=512)
    ap.add_argument("--staged", action="store_true", help="per-stage host I/O (baseline)")
    ap.add_argument("--no-icp", action="store_true")
    ap.add_argument("--pool-devices", type=int, default=8)
    ap.add_argument("--job-devices", type=int, default=4)
    ap.add_argument("--priority", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compilation_cache()

    spec = JobSpec(
        kind="mapgen",
        config=MapGenJobConfig(
            partitions=args.partitions, frames=args.frames,
            lidar_points=args.lidar_points, fused=not args.staged,
            icp_refine=not args.no_icp,
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
