"""The full HD-map-generation job (paper §5.2).

Stages, mirroring the paper's Figure 10:

  1. load        — decode BinPipe drive-log partitions, stack sensor arrays
  2. slam        — EKF propagation (odometry+IMU) corrected by GPS
  3. transform   — LiDAR scans vehicle->world under the SLAM poses
  4. icp_refine  — scan-to-scan ICP (Pallas kernel) refining consecutive
                   relative poses; the paper's 30x-offloaded hot spot
  5. rasterize   — 2D reflectance/elevation grid (segment scatter-reduce)
  6. label       — semantic layer on top of the grid

Stages 2-6 are jax-traceable, so the job runs either FUSED (one jit, the
paper's one-Spark-job 5x path) or STAGED (host round-trip per stage) through
``core.pipeline.Pipeline`` — benchmarked in ``benchmarks/mapgen.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binpipe import stack_batch
from repro.core.pipeline import Pipeline, Stage
from repro.core.rdd import ShardedDataset
from repro.kernels.icp.ops import icp_step
from repro.kernels.icp.ref import icp_step_ref
from repro.mapgen import gridmap, slam
from repro.mapgen.gridmap import GridMap, GridSpec


@dataclasses.dataclass
class MapGenConfig:
    grid: GridSpec = GridSpec(x_min=-40.0, y_min=-40.0, cells_x=160, cells_y=160, resolution=0.5)
    dt: float = 0.1
    icp_refine: bool = True
    use_pallas_icp: bool = True


class MapGenPipeline:
    def __init__(self, cfg: MapGenConfig = MapGenConfig()):
        self.cfg = cfg

    # ---- stage 1 (host): decode + stack ----
    def load(self, dataset: ShardedDataset) -> dict[str, jnp.ndarray]:
        recs = dataset.collect()
        batch = stack_batch(recs, ["lidar", "odom_v", "imu_yaw_rate", "gps", "pose_true"])
        return {
            "lidar": jnp.asarray(batch["lidar"]),  # (T, N, 3)
            "odom_v": jnp.asarray(batch["odom_v"], jnp.float32),
            "imu_yaw_rate": jnp.asarray(batch["imu_yaw_rate"], jnp.float32),
            "gps": jnp.asarray(batch["gps"], jnp.float32),
            "pose_true": jnp.asarray(batch["pose_true"], jnp.float32),
        }

    # ---- jax stages ----
    def stage_slam(self, data: dict) -> dict:
        poses = slam.propagate_and_correct(
            data["odom_v"], data["imu_yaw_rate"], data["gps"], dt=self.cfg.dt
        )
        return dict(data, poses=poses)

    def stage_transform(self, data: dict) -> dict:
        world = jax.vmap(slam.transform_cloud)(data["poses"], data["lidar"])
        return dict(data, world=world)

    def stage_icp_refine(self, data: dict) -> dict:
        """Scan-to-scan ICP between consecutive world-frame clouds; the
        residual transform corrects each pose's cloud.  (One ICP iteration
        per pair keeps the stage compile-light; iterations are configurable
        in the kernel op.)"""
        if not self.cfg.icp_refine:
            return dict(data, refined=data["world"], icp_err=jnp.zeros((1,)))
        clouds = data["world"]  # (T, N, 3)

        def refine(prev, cur):
            step = icp_step if self.cfg.use_pallas_icp else icp_step_ref
            R, t, err = step(cur, prev)
            return cur @ R.T + t, err

        refined_tail, errs = jax.vmap(refine)(clouds[:-1], clouds[1:])
        refined = jnp.concatenate([clouds[:1], refined_tail], axis=0)
        return dict(data, refined=refined, icp_err=errs)

    def stage_rasterize(self, data: dict) -> dict:
        pts = data["refined"].reshape(-1, 3)
        # reflectance stub: deterministic per-point pseudo-intensity
        inten = (jnp.abs(jnp.sin(pts[:, 0] * 12.9898) * jnp.cos(pts[:, 1] * 78.233)))
        counts, elev, refl = gridmap.rasterize(pts, inten, self.cfg.grid)
        return dict(data, counts=counts, elevation=elev, reflectance=refl)

    def stage_label(self, data: dict) -> dict:
        labels = gridmap.label_map(data["counts"], data["elevation"], data["reflectance"])
        return dict(data, labels=labels)

    # ------------------------------------------------------------------
    def as_pipeline(self) -> Pipeline:
        return Pipeline(
            [
                Stage("slam", self.stage_slam),
                Stage("transform", self.stage_transform),
                Stage("icp_refine", self.stage_icp_refine),
                Stage("rasterize", self.stage_rasterize),
                Stage("label", self.stage_label),
            ],
            name="mapgen",
        )

    def run(self, dataset: ShardedDataset, fused: bool = True, store=None) -> GridMap:
        data = self.load(dataset)
        pipe = self.as_pipeline()
        out = pipe.run_fused(data) if fused else pipe.run_staged(data, store)
        return GridMap(
            counts=jnp.asarray(out["counts"]),
            elevation=jnp.asarray(out["elevation"]),
            reflectance=jnp.asarray(out["reflectance"]),
            labels=jnp.asarray(out["labels"]),
        ), out

    def pose_error(self, out: dict) -> float:
        """Mean position error of SLAM poses vs ground truth (meters)."""
        est = np.asarray(out["poses"])[:, :2]
        true = np.asarray(out["pose_true"])[:, :2]
        return float(np.mean(np.linalg.norm(est - true, axis=1)))
