"""The five platform services as ServiceDriver implementations.

Each service (train, simulate, scenario, mapgen, serve) exposes a typed
``*JobConfig`` payload and a driver that runs the job on its allocated
container — the same code path the thin ``repro.launch.*`` CLI wrappers and
the heterogeneous benchmark submit through.  Heavy service imports happen
inside ``run`` so ``Platform.submit`` stays cheap.

Service → driver table:

    kind        driver            service package        workload
    ----------  ----------------  ---------------------  --------------------
    train       TrainDriver       repro.training         LM training + ckpt
    simulate    SimulateDriver    repro.sim.replay       replay simulation
    scenario    ScenarioDriver    repro.scenario         closed-loop sweeps
    mapgen      MapGenDriver      repro.mapgen           HD-map generation
    serve       ServeDriver       repro.serving          batch/continuous LM
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
from typing import Any, Optional, Sequence

import numpy as np

from repro.core.scheduler import Container
from repro.platform.driver import register_driver
from repro.platform.spec import JobSpec


def coerce_config(config: Any, cls):
    """Coerce a spec's config payload into the service's typed dataclass.

    Accepts ``None`` (all defaults), an instance of ``cls``, or a dict —
    unknown dict keys are an error so payload typos fail at submit time.
    """
    if config is None:
        return cls()
    if isinstance(config, cls):
        return config
    if isinstance(config, dict):
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(config) - known)
        if unknown:
            raise ValueError(
                f"unknown {cls.__name__} keys {unknown}; known: {sorted(known)}"
            )
        return cls(**config)
    raise TypeError(
        f"config must be None, dict, or {cls.__name__}; got {type(config).__name__}"
    )


def _smoke_cfg(arch: str, scale: str, vocab: int, seq: int):
    """Shared model-config derivation so train and serve jobs that point at
    the same checkpoint directory agree on parameter shapes."""
    from repro.config import get_arch, scale_down

    cfg = get_arch(arch)
    if scale == "smoke":
        cfg = scale_down(cfg, vocab_size=vocab, max_seq_len=max(seq, 512))
    return cfg


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainJobConfig:
    arch: str = "qwen2-0.5b"
    scale: str = "smoke"  # smoke: reduced config for CPU; full: real config
    steps: int = 100
    batch: int = 8
    seq: int = 256
    vocab: int = 512  # smoke-scale vocab
    lr: float = 1e-3
    microbatches: int = 1
    ckpt_dir: str = "/tmp/repro_train"
    ckpt_every: int = 50
    # fail_at injects a HARD crash (os._exit) at this step — it simulates
    # node death for the external crash-restart loop (the CLI restart path /
    # test_train_integration), not a recoverable ContainerFailure; don't use
    # it for jobs co-scheduled in-process with other tenants
    fail_at: int = -1
    log_every: int = 10


@register_driver
class TrainDriver:
    """End-to-end LM training with crash-restart fault tolerance (paper §4).

    Interruptible between steps: on preemption the driver writes a durable
    checkpoint before yielding, so the resumed attempt restores from exactly
    the step it stopped at — the same path crash-restart already exercises.
    """

    kind = "train"

    def prepare(self, spec: JobSpec) -> TrainJobConfig:
        return coerce_config(spec.config, TrainJobConfig)

    def run(self, container: Container, cfg: TrainJobConfig, token=None) -> dict:
        import jax
        import jax.numpy as jnp

        from repro.config import ParallelConfig, TrainConfig
        from repro.core.tiered_store import TieredStore
        from repro.data.loader import BatchLoader
        from repro.data.synthetic import lm_token_dataset
        from repro.distributed.mesh import single_device_mesh
        from repro.training.checkpoint import CheckpointManager
        from repro.training.train_loop import make_train_step

        mcfg = _smoke_cfg(cfg.arch, cfg.scale, cfg.vocab, cfg.seq)
        tcfg = TrainConfig(
            learning_rate=cfg.lr,
            warmup_steps=max(cfg.steps // 10, 1),
            total_steps=cfg.steps,
            checkpoint_every=cfg.ckpt_every,
        )
        pcfg = ParallelConfig(num_microbatches=cfg.microbatches)
        mesh = single_device_mesh()  # CPU-scale; pods use dryrun configs

        bundle = make_train_step(mcfg, tcfg, pcfg, mesh)
        store = TieredStore(cfg.ckpt_dir, mem_capacity=4 << 30)
        ckpt = CheckpointManager(store, keep=tcfg.keep_checkpoints)

        with mesh:
            state_like = jax.eval_shape(
                bundle.init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32)
            )
            start_step = 0
            try:
                state, start_step = ckpt.restore(state_like)
                print(f"[train] resumed from checkpoint step {start_step}")
            except FileNotFoundError:
                state = jax.jit(bundle.init_fn)(jax.random.PRNGKey(tcfg.seed))
                print("[train] fresh init")

            step_fn = jax.jit(bundle.train_step, donate_argnums=(0,))
            ds = lm_token_dataset(
                vocab=mcfg.vocab_size, seq_len=cfg.seq,
                seqs_per_partition=max(cfg.batch, 8), num_partitions=16,
            )
            loader = BatchLoader(ds, batch_size=cfg.batch, straggler_timeout_s=5.0)

            t0 = time.perf_counter()
            tokens_done = 0
            step_i = start_step
            # replay determinism: the synthetic stream restarts at batch 0
            # every attempt, so a resumed run fast-forwards past the batches
            # the checkpointed steps already consumed — batch k always pairs
            # with step k and a faulted run's final params are bitwise-equal
            # to an uninterrupted one's (the campaign artifact-version story)
            skip = start_step
            last = {}
            try:
                for nb in loader.batches(epochs=1_000_000):
                    if step_i >= cfg.steps:
                        break
                    if skip > 0:
                        skip -= 1
                        continue
                    if token is not None:
                        # load signal for the elastic controller, then the
                        # cancellation point between steps; a preempt saves a
                        # durable checkpoint first so the resume loses no work
                        token.state["load"] = {
                            "kind": "train",
                            "busy": 1.0 - step_i / max(cfg.steps, 1),
                            "remaining_steps": cfg.steps - step_i,
                        }
                        token.checkpoint(save=lambda: ckpt.save(
                            jax.device_get(state), step_i, durable=True
                        ))
                    batch = {k: jnp.asarray(v) for k, v in nb.items()}
                    state, metrics = step_fn(state, batch)
                    step_i += 1
                    tokens_done += cfg.batch * cfg.seq
                    if step_i % cfg.log_every == 0 or step_i == cfg.steps:
                        last = {k: float(v)
                                for k, v in jax.device_get(metrics).items()}
                        dt = time.perf_counter() - t0
                        print(
                            f"[train] step {step_i:5d} loss={last['loss']:.4f} "
                            f"acc={last['accuracy']:.3f} "
                            f"gnorm={last['grad_norm']:.2f} "
                            f"tok/s={tokens_done/max(dt,1e-9):,.0f}"
                        )
                    if step_i % cfg.ckpt_every == 0 or step_i == cfg.steps:
                        ckpt.save(jax.device_get(state), step_i, durable=True)
                    if cfg.fail_at == step_i:
                        print(f"[train] INJECTED FAILURE at step {step_i}",
                              flush=True)
                        os._exit(42)
            finally:
                loader.close()
                store.flush()
                store.close()
            dt = time.perf_counter() - t0
            print(
                f"[train] done at step {step_i}; "
                f"speculative_fetches={loader.speculative_fetches}"
            )
            # content fingerprint of the final parameters: the campaign
            # layer versions checkpoint artifacts by it, and the chaos
            # benchmark asserts faulted == fault-free through it
            h = hashlib.sha256()
            final = state["params"] if isinstance(state, dict) \
                and "params" in state else state
            for leaf in jax.tree_util.tree_leaves(jax.device_get(final)):
                h.update(np.asarray(leaf).tobytes())
            return {
                "steps": step_i,
                "resumed_from_step": start_step,
                "final_loss": last.get("loss", float("nan")),
                "accuracy": last.get("accuracy", float("nan")),
                "tokens_per_s": tokens_done / max(dt, 1e-9),
                "speculative_fetches": loader.speculative_fetches,
                "params_digest": h.hexdigest(),
            }


# ---------------------------------------------------------------------------
# simulate (replay)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SimulateJobConfig:
    partitions: int = 8
    frames: int = 16
    lidar_points: int = 512
    channels: tuple = (16, 32, 64)  # perception CNN width per block
    pallas_conv: bool = False
    ab_test: bool = False
    seed: int = 0


@register_driver
class SimulateDriver:
    """Distributed replay simulation over drive-log partitions (paper §3)."""

    kind = "simulate"

    def prepare(self, spec: JobSpec) -> SimulateJobConfig:
        return coerce_config(spec.config, SimulateJobConfig)

    def run(self, container: Container, cfg: SimulateJobConfig) -> dict:
        import jax

        from repro.data.synthetic import drive_log_dataset
        from repro.sim.replay import PerceptionModel, ReplaySimulator

        ds = drive_log_dataset(
            num_partitions=cfg.partitions, frames_per_partition=cfg.frames,
            lidar_points=cfg.lidar_points,
        )
        model = PerceptionModel(
            channels=tuple(cfg.channels), use_pallas=cfg.pallas_conv
        )
        params = model.init(jax.random.PRNGKey(cfg.seed))
        sim = ReplaySimulator(model, params)
        rep = sim.simulate(ds)
        print(
            f"[simulate] partitions={rep.partitions} frames={rep.frames} "
            f"mean={rep.mean_score:.4f} std={rep.score_std:.4f} "
            f"wall={rep.wall_time_s:.2f}s"
        )
        metrics = {
            "partitions": rep.partitions,
            "frames": rep.frames,
            "mean_score": rep.mean_score,
            "score_std": rep.score_std,
            "sim_wall_s": rep.wall_time_s,
        }
        if cfg.ab_test:
            cand = model.init(jax.random.PRNGKey(cfg.seed + 1))
            ab = sim.ab_test(ds, cand)
            print(
                f"[simulate] A/B: frames={ab.frames} flips={ab.decision_flips} "
                f"flip_rate={ab.flip_rate:.3f} mad={ab.mean_abs_diff:.4f}"
            )
            metrics.update(
                decision_flips=ab.decision_flips,
                flip_rate=ab.flip_rate,
                mean_abs_diff=ab.mean_abs_diff,
            )
        return metrics


# ---------------------------------------------------------------------------
# scenario (closed-loop sweeps)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ScenarioJobConfig:
    families: Optional[Sequence[str]] = None  # default: all five
    per_family: int = 64
    steps: int = 100
    dt: float = 0.1
    seed: int = 0
    policy: str = "aeb"  # baseline | aeb
    use_pallas: bool = False
    # sweep sharding: job i of n runs scenarios [i*S/n, (i+1)*S/n) of the
    # seed-deterministic batch, so the union over shards is the full sweep
    shard_index: int = 0
    num_shards: int = 1
    # checkpoint granularity: the shard rolls out in `chunks` scenario
    # slices with a cancellation point between them, and completed chunks
    # survive preemption (scenarios are independent, so chunked == whole).
    # `chunks` sets the slice size at the *requested* device count; an
    # attempt on a resized container re-shards proportionally (see
    # ScenarioDriver.run)
    chunks: int = 1


@dataclasses.dataclass
class _ScenarioCtx:
    """Run context: the coerced config plus the spec's requested devices
    (the baseline a resized grant's chunk size is scaled against)."""

    cfg: ScenarioJobConfig
    requested_devices: int


def _scenario_gaps(n: int, done: dict) -> list[tuple[int, int]]:
    """Scenario index ranges of [0, n) not covered by completed chunks.

    ``done`` maps (lo, hi) offset ranges to their rollout metrics; ranges
    never overlap (each attempt only rolls out gaps), so the uncovered
    remainder is a simple sorted walk.
    """
    gaps: list[tuple[int, int]] = []
    pos = 0
    for lo, hi in sorted(done):
        if lo > pos:
            gaps.append((pos, lo))
        pos = max(pos, hi)
    if pos < n:
        gaps.append((pos, n))
    return gaps


@register_driver
class ScenarioDriver:
    """One shard of a closed-loop scenario sweep (paper §3 simulation).

    **Elastic re-sharding**: completed chunks are stored in ``token.state``
    keyed by the *scenario index range* they cover, not by a chunk number —
    so every resumed attempt is free to recompute its chunk boundaries from
    the devices it was actually granted (a shrunk container takes
    proportionally smaller bites, a re-grown one goes back to full-size
    slices).  Scenarios are independent and ranges always partition the
    shard, so the merged result is bitwise-identical however many resizes
    happened along the way.
    """

    kind = "scenario"

    def prepare(self, spec: JobSpec) -> _ScenarioCtx:
        cfg = coerce_config(spec.config, ScenarioJobConfig)
        if not 0 <= cfg.shard_index < cfg.num_shards:
            raise ValueError(
                f"shard_index {cfg.shard_index} outside num_shards {cfg.num_shards}"
            )
        if cfg.chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {cfg.chunks}")
        if cfg.policy not in scenario_policies():
            raise ValueError(
                f"unknown policy {cfg.policy!r}; known: {sorted(scenario_policies())}"
            )
        return _ScenarioCtx(cfg, max(1, spec.devices))

    def run(self, container: Container, ctx: _ScenarioCtx, token=None) -> dict:
        import jax

        from repro.scenario.runner import slice_batch
        from repro.scenario.world import rollout

        cfg = ctx.cfg
        batch, names = _cached_build_batch(
            tuple(cfg.families) if cfg.families else None,
            cfg.per_family,
            cfg.seed,
        )
        S = batch.num_scenarios
        bounds = np.linspace(0, S, cfg.num_shards + 1, dtype=int)
        lo, hi = int(bounds[cfg.shard_index]), int(bounds[cfg.shard_index + 1])
        shard = slice_batch(batch, lo, hi)
        n = hi - lo
        # completed chunks persist across preemptions/resizes in the token
        # state as (lo, hi) -> metrics; an attempt rolls out only the gaps
        state = token.state if token is not None else {}
        done: dict = state.setdefault("done", {})
        # re-shard to the granted container: `chunks` slices at the full
        # request, proportionally smaller ones on a shrunk grant (ceil, so a
        # tiny grant still makes progress one scenario at a time)
        base = -(-n // max(1, cfg.chunks))
        per_chunk = max(1, -(-base * max(1, container.size)
                             // ctx.requested_devices))
        t0 = time.perf_counter()
        try:
            for gap_lo, gap_hi in _scenario_gaps(n, done) or ([] if n else [(0, 0)]):
                for clo in range(gap_lo, gap_hi, per_chunk) or [gap_lo]:
                    chi = min(clo + per_chunk, gap_hi)
                    if (clo, chi) in done:  # the synthetic empty-shard chunk
                        continue
                    if token is not None:
                        remaining = n - sum(h - l for l, h in done)
                        state["load"] = {
                            "kind": "scenario",
                            "busy": remaining / n if n else 0.0,
                            "remaining": remaining,
                            "total": n,
                        }
                        token.checkpoint()  # cancellation/resize point
                    m, _ = rollout(
                        slice_batch(shard, clo, chi),
                        scenario_policies()[cfg.policy],
                        steps=cfg.steps, dt=cfg.dt, use_pallas=cfg.use_pallas,
                    )
                    done[(clo, chi)] = jax.device_get(jax.block_until_ready(m))
        finally:
            # interrupted attempts count too, or the resumed attempt's
            # scenarios_per_sec would be inflated
            state["wall_s"] = (
                state.get("wall_s", 0.0) + time.perf_counter() - t0
            )
        wall = state["wall_s"]
        parts = [done[r] for r in sorted(done)]
        m = (
            parts[0]
            if len(parts) == 1
            else jax.tree.map(lambda *xs: np.concatenate(xs), *parts)
        )
        collided = np.asarray(m.collided).astype(bool)
        return {
            "scenarios": n,
            "steps": cfg.steps,
            "chunks": len(done),
            "collision_rate": float(collided.mean()) if hi > lo else 0.0,
            "scenarios_per_sec": n / max(wall, 1e-9),
            "shard": f"{cfg.shard_index}/{cfg.num_shards}",
            # raw per-scenario metrics for cross-shard aggregation
            "_family_id": np.asarray(batch.family_id[lo:hi]),
            "_family_names": list(names),
            "_rollout": m,
        }


def scenario_policies() -> dict:
    """Name -> policy registry; the single source for driver validation and
    the CLI's ``--policy`` choices."""
    from repro.scenario.world import aeb_policy, baseline_policy

    return {"baseline": baseline_policy, "aeb": aeb_policy}


@functools.lru_cache(maxsize=8)
def _cached_build_batch(families_key, per_family: int, seed: int):
    """Sweeps are pure functions of (families, per_family, seed); shard jobs
    of one sweep share the compiled batch instead of rebuilding it."""
    import jax

    from repro.scenario.dsl import build_batch

    return build_batch(
        list(families_key) if families_key else None,
        per_family,
        jax.random.PRNGKey(seed),
    )


def aggregate_scenario_metrics(metric_dicts: Sequence[dict], wall_time_s: float):
    """Merge per-shard scenario job metrics into one ScenarioReport."""
    from repro.scenario import metrics as M

    return M.merge_rollouts(
        [m["_family_id"] for m in metric_dicts],
        metric_dicts[0]["_family_names"],
        [m["_rollout"] for m in metric_dicts],
        steps=int(metric_dicts[0]["steps"]),
        wall_time_s=wall_time_s,
    )


# ---------------------------------------------------------------------------
# mapgen
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MapGenJobConfig:
    partitions: int = 4
    frames: int = 16
    lidar_points: int = 512
    fused: bool = True  # False: per-stage host I/O (baseline)
    icp_refine: bool = True


@register_driver
class MapGenDriver:
    """HD-map generation pipeline over drive logs (paper §5)."""

    kind = "mapgen"

    def prepare(self, spec: JobSpec) -> MapGenJobConfig:
        return coerce_config(spec.config, MapGenJobConfig)

    def run(self, container: Container, cfg: MapGenJobConfig) -> dict:
        from repro.data.synthetic import drive_log_dataset
        from repro.mapgen.pipeline import MapGenConfig, MapGenPipeline

        ds = drive_log_dataset(
            num_partitions=cfg.partitions, frames_per_partition=cfg.frames,
            lidar_points=cfg.lidar_points,
        )
        pipe = MapGenPipeline(MapGenConfig(icp_refine=cfg.icp_refine))
        gm, out = pipe.run(ds, fused=cfg.fused)
        occ = int(np.asarray(gm.counts > 0).sum())
        lanes = int((np.asarray(gm.labels) == 2).sum())
        pose_err = float(pipe.pose_error(out))
        print(
            f"[mapgen] mode={'fused' if cfg.fused else 'staged'} "
            f"pose_err={pose_err:.3f}m occupied={occ} lane_cells={lanes}"
        )
        return {
            "mode": "fused" if cfg.fused else "staged",
            "pose_error_m": pose_err,
            "occupied_cells": occ,
            "lane_cells": lanes,
        }


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


# gauges reflect the latest attempt; everything else accumulates
_STAT_GAUGES = ("replicas", "replicas_alive", "cells", "cells_alive",
                "replicas_per_cell")


def _merge_router_stats(prev: Optional[dict], cur: dict) -> dict:
    """Accumulate routing stats across a serve job's preempted/resumed
    attempts (each attempt builds a fresh router/cell tier).  Counter lists
    are padded to the longer shape — replica counts can differ between
    attempts when autoscaling added/retired replicas mid-run."""
    if not prev:
        return cur
    merged = dict(cur)
    for k, v in cur.items():
        pv = prev.get(k)
        if pv is None or k in _STAT_GAUGES:
            continue
        if isinstance(v, list):
            if any(isinstance(x, (list, tuple)) for x in list(v) + list(pv)):
                merged[k] = list(pv) + list(v)  # event lists concatenate
            else:
                width = max(len(pv), len(v))
                pad = lambda xs: list(xs) + [0] * (width - len(xs))  # noqa: E731
                merged[k] = [a + b for a, b in zip(pad(pv), pad(v))]
        elif isinstance(v, (int, float)):
            merged[k] = pv + v
    return merged


@dataclasses.dataclass
class ServeJobConfig:
    arch: str = "qwen2-0.5b"
    scale: str = "smoke"
    batch: int = 4
    prompt_len: int = 64
    gen: int = 32
    temperature: float = 0.0
    seed: int = 0
    engine: str = "static"  # static | continuous
    page_size: int = 16
    slots: int = 0  # continuous decode slots per replica (0 = batch)
    replicas: int = 1  # continuous engine replicas behind a JSQ router
    # pool-level cell tier (continuous only): > 1 fans the tenant out over
    # `cells` serve cells of `replicas` engines each behind a CellRouter
    # (JSQ across cells, whole-cell failover)
    cells: int = 1
    # elastic replica scaling: sustained queue depth above/below the water
    # marks adds/retires engine replicas mid-stream (per cell, hysteresis
    # in serving.cell_router.advise_replicas); 0 disables
    max_replicas: int = 0
    scale_high_water: int = 32
    scale_low_water: int = 0
    scale_window: int = 3
    # graceful degradation (cell tier): when every cell has died the router
    # sheds in-flight work instead of raising, and the driver rebuilds up to
    # this many fresh cells per attempt before giving up (0 = fail as soon
    # as the last cell dies, the pre-chaos behavior)
    cell_rebuild_retries: int = 1
    # deadline-aware serving (continuous only): per-request latency budget
    # in seconds (0 disables).  Requests whose projected completion — from
    # the live queue-wait/prefill/decode estimator (serving.deadline) —
    # cannot make the budget are degraded (generation truncated to what
    # fits, >= deadline_min_tokens) or shed before touching an engine
    deadline_s: float = 0.0
    deadline_min_tokens: int = 1
    # hedged dispatch (cell tier): admitted requests projected past this
    # fraction of their budget are duplicated to a second cell; first win
    # delivers, the loser is cancelled.  0 disables; sensible: 0.7-0.9
    hedge_threshold: float = 0.0
    # SLO-driven predictive autoscaling: replica scaling follows the
    # forecast arrival rate (windowed rate + slope, Little's-law sizing)
    # instead of queue-depth hysteresis (requires max_replicas > replicas)
    predictive_autoscale: bool = False
    # serving fast path (continuous only; all default off — see
    # serving.continuous): n-gram speculative decoding depth, prompt
    # prefix-page sharing across requests, and the per-step chunked-prefill
    # token budget folded into the decode program
    spec_k: int = 0
    prefix_cache: bool = False
    prefill_chunk: int = 0
    vocab: int = 512  # smoke-scale vocab (must match a ckpt's train job)
    seq: int = 512  # smoke-scale max_seq_len (match the train job's --seq
    #                 when restoring from ckpt_dir; params depend on it)
    ckpt_dir: Optional[str] = None  # serve params from this train checkpoint


@register_driver
class ServeDriver:
    """Static-batch or continuous-batching LM serving (paper §4.3).

    ``replicas > 1`` (continuous only) fans the tenant out over N engine
    replicas sharing the params, fronted by the join-shortest-queue
    :class:`~repro.serving.router.ServeRouter`.  ``cells > 1`` adds the
    pool-level tier: ``cells`` serve cells of ``replicas`` engines each
    behind a :class:`~repro.serving.cell_router.CellRouter` (JSQ across
    cells, whole-cell failover), and ``max_replicas > replicas`` turns on
    sustained-queue-depth replica autoscaling inside each cell.
    Interruptible between engine steps: a preempt drains in-flight
    sequences into resumable continuation requests stashed in the token
    state, so the resumed attempt finishes them instead of starting over,
    and each checkpoint publishes the router's queue depth / live tokens
    as the load signal the ElasticController samples.
    """

    kind = "serve"

    def prepare(self, spec: JobSpec) -> ServeJobConfig:
        cfg = coerce_config(spec.config, ServeJobConfig)
        if cfg.engine not in ("static", "continuous"):
            raise ValueError(f"engine must be static|continuous, got {cfg.engine!r}")
        if cfg.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {cfg.replicas}")
        if cfg.replicas > 1 and cfg.engine != "continuous":
            raise ValueError("replicas > 1 requires engine='continuous'")
        if cfg.cells < 1:
            raise ValueError(f"cells must be >= 1, got {cfg.cells}")
        if cfg.cells > 1 and cfg.engine != "continuous":
            raise ValueError("cells > 1 requires engine='continuous'")
        if cfg.max_replicas and cfg.max_replicas < cfg.replicas:
            raise ValueError(
                f"max_replicas {cfg.max_replicas} below replicas {cfg.replicas}"
            )
        if cfg.deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {cfg.deadline_s}")
        if not 0.0 <= cfg.hedge_threshold <= 1.0:
            raise ValueError(
                f"hedge_threshold must be in [0, 1], got {cfg.hedge_threshold}"
            )
        if cfg.deadline_min_tokens < 1:
            raise ValueError(
                f"deadline_min_tokens must be >= 1, got {cfg.deadline_min_tokens}"
            )
        if (cfg.deadline_s or cfg.hedge_threshold or cfg.predictive_autoscale) \
                and cfg.engine != "continuous":
            raise ValueError(
                "deadline/hedging/predictive autoscaling require "
                "engine='continuous'"
            )
        if cfg.hedge_threshold and cfg.cells < 2:
            raise ValueError("hedge_threshold requires cells >= 2")
        if cfg.predictive_autoscale and not (
            cfg.max_replicas and cfg.max_replicas > cfg.replicas
        ):
            raise ValueError(
                "predictive_autoscale requires max_replicas > replicas"
            )
        if cfg.spec_k < 0 or cfg.prefill_chunk < 0:
            raise ValueError("spec_k/prefill_chunk must be >= 0")
        if (cfg.spec_k or cfg.prefix_cache or cfg.prefill_chunk) \
                and cfg.engine != "continuous":
            raise ValueError(
                "spec_k/prefix_cache/prefill_chunk require engine='continuous'"
            )
        return cfg

    def _params(self, cfg: ServeJobConfig, mcfg):
        """Fresh random params, or the newest checkpoint from a train job's
        ``ckpt_dir`` — how a serve tenant picks up a co-scheduled train
        tenant's output through the tiered store."""
        import jax
        import jax.numpy as jnp

        from repro.models import model_zoo

        if cfg.ckpt_dir is None:
            return model_zoo.init_params(model_zoo.build_model(mcfg),
                                         jax.random.PRNGKey(cfg.seed))
        from repro.config import ParallelConfig, TrainConfig
        from repro.core.tiered_store import TieredStore
        from repro.distributed.mesh import single_device_mesh
        from repro.training.checkpoint import CheckpointManager
        from repro.training.train_loop import make_train_step

        bundle = make_train_step(
            mcfg, TrainConfig(), ParallelConfig(), single_device_mesh()
        )
        state_like = jax.eval_shape(
            bundle.init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        store = TieredStore(cfg.ckpt_dir, mem_capacity=1 << 30)
        try:
            state, step = CheckpointManager(store).restore(state_like)
        finally:
            store.close()
        print(f"[serve] restored params from checkpoint step {step}")
        return state["params"]

    def run(self, container: Container, cfg: ServeJobConfig, token=None) -> dict:
        import jax
        import jax.numpy as jnp

        mcfg = _smoke_cfg(cfg.arch, cfg.scale, cfg.vocab,
                          max(cfg.seq, cfg.prompt_len + cfg.gen))
        params = self._params(cfg, mcfg)

        key = jax.random.PRNGKey(cfg.seed + 1)
        B, S = cfg.batch, cfg.prompt_len
        prompt = {
            "tokens": jax.random.randint(key, (B, S), 0, mcfg.vocab_size, jnp.int32)
        }
        if mcfg.family == "vlm":
            F = mcfg.frontend_tokens
            prompt["patches"] = jax.random.normal(
                key, (B, F, mcfg.frontend_dim), jnp.float32
            )
            prompt["positions3"] = jnp.broadcast_to(
                jnp.arange(S + F, dtype=jnp.int32), (3, B, S + F)
            )
        if mcfg.family == "encdec":
            prompt["src_emb"] = jax.random.normal(
                key, (B, S, mcfg.frontend_dim), jnp.float32
            )

        if cfg.engine == "continuous":
            import itertools

            from repro.serving.cell_router import (
                CellRouter,
                InProcessCell,
                NoCellsAlive,
            )
            from repro.serving.continuous import ContinuousBatchingEngine
            from repro.serving.deadline import (
                ArrivalForecaster,
                CompletionEstimator,
                DeadlineAdmission,
                count_misses,
            )
            from repro.serving.router import ServeRouter
            from repro.serving.scheduler import Request, token_latencies

            seeds = itertools.count(cfg.seed)
            # replica k runs on the container's k-th device (round robin)
            devices = itertools.cycle(container.jax_devices())

            # observability context bound by the executor (None for bare
            # tokens / tracing-off): serve-stage spans nest under the
            # attempt span, stage histograms land in the platform registry
            tr = getattr(token, "tracer", None) if token is not None else None
            if tr is not None and not tr.enabled:
                tr = None
            tspan = getattr(token, "span", None) if token is not None else None
            obs = getattr(token, "obs", None) if token is not None else None

            # deadline-aware serving: the completion estimator feeds on the
            # same stage events the obs histograms record, warm-started
            # from any prior attempt's serve_* series in the registry
            deadline_on = cfg.deadline_s > 0
            estimator = CompletionEstimator()
            if deadline_on and obs is not None:
                estimator.seed_from_histograms(
                    obs.dump().get("histograms", {}), nominal_prompt_len=S,
                )
            admission = DeadlineAdmission(
                estimator,
                min_tokens=cfg.deadline_min_tokens,
                hedge_threshold=cfg.hedge_threshold,
            ) if deadline_on else None
            forecaster = (
                ArrivalForecaster() if cfg.predictive_autoscale else None
            )

            def on_trace(name, **tags):
                # router/cell-router lifecycle events (failover, salvage,
                # continuation reroute, scale) onto the attempt span
                if tr is not None:
                    tr.event(tspan, name, **tags)

            def on_stage(stage, info):
                # engine stage callback: queue-wait/prefill per admission,
                # one decode span per engine step
                d = float(info.get("dur_s", 0.0))
                if deadline_on:  # the estimator eats the same events
                    if stage == "prefill":
                        estimator.observe_prefill(
                            int(info.get("plen") or 0), d)
                        if "queue_wait_s" in info:
                            estimator.observe_queue_wait(info["queue_wait_s"])
                    elif stage == "decode":
                        # fast-path steps emit several tokens per program
                        # call; the estimator tracks seconds *per token*
                        estimator.observe_decode_step(
                            d, tokens=int(info.get("tokens") or 1)
                        )
                if obs is not None:
                    obs.observe(f"serve_{stage}_s", d)
                    if "queue_wait_s" in info:
                        obs.observe("serve_queue_wait_s", info["queue_wait_s"])
                if tr is not None:
                    t1 = tr.now()
                    sp = tr.start(
                        f"serve.{stage}", job=token.job_name,
                        attempt=token.attempt, parent=tspan, t=t1 - d,
                        **{k: info[k] for k in ("rid", "slots") if k in info},
                    )
                    tr.end(sp, t=t1)
                    qw = float(info.get("queue_wait_s") or 0.0)
                    if stage == "prefill" and qw > 0.0:
                        qs = tr.start(
                            "serve.queue_wait", job=token.job_name,
                            attempt=token.attempt, parent=tspan,
                            t=t1 - d - qw, rid=info.get("rid"),
                        )
                        tr.end(qs, t=t1 - d)

            stage_sink = (
                on_stage
                if (tr is not None or obs is not None or deadline_on)
                else None
            )
            trace_sink = on_trace if tr is not None else None

            def make_engine():
                # unique sampling seed per engine, including autoscaled ones
                return ContinuousBatchingEngine(
                    mcfg, params,
                    num_slots=cfg.slots or B,
                    page_size=cfg.page_size,
                    max_len=S + cfg.gen,
                    seed=next(seeds),
                    on_stage=stage_sink,
                    spec_k=cfg.spec_k,
                    prefix_cache=cfg.prefix_cache,
                    prefill_chunk=cfg.prefill_chunk,
                    device=next(devices),
                )

            cell_tier = cfg.cells > 1 or cfg.max_replicas > cfg.replicas
            cap = cfg.max_replicas or cfg.replicas
            if cell_tier:
                # the pool-level tier: JSQ across cells, whole-cell
                # failover, sustained-queue-depth replica autoscaling
                cells = [
                    InProcessCell(
                        f"cell{c}", make_engine,
                        replicas=cfg.replicas, max_replicas=cap,
                    )
                    for c in range(cfg.cells)
                ]
                router = CellRouter(
                    cells,
                    autoscale=cfg.max_replicas > cfg.replicas,
                    high_water=cfg.scale_high_water,
                    low_water=cfg.scale_low_water,
                    window=cfg.scale_window,
                    min_replicas=cfg.replicas,  # never below the baseline
                    max_replicas=cap,
                    # losing the last cell sheds work for rebuild below
                    # instead of raising out of a router step
                    shed_stranded=cfg.cell_rebuild_retries > 0,
                    on_trace=trace_sink,
                    admission=admission,
                    forecaster=forecaster,
                    per_replica_slots=cfg.slots or B,
                )
            else:
                router = ServeRouter(
                    [make_engine() for _ in range(cfg.replicas)],
                    on_trace=trace_sink,
                    admission=admission,
                )
            # a preempted attempt left its unfinished work as continuation
            # requests in the token state; completed outputs carry over too
            state = token.state if token is not None else {}
            outs = state.setdefault("outs", [])
            reqs = state.pop("cont", None)
            if reqs is None:
                # fresh start or a ContainerFailure retry (which drains
                # nothing): re-serve only the requests not already finished
                done_rids = {o.rid for o in outs}
                reqs = [
                    Request(
                        rid=i, tokens=np.asarray(prompt["tokens"][i]),
                        max_new_tokens=cfg.gen, temperature=cfg.temperature,
                        deadline_s=cfg.deadline_s if deadline_on else None,
                    )
                    for i in range(B)
                    if i not in done_rids
                ]
            for r in reqs:
                router.submit(r)
            # the trace clock continues from prior attempts so carried
            # token_times stay monotonic across a preempt/resume
            base = state.get("wall_s", 0.0)
            n0 = len(outs)  # completions before this attempt
            t0 = time.perf_counter()

            def preempt_save():
                # in-flight work from alive cells, plus anything graceful
                # degradation shed while every cell was down
                cont = router.drain_continuations()
                if cell_tier:
                    cont.extend(router.take_stranded())
                state["cont"] = cont

            rebuilds = 0  # fresh cells built into dead slots (this attempt)

            def _recover_stranded():
                """Graceful degradation: after a step left work shed (every
                cell died mid-flight), rebuild a dead slot — up to the
                configured budget — and replay the shed requests, instead
                of the tenant failing outright."""
                nonlocal rebuilds
                if not (cell_tier and router.stranded):
                    return
                if router.num_alive == 0:
                    if rebuilds >= cfg.cell_rebuild_retries:
                        raise NoCellsAlive(
                            f"all {len(router.cells)} serve cells failed and "
                            f"the rebuild budget ({cfg.cell_rebuild_retries}) "
                            f"is spent; {len(router.stranded)} requests shed "
                            f"(last, cell {router.failures[-1][0]}: "
                            f"{router.failures[-1][1]})"
                        )
                    dead = next(
                        i for i, a in enumerate(router.alive) if not a
                    )
                    router.revive(dead, InProcessCell(
                        f"cell{dead}-rebuild{rebuilds}", make_engine,
                        replicas=cfg.replicas, max_replicas=cap,
                    ))
                    rebuilds += 1
                    print(
                        f"[serve/continuous] degraded: rebuilt cell slot "
                        f"{dead} (rebuild {rebuilds}/"
                        f"{cfg.cell_rebuild_retries})"
                    )
                router.salvage(router.take_stranded())

            try:
                while router.has_work() or (cell_tier and router.stranded):
                    if token is not None:
                        if cell_tier:
                            # chaos directives land between engine steps: a
                            # kill_cell makes the picked cell's next step
                            # die through the real failover path
                            for d in token.drain_directives():
                                if d[0] != "kill_cell":
                                    continue
                                alive = [i for i, a in
                                         enumerate(router.alive) if a]
                                if alive:
                                    victim = alive[int(d[1]) % len(alive)]
                                    router.inject_cell_failure(victim)
                                    print("[serve/continuous] chaos: cell "
                                          f"{victim} marked for death")
                        # load signal the ElasticController samples: queued
                        # depth + live tokens, and a normalized busy fraction
                        state["load"] = {
                            "kind": "serve",
                            "busy": 1.0 - len(outs) / max(B, 1),
                            "queue_depth": router.queue_depth(),
                            "load_tokens": router.load_tokens(),
                        }
                        if deadline_on:
                            # SLO signal: the miss+shed fraction so far —
                            # the controller treats a tenant bleeding its
                            # budget as busy even when its queue is short
                            shed_n = len(router.deadline_shed)
                            miss_n = count_misses(outs)
                            state["load"]["slo_pressure"] = (
                                (miss_n + shed_n)
                                / max(1, len(outs) + shed_n)
                            )
                            if forecaster is not None:
                                state["load"]["forecast_rate"] = (
                                    forecaster.rate(
                                        base + time.perf_counter() - t0)
                                )
                        # cancellation point between engine steps; a preempt
                        # drains in-flight sequences into resumable requests
                        token.checkpoint(save=preempt_save)
                    outs.extend(router.step(base + time.perf_counter() - t0))
                    _recover_stranded()
            finally:
                # interrupted attempts count toward wall time and routing
                # stats too, or resumed jobs would report inflated rates
                # and only their final attempt's routing
                state["wall_s"] = (
                    state.get("wall_s", 0.0) + time.perf_counter() - t0
                )
                state["router_stats"] = _merge_router_stats(
                    state.get("router_stats"), router.stats()
                )
            dt = state["wall_s"]
            toks = sum(len(o.tokens) for o in outs)
            # a deadline policy may have shed every request: no outputs is
            # a legal (if degenerate) serve result, not a crash
            lat = token_latencies(outs)
            if len(lat):
                p50 = np.percentile(lat, 50) * 1e3
                p99 = np.percentile(lat, 99) * 1e3
            else:
                p50 = p99 = 0.0
            # per-request spans for this attempt's completions: the engine's
            # relative trace clock (base + elapsed) mapped back onto the
            # tracer timeline by anchoring "now" to the end of the attempt
            new_outs = [o for o in outs[n0:] if len(o.token_times)]
            if tr is not None and new_outs:
                t_end_abs = tr.now()
                t_end_rel = state["wall_s"]

                def to_abs(tt):
                    return t_end_abs - (t_end_rel - tt)

                for o in new_outs:
                    arr = (o.arrival_time if np.isfinite(o.arrival_time)
                           else o.token_times[0])
                    sp = tr.start(
                        "serve.request", job=token.job_name,
                        attempt=token.attempt, parent=tspan, t=to_abs(arr),
                        rid=o.rid, tokens=len(o.tokens),
                        ttft_s=max(o.token_times[0] - arr, 0.0),
                    )
                    dsp = tr.start(
                        "serve.decode", job=token.job_name,
                        attempt=token.attempt, parent=sp,
                        t=to_abs(o.token_times[0]), rid=o.rid,
                    )
                    tr.end(dsp, t=to_abs(o.token_times[-1]))
                    tr.end(sp, t=to_abs(o.token_times[-1]))
            # fast-path engine counters (speculation, prefix sharing,
            # chunked prefill) aggregated across replicas/cells by the
            # router stats
            from repro.serving.scheduler import FASTPATH_COUNTERS
            fast_counts = {
                k: int(state["router_stats"].get(k, 0))
                for k in FASTPATH_COUNTERS
                if int(state["router_stats"].get(k, 0))
            }
            if tr is not None and fast_counts:
                # onto the attempt span: the trace report folds these into
                # its per-job summary line
                tr.event(tspan, "serve.fastpath", **fast_counts)
            if obs is not None:
                for o in new_outs:
                    arr = (o.arrival_time if np.isfinite(o.arrival_time)
                           else o.token_times[0])
                    obs.observe(
                        "serve_ttft_s", max(o.token_times[0] - arr, 0.0))
                obs.observe("serve_tokens_per_s", toks / max(dt, 1e-9))
                # registry counters land in metrics["obs"]
                for k, v in fast_counts.items():
                    obs.inc(f"serve_{k}", v)
                if deadline_on:
                    new_miss = count_misses(new_outs)
                    new_shed = len(router.deadline_shed)
                    if new_miss:
                        obs.inc("deadline_miss", new_miss)
                    if new_shed:
                        obs.inc("deadline_shed", new_shed)
            print(
                f"[serve/continuous] {toks} tokens in {dt:.2f}s "
                f"({toks/dt:,.1f} tok/s) p50/p99 token latency "
                f"{p50:.1f}/{p99:.1f} ms replicas={cfg.replicas} "
                f"routed={router.routed}"
            )
            if outs:
                first = min(outs, key=lambda o: o.rid)
                print("[serve/continuous] first sequence:", first.tokens[:16])
            deadline_metrics = {}
            if deadline_on:
                deadline_metrics = {
                    "deadline_miss": count_misses(outs),
                    "deadline_shed": int(
                        state["router_stats"].get("deadline_shed", 0)),
                    "deadline_degraded": int(
                        state["router_stats"].get("deadline_degraded", 0)),
                    "hedges": int(state["router_stats"].get("hedges", 0)),
                }
            return {
                "engine": "continuous",
                "tokens": toks,
                "tokens_per_s": toks / max(dt, 1e-9),
                "p50_token_ms": float(p50),
                "p99_token_ms": float(p99),
                **deadline_metrics,
                **{f"replica_{k}": v
                   for k, v in state["router_stats"].items()},
                # generated token ids per request id, for comparing runs
                "_request_tokens": {o.rid: list(o.tokens) for o in outs},
            }

        from repro.serving.engine import ServeEngine

        engine = ServeEngine(
            mcfg, params, max_len=S + cfg.gen + (mcfg.frontend_tokens or 0)
        )
        t0 = time.perf_counter()
        out = engine.generate(
            prompt, cfg.gen, temperature=cfg.temperature, seed=cfg.seed
        )
        dt = time.perf_counter() - t0
        toks = B * cfg.gen
        print(
            f"[serve] generated {out.shape} tokens in {dt:.2f}s "
            f"({toks/dt:,.1f} tok/s)"
        )
        print("[serve] first sequence:", jax.device_get(out[0])[:16].tolist())
        return {
            "engine": "static",
            "tokens": toks,
            "tokens_per_s": toks / max(dt, 1e-9),
            # raw generated token ids (seeded sampling => deterministic for
            # fixed params); the campaign rollout artifact content-hashes
            # this so fault-free and chaos legs can be compared bitwise
            "_tokens": np.asarray(jax.device_get(out)),
        }
