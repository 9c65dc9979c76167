"""Train cells: the jitted ``train_step`` of ``make_train_step``, fed by
``BatchLoader``, set up as ``TrainDriver`` sets them up.

Set-up builds the step and its state once (weights from the seed, AdamW
state from the program's own ``optimizer.init``) and drives that same state
through the first ``CHECKED_STEPS`` steps with the window's own call and
feed; those steps compile the step and are what the check compares.  The
window then runs further steps on the same state for ``seconds``.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

import traffic as traffic_gen
import weights as weights_mod
from run_common import Compiles, Record, host_span, peak_bytes

CHECKED_STEPS = 3
PARTITIONS = 256  # rows enough for set-up and any window, each row different


def _configs(ctx):
    from repro.config import ParallelConfig, TrainConfig

    t = ctx.config["train"]
    mcfg = ctx.family.model_config(ctx.config)
    mcfg = dataclasses.replace(mcfg, remat=t["remat"])
    tcfg = TrainConfig(
        learning_rate=t["learning_rate"], warmup_steps=t["warmup_steps"],
        total_steps=t["total_steps"], weight_decay=t["weight_decay"],
        beta1=t["beta1"], beta2=t["beta2"], eps=t["eps"], grad_clip=t["grad_clip"],
        z_loss_coef=t["z_loss_coef"],
    )
    return mcfg, tcfg, ParallelConfig(num_microbatches=t["microbatches"])


def dataset(cfg: dict, mix: dict, seed: int):
    """The loader's source: partition ``p`` holds the rows of
    ``traffic.train_rows(seed, p)``."""
    from repro.core.rdd import ShardedDataset

    t = cfg["train"]
    spp, S, V = mix["sequences_per_partition"], t["seq_len"], cfg["vocab_size"]

    def gen(part):
        rows = traffic_gen.train_rows(seed, part, spp, S, V)
        return [{"tokens": r[:-1], "targets": r[1:]} for r in rows]

    return ShardedDataset.from_generator(gen, PARTITIONS, name="bench_train")


def batch_rows(cfg: dict, mix: dict, seed: int, k: int) -> np.ndarray:
    """The (B, S + 1) rows of the loader's ``k``-th batch."""
    t = cfg["train"]
    B, spp = t["sequences_per_step"], mix["sequences_per_partition"]
    rows = [traffic_gen.train_rows(seed, p, spp, t["seq_len"], cfg["vocab_size"])
            for p in range(k * B // spp, (k + 1) * B // spp)]
    return np.concatenate(rows)


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                for x in xs])([x for _, x in flat])
    return {weights_mod._path_name(p): float(n) for (p, _), n in zip(flat, norms)}


def run(ctx) -> Record:
    import jax
    import jax.numpy as jnp

    from repro.data.loader import BatchLoader
    from repro.distributed.mesh import single_device_mesh
    from repro.models import model_zoo
    from repro.training.train_loop import make_train_step

    cfg, mix, seed, seconds, fam = ctx.config, ctx.mix, ctx.seed, ctx.seconds, ctx.family
    t = cfg["train"]
    dev = jax.devices()[0]
    rec = Record(kind="train", cfg=cfg, mix=mix, family=fam)
    mcfg, tcfg, pcfg = _configs(ctx)
    mesh = single_device_mesh()
    bundle = make_train_step(mcfg, tcfg, pcfg, mesh)
    tokens_per_step = t["sequences_per_step"] * t["seq_len"]
    rec.sizing = {"tokens_per_step": tokens_per_step, "microbatches": t["microbatches"],
                  "remat": t["remat"]}
    loader = BatchLoader(dataset(cfg, mix, seed), batch_size=t["sequences_per_step"],
                         straggler_timeout_s=5.0)
    try:
        with mesh:
            specs = model_zoo.param_specs(bundle.model)
            with host_span("bench.weights"):
                params = weights_mod.program_tree(fam, cfg, specs, seed)
                state = {"params": params,
                         "opt": jax.jit(bundle.optimizer.init)(params),
                         "step": jnp.zeros((), jnp.int32)}
                del params
            step_fn = jax.jit(bundle.train_step, donate_argnums=(0,))
            it = loader.batches(epochs=1_000_000)

            def one_step(state):
                with host_span("bench.input"):
                    t_in = time.perf_counter()
                    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
                    wait = time.perf_counter() - t_in
                with host_span("bench.train_step"):
                    state, metrics = step_fn(state, batch)
                return state, metrics, wait

            losses = []
            for k in range(CHECKED_STEPS):
                state, metrics, _ = one_step(state)
                losses.append(float(metrics["loss"]))
                if k == 0:
                    b1 = t["beta1"]
                    g1 = _leaf_norms(jax.tree.map(lambda m: m / (1 - b1),
                                                  state["opt"]["m"]))
            p0 = weights_mod.program_tree(fam, cfg, specs, seed)
            delta = _leaf_norms(jax.tree.map(
                lambda m, p: m - p.astype(jnp.float32), state["opt"]["master"], p0))
            del p0
            rec.extra["program"] = {"loss": losses, "grad": g1, "delta": delta}

            compiles = Compiles()
            rec.setup_s = time.perf_counter() - ctx.t_start
            ctx.start_trace()
            waits, n = [], 0
            with compiles, host_span("bench.window"):
                t0 = time.perf_counter()
                prev = None
                while time.perf_counter() - t0 < seconds:
                    state, metrics, wait = one_step(state)
                    waits.append(wait)
                    n += 1
                    if prev is not None:
                        with host_span("bench.sync"):
                            float(prev["loss"])
                    prev = metrics
                with host_span("bench.sync"):
                    jax.block_until_ready(state)
                rec.window_s = rec.end_s = time.perf_counter() - t0
            ctx.stop_trace()
    finally:
        loader.close()
    rec.compiles = compiles.count
    rec.extra["compiled_in_window"] = sorted(set(compiles.names))
    rec.steps = [{"tokens": tokens_per_step, "input_wait_s": w} for w in waits]
    rec.attempted, rec.failed = n, 0
    rec.memory_peak_bytes = peak_bytes(dev)
    del state, metrics, prev
    gc.collect()
    return rec


def compare(program: dict, ref: dict, grad_floor: float = 1e-3) -> dict:
    """Three numbers from per-leaf norms (``limit_checks`` holds two).

    ``loss_gap``: the largest relative gap of a step's loss.  ``grad_gap``
    and ``update_gap``: by the worst leaf, |program norm - reference norm|
    over the larger of the reference leaf's norm and the median leaf's.
    Leaves whose reference gradient is under ``grad_floor`` of the median
    leaf's (a key bias under softmax) move by round-off alone and are left
    out of both."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(program["loss"], ref["loss"]))
    med_g = float(np.median(list(ref["grad"].values())))
    keep = [k for k, v in ref["grad"].items() if v >= grad_floor * med_g]
    med_d = float(np.median([ref["delta"][k] for k in keep]))

    def worst(key, med):
        return max((abs(program[key][k] - ref[key][k]) / max(ref[key][k], med), k)
                   for k in keep)

    grad_gap, grad_leaf = worst("grad", med_g)
    update_gap, update_leaf = worst("delta", med_d)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "update_gap": update_gap,
            "worst_leaf": {"grad": grad_leaf, "update": update_leaf},
            "left_out": sorted(set(ref["grad"]) - set(keep))}


def reference_readings(ctx, quant=None, half: bool = False) -> dict:
    """The reference's losses, first clipped gradient and change after
    ``CHECKED_STEPS`` steps, per leaf, on the loader's first batches of the
    cell ``ctx``.  ``half`` plants a fault: each step takes the mean over
    the first half of its batch only."""
    import jax
    import jax.numpy as jnp

    import reference

    cfg, fam, seed = ctx.config, ctx.family, ctx.seed
    w0 = {k: v.astype(jnp.float32) for k, v in weights_mod.make(fam, cfg, seed).items()}
    batches = [batch_rows(cfg, ctx.mix, seed, k) for k in range(CHECKED_STEPS)]
    batches = [jnp.asarray(b[: len(b) // 2] if half else b) for b in batches]
    tcfg = dict(cfg["train"])
    losses, g1, w3 = reference.adamw_steps(fam, cfg, tcfg, w0, batches, quant)
    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(x * x)))
    out = {"loss": losses,
           "grad": {k: float(norm(v)) for k, v in g1.items()},
           "delta": {k: float(norm(w3[k] - w0[k])) for k in w0}}
    return out


def limit_checks(c: dict, lim: dict) -> dict:
    """The numbers of ``compare`` that the check holds, each beside its limit.
    ``loss_gap`` is not held: neither the float8 control nor a planted fault
    reads three times what sound runs read on it."""
    return {k: {"value": c[k], "max": lim[k]} for k in ("grad_gap", "update_gap")}


def check(ctx, rec: Record) -> dict:
    ref = reference_readings(ctx)
    c = compare(rec.extra["program"], ref)
    rec.extra["left_out"] = c["left_out"]
    return {"window_compiles": {"value": rec.compiles, "max": 0},
            **limit_checks(c, ctx.limits)}
