"""Pure data-parallel train step under ``shard_map`` with explicit collectives.

This is the path where the wire format is ours (not GSPMD's): gradients are
reduced with either a flat psum, a pod-hierarchical reduce (ICI first, DCN
once), or the int8 error-feedback compressed reduce from
``distributed.collectives`` — the cross-pod bandwidth tricks of DESIGN.md §6.
Params/opt state are replicated (pure DP targets the paper's Paddle-trainer
deployment, one model replica per worker, PS-style sync).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, TrainConfig
from repro.distributed import collectives
from repro.models import model_zoo
from repro.training import optimizer as opt_lib
from repro.training.train_loop import clip_by_global_norm


def make_dp_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh: Mesh,
    compression: str = "none",  # none | int8
    hierarchical: bool = True,
):
    """Returns (init_fn, step_fn).  step_fn: (state, batch) -> (state, metrics).

    state = {params, opt, step, residual?}; batch leaves sharded over the dp
    axes on dim 0.
    """
    model = model_zoo.build_model(cfg)
    optimizer = opt_lib.make_optimizer(tcfg)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ici_axes = tuple(a for a in dp_axes if a != "pod")
    dcn_axes = tuple(a for a in dp_axes if a == "pod")

    def per_device_step(state, batch):
        params = state["params"]

        def loss_fn(p):
            return model_zoo.loss_fn(model, p, batch)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)

        if compression == "int8":
            grads, new_residual = collectives.compressed_psum_mean(
                grads, state["residual"], dp_axes
            )
        else:
            new_residual = state.get("residual")
            if hierarchical and dcn_axes:
                grads = collectives.hierarchical_psum_mean(grads, ici_axes, dcn_axes)
            else:
                grads = collectives.psum_mean(grads, dp_axes)

        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        new_params, new_opt = optimizer.update(grads, state["opt"], params, state["step"])
        metrics = collectives.psum_mean(metrics, dp_axes)
        metrics = dict(metrics, grad_norm=gnorm)
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        if compression == "int8":
            new_state["residual"] = new_residual
        return new_state, metrics

    def init_fn(key):
        params = model_zoo.init_params(model, key)
        state = {
            "params": params,
            "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }
        if compression == "int8":
            state["residual"] = collectives.init_residual(params)
        return state

    # state replicated; batch split over dp axes on dim 0
    state_spec = P()
    dp_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])

    def batch_specs(batch):
        def one(name, x):
            if name == "positions3":
                return P(None, dp_spec[0])
            return dp_spec

        return {k: one(k, v) for k, v in batch.items()}

    def step_fn(state, batch):
        smapped = jax.shard_map(
            per_device_step,
            mesh=mesh,
            in_specs=(state_spec, batch_specs(batch)),
            out_specs=(state_spec, state_spec),
            check_vma=False,
        )
        return smapped(state, batch)

    return init_fn, step_fn
