"""Train-step factory: pjit/GSPMD path with microbatching, clipping, remat.

``make_train_step`` builds a jitted SPMD ``(state, batch) -> (state, metrics)``
whose in/out shardings come from the logical rule tables, so the same factory
serves the 1-device test mesh, the 16x16 pod and the 2x16x16 multi-pod mesh.

The step's layers carry ``jax.named_scope`` names (``embed``, ``attention``,
``mlp``, ``lm_head`` in the model; ``layers``, ``microbatches``,
``grad_accum``, ``optimizer`` here and in the layer runner).  They change
only each device op's HLO ``op_name`` metadata, so a profile can bucket the
chip's time by layer.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ParallelConfig, TrainConfig
from repro.distributed.sharding import (
    logical_to_spec,
    resolve_rules,
    rules_for_model,
    sanitize_specs,
)
from repro.models import model_zoo
from repro.training import optimizer as opt_lib


@dataclasses.dataclass
class TrainStepBundle:
    """Everything the launcher / dry-run needs for one training job."""

    model: Any
    optimizer: opt_lib.Optimizer
    rules: dict
    param_spec_tree: Any  # PartitionSpecs for params
    opt_spec_tree: Any
    batch_spec_tree: Any
    train_step: Any  # callable (state, batch) -> (state, metrics)
    init_fn: Any  # (key) -> state


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale), tree), norm


def _split_microbatches(batch: dict, n: int) -> dict:
    """(B, ...) -> (n, B//n, ...), leading-dim split per input."""

    def one(name, x):
        if name == "positions3":  # (3, B, S)
            B = x.shape[1]
            return jnp.moveaxis(x.reshape(x.shape[0], n, B // n, *x.shape[2:]), 1, 0)
        B = x.shape[0]
        return x.reshape(n, B // n, *x.shape[1:])

    return {k: one(k, v) for k, v in batch.items()}


def make_loss_and_grad(model, num_microbatches: int):
    from repro.models.scan_utils import scan_or_unroll

    def loss_fn(params, batch):
        return model_zoo.loss_fn(model, params, batch)

    vg = jax.value_and_grad(loss_fn, has_aux=True)

    if num_microbatches <= 1:
        def grads_fn(params, batch):
            (loss, metrics), grads = vg(params, batch)
            return loss, metrics, grads

        return grads_fn

    def grads_fn(params, batch):
        mb = _split_microbatches(batch, num_microbatches)

        def body(acc, mb_batch):
            (loss, metrics), grads = vg(params, mb_batch)
            acc_g, acc_l = acc
            with jax.named_scope("grad_accum"):
                acc_g = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / num_microbatches, acc_g, grads
                )
            return (acc_g, acc_l + loss / num_microbatches), metrics

        with jax.named_scope("grad_accum"):
            zero_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        # unrolled when cfg.scan_layers=False (roofline lowers need exact costs)
        with jax.named_scope("microbatches"):
            (grads, loss), metrics = scan_or_unroll(
                body, (zero_g, 0.0), mb, model.cfg.scan_layers
            )
        metrics = jax.tree.map(lambda m: jnp.mean(m), metrics)
        return loss, metrics, grads

    return grads_fn


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    pcfg: ParallelConfig,
    mesh: Mesh,
    shape=None,
) -> TrainStepBundle:
    model = model_zoo.build_model(cfg)
    optimizer = opt_lib.make_optimizer(tcfg)
    rules = rules_for_model(cfg, mesh, weights_2d=pcfg.weights_2d)

    param_logical = model_zoo.param_logical(model)
    param_specs_tree = model_zoo.param_specs(model)
    is_lg = lambda x: x is None or (
        isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)
    )
    param_spec_tree = jax.tree.map(
        lambda lg: logical_to_spec(lg, mesh, rules), param_logical, is_leaf=is_lg
    )
    param_spec_tree = sanitize_specs(param_spec_tree, param_specs_tree, mesh)

    opt_logical = optimizer.state_logical(param_logical)
    opt_shapes = jax.eval_shape(optimizer.init, param_specs_tree)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    opt_spec_tree = opt_lib.zero1_state_specs(
        opt_logical, opt_shapes, mesh, rules, dp_axes, enabled=pcfg.zero1
    )
    opt_spec_tree = sanitize_specs(opt_spec_tree, opt_shapes, mesh)

    grads_fn = make_loss_and_grad(model, pcfg.num_microbatches)

    def train_step(state, batch):
        params, opt_state, step = state["params"], state["opt"], state["step"]
        loss, metrics, grads = grads_fn(params, batch)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            new_params, new_opt = optimizer.update(grads, opt_state, params, step)
        # re-constrain updated trees to their target shardings
        new_params = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            new_params,
            param_spec_tree,
        )
        metrics = dict(metrics, grad_norm=gnorm, lr=opt_lib.lr_schedule(tcfg)(step))
        return {"params": new_params, "opt": new_opt, "step": step + 1}, metrics

    def init_fn(key):
        params = model_zoo.init_params(model, key)
        return {
            "params": params,
            "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    return TrainStepBundle(
        model=model,
        optimizer=optimizer,
        rules=rules,
        param_spec_tree=param_spec_tree,
        opt_spec_tree=opt_spec_tree,
        batch_spec_tree=None,
        train_step=train_step,
        init_fn=init_fn,
    )


def state_shardings(bundle: TrainStepBundle, mesh: Mesh):
    ps = jax.tree.map(
        lambda s: NamedSharding(mesh, s), bundle.param_spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    os_ = jax.tree.map(
        lambda s: NamedSharding(mesh, s), bundle.opt_spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    return {
        "params": ps,
        "opt": os_,
        "step": NamedSharding(mesh, P()),
    }


def batch_shardings(cfg: ModelConfig, shape, mesh: Mesh, rules) -> dict:
    lg = model_zoo.input_logical(cfg, shape)
    return {
        k: NamedSharding(mesh, logical_to_spec(v, mesh, rules)) for k, v in lg.items()
    }
