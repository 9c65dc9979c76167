"""Unified model API: ``build_model(cfg)`` returns a family-specific model
object with one interface (plan/init/forward/prefill/decode_step/input specs),
so the training service, serving engine, and dry-run treat all ten assigned
architectures identically.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.distributed.sharding import constrain
from repro.models import layers, params as P, ssm
from repro.models.encdec import EncDecModel
from repro.models.hybrid import HybridModel
from repro.models.ssm import SSMState
from repro.models.transformer import TransformerLM, _maybe_remat, _zero_metrics
from repro.models.scan_utils import scan_or_unroll
from repro.training import losses

# encoder source length held fixed for enc-dec decode shapes (DESIGN.md §4)
ENCDEC_DECODE_SRC_LEN = 4096


# ---------------------------------------------------------------------------
# Pure-SSM LM (mamba2)
# ---------------------------------------------------------------------------


class SSMModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def plan(self) -> dict:
        cfg = self.cfg
        layer = {"ln": layers.norm_plan(cfg), "ssm": ssm.ssm_plan(cfg)}
        return {
            "embed": layers.embed_plan(cfg),
            "layers": P.stack_plan(layer, cfg.num_layers),
            "final_norm": layers.norm_plan(cfg),
        }

    def _run(self, params, x, mode: str, state: Optional[SSMState] = None):
        cfg = self.cfg
        want_state = mode in ("prefill", "decode")

        def body(h, xs):
            if mode == "decode":
                lp, st = xs
            else:
                lp, st = xs, None
            out, new_st = ssm.apply_ssm(
                cfg, lp["ssm"], layers.apply_norm(cfg, lp["ln"], h),
                state=st, return_state=want_state,
            )
            if not want_state:
                new_st = jnp.zeros((), jnp.float32)
            return h + out, new_st

        if mode == "train":
            body_r = _maybe_remat(body, cfg)
            x, _ = scan_or_unroll(body_r, x, params["layers"], cfg.scan_layers)
            return x, None
        if mode == "prefill":
            x, states = scan_or_unroll(body, x, params["layers"], cfg.scan_layers)
            return x, states
        x, states = scan_or_unroll(body, x, (params["layers"], state), cfg.scan_layers)
        return x, states

    def forward(self, params, batch):
        cfg = self.cfg
        x = layers.embed_tokens(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        x = constrain(x, ("batch", "seq", "act_embed"))
        x, _ = self._run(params, x, "train")
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        return constrain(logits, ("batch", "seq", "vocab_act")), _zero_metrics()

    def prefill(self, params, batch, max_len: int = 0):
        cfg = self.cfg
        x = layers.embed_tokens(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        x, states = self._run(params, x, "prefill")
        x = layers.apply_norm(cfg, params["final_norm"], x[:, -1:])
        logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        return logits, states

    def decode_step(self, params, state, batch):
        cfg = self.cfg
        x = layers.embed_tokens(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        x, new_state = self._run(params, x, "decode", state=state)
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.lm_logits(params["embed"], x, cfg.tie_embeddings)
        return logits, new_state

    def init_decode_state(self, batch_size: int, max_len: int = 0) -> SSMState:
        base = ssm.init_ssm_state(self.cfg, batch_size)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.cfg.num_layers,) + x.shape), base
        )

    def decode_state_logical(self, long_context: bool = False) -> SSMState:
        base = ssm.ssm_state_logical()
        batch_lg = "batch_rep" if long_context else "batch"
        return jax.tree.map(
            lambda lg: ("layers", batch_lg) + tuple(lg[1:]),
            base,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )


# ---------------------------------------------------------------------------
# build + uniform helpers
# ---------------------------------------------------------------------------


def build_model(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        return TransformerLM(cfg)
    if cfg.family == "ssm":
        return SSMModel(cfg)
    if cfg.family == "hybrid":
        return HybridModel(cfg)
    if cfg.family == "encdec":
        return EncDecModel(cfg)
    raise ValueError(cfg.family)


def init_params(model, key: jax.Array):
    return P.init_params(model.plan(), key)


def param_specs(model):
    return P.param_specs(model.plan())


def param_logical(model):
    return P.param_logical(model.plan())


def loss_fn(model, params, batch) -> tuple[jax.Array, dict]:
    cfg = model.cfg
    logits, moe_metrics = model.forward(params, batch)
    with jax.named_scope("lm_head"):
        mask = losses.loss_mask_for(cfg, batch)
        loss, metrics = losses.ce_loss(cfg, logits, batch["targets"], mask)
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_coef * moe_metrics.aux_loss
        loss = loss + cfg.moe.router_z_coef * moe_metrics.router_z_loss
        metrics = dict(
            metrics,
            moe_aux=moe_metrics.aux_loss,
            moe_drop=moe_metrics.drop_fraction,
        )
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one (arch x shape) cell, as ShapeDtypeStructs."""
    B, S = shape.global_batch, shape.seq_len
    mode = shape.mode
    i32, bf16 = jnp.int32, jnp.bfloat16
    specs: dict[str, Any] = {}

    if cfg.family == "encdec":
        if mode in ("train", "prefill"):
            s_src = s_dec = S // 2
            specs["src_emb"] = _sds((B, s_src, cfg.frontend_dim), bf16)
            specs["tokens"] = _sds((B, s_dec), i32)
            if mode == "train":
                specs["targets"] = _sds((B, s_dec), i32)
        else:  # decode
            specs["tokens"] = _sds((B, 1), i32)
        return specs

    if cfg.family == "vlm":
        F = cfg.frontend_tokens
        if mode in ("train", "prefill"):
            specs["patches"] = _sds((B, F, cfg.frontend_dim), bf16)
            specs["tokens"] = _sds((B, S - F), i32)
            specs["positions3"] = _sds((3, B, S), i32)
            if mode == "train":
                specs["targets"] = _sds((B, S), i32)
        else:
            specs["tokens"] = _sds((B, 1), i32)
            specs["positions3"] = _sds((3, B, 1), i32)
        return specs

    # dense / moe / ssm / hybrid
    if mode in ("train", "prefill"):
        specs["tokens"] = _sds((B, S), i32)
        if mode == "train":
            specs["targets"] = _sds((B, S), i32)
    else:
        specs["tokens"] = _sds((B, 1), i32)
    return specs


def input_logical(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, tuple]:
    """Logical sharding for each input."""
    batch_lg = "batch_rep" if shape.name == "long_500k" else "batch"
    lg = {
        "tokens": (batch_lg, None),
        "targets": (batch_lg, None),
        "src_emb": (batch_lg, None, None),
        "patches": (batch_lg, None, None),
        "positions3": (None, batch_lg, None),
    }
    return {k: lg[k] for k in input_specs(cfg, shape)}


def decode_state_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Decode-state ShapeDtypeStructs for a decode cell (no allocation)."""
    model = build_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        fn = lambda: model.init_decode_state(B, S, ENCDEC_DECODE_SRC_LEN)
    else:
        fn = lambda: model.init_decode_state(B, S)
    return jax.eval_shape(fn)


def decode_state_logical(cfg: ModelConfig, shape: ShapeConfig):
    model = build_model(cfg)
    return model.decode_state_logical(long_context=shape.name == "long_500k")


def make_train_batch(cfg: ModelConfig, shape: ShapeConfig, key: jax.Array) -> dict:
    """Materialize a random batch matching input_specs (tests/examples)."""
    specs = input_specs(cfg, shape)
    batch = {}
    for name, s in specs.items():
        key, k = jax.random.split(key)
        if s.dtype == jnp.int32:
            hi = cfg.vocab_size if name in ("tokens", "targets") else max(shape.seq_len, 2)
            batch[name] = jax.random.randint(k, s.shape, 0, hi, jnp.int32)
        else:
            batch[name] = jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
    return batch
