"""The train step's layers are named inside the program: every scope rides
into the compiled HLO's ``op_name`` metadata, the backward pass included,
and ``repro.obs`` stays stdlib-only at import."""

from __future__ import annotations

import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.config import ParallelConfig, TrainConfig, get_arch, scale_down
from repro.distributed.mesh import single_device_mesh
from repro.training.train_loop import make_train_step

SCOPES = ("embed", "attention", "mlp", "lm_head", "layers", "microbatches",
          "grad_accum", "optimizer")


@pytest.fixture(scope="module")
def op_names():
    cfg = scale_down(get_arch("qwen2-0.5b"), vocab_size=128, num_layers=2)
    cfg = dataclasses.replace(cfg, remat="dots")
    mesh = single_device_mesh()
    bundle = make_train_step(cfg, TrainConfig(), ParallelConfig(num_microbatches=2), mesh)
    state = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((4, 32), jnp.int32) for k in ("tokens", "targets")}
    with mesh:
        text = jax.jit(bundle.train_step).lower(state, batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_train_step_carries_scope(op_names, scope):
    assert any(scope in re.split(r"[/()]", n) for n in op_names)


def test_backward_and_recomputed_ops_keep_the_inner_scope(op_names):
    bwd = [n for n in op_names if "transpose(jvp(layers))" in n]
    assert any("/attention/" in n for n in bwd)
    assert any("/mlp/" in n for n in bwd)
    assert any("rematted_computation/attention/" in n for n in bwd)


def test_obs_imports_no_jax():
    code = "import sys, repro.obs; assert 'jax' not in sys.modules; repro.obs.annotate"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "PYTHONPATH": src})
