"""The dense family (``bench/families/qwen-dense.py``) gives the numbers the
harness gave when the dense Qwen block was built into ``weights.py``,
``reference.py`` and ``flops.py``: taken on the CPU at commit fe56d3f, at
``test_bench_faults.TINY`` and on the committed qwen2-0.5b file."""

import bench_path  # noqa: F401
import numpy as np
import pytest

import flops
import spec
import train
import weights
from test_bench_faults import TINY, TRAIN_MIX, ctx_for

LEAVES = {
    "embed.embedding": ((512, 64), "embed"),
    "final_norm.scale": ((64,), "norm"),
    "layers.attn.k.b": ((2, 32), "bias"),
    "layers.attn.k.w": ((2, 64, 32), "matrix"),
    "layers.attn.o.w": ((2, 64, 64), "matrix"),
    "layers.attn.q.b": ((2, 64), "bias"),
    "layers.attn.q.w": ((2, 64, 64), "matrix"),
    "layers.attn.v.b": ((2, 32), "bias"),
    "layers.attn.v.w": ((2, 64, 32), "matrix"),
    "layers.ln1.scale": ((2, 64), "norm"),
    "layers.ln2.scale": ((2, 64), "norm"),
    "layers.mlp.down.w": ((2, 128, 64), "matrix"),
    "layers.mlp.gate.w": ((2, 64, 128), "matrix"),
    "layers.mlp.up.w": ((2, 64, 128), "matrix"),
}
LOSSES = [6.6227030754089355, 6.8612060546875, 6.819761276245117]
GRAD_NORMS = {
    "embed.embedding": 0.5011947751045227,
    "final_norm.scale": 0.07195954769849777,
    "layers.attn.k.b": 0.011978683061897755,
    "layers.attn.k.w": 0.24099420011043549,
    "layers.attn.o.w": 0.4534391462802887,
    "layers.attn.q.b": 0.030704515054821968,
    "layers.attn.q.w": 0.2408680021762848,
    "layers.attn.v.b": 0.12426774948835373,
    "layers.attn.v.w": 0.34947848320007324,
    "layers.ln1.scale": 0.058430109173059464,
    "layers.ln2.scale": 0.048547688871622086,
    "layers.mlp.down.w": 0.37894850969314575,
    "layers.mlp.gate.w": 0.25836116075515747,
    "layers.mlp.up.w": 0.25910520553588867,
}
# flops.<function>(family, qwen2-0.5b, argument) at fe56d3f
FLOPS = [
    ("train_flops_per_token", 2048, 3228137472.0),
    ("prefill_flops", 300, 218851827712.0),
    ("token_flops", np.arange(1, 40) + 7, 38619549696.0),
]


def test_leaf_names_and_shapes():
    fam = spec.family(TINY)
    assert fam.leaf_specs(TINY) == LEAVES
    made = weights.make(fam, TINY, 3)
    assert {k: v.shape for k, v in made.items()} == {k: s for k, (s, _) in LEAVES.items()}


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    ctx, _ = ctx_for("qwen2-0.5b.train-2k", TRAIN_MIX, "train", tmp_path_factory.mktemp("r"))
    return train.reference_readings(ctx)


def test_reference_losses(readings):
    np.testing.assert_allclose(readings["loss"], LOSSES, rtol=1e-6)


def test_reference_first_gradient(readings):
    assert set(readings["grad"]) == set(GRAD_NORMS)
    for k, v in GRAD_NORMS.items():
        np.testing.assert_allclose(readings["grad"][k], v, rtol=1e-6, err_msg=k)


@pytest.mark.parametrize("name,arg,want", FLOPS, ids=[f[0] for f in FLOPS])
def test_operations_per_token(name, arg, want):
    cfg = spec.config(spec.load_benchmark(), "qwen2-0.5b")
    assert getattr(flops, name)(spec.family(cfg), cfg, arg) == want
