"""A configuration, a traffic mix, a per-layer metric and a model family
added as new files and new entries are found by name, and a run reports the
new metric and checks the new family against its own reference."""

import json
import shutil
import argparse
from pathlib import Path

import bench_path  # noqa: F401
import numpy as np
import pytest

import reference
import run
import serve
import spec
import traffic
import weights

TINY = {
    "source": "test", "arch": "qwen2-0.5b", "family": "qwen-dense", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "attention_bias": True, "qk_norm": False,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16", "reduced": [],
    "serve": {"page_size": 16, "max_slots": 4, "headroom_bytes": 0},
    "limits": {"serve": {"served_logit_gap": 0.5}},
}
MIX = {"kind": "serve", "arrival": "gamma", "rate_per_s": 8.0, "cv": 2.0, "round": 16,
       "drain_s": 20, "prompt": {"median": 24, "sigma": 0.8, "min": 8, "max": 64},
       "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 32}}
READER = '''
def read(run):
    return float(len(run.due))
'''
# A family whose parameter tree differs from the dense one: no q/k/v biases
# and a head of its own (the program's ``embed.head``).
UNTIED = '''
from pathlib import Path

import spec
from reference import F32, mm

dense = spec.family({"family": "qwen-dense"}, Path(__file__).resolve().parents[1])


def leaf_specs(cfg):
    s = dense.leaf_specs(dict(cfg, attention_bias=False))
    s["embed.head"] = ((cfg["hidden_size"], cfg["vocab_size"]), "matrix")
    return s


def model_config(cfg):
    return dense.model_config(dict(cfg, attention_bias=False, tie_word_embeddings=False))


def logits(cfg, w, h, quant=None):
    return mm(h, w["embed.head"].astype(F32), quant)


hidden, layer_flops, head_flops = dense.hidden, dense.layer_flops, dense.head_flops
'''
UNTIED_CFG = dict({k: v for k, v in TINY.items()
                   if k not in ("attention_bias", "tie_word_embeddings")},
                  family="toy-untied",
                  train=dict(seq_len=64, sequences_per_step=4, microbatches=1,
                             remat="dots", learning_rate=3e-4, warmup_steps=100,
                             total_steps=1000, weight_decay=0.1, beta1=0.9, beta2=0.95,
                             eps=1e-8, grad_clip=1.0, z_loss_coef=1e-4),
                  limits=json.loads((bench_path.BENCH / "configs/qwen2-0.5b.json")
                                    .read_text())["limits"])
# Two kinds of layer in one stack: a leading dense layer, then narrower ones.
TWO_STACKS = '''
import functools
from pathlib import Path

import spec
from reference import F32, rms, scan_layers

dense = spec.family({"family": "qwen-dense"}, Path(__file__).resolve().parents[1])


def leaf_specs(cfg):
    k = cfg["first_k_dense_replace"]
    narrow = dict(cfg, intermediate_size=cfg["moe_intermediate_size"],
                  num_hidden_layers=cfg["num_hidden_layers"] - k)
    return {**dense.leaf_specs(narrow), **dense.layer_specs(cfg, "dense_layers", k)}


def hidden(cfg, w, tokens, quant=None):
    layer = functools.partial(dense.layer, cfg, quant)
    x = w["embed.embedding"].astype(F32)[tokens]
    x = scan_layers(layer, x, w, "dense_layers")
    x = scan_layers(layer, x, w, "layers")
    return rms(x, w["final_norm.scale"].astype(F32), cfg["rms_norm_eps"])


logits = dense.logits
'''


def make_root(tmp_path):
    shutil.copytree(bench_path.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = json.loads((bench_path.ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "bench/traffic/tiny-chat.json").write_text(json.dumps(MIX))
    (tmp_path / "bench/metrics/requests_due.py").write_text(READER)
    (tmp_path / "bench/families/toy-untied.py").write_text(UNTIED)
    (tmp_path / "bench/families/toy-two-stacks.py").write_text(TWO_STACKS)
    (tmp_path / "bench/configs/toy-untied.json").write_text(json.dumps(UNTIED_CFG))
    (tmp_path / "bench/traffic/tiny-train.json").write_text(
        json.dumps({"kind": "train", "sequences_per_partition": 2}))
    bm["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [], "why": "test"})
    bm["configs"].append({"name": "toy-untied", "source": "test",
                          "file": "bench/configs/toy-untied.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": "tiny.chat", "config": "tiny",
                            "traffic": "tiny-chat", "chips": 1, "why": "test"})
    bm["workloads"].append({"name": "toy-untied.train", "config": "toy-untied",
                            "traffic": "tiny-train", "chips": 1, "why": "test"})
    bm["end_to_end"].append({"name": "requests_due", "unit": "requests",
                             "better": "higher", "bound": 0.01, "source": "host_clock",
                             "workloads": ["tiny.chat"]})
    for m in bm["end_to_end"]:
        if m["name"] == "setup_s":
            assert "workloads" not in m
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return bm


def test_new_files_are_found_by_name(tmp_path):
    bm = make_root(tmp_path)
    assert spec.cell(bm, "tiny.chat")["traffic"] == "tiny-chat"
    assert spec.config(bm, "tiny", tmp_path)["hidden_size"] == 64
    assert spec.traffic("tiny-chat", tmp_path / "bench")["rate_per_s"] == 8.0
    names = [m["name"] for m in spec.metrics_for(bm, "tiny.chat", trace=False)]
    assert names == ["setup_s", "requests_due"]
    assert spec.reader("requests_due", tmp_path / "bench")(
        argparse.Namespace(due={1: 0.0, 2: 1.0})) == 2.0


def test_run_reports_the_new_metric(tmp_path, monkeypatch):
    make_root(tmp_path)
    monkeypatch.setattr(serve, "size_pool", lambda *a: {"num_pages": 64, "slots": 4})
    ns = argparse.Namespace(workload="tiny.chat", seed=2**31 + 3, seconds=2.0,
                            trace=0, trace_dir=str(tmp_path / "tr"))
    ctx, bm = run.build_ctx(ns, root=tmp_path)
    ctx.check_tokens = 32
    out = run.run_cell(ctx, bm, {"platform": "cpu", "kind": "cpu", "count": 1})
    assert out["correct"], out["checks"]
    assert out["metrics"]["requests_due"]["value"] == round(8.0 * 2.0)
    assert set(out["metrics"]) == {"setup_s", "requests_due"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cfg,missing", [
    ({}, "families/<name>.py"),
    ({"family": "no-such-family"}, "families/no-such-family.py"),
])
def test_a_family_must_exist(cfg, missing, tmp_path):
    make_root(tmp_path)
    with pytest.raises(spec.SpecError, match=missing):
        spec.family(cfg, tmp_path / "bench")


def test_new_family_trains_from_new_files_only(tmp_path):
    """Set-up, the window and the check of a train cell whose family has a
    parameter tree of its own: correct, with no harness file edited."""
    make_root(tmp_path)
    ns = argparse.Namespace(workload="toy-untied.train", seed=2**31 + 5, seconds=2.0,
                            trace=0, trace_dir=str(tmp_path / "tr"))
    ctx, bm = run.build_ctx(ns, root=tmp_path)
    assert Path(ctx.family.__file__) == tmp_path / "bench/families/toy-untied.py"
    assert "embed.head" in ctx.family.leaf_specs(ctx.config)
    out = run.run_cell(ctx, bm, {"platform": "cpu", "kind": "cpu", "count": 1})
    assert out["correct"], out["checks"]
    assert out["checks"]["update_gap"]["value"] > 0


def test_two_kinds_of_layer_in_one_stack(tmp_path):
    """A family with two stacks of layers gets its leaves from ``weights``
    and its loss and gradient from the shared reference."""
    import jax
    import jax.numpy as jnp

    make_root(tmp_path)
    cfg = dict(TINY, family="toy-two-stacks", num_hidden_layers=3,
               first_k_dense_replace=1, moe_intermediate_size=32)
    fam = spec.family(cfg, tmp_path / "bench")
    w = weights.make(fam, cfg, 2**31 + 7)
    assert w["dense_layers.mlp.up.w"].shape == (1, 64, 128)
    assert w["layers.mlp.up.w"].shape == (2, 64, 32)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    rows = jnp.asarray(traffic.train_rows(7, 0, 2, 32, cfg["vocab_size"]))
    tcfg = {"z_loss_coef": 1e-4}
    loss = float(jax.jit(lambda w: reference.loss(fam, cfg, tcfg, w, rows))(w))
    l, g = jax.jit(lambda w: reference.grad(fam, cfg, tcfg, w, rows))(w)
    assert np.isfinite(loss) and loss > 0
    np.testing.assert_allclose(float(l), loss, rtol=1e-5)
    assert set(g) == set(w)
    assert all(np.isfinite(np.asarray(x)).all() for x in g.values())
    for k in ("dense_layers.mlp.down.w", "layers.mlp.down.w", "dense_layers.attn.o.w"):
        assert float(jnp.linalg.norm(g[k])) > 0, k
