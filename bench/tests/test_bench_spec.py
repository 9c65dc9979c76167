"""A configuration, a traffic mix and a per-layer metric added as new files
and new entries are found by name, and a run reports the new metric."""

import json
import shutil
import argparse

import bench_path  # noqa: F401

import run
import serve
import spec

TINY = {
    "source": "test", "arch": "qwen2-0.5b", "hidden_size": 64,
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


def make_root(tmp_path):
    shutil.copytree(bench_path.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = json.loads((bench_path.ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "bench/traffic/tiny-chat.json").write_text(json.dumps(MIX))
    (tmp_path / "bench/metrics/requests_due.py").write_text(READER)
    bm["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny.chat", "config": "tiny",
                            "traffic": "tiny-chat", "chips": 1, "why": "test"})
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
