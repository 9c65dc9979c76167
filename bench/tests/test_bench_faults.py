"""The harness, with the look for a chip skipped, run at a tiny size with the
timed path broken underneath: ``correct`` must come out false for each fault
a cell can have, and true for the unbroken path.  And the control (the
reference at float8, in the program's place) must fail the served-token
comparison that the program passes."""

import time
from pathlib import Path

import bench_path  # noqa: F401
import pytest

import run
import serve
import spec
import train

TINY = dict(
    arch="qwen2-0.5b", family="qwen-dense", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
    vocab_size=512,
    rms_norm_eps=1e-6, rope_theta=1e6, attention_bias=True, qk_norm=False,
    tie_word_embeddings=True, torch_dtype="bfloat16",
    serve={"page_size": 16, "max_slots": 4, "headroom_bytes": 0},
    train=dict(seq_len=64, sequences_per_step=4, microbatches=1, remat="dots",
               learning_rate=3e-4, warmup_steps=100, total_steps=1000,
               weight_decay=0.1, beta1=0.9, beta2=0.95, eps=1e-8, grad_clip=1.0,
               z_loss_coef=1e-4),
)
CHAT = dict(kind="serve", arrival="gamma", rate_per_s=8.0, cv=2.0, drain_s=20,
            prompt=dict(median=24, sigma=0.8, min=8, max=64),
            output=dict(median=8, sigma=0.8, min=2, max=32))
TRAIN_MIX = dict(kind="train", sequences_per_partition=2)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


# The served-token limit at this tiny size: sound runs read 0.002-0.02 and
# the float8 control about 0.5 (CPU).  No serve cell is in BENCHMARK.json
# yet, so no chip limit exists to hold them to.
TINY_SERVE_LIMITS = {"served_logit_gap": 0.1}


def limits(kind):
    """Training: the limits the chip cell holds (the qwen2-0.5b file)."""
    if kind == "serve":
        return TINY_SERVE_LIMITS
    bm = spec.load_benchmark()
    return spec.config(bm, "qwen2-0.5b")["limits"][kind]


def ctx_for(cell, mix, kind, tmp_path):
    bm = spec.load_benchmark()
    c = run.Ctx(cell={"name": cell, "chips": 1}, config=TINY, mix=mix,
                seed=2**31 + 11, seconds=2.0, trace=False,
                t_start=time.perf_counter(), trace_dir=Path(tmp_path),
                limits=limits(kind), check_tokens=48)
    return c, bm


@pytest.fixture
def small_pool(monkeypatch):
    monkeypatch.setattr(serve, "size_pool", lambda *a: {"num_pages": 64, "slots": 4})


@pytest.mark.parametrize("broken", [False, True])
def test_serve_token_altered(broken, small_pool, monkeypatch, tmp_path):
    from repro.serving.continuous import ContinuousBatchingEngine

    if broken:
        sample = ContinuousBatchingEngine._sample
        monkeypatch.setattr(
            ContinuousBatchingEngine, "_sample",
            lambda self, *a: (sample(self, *a) + 1) % self.cfg.vocab_size)
    ctx, bm = ctx_for("qwen2-0.5b.serve-chat", CHAT, "serve", tmp_path)
    out = run.run_cell(ctx, bm, CPU)
    assert out["correct"] is (not broken), out["checks"]


def test_control_fails_where_program_passes(small_pool, tmp_path):
    ctx, _ = ctx_for("qwen2-0.5b.serve-chat", CHAT, "serve", tmp_path)
    rec = serve.run(ctx)
    program, control, n = serve.gaps(ctx, rec, quant="fp8")
    assert n >= 32
    assert program <= ctx.limits["served_logit_gap"] < control


def _break_step(monkeypatch, how):
    import repro.training.train_loop as tl

    orig = tl.make_train_step

    def make(*a, **k):
        bundle = orig(*a, **k)
        step = bundle.train_step
        if how == "unchanged":
            bundle.train_step = lambda state, batch: (state, step(state, batch)[1])
        else:
            bundle.train_step = lambda state, batch: step(
                state, {key: v[: v.shape[0] // 2] for key, v in batch.items()})
        return bundle

    monkeypatch.setattr(tl, "make_train_step", make)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_train_faults(fault, monkeypatch, tmp_path):
    if fault:
        _break_step(monkeypatch, fault)
    ctx, bm = ctx_for("qwen2-0.5b.train-2k", TRAIN_MIX, "train", tmp_path)
    out = run.run_cell(ctx, bm, CPU)
    assert out["correct"] is (fault is None), out["checks"]


@pytest.mark.parametrize("control", ["fp8", "half_batch"])
def test_train_control_in_program_place_fails(control, monkeypatch, tmp_path):
    """The reference put in the program's place, at float8 or over half of
    each batch, is not correct against the limits the chip cell holds."""
    program_run = train.run

    def control_run(ctx):
        rec = program_run(ctx)
        rec.extra["program"] = train.reference_readings(
            ctx, quant="fp8" if control == "fp8" else None, half=control == "half_batch")
        return rec

    monkeypatch.setattr(train, "run", control_run)
    ctx, bm = ctx_for("qwen2-0.5b.train-2k", TRAIN_MIX, "train", tmp_path)
    out = run.run_cell(ctx, bm, CPU)
    assert out["correct"] is False, out["checks"]
