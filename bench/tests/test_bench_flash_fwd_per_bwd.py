"""The flash forward-per-backward reader: forward kernel runs over dkv
kernel runs in the traced window."""

import argparse
import gzip
import json

import bench_path  # noqa: F401
import pytest

import layer_trace as lt
import spec

NAME = "train.flash_fwd_per_bwd"
LAYER = "jit(train_step)/jit(main)/microbatches/while/body/closed_call"
FWD = "vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/pallas_call"
DKV = "vmap(vmap(jit(_splash_attention)))/splash_mqa_dkv_no_residuals/pallas_call"


def _chip_slice():
    raw = (bench_path.BENCH / "tests/data/chip_train_trace_slice.json.gz").read_bytes()
    return json.loads(gzip.decompress(raw))


def _layers(n: int, fwd_per_layer: int):
    """Device 0's events of ``n`` layers' forward and backward, each layer
    running the forward kernel ``fwd_per_layer`` times and dkv and dq once,
    inside a window from 100 to 100 + 100 n; a forward before the window."""
    ops = [("%splash_mqa_fwd_residuals.9 = bf16[2,2,7,2048,64] custom-call()", 0, 50,
            f"{LAYER}/jvp(layers)/attention/jvp(flash_attention)/{FWD}")]
    for i in range(n):
        t = 100 + 100 * i
        for j in range(fwd_per_layer):
            ops.append((f"%splash_mqa_fwd_residuals.{j + 1} = bf16[2,2,7,2048,64] custom-call()",
                        t + 10 * j, t + 10 * j + 8,
                        f"{LAYER}/transpose(jvp(layers))/attention/jvp(flash_attention)/{FWD}"))
        ops.append(("%splash_mqa_dkv_no_residuals.3 = bf16[2,2,2048,64] custom-call()", t + 40,
                    t + 50, f"{LAYER}/transpose(jvp(layers))/attention/"
                    f"transpose(jvp(flash_attention))/{DKV}"))
        ops.append(("%splash_mqa_dq_no_residuals.4 = bf16[2,2,7,2048,64] custom-call()", t + 50,
                    t + 60, f"{LAYER}/transpose(jvp(layers))/attention/"
                    f"transpose(jvp(flash_attention))/{DKV.replace('dkv', 'dq')}"))
        ops.append(("%fusion.5 = bf16[2,2048,896] fusion()", t + 60, t + 90,
                    f"{LAYER}/transpose(jvp(layers))/mlp/dot_general"))
    return {"devices": {"/device:TPU:0": ops},
            "host": [("bench.window", 100, 100 + 100 * n, "main")]}


@pytest.fixture
def profiled(tmp_path, monkeypatch):
    """The reader over a stand-in profile whose extraction is given."""
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(lt, "TRACE_DIR", tmp_path)
    lt._reduce_file.cache_clear()

    def use(ex):
        monkeypatch.setattr(lt, "extract", lambda path: ex)
        window = lt.reduce(ex)["window_s"]
        return argparse.Namespace(trace={"window_s": window}, steps=[{"tokens": 1}] * 2)

    yield use
    lt._reduce_file.cache_clear()


@pytest.mark.parametrize("fwd_per_layer", [2, 1])
def test_reads_forward_runs_per_dkv_run(profiled, fwd_per_layer):
    run = profiled(_layers(3, fwd_per_layer))
    assert spec.reader(NAME, bench_path.BENCH)(run) == float(fwd_per_layer)


def test_a_trace_without_the_kernel_reads_none(profiled):
    """A recorded chip trace of the einsum route runs no splash kernel."""
    run = profiled(_chip_slice())
    assert spec.reader(NAME, bench_path.BENCH)(run) is None


def test_untraced_run_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(lt, "TRACE_DIR", tmp_path)
    run = argparse.Namespace(trace=None, steps=[{"tokens": 1}])
    assert spec.reader(NAME, bench_path.BENCH)(run) is None
