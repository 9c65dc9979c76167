"""The flash kernel's engagement reader: device time of the ops under the
``flash_attention`` scope, per window step."""

import argparse
import gzip
import json

import bench_path  # noqa: F401
import pytest

import layer_trace as lt
import spec

NAME = "train.device_ms.attention_kernel"
LAYER = "jit(train_step)/jit(main)/microbatches/while/body/closed_call/transpose(jvp(layers))"
KERNEL = "vmap(vmap(jit(_splash_attention)))/splash_mqa_dq_no_residuals/pallas_call"


def _chip_slice():
    raw = (bench_path.BENCH / "tests/data/chip_train_trace_slice.json.gz").read_bytes()
    return json.loads(gzip.decompress(raw))


def _with_kernel():
    """Ops of one layer's backward: the kernel's dq call and a layout copy
    under the scope, a projection and the MLP outside it, and a kernel call
    before the window."""
    return {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 100, 200, f"{LAYER}/attention/dot_general"),
            ("splash_mqa_dq_no_residuals.1", 200, 260,
             f"{LAYER}/attention/transpose(jvp(flash_attention))/{KERNEL}"),
            ("copy.2", 260, 270, f"{LAYER}/attention/transpose(jvp(flash_attention))/transpose"),
            ("fusion.3", 270, 300, f"{LAYER}/mlp/dot_general"),
            ("splash_mqa_fwd_residuals.4", 0, 50,  # before the window
             f"{LAYER}/attention/jvp(flash_attention)/pallas_call"),
        ]},
        "host": [("bench.window", 100, 400, "main")],
    }


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


def test_reads_the_kernel_scope_per_step(profiled):
    run = profiled(_with_kernel())
    assert spec.reader(NAME, bench_path.BENCH)(run) == pytest.approx(1e3 * 70e-9 / 2)


def test_a_trace_without_the_scope_reads_none(profiled):
    """A recorded chip trace of the einsum route has no such scope."""
    run = profiled(_chip_slice())
    assert spec.reader(NAME, bench_path.BENCH)(run) is None


def test_untraced_run_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(lt, "TRACE_DIR", tmp_path)
    run = argparse.Namespace(trace=None, steps=[{"tokens": 1}])
    assert spec.reader(NAME, bench_path.BENCH)(run) is None
