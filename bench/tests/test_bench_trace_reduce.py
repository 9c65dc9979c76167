"""The trace-to-metric reduction: busy/idle from the union of device
operation intervals, time by operation, idle gaps by host span."""

import bench_path  # noqa: F401
import pytest

import trace_reduce as tr


def synthetic():
    return {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 100, 200), ("fusion.2", 200, 250),   # busy 100-250
            ("paged_decode.3", 400, 500),
            ("fusion.9", 0, 50),                              # before the window
        ]},
        "host": [
            ("bench.window", 100, 600),
            ("bench.router_step", 240, 380),   # covers idle 250-380
            ("bench.wait_arrival", 520, 580),  # covers idle 520-580
        ],
    }


def test_reduce_synthetic():
    r = tr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["op_s"] == pytest.approx({"fusion": 150e-9, "paged_decode": 100e-9})
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.router_step"] == pytest.approx(130e-9)
    assert gaps["bench.wait_arrival"] == pytest.approx(60e-9)
    assert gaps["(none)"] == pytest.approx(60e-9)  # 380-400 and 500-520, 580-600
    assert r["device_ops"][0] == ["fusion", pytest.approx(150e-9)]


def test_nested_ops_count_self_time():
    ex = synthetic()
    ex["devices"]["/device:TPU:0"] += [("%while.5 = (s32[]) while()", 300, 360),
                                       ("%fusion.7 = f32[] fusion()", 310, 340)]
    r = tr.reduce(ex)
    assert r["op_s"]["while"] == pytest.approx(30e-9)
    assert r["op_s"]["fusion"] == pytest.approx(180e-9)
    assert r["busy_s"] == pytest.approx(310e-9)


def test_op_family_folds_numeric_suffixes():
    assert tr.op_family("fusion.123") == "fusion"
    assert tr.op_family("copy-start.3") == "copy-start"
    assert tr.op_family("paged_decode") == "paged_decode"
    assert tr.op_family(
        "%copy.80 = bf16[24,36658,16,2,64]{4,2,3,1,0:T(8,128)(2,1)} copy(%x)") == "copy"
    assert tr.op_family("%paged_decode_attention.7 = bf16[256,1,14,64] custom-call()") \
        == "paged_decode_attention"


def test_window_span_required():
    ex = synthetic()
    ex["host"] = [h for h in ex["host"] if h[0] != "bench.window"]
    with pytest.raises(ValueError):
        tr.reduce(ex)


def test_extract_reads_host_spans_from_a_live_profile(tmp_path):
    import time

    import jax

    from run_common import host_span

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with host_span("bench.window"):
        with host_span("bench.router_step"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    ex = tr.extract(tr.latest_xplane(str(tmp_path)))
    names = [n for n, _, _ in ex["host"]]
    assert names.count("bench.window") == 1 and "bench.router_step" in names
    lo, hi = tr.window_of(ex)
    assert hi - lo >= 10e6  # ns
    if not ex["devices"]:  # a CPU run has no TPU plane to reduce
        with pytest.raises(ValueError):
            tr.reduce(ex)


def test_reduce_a_slice_of_a_chip_trace():
    """A 0.67 s slice of a qwen2-0.5b serve trace recorded on a TPU v5e
    (``extract`` output, op names cut to 120 characters): one decode step
    between prefills.  Freezes what the reduction reads from it."""
    import gzip
    import json

    raw = gzip.decompress((bench_path.BENCH / "tests/data/chip_trace_slice.json.gz")
                          .read_bytes())
    ex = json.loads(raw)
    r = tr.reduce(ex)
    assert r["window_s"] == pytest.approx(0.671726379)
    assert r["busy_s"] == pytest.approx(0.596335767)
    assert [k for k, _ in r["device_ops"][:2]] == ["paged_decode_attention", "copy"]
    assert r["op_s"]["paged_decode_attention"] == pytest.approx(0.304960701)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
