"""Device time by the program's named scopes, the program's own spans, and
idle gaps by the innermost host span (``layer_trace``)."""

import argparse
import time

import bench_path  # noqa: F401
import pytest

import layer_trace as lt
import spec

STEP = "jit(train_step)/jit(main)/microbatches/while/body/closed_call"
SCOPE_READERS = {
    "train.device_ms.attention": ("attention",),
    "train.device_ms.mlp": ("mlp",),
    "train.device_ms.vocab": ("embed", "lm_head"),
    "train.device_ms.scan": ("layers", "microbatches"),
    "train.device_ms.optimizer": ("grad_accum", "optimizer"),
    "train.device_ms.other": (lt.UNSCOPED,),
}
READERS = [*SCOPE_READERS, "loader.wait_ms_p50"]


def synthetic():
    return {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 100, 200, f"{STEP}/jvp(layers)/while/body/closed_call/attention/dot"),
            ("fusion.2", 200, 250, f"{STEP}/jvp(layers)/while/body/dynamic_update_slice"),
            ("copy.3", 400, 500, ""),
            ("%while.4 = () while()", 500, 590, "jit(train_step)/jit(main)/microbatches/while"),
            ("fusion.5", 510, 560, "jit(train_step)/jit(main)/optimizer/mul"),
            ("fusion.6", 0, 50, f"{STEP}/jvp(embed)/gather"),  # before the window
        ]},
        "host": [
            ("bench.window", 100, 600, "main"),
            ("bench.input", 240, 400, "main"),        # idle 250-400
            ("repro.loader.wait", 260, 300, "main"),  # inside bench.input
            ("bench.train_step", 590, 640, "main"),   # idle 590-600
            ("bench.other", 250, 400, "loader"),      # another thread: ignored
        ],
    }


@pytest.mark.parametrize("path,scope", [
    (f"{STEP}/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", "attention"),
    (f"{STEP}/transpose(jvp(layers))/while/body/closed_call/checkpoint/mlp/mul", "mlp"),
    (f"{STEP}/transpose(jvp(lm_head))/jit(take_along_axis)/gather", "lm_head"),
    (f"{STEP}/jvp(layers)/while/body/dynamic_update_slice", "layers"),
    (f"{STEP}/grad_accum/add", "grad_accum"),
    ("jit(train_step)/jit(main)/optimizer/jit(clip)/min", "optimizer"),
    ("jit(train_step)/jit(main)/div", lt.UNSCOPED),
    ("", lt.UNSCOPED),
])
def test_scope_of_takes_the_innermost_name(path, scope):
    assert lt.scope_of(path) == scope


def test_reduce_buckets_self_time_by_scope():
    r = lt.reduce(synthetic())
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(340e-9)
    assert r["scope_s"] == pytest.approx({
        "attention": 100e-9, "layers": 50e-9, lt.UNSCOPED: 100e-9,
        "microbatches": 40e-9, "optimizer": 50e-9})  # the while less its body
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"])


def test_idle_gaps_go_to_the_innermost_span_of_the_window_thread():
    r = lt.reduce(synthetic())
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.input": 110e-9, "repro.loader.wait": 40e-9,
                                  "bench.train_step": 10e-9})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_idle_not_under_any_span_is_none():
    ex = synthetic()
    ex["host"] = [h for h in ex["host"] if h[0] != "bench.input"]
    gaps = dict(lt.reduce(ex)["idle_gaps"])
    assert gaps["(none)"] == pytest.approx(110e-9)
    assert sum(gaps.values()) == pytest.approx(160e-9)


def test_spans_are_the_program_spans_in_the_window():
    r = lt.reduce(synthetic())
    assert r["spans"] == {"repro.loader.wait": [pytest.approx(40e-9)]}


def test_a_program_without_scopes_reads_none():
    ex = synthetic()
    ex["devices"]["/device:TPU:0"] = [(n, s, e, "") for n, s, e, _ in
                                      ex["devices"]["/device:TPU:0"]]
    assert lt.reduce(ex)["scope_s"] is None


def test_window_span_required():
    ex = synthetic()
    ex["host"] = ex["host"][1:]
    with pytest.raises(ValueError):
        lt.reduce(ex)


def _run(trace=None, steps=4):
    return argparse.Namespace(trace=trace, steps=[{"tokens": 1}] * steps)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_none_without_a_trace(name, tmp_path, monkeypatch):
    read = spec.reader(name, bench_path.BENCH)
    assert read(_run()) is None
    monkeypatch.setattr(lt, "TRACE_DIR", tmp_path)  # a trace, but no profile
    assert read(_run({"window_s": 1.0, "busy_s": 1.0})) is None


@pytest.fixture
def profiled(tmp_path, monkeypatch):
    """``for_run`` over a stand-in profile whose extraction is ``synthetic``."""
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(lt, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(lt, "extract", lambda path: synthetic())
    lt._reduce_file.cache_clear()
    yield
    lt._reduce_file.cache_clear()


@pytest.mark.parametrize("name,scopes", SCOPE_READERS.items())
def test_scope_reader_reads_ms_per_step(name, scopes, profiled):
    r = lt.reduce(synthetic())
    run = _run({"window_s": r["window_s"]}, steps=4)
    want = 1e3 * sum(r["scope_s"].get(k, 0.0) for k in scopes) / 4
    assert spec.reader(name, bench_path.BENCH)(run) == pytest.approx(want)


def test_loader_wait_reader(profiled):
    run = _run({"window_s": 500e-9})
    assert spec.reader("loader.wait_ms_p50", bench_path.BENCH)(run) == pytest.approx(40e-6)


def test_another_runs_profile_reads_none(profiled):
    run = _run({"window_s": 1.0})
    assert lt.for_run(run) is None
    assert spec.reader("train.device_ms.attention", bench_path.BENCH)(run) is None


def test_op_paths_look_up_the_module_running_each_op():
    names = {"jit_a(1)": {"fusion.1": "jit(a)/attention/dot"},
             "jit_b(2)": {"fusion.1": "jit(b)/mlp/dot"}}
    modules = [(100, 200, "jit_a(1)"), (300, 400, "jit_b(2)")]
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 110, 120),
           ("fusion.1", 310, 320), ("%fusion.9 = f32[] fusion()", 330, 340),
           ("%fusion.1 = f32[8] fusion()", 250, 260)]  # between modules
    assert [p for *_, p in lt._op_paths(ops, modules, names)] == [
        "jit(a)/attention/dot", "", "jit(b)/mlp/dot", ""]


def test_hlo_op_names_read_from_a_live_profile(tmp_path):
    """The profile keeps each module's optimized HLO; its instructions carry
    the scope paths, the backward pass's included."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        with jax.named_scope("mlp"):
            return jnp.tanh(y).sum()

    g = jax.jit(jax.grad(f))
    x = jnp.ones((64, 64))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    names = lt.hlo_op_names(lt.trace_reduce.latest_xplane(str(tmp_path)))
    (module,) = [m for m in names if m.startswith("jit_f(")]
    paths = set(names[module].values())
    assert {lt.scope_of(p) for p in paths} >= {"attention", "mlp"}
    assert any("transpose(jvp(attention))" in p for p in paths)


def test_loader_wait_spans_nest_in_the_harness_input_span(tmp_path):
    """A CPU profile of ``BatchLoader``: one ``repro.loader.wait`` per batch
    taken, each inside the harness's ``bench.input`` on the same thread and
    clock."""
    import jax
    import numpy as np

    from repro.core.rdd import ShardedDataset
    from repro.data.loader import BatchLoader
    from run_common import host_span

    def gen(part):
        time.sleep(0.002)
        return [{"tokens": np.full(8, part), "targets": np.full(8, part)}] * 2

    loader = BatchLoader(ShardedDataset.from_generator(gen, 16, name="t"), batch_size=2)
    it = loader.batches()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with host_span("bench.window"):
        for _ in range(5):
            with host_span("bench.input"):
                next(it)
    jax.profiler.stop_trace()
    loader.close()
    host = lt.extract(lt.trace_reduce.latest_xplane(str(tmp_path)))["host"]
    waits = [h for h in host if h[0] == "repro.loader.wait"]
    inputs = [h for h in host if h[0] == "bench.input"]
    assert len(waits) == len(inputs) == 5
    for _, s, e, th in waits:
        assert any(s >= si and e <= ei and th == ti for _, si, ei, ti in inputs)


def test_reduce_a_slice_of_a_chip_train_trace():
    """A 169 ms slice of a qwen2-0.5b.train-2k trace recorded on a TPU v5e
    (``extract`` output; op names cut to the instruction; the window set to
    the slice): the last microbatch's backward pass, the optimizer, and the
    next step's embedding and first layers, each op with its scope path as
    the profile's HLO gives it."""
    import gzip
    import json

    raw = (bench_path.BENCH / "tests/data/chip_train_trace_slice.json.gz").read_bytes()
    r = lt.reduce(json.loads(gzip.decompress(raw)))
    assert r["window_s"] == pytest.approx(0.1688)
    assert r["busy_s"] == pytest.approx(0.168785781)
    assert set(r["scope_s"]) == {*lt.SCOPES, lt.UNSCOPED}
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["scope_s"][lt.UNSCOPED] <= 0.05 * r["busy_s"]
    assert r["scope_s"]["attention"] == pytest.approx(0.097057028)
    assert max(r["scope_s"], key=r["scope_s"].get) == "attention"
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])
