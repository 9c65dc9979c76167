"""Client-side arithmetic: step-return mapping, time to first token from the
due time with unfinished requests counted, rates over the window, and the
percentile."""

import bench_path  # noqa: F401
import pytest

import stats
from run_common import Record
import spec


def test_percentile_interpolates_linearly():
    v = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(v, 0.5) == 3.0
    assert stats.percentile(v, 0.95) == pytest.approx(4.8)
    assert stats.percentile([7.0], 0.95) == 7.0


def test_token_times_map_to_step_returns():
    step_return = {0.0: 0.25, 0.25: 0.5, 0.5: 0.9}
    assert stats.returned_times([0.0, 0.25, 0.5], step_return) == [0.25, 0.5, 0.9]


def test_ttft_counts_from_due_and_keeps_unfinished():
    due = {0: 0.0, 1: 1.0, 2: 2.0}
    first = {0: 0.5, 1: 1.25}
    assert stats.ttfts(due, first, end=10.0) == [0.25, 0.5, 8.0]


def _serve_record():
    rec = Record(kind="serve", cfg={}, mix={})
    # two steps: step 0 admits rids 0 and 1 (two first tokens) and decodes
    # both; step 1 decodes both again
    rec.steps = [
        {"now": 0.0, "ret": 0.5, "prefills": [(0, 10, 0.1, 0.0), (1, 20, 0.2, 0.0)],
         "decode": (0.2, 2, [11, 21])},
        {"now": 0.5, "ret": 2.0, "prefills": [], "decode": (1.0, 2, [12, 22])},
    ]
    rec.window_s = 2.0
    rec.due = {0: 0.0, 1: 0.0, 2: 1.0}
    rec.first = {0: 0.5, 1: 0.5}
    rec.end_s = 4.0
    rec.token_times = {0: [0.5, 0.5, 2.0], 1: [0.5, 0.5, 2.0]}
    return rec


def test_serve_readers_on_a_known_record():
    rec = _serve_record()
    assert spec.reader("serve_tok_s")(rec) == pytest.approx(6 / 2.0)
    # rid 2 never got a token: counted at end - due = 3.0 s
    assert spec.reader("serve_ttft_p95_ms")(rec) == pytest.approx(
        1e3 * stats.percentile([0.5, 0.5, 3.0], 0.95))
    assert spec.reader("serve_itl_p95_ms")(rec) == pytest.approx(
        1e3 * stats.percentile([0.0, 0.0, 1.5, 1.5], 0.95))
    assert spec.reader("engine.decode_slots_mean")(rec) == 2.0
    assert spec.reader("engine.decode_step_ms_p50")(rec) == pytest.approx(600.0)
    assert spec.reader("model.prefill_ms_p50")(rec) == pytest.approx(150.0)


def test_rate_is_all_work_over_all_time():
    assert stats.rate(300.0, 1.5) == 200.0
    rec = Record(kind="train", cfg={}, mix={})
    rec.steps = [{"tokens": 16384, "input_wait_s": 0.001}] * 3
    rec.window_s = 2.0
    assert spec.reader("train_tok_s")(rec) == 3 * 16384 / 2.0
    assert spec.reader("train.input_wait_ms_p50")(rec) == pytest.approx(1.0)
