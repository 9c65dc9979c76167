"""The traffic generator: same seed same inputs, lengths in range, and every
seed the same set of sizes and gaps."""

import bench_path  # noqa: F401
import numpy as np
import pytest

import spec
import traffic

SEED = 2**31 + 12345  # beyond 32 signed bits: run seeds may be that large


@pytest.mark.parametrize("mix_name", ["serve-batch", "serve-chat"])
def test_serve_mix_is_deterministic_and_in_range(mix_name):
    mix = spec.traffic(mix_name)
    a = traffic.serve_requests(mix, SEED, 30.0, 151936)
    b = traffic.serve_requests(mix, SEED, 30.0, 151936)
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    plens = np.array([len(r["prompt"]) for r in a])
    outs = np.array([r["max_new"] for r in a])
    assert plens.min() >= mix["prompt"]["min"] and plens.max() <= mix["prompt"]["max"]
    assert outs.min() >= mix["output"]["min"] and outs.max() <= mix["output"]["max"]
    assert all(r["prompt"].max() < 151936 and r["prompt"].min() >= 0 for r in a)
    arr = np.array([r["arrival"] for r in a])
    assert np.all(np.diff(arr) >= 0) and arr.min() >= 0 and arr.max() < 30.0


def test_seeds_share_sizes_and_gaps_in_another_order():
    mix = spec.traffic("serve-chat")
    a = traffic.serve_requests(mix, 1, 30.0, 1000)
    b = traffic.serve_requests(mix, 2, 30.0, 1000)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30)
    key = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert key(a) == key(b)
    ga = sorted(np.diff([r["arrival"] for r in a]))
    gb = sorted(np.diff([r["arrival"] for r in b]))
    assert not np.allclose([r["arrival"] for r in a], [r["arrival"] for r in b])
    # same gaps but the one the order leaves last
    assert len(set(np.round(ga, 9)) & set(np.round(gb, 9))) >= len(ga) - 1


def test_backlog_rounds_have_the_same_mix():
    mix = spec.traffic("serve-batch")
    r = traffic.serve_requests(mix, SEED, 30.0, 1000)
    n = mix["round"]
    first = sorted((len(x["prompt"]), x["max_new"]) for x in r[:n])
    second = sorted((len(x["prompt"]), x["max_new"]) for x in r[n:2 * n])
    assert first == second
    assert len(r) == mix["requests"]


def test_lognormal_quantiles_match_median():
    d = {"median": 512, "sigma": 0.8, "min": 64, "max": 4096}
    q = traffic.lognormal_quantiles(d, 1001)
    assert abs(np.median(q) - 512) <= 1


def test_train_rows_are_deterministic_distinct_and_in_range():
    a = traffic.train_rows(SEED, 0, 8, 2048, 151936)
    b = traffic.train_rows(SEED, 0, 8, 2048, 151936)
    c = traffic.train_rows(SEED, 1, 8, 2048, 151936)
    assert a.shape == (8, 2049) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({r.tobytes() for r in np.concatenate([a, c])}) == 16
    assert a.min() >= 0 and a.max() < 151936
