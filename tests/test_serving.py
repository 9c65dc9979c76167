"""ServeEngine: greedy decode is deterministic and matches manual stepping.
ContinuousBatchingEngine: paged continuous decode reproduces the static
engine's greedy tokens through joins, evictions and preemption."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch, scale_down
from repro.models import model_zoo as mz
from repro.serving.continuous import ContinuousBatchingEngine
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import Request


@pytest.fixture(scope="module")
def setup():
    cfg = scale_down(get_arch("qwen2-0.5b"), num_layers=2)
    model = mz.build_model(cfg)
    params = mz.init_params(model, jax.random.PRNGKey(0))
    return cfg, model, params


def test_generate_shapes_and_determinism(setup):
    cfg, model, params = setup
    B, S, G = 2, 16, 8
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)}
    eng = ServeEngine(cfg, params, max_len=S + G)
    out1 = eng.generate(dict(prompt), G)
    eng2 = ServeEngine(cfg, params, max_len=S + G)
    out2 = eng2.generate(dict(prompt), G)
    assert out1.shape == (B, G)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.max()) < cfg.vocab_size


def test_generate_matches_manual_decode(setup):
    cfg, model, params = setup
    B, S, G = 1, 12, 4
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)}
    eng = ServeEngine(cfg, params, max_len=S + G)
    out = np.asarray(eng.generate(dict(prompt), G))

    logits, state = model.prefill(params, dict(prompt), S + G)
    toks = []
    tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], -1).astype(jnp.int32)
    for _ in range(G):
        toks.append(np.asarray(tok))
        logits, state = model.decode_step(params, state, {"tokens": tok[:, None]})
        tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], -1).astype(jnp.int32)
    np.testing.assert_array_equal(out, np.stack(toks, 1))


def test_temperature_sampling_stays_in_vocab(setup):
    cfg, model, params = setup
    prompt = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    eng = ServeEngine(cfg, params, max_len=24)
    out = eng.generate(prompt, 8, temperature=1.0, seed=3)
    assert int(out.max()) < cfg.vocab_size and int(out.min()) >= 0


# ---------------------------------------------------------------------------
# continuous batching over the paged KV cache
# ---------------------------------------------------------------------------


def test_continuous_matches_static_greedy(setup):
    """Fewer slots than requests: sequences join and evict mid-flight, yet
    every request reproduces the static engine's greedy continuation."""
    cfg, model, params = setup
    B, S, G = 3, 12, 6
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab_size)
    ref = np.asarray(ServeEngine(cfg, params, max_len=S + G).generate(
        {"tokens": prompt}, G
    ))
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=8, max_len=64)
    outs = eng.run([
        Request(rid=i, tokens=np.asarray(prompt[i]), max_new_tokens=G)
        for i in range(B)
    ])
    got = np.array([o.tokens for o in sorted(outs, key=lambda o: o.rid)])
    np.testing.assert_array_equal(got, ref)


def test_continuous_variable_lengths_match_per_request(setup):
    """Variable prompt/gen lengths: each request matches its own B=1 static
    decode (no cross-request contamination through the shared page pool)."""
    cfg, model, params = setup
    rng = np.random.default_rng(3)
    reqs = [
        Request(
            rid=i,
            tokens=rng.integers(0, cfg.vocab_size, int(pl)).astype(np.int32),
            max_new_tokens=int(g),
        )
        for i, (pl, g) in enumerate([(5, 4), (17, 9), (9, 2), (24, 6)])
    ]
    eng = ContinuousBatchingEngine(cfg, params, num_slots=3, page_size=8, max_len=64)
    outs = {o.rid: o.tokens for o in eng.run(list(reqs))}
    for r in reqs:
        ref = np.asarray(
            ServeEngine(cfg, params, max_len=r.prompt_len + r.max_new_tokens).generate(
                {"tokens": jnp.asarray(r.tokens[None])}, r.max_new_tokens
            )
        )[0]
        np.testing.assert_array_equal(np.asarray(outs[r.rid]), ref)


def test_continuous_preemption_requeue(setup):
    """A page pool too small for both sequences forces preemption; the
    continuation re-prefills and still matches static greedy."""
    cfg, model, params = setup
    B, S, G = 2, 12, 8
    prompt = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, cfg.vocab_size)
    ref = np.asarray(ServeEngine(cfg, params, max_len=S + G).generate(
        {"tokens": prompt}, G
    ))
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=8, max_len=32, num_pages=4
    )
    outs = eng.run([
        Request(rid=i, tokens=np.asarray(prompt[i]), max_new_tokens=G)
        for i in range(B)
    ])
    got = np.array([o.tokens for o in sorted(outs, key=lambda o: o.rid)])
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# multi-replica routing over real engines
# ---------------------------------------------------------------------------


def test_router_over_replicas_matches_static_greedy(setup):
    """Requests JSQ-routed across two real engine replicas each reproduce
    the static engine's greedy continuation."""
    from repro.serving.router import ServeRouter

    cfg, model, params = setup
    B, S, G = 4, 12, 6
    prompt = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
    ref = np.asarray(ServeEngine(cfg, params, max_len=S + G).generate(
        {"tokens": prompt}, G
    ))
    engines = [
        ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=8,
                                 max_len=64, seed=r)
        for r in range(2)
    ]
    router = ServeRouter(engines)
    outs = router.run([
        Request(rid=i, tokens=np.asarray(prompt[i]), max_new_tokens=G)
        for i in range(B)
    ])
    got = np.array([o.tokens for o in sorted(outs, key=lambda o: o.rid)])
    np.testing.assert_array_equal(got, ref)
    # both replicas saw work
    assert sorted(router.routed) == [2, 2]


def test_router_replica_death_reroutes_real_continuations(setup):
    """A replica that dies mid-decode is failed over: its in-flight
    sequences (prompt + generated so far) finish on the survivor with the
    same greedy tokens."""
    from repro.serving.router import ServeRouter

    cfg, model, params = setup
    B, S, G = 2, 12, 6
    prompt = jax.random.randint(jax.random.PRNGKey(8), (B, S), 0, cfg.vocab_size)
    ref = np.asarray(ServeEngine(cfg, params, max_len=S + G).generate(
        {"tokens": prompt}, G
    ))
    engines = [
        ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=8,
                                 max_len=64, seed=r)
        for r in range(2)
    ]
    # replica 1 survives two decode steps, then the "node" dies
    real_step, calls = engines[1].step, []

    def dying_step(now=float("inf")):
        calls.append(now)
        if len(calls) > 2:
            raise RuntimeError("injected device loss")
        return real_step(now)

    engines[1].step = dying_step
    router = ServeRouter(engines)
    outs = router.run([
        Request(rid=i, tokens=np.asarray(prompt[i]), max_new_tokens=G)
        for i in range(B)
    ])
    got = np.array([o.tokens for o in sorted(outs, key=lambda o: o.rid)])
    np.testing.assert_array_equal(got, ref)
    assert router.alive == [True, False]
    assert router.rerouted >= 1


def test_router_salvages_outputs_finished_inside_failing_step(setup):
    """A request that completes at admission time (max_new_tokens=1) inside
    the same engine step whose decode then raises must still be delivered,
    not lost with the dead replica."""
    from repro.serving.router import ServeRouter

    cfg, model, params = setup
    rng = np.random.default_rng(11)
    engines = [
        ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=8,
                                 max_len=64, seed=r)
        for r in range(2)
    ]

    def boom(*args, **kwargs):
        raise RuntimeError("injected decode death")

    engines[1]._decode = boom  # admission still works; decode dies
    router = ServeRouter(engines)
    gens = [4, 1, 4, 4]  # rid 1 (one-token) and rid 3 land on replica 1
    reqs = [
        Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                max_new_tokens=g)
        for i, g in enumerate(gens)
    ]
    outs = router.run(list(reqs))
    assert sorted(o.rid for o in outs) == [0, 1, 2, 3]
    by_rid = {o.rid: o.tokens for o in outs}
    assert [len(by_rid[i]) for i in range(4)] == gens
    assert router.alive == [True, False]
    assert router.rerouted >= 1  # rid 3 finished on the survivor


def test_router_replica_churn_preserves_greedy_outputs(setup):
    """Elastic churn mid-stream — a replica added, another retired while
    sequences are in flight — must not change any request's greedy tokens:
    untouched replicas keep their work (stable tie-break indices), and the
    retired replica's continuations finish identically on the survivors."""
    from repro.serving.router import ServeRouter

    cfg, model, params = setup
    B, S, G = 6, 12, 6
    prompt = jax.random.randint(jax.random.PRNGKey(9), (B, S), 0, cfg.vocab_size)
    ref = np.asarray(ServeEngine(cfg, params, max_len=S + G).generate(
        {"tokens": prompt}, G
    ))
    engines = [
        ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=8,
                                 max_len=64, seed=r)
        for r in range(2)
    ]
    router = ServeRouter(engines)
    reqs = [
        Request(rid=i, tokens=np.asarray(prompt[i]), max_new_tokens=G)
        for i in range(B)
    ]
    for r in reqs[:4]:
        router.submit(r)
    outs = []
    outs.extend(router.step())
    outs.extend(router.step())  # sequences now mid-flight on replicas 0/1
    # scale up: the newcomer is appended, untouched indices are stable
    router.add_replica(ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=8, max_len=64, seed=2
    ))
    for r in reqs[4:]:
        router.submit(r)  # JSQ prefers the empty newcomer
    outs.extend(router.step())
    # scale down: replica 1's in-flight work rebalances to the survivors
    conts = router.retire_replica(1)
    while router.has_work():
        outs.extend(router.step())
    got = np.array([o.tokens for o in sorted(outs, key=lambda o: o.rid)])
    np.testing.assert_array_equal(got, ref)
    assert router.alive == [True, False, True]
    assert router.retired == 1
    assert router.rebalanced == len(conts) >= 1
    assert router.routed[2] >= 2  # the newcomer really absorbed load


def test_continuous_temperature_and_validation(setup):
    cfg, model, params = setup
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=8, max_len=32)
    outs = eng.run([
        Request(rid=0, tokens=np.zeros((8,), np.int32), max_new_tokens=6,
                temperature=0.9)
    ])
    toks = outs[0].tokens
    assert len(toks) == 6 and max(toks) < cfg.vocab_size and min(toks) >= 0
    with pytest.raises(ValueError):
        eng.submit(Request(rid=1, tokens=np.zeros((30,), np.int32), max_new_tokens=8))
    # worst-case page need beyond the pool is rejected at submit, not
    # discovered as a busy-spinning never-admissible queue head
    tiny = ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=8, max_len=32, num_pages=2
    )
    with pytest.raises(ValueError, match="pages"):
        tiny.submit(Request(rid=2, tokens=np.zeros((8,), np.int32), max_new_tokens=9))


# ---------------------------------------------------------------------------
# serving fast path: speculative decoding, prefix sharing, fused chunked
# prefill — each leg must reproduce the all-off engine's greedy tokens
# bitwise (same jitted programs, same sampling order)
# ---------------------------------------------------------------------------


def _shared_prefix_reqs(cfg, n=6, sys_len=40, gen=10, seed=21):
    """n requests sharing a system prompt, with short unique tails — the
    workload prefix caching exists for.  Small-alphabet tails keep n-gram
    speculation proposals plausible."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, sys_len).astype(np.int32)
    return [
        Request(
            rid=i,
            tokens=np.concatenate(
                [sys_prompt,
                 rng.integers(0, 8, int(rng.integers(4, 10))).astype(np.int32)]
            ),
            max_new_tokens=gen,
        )
        for i in range(n)
    ]


def _run_fastpath(cfg, params, req_factory, **engine_kw):
    kw = dict(num_slots=4, page_size=8, max_len=96)
    kw.update(engine_kw)
    eng = ContinuousBatchingEngine(cfg, params, **kw)
    outs = eng.run(req_factory())
    return {o.rid: o.tokens for o in outs}, eng


@pytest.mark.serving_fastpath
def test_speculative_decode_matches_baseline_bitwise(setup):
    cfg, model, params = setup
    factory = lambda: _shared_prefix_reqs(cfg)
    base, _ = _run_fastpath(cfg, params, factory)
    spec, eng = _run_fastpath(cfg, params, factory, spec_k=3)
    assert base == spec  # greedy tokens identical, request by request
    assert eng.counters["spec_proposed"] > 0
    # accepted drafts are where the speedup comes from; with repetitive
    # small-alphabet tails the n-gram proposer lands at least some
    assert 0 <= eng.counters["spec_accepted"] <= eng.counters["spec_proposed"]


@pytest.mark.serving_fastpath
def test_prefix_sharing_matches_baseline_bitwise(setup):
    cfg, model, params = setup
    factory = lambda: _shared_prefix_reqs(cfg)
    base, _ = _run_fastpath(cfg, params, factory)
    shared, eng = _run_fastpath(cfg, params, factory, prefix_cache=True)
    assert base == shared
    # later requests hit the first request's registered system prompt
    assert eng.counters["prefix_hits"] > 0
    assert eng.counters["pages_shared"] > 0
    # after every request finished, only the index keeps pages resident
    assert len(eng.prefix) > 0
    assert eng.alloc.pages_in_use() == len(eng.prefix.held_pages())


@pytest.mark.serving_fastpath
def test_mixed_step_prefill_matches_bucketed_bitwise(setup):
    cfg, model, params = setup
    factory = lambda: _shared_prefix_reqs(cfg)
    base, _ = _run_fastpath(cfg, params, factory)
    mixed, eng = _run_fastpath(cfg, params, factory, prefill_chunk=16)
    assert base == mixed
    assert eng.counters["prefill_chunks"] > 0


@pytest.mark.serving_fastpath
def test_all_fastpaths_on_matches_baseline_bitwise(setup):
    cfg, model, params = setup
    factory = lambda: _shared_prefix_reqs(cfg)
    base, _ = _run_fastpath(cfg, params, factory)
    fast, eng = _run_fastpath(
        cfg, params, factory, spec_k=3, prefix_cache=True, prefill_chunk=16
    )
    assert base == fast
    for k in ("spec_proposed", "prefix_hits", "pages_shared", "prefill_chunks"):
        assert eng.counters[k] > 0, k


@pytest.mark.serving_fastpath
def test_fastpath_temperature_sampling_stays_in_vocab(setup):
    """Sampled (temperature > 0) slots ride the fast path too — they just
    skip speculation — and stay within the vocab."""
    cfg, model, params = setup
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=8, max_len=64,
        spec_k=3, prefix_cache=True, prefill_chunk=16,
    )
    outs = eng.run([
        Request(rid=i, tokens=np.full((20,), i, np.int32), max_new_tokens=8,
                temperature=0.9)
        for i in range(3)
    ])
    assert sorted(o.rid for o in outs) == [0, 1, 2]
    for o in outs:
        assert len(o.tokens) == 8
        assert max(o.tokens) < cfg.vocab_size and min(o.tokens) >= 0


@pytest.mark.serving_fastpath
def test_prefix_index_reclaims_under_pool_pressure(setup):
    """Distinct prompts through a pool too small to keep every finished
    prompt pinned: admission must evict LRU index entries (never pages a
    live sequence holds) instead of wedging, and outputs stay bitwise
    equal to the unshared engine."""
    cfg, model, params = setup
    rng = np.random.default_rng(33)
    prompts = [rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
               for _ in range(4)]
    factory = lambda: [
        Request(rid=i, tokens=p.copy(), max_new_tokens=6)
        for i, p in enumerate(prompts)
    ]
    kw = dict(num_slots=2, page_size=8, max_len=32, num_pages=6)
    base, _ = _run_fastpath(cfg, params, factory, **kw)
    shared, eng = _run_fastpath(cfg, params, factory, prefix_cache=True, **kw)
    assert base == shared
    assert eng.prefix.evicted > 0  # pressure actually forced eviction


@pytest.mark.serving_fastpath
def test_fastpath_preemption_requeue_matches_baseline(setup):
    """The preempt-and-requeue path (pool too small for both sequences)
    under all three fast paths still reproduces baseline greedy tokens."""
    cfg, model, params = setup
    B, S, G = 2, 12, 8
    prompt = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, cfg.vocab_size)
    factory = lambda: [
        Request(rid=i, tokens=np.asarray(prompt[i]), max_new_tokens=G)
        for i in range(B)
    ]
    kw = dict(num_slots=2, page_size=8, max_len=32, num_pages=4)
    base, _ = _run_fastpath(cfg, params, factory, **kw)
    fast, eng = _run_fastpath(
        cfg, params, factory, spec_k=2, prefix_cache=True, prefill_chunk=8, **kw
    )
    assert base == fast


@pytest.mark.serving_fastpath
def test_fastpath_config_validation(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(
            cfg, params, num_slots=2, page_size=8, max_len=32, spec_k=-1
        )
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(
            cfg, params, num_slots=2, page_size=8, max_len=32, prefill_chunk=-2
        )
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(
            cfg, params, num_slots=2, page_size=8, max_len=32,
            spec_k=2, spec_ngram=0,
        )


@pytest.mark.parametrize("tier", [{"replicas": 2}, {"cells": 2}])
def test_compile_refusal_surfaces_in_job_error(monkeypatch, tier):
    """A program the chip's compiler refuses fails every replica the same
    way; failover must not bury that cause under "all replicas failed"."""
    from repro.platform import FAILED, JobSpec, Platform, ServeJobConfig

    def refused(self, now=float("inf")):
        raise ValueError("Mosaic failed to compile TPU kernel: test refusal")

    monkeypatch.setattr(ContinuousBatchingEngine, "step", refused)
    p = Platform(total_devices=2)
    rep = p.wait(p.submit(JobSpec(kind="serve", config=ServeJobConfig(
        engine="continuous", batch=2, prompt_len=8, gen=2, **tier,
    ))), timeout_s=120)
    assert rep.state == FAILED
    assert "Mosaic failed to compile TPU kernel: test refusal" in rep.error
