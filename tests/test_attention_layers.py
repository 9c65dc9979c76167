"""Attention paths agree; rotary/mrope/qk-norm properties."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import ModelConfig
from repro.models import attention, layers


def _cfg(**kw):
    base = dict(
        name="t", family="dense", num_layers=1, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
        vocab_pad_multiple=64, dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


def _qkv(key, B=2, S=64, Hq=4, Hkv=2, D=16):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (B, S, Hq, D)),
        jax.random.normal(ks[1], (B, S, Hkv, D)),
        jax.random.normal(ks[2], (B, S, Hkv, D)),
    )


def test_blocked_equals_full():
    q, k, v = _qkv(jax.random.PRNGKey(0), S=128)
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    full = attention.sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    for bq in (16, 32, 64):
        blk = attention.blocked_sdpa(
            q, k, v, q_pos=pos, kv_pos=pos, causal=True, block_q=bq
        )
        np.testing.assert_allclose(np.asarray(blk), np.asarray(full), atol=1e-5, rtol=1e-5)
    # unrolled variant too
    blk = attention.blocked_sdpa(
        q, k, v, q_pos=pos, kv_pos=pos, causal=True, block_q=32, unroll=True
    )
    np.testing.assert_allclose(np.asarray(blk), np.asarray(full), atol=1e-5, rtol=1e-5)


def test_flash_equals_sdpa_inside_model_path():
    q, k, v = _qkv(jax.random.PRNGKey(1), S=128)
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    full = attention.sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    fl = attention.flash_sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(full), atol=2e-5, rtol=2e-5)


@pytest.fixture
def routes(monkeypatch):
    """``attend`` on a TPU backend, each core replaced by a recorder."""
    seen = []
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "flash_sdpa", lambda q, *a, **kw: seen.append("flash") or q)
    monkeypatch.setattr(attention, "sdpa", lambda q, *a, **kw: seen.append("sdpa") or q)
    return seen


def _attend(cfg, S, T=None):
    q, k, v = _qkv(jax.random.PRNGKey(8), B=1, S=S)
    if T is not None:
        k, v = k[:, :T], v[:, :T]
    pos = jnp.broadcast_to(jnp.arange(S), (1, S))
    kv_pos = jnp.broadcast_to(jnp.arange(k.shape[1]), (1, k.shape[1]))
    return attention.attend(cfg, q, k, v, q_pos=pos, kv_pos=kv_pos, causal=True)


def test_flash_route_takes_full_self_attention_on_one_tpu(routes):
    assert _cfg().attention_impl == "flash"
    _attend(_cfg(), 128)
    _attend(_cfg(), 256)
    assert routes == ["flash", "flash"]


@pytest.mark.parametrize("case", ["decode", "prefill_96", "two_device_mesh", "einsum", "cpu"])
def test_other_calls_reach_the_einsum_core(routes, monkeypatch, case):
    cfg = _cfg()
    if case == "decode":
        _attend(cfg, 1, T=64)
    elif case == "prefill_96":
        _attend(cfg, 96)
    elif case == "two_device_mesh":
        mesh = jax.sharding.AbstractMesh(
            (2,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        with jax.sharding.use_abstract_mesh(mesh):
            _attend(cfg, 128)
    elif case == "einsum":
        _attend(dataclasses.replace(cfg, attention_impl="einsum"), 128)
    else:
        monkeypatch.setattr(attention, "_on_tpu", lambda: False)
        _attend(cfg, 128)
    assert routes == ["sdpa"]


def test_train_loss_and_grads_through_the_flash_route(monkeypatch):
    """A tiny model's microbatched loss and gradients with the kernel forced
    on (interpret mode) match the einsum route."""
    from repro.models import model_zoo
    from repro.training.train_loop import make_loss_and_grad

    cfg = _cfg(num_layers=2, remat="dots")
    model = model_zoo.build_model(cfg)
    params = jax.tree.map(lambda p: p.astype(jnp.float32),  # f32 grads, not bf16
                          model_zoo.init_params(model, jax.random.PRNGKey(9)))
    toks = jax.random.randint(jax.random.PRNGKey(10), (4, 129), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    def run(impl):
        m = model_zoo.build_model(dataclasses.replace(cfg, attention_impl=impl))
        loss, _, grads = jax.jit(make_loss_and_grad(m, 2))(params, batch)
        return loss, grads

    ref_loss, ref_grads = run("einsum")
    flash_calls = []
    real = attention.flash_sdpa
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "flash_sdpa",
                        lambda *a, **kw: flash_calls.append(1) or real(*a, **kw))
    loss, grads = run("flash")
    assert flash_calls  # the route engaged
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        r = ref_grads
        for key in path:
            r = r[key.key]
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_causality():
    """Changing a future token never changes a past output."""
    q, k, v = _qkv(jax.random.PRNGKey(2), S=32)
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out1 = attention.sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(100.0)
    out2 = attention.sdpa(q, k2, v2, q_pos=pos, kv_pos=pos, causal=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-6
    )
    assert float(jnp.max(jnp.abs(out1[:, -1] - out2[:, -1]))) > 1e-3


def test_rotary_preserves_norm_and_relative_phase():
    cfg = _cfg()
    S, hd = 16, cfg.resolved_head_dim
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, 2, hd))
    pos = jnp.arange(S)[None, :]
    ang = layers.rope_angles(cfg, pos)
    out = layers.apply_rotary(x, ang, hd)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # relative property: <R(p)q, R(p+d)k> depends only on d
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(5), (1, 1, 1, hd))
    def dot_at(p, d):
        aq = layers.rope_angles(cfg, jnp.array([[p]]))
        ak = layers.rope_angles(cfg, jnp.array([[p + d]]))
        return float(jnp.sum(layers.apply_rotary(q, aq, hd) * layers.apply_rotary(k, ak, hd)))
    assert dot_at(0, 3) == pytest.approx(dot_at(7, 3), rel=1e-4)
    assert dot_at(0, 3) != pytest.approx(dot_at(0, 5), rel=1e-3)


def test_partial_rotary_leaves_tail_untouched():
    cfg = _cfg(rotary_pct=0.25, head_dim=16)
    hd = 16
    r = layers.rotary_dims(cfg)
    assert r == 4
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 1, hd))
    ang = layers.rope_angles(cfg, jnp.arange(8)[None, :])
    out = layers.apply_rotary(x, ang, hd)
    np.testing.assert_array_equal(np.asarray(out[..., r:]), np.asarray(x[..., r:]))


def test_mrope_equals_rope_when_streams_equal():
    cfg = _cfg(head_dim=16, rope_mode="mrope")
    S = 8
    pos = jnp.arange(S)[None, :]
    pos3 = jnp.broadcast_to(pos[None], (3, 1, S))
    sections = layers.mrope_sections(cfg)
    a1 = layers.rope_angles(cfg, pos)
    a3 = layers.mrope_angles(cfg, pos3, sections)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a3), atol=1e-6)


def test_gqa_repeat_matches_explicit():
    q, k, v = _qkv(jax.random.PRNGKey(7), Hq=8, Hkv=2)
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out = attention.sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=False)
    kr = jnp.repeat(k, 4, axis=2)
    vr = jnp.repeat(v, 4, axis=2)
    out2 = attention.sdpa(q, kr, vr, q_pos=pos, kv_pos=pos, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 5), st.integers(0, 30))
def test_decode_position_mask_property(qlen_unused, pos0):
    """A decode query at position p attends only to cache slots <= p."""
    cfg = _cfg()
    B, T, Hkv, D = 1, 32, 2, 16
    key = jax.random.PRNGKey(pos0)
    q = jax.random.normal(key, (B, 1, 4, D))
    k = jax.random.normal(key, (B, T, Hkv, D))
    v = jnp.zeros((B, T, Hkv, D)).at[:, pos0 + 1 :].set(1e3)  # poison future slots
    q_pos = jnp.full((B, 1), pos0)
    kv_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    out = attention.sdpa(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True)
    assert float(jnp.max(jnp.abs(out))) < 100.0  # poison never leaks


def _pallas_calls(jaxpr, seen=None):
    """Names of the ``pallas_call``s in a jaxpr, its sub-jaxprs included."""
    seen = [] if seen is None else seen
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            seen.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _pallas_calls(sub.jaxpr, seen)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _pallas_calls(sub, seen)
    return seen


def test_dots_remat_saves_the_flash_residuals(monkeypatch):
    """Two flash-routed layers under the ``dots`` remat: saving the forward
    kernel's output and logsumexp by name leaves one forward kernel per
    backward (the dots policy alone reruns it) and changes no loss or
    gradient."""
    from repro.models import model_zoo
    from repro.training.train_loop import make_loss_and_grad

    cfg = _cfg(num_layers=2, remat="dots")
    model = model_zoo.build_model(cfg)
    params = jax.tree.map(lambda p: p.astype(jnp.float32),
                          model_zoo.init_params(model, jax.random.PRNGKey(11)))
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, 129), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)

    def run():
        fn = make_loss_and_grad(model, 1)
        calls = _pallas_calls(jax.make_jaxpr(fn)(params, batch).jaxpr)
        loss, _, grads = jax.jit(fn)(params, batch)
        return calls, loss, grads

    calls, loss, grads = run()
    with monkeypatch.context() as m:  # the dots policy alone
        m.setattr(jax.checkpoint_policies, "save_from_both_policies", lambda dots, _: dots)
        dots_calls, dots_loss, dots_grads = run()
    fwd = lambda c: c.count("splash_mqa_fwd_residuals")  # noqa: E731
    assert [c.count("splash_mqa_dkv_no_residuals") for c in (calls, dots_calls)] == [1, 1]
    assert (fwd(calls), fwd(dots_calls)) == (1, 2)
    np.testing.assert_allclose(float(loss), float(dots_loss), rtol=1e-6)
    jax.tree_util.tree_map_with_path(
        lambda path, g, r: np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=1e-6, rtol=1e-5,
            err_msg=jax.tree_util.keystr(path)),
        grads, dots_grads)
