"""The Pallas kernels of the main paths compile for a TPU v5e.

Interpret mode on the CPU accepts block shapes and layouts that the chip's
compiler refuses, so each kernel is compiled here against a described
``v5e:2x2`` topology (no chip attached) at the widths the entry points use:
qwen2-0.5b's decode attention (8 slots, 2 KV heads x 7 query heads of width
64, 16-token pages, bf16), a 2048 x 8 collision sweep, one 512-point ICP
step, and the flash attention's forward and backward at the train cell's
2 x 2048 tokens (qwen2-0.5b's 14/2 heads of width 64, qwen3-4b's 32/8 of
width 128).  A two-layer train step at qwen2-0.5b's widths checks that the
route puts the kernel in the step and no score matrix, and that the forward
kernel runs once per backward.  The topology is
described inside a fixture, never at import, so every test worker collects
the same tests and only the worker that runs them loads the TPU compiler.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.collision.ops import collision_ttc
from repro.kernels.decode_attention.kernel import (
    paged_decode_fwd,
    paged_decode_qtok_fwd,
)
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.icp.ops import icp_step

B, HKV, G, HD, PAGE, N_PAGES, Q = 8, 2, 7, 64, 16, 10, 8
P = B * N_PAGES + 1  # pool pages, null page last
BF = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the persistent
    # cache without a chip, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paged(one):
    s = lambda shape, dt=BF: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    fn = lambda *a: paged_decode_fwd(*a, interpret=False)  # noqa: E731
    return fn, (
        s((B, HKV, G, HD)), s((P, PAGE, HKV, HD)), s((P, PAGE, HKV, HD)),
        s((B, HKV, HD)), s((B, HKV, HD)), s((B, N_PAGES), I32), s((B,), I32),
    )


def _paged_qtok(one):
    s = lambda shape, dt=BF: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    fn = lambda *a: paged_decode_qtok_fwd(*a, group=G, interpret=False)  # noqa: E731
    return fn, (
        s((B, HKV, Q * G, HD)), s((P, PAGE, HKV, HD)), s((P, PAGE, HKV, HD)),
        s((B, Q, HKV, HD)), s((B, Q, HKV, HD)), s((B, N_PAGES), I32), s((B,), I32),
    )


def _collision(one):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)  # noqa: E731
    S, A = 2048, 8
    fn = lambda *a: collision_ttc(*a, interpret=False)  # noqa: E731
    return fn, (s((S, 2)), s((S, 2)), s((S,)), s((S, A, 2)), s((S, A, 2)), s((S, A)))


def _icp(one):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)  # noqa: E731
    return (lambda a, b: icp_step(a, b, interpret=False)), (s((512, 3)), s((512, 3)))


def _flash(n_heads, n_kv, hd):
    def build(one):
        s = lambda shape: jax.ShapeDtypeStruct(shape, BF, sharding=one)  # noqa: E731

        def loss(q, k, v):
            out = fa_ops.flash_attention(q, k, v, causal=True, interpret=False)
            return jnp.sum(out.astype(jnp.float32))

        fn = jax.grad(loss, argnums=(0, 1, 2))
        return fn, (s((2, 2048, n_heads, hd)), s((2, 2048, n_kv, hd)), s((2, 2048, n_kv, hd)))

    return build


@pytest.mark.parametrize(
    "build", [_paged, _paged_qtok, _collision, _icp, _flash(14, 2, 64), _flash(32, 8, 128)],
    ids=["paged_decode_fwd", "paged_decode_qtok_fwd", "collision_ttc",
         "icp_step", "flash_attention_grad_qwen2_0_5b", "flash_attention_grad_qwen3_4b"],
)
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _train_step_hlo(one_chip, monkeypatch) -> str:
    """The compiled HLO of a train step of two qwen2-0.5b layers, one
    microbatch of 2 x 2048 tokens, ``dots`` remat, on the flash route."""
    from repro.config import ParallelConfig, TrainConfig, get_arch
    from repro.models import attention
    from repro.training.train_loop import make_train_step, state_shardings

    # the described chip's backend is not the process's: steer the route
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa_ops, "default_interpret", lambda: False)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1), ("data", "model"))
    cfg = dataclasses.replace(get_arch("qwen2-0.5b"), num_layers=2)
    bundle = make_train_step(cfg, TrainConfig(), ParallelConfig(num_microbatches=1), mesh)
    state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0)), state_shardings(bundle, mesh))
    tok = jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec()))
    with mesh:
        return jax.jit(bundle.train_step).lower(
            state, {"tokens": tok, "targets": tok}).compile().as_text()


def _splash_calls(text: str) -> list:
    return re.findall(r"%(splash_mqa_\w+?)(?:\.\d+)? = ", text)


def test_train_step_takes_the_flash_kernel_for_v5e(one_chip, monkeypatch):
    """The step holds the kernel's forward, dq and dkv calls and no f32
    score matrix."""
    text = _train_step_hlo(one_chip, monkeypatch)
    assert set(_splash_calls(text)) == {"splash_mqa_fwd_residuals", "splash_mqa_dq_no_residuals",
                                        "splash_mqa_dkv_no_residuals"}
    assert not re.search(r"f32\[[\d,]*2048,2048\]", text)


def test_train_step_runs_the_flash_forward_once_for_v5e(one_chip, monkeypatch):
    """The ``dots`` remat keeps the forward kernel's output and logsumexp,
    so the backward takes them and does not rerun the forward kernel."""
    calls = _splash_calls(_train_step_hlo(one_chip, monkeypatch))
    assert calls.count("splash_mqa_fwd_residuals") == calls.count("splash_mqa_dkv_no_residuals")
