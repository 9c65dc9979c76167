"""GPipe-style pipeline parallelism via shard_map + ppermute (optional axis).

The production 16x16 mesh spends its parallelism on data x tensor; PP is the
depth-dominant option (DESIGN.md §6): layers are split into S stages laid out
on a 'stage' mesh axis, microbatches stream through, and activations hop
stage-to-stage with ``jax.lax.ppermute``.

Schedule: synchronous GPipe.  Every device runs the same program (SPMD);
during pipeline fill/drain a stage computes on a zero bubble and its output
is masked.  Autodiff through the schedule gives the backward pipeline for
free (ppermute transposes to the reverse permutation), so ``jax.grad`` of
``pipeline_apply`` is a correct pipelined backward pass.

Bubble fraction: (S-1)/(M+S-1) for M microbatches — reported by
:func:`bubble_fraction` and asserted in tests.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves stacked (S, ...) — one slice per stage
    x: jax.Array,  # (M, mb, ...) microbatched inputs
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run x through S pipelined stages. Returns (M, mb, ...) outputs.

    ``stage_fn(params_slice, activations) -> activations`` must preserve the
    activation shape (classic equal-width pipeline stages).
    """
    S = mesh.shape[axis]
    M = x.shape[0]

    def per_device(params_slice, x_all):
        # params_slice: this stage's params (leading stage dim squeezed)
        params_slice = jax.tree.map(lambda t: t[0], params_slice)
        x_all = x_all  # (M, mb, ...) replicated
        sid = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]
        carry_in = jnp.zeros(mb_shape, x_all.dtype)  # activation arriving from prev stage
        outputs = jnp.zeros((M,) + mb_shape, x_all.dtype)

        def tick(state, t):
            carry, outs = state
            # stage 0 injects microbatch t (clamped); others take the carry
            inject = x_all[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(sid == 0, inject, carry)
            out = stage_fn(params_slice, inp)
            # valid iff this stage is working on a real microbatch: 0 <= t - sid < M
            mb_idx = t - sid
            valid = (mb_idx >= 0) & (mb_idx < M)
            out = jnp.where(valid, out, jnp.zeros_like(out))
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                valid & (sid == S - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, M - 1)].set(out),
                lambda o: o,
                outs,
            )
            # hop to the next stage (ring; the wraparound edge is masked next tick)
            carry_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return (carry_next, outs), None

        (_, outputs), _ = jax.lax.scan(tick, (carry_in, outputs), jnp.arange(M + S - 1))
        # everyone returns; only the last stage's buffer is non-zero -> psum
        return jax.lax.psum(outputs, axis)

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def mlp_stage_fn(d_model: int):
    """A reference stage: residual MLP block (for tests/examples)."""

    def fn(params, x):
        h = jnp.tanh(x @ params["w1"])
        return x + h @ params["w2"]

    return fn


def serial_reference(stage_fn, stage_params, x):
    """Ground truth: run the stages sequentially on one device."""
    S = jax.tree.leaves(stage_params)[0].shape[0]
    out = []
    for m in range(x.shape[0]):
        h = x[m]
        for i in range(S):
            p = jax.tree.map(lambda t: t[i], stage_params)
            h = stage_fn(p, h)
        out.append(h)
    return jnp.stack(out)
