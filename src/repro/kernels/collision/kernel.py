"""Collision / time-to-collision Pallas kernel (paper §3 simulation service).

The closed-loop scenario simulator checks every ego-agent pair each world
step.  Over a fleet-scale batch that is a dense ``(S, A)`` problem: tiled
over scenarios (sublanes) x agents (lanes), the whole thing is elementwise
VPU math — signed disc distance plus the smaller positive root of the
constant-velocity quadratic ``|p + v t| = r_e + r_a``.

Ego state arrives as per-scenario ``(S, 1)`` columns broadcast against the
agent tiles (a 1-D block does not match XLA's tiled layout on the chip);
both grid dimensions are embarrassingly parallel (no cross-tile scratch).  Padded agent columns (beyond ``n_valid``) are masked to
``TTC_MAX`` / no-hit so the ops wrapper can pad freely to lane multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.collision.ref import TTC_MAX, _EPS
from repro.kernels.common import default_interpret, tpu_compiler_params


def _collision_kernel(
    ex_ref,  # (Bs, 1) ego x
    ey_ref,  # (Bs, 1)
    evx_ref,  # (Bs, 1) ego vel x
    evy_ref,  # (Bs, 1)
    er_ref,  # (Bs, 1) ego radius
    ax_ref,  # (Bs, Ba) agent x
    ay_ref,  # (Bs, Ba)
    avx_ref,  # (Bs, Ba)
    avy_ref,  # (Bs, Ba)
    ar_ref,  # (Bs, Ba) agent radius
    dist_ref,  # (Bs, Ba) out f32
    ttc_ref,  # (Bs, Ba) out f32
    hit_ref,  # (Bs, Ba) out int32
    *,
    ba: int,
    n_valid: int,
):
    j = pl.program_id(1)

    px = ax_ref[...] - ex_ref[...]
    py = ay_ref[...] - ey_ref[...]
    vx = avx_ref[...] - evx_ref[...]
    vy = avy_ref[...] - evy_ref[...]
    rad = ar_ref[...] + er_ref[...]

    c2 = px * px + py * py
    a = vx * vx + vy * vy
    b = 2.0 * (px * vx + py * vy)
    c = c2 - rad * rad

    dist = jnp.sqrt(jnp.maximum(c2, 0.0)) - rad
    disc = b * b - 4.0 * a * c
    t_hit = (-b - jnp.sqrt(jnp.maximum(disc, 0.0))) / (2.0 * a + _EPS)
    approaching = (disc > 0.0) & (t_hit > 0.0)
    ttc = jnp.where(c <= 0.0, 0.0, jnp.where(approaching, t_hit, TTC_MAX))
    hit = dist <= 0.0

    # mask padded agent columns
    col = j * ba + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    valid = col < n_valid
    dist_ref[...] = jnp.where(valid, dist, TTC_MAX)
    ttc_ref[...] = jnp.where(valid, ttc, TTC_MAX)
    hit_ref[...] = jnp.where(valid & hit, 1, 0).astype(jnp.int32)


def collision_ttc_fwd(
    ego_xyvr: tuple[jax.Array, ...],  # 5 x (S, 1) f32: x, y, vx, vy, r
    agent_xyvr: tuple[jax.Array, ...],  # 5 x (S, A) f32: x, y, vx, vy, r
    *,
    n_valid_agents: int,
    block_s: int = 256,
    block_a: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    S, A = agent_xyvr[0].shape
    bs, ba = min(block_s, S), min(block_a, A)
    assert S % bs == 0 and A % ba == 0, (S, A, bs, ba)
    nS, nA = S // bs, A // ba

    kernel = functools.partial(_collision_kernel, ba=ba, n_valid=n_valid_agents)
    ego_spec = pl.BlockSpec((bs, 1), lambda i, j: (i, 0))
    agent_spec = pl.BlockSpec((bs, ba), lambda i, j: (i, j))
    dist, ttc, hit = pl.pallas_call(
        kernel,
        grid=(nS, nA),
        in_specs=[ego_spec] * 5 + [agent_spec] * 5,
        out_specs=[agent_spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((S, A), jnp.float32),
            jax.ShapeDtypeStruct((S, A), jnp.float32),
            jax.ShapeDtypeStruct((S, A), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=tpu_compiler_params(("parallel", "parallel")),
    )(*ego_xyvr, *agent_xyvr)
    return dist, ttc, hit
