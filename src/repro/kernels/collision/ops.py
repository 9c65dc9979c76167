"""Jitted wrapper over the collision/TTC Pallas kernel.

Pads the scenario axis to a sublane-friendly multiple and the agent axis to
a lane multiple, splits the vector inputs into the SoA component arrays the
kernel tiles over, and slices the pad back off.  Matches
:func:`repro.kernels.collision.ref.collision_ttc_ref` to fp32 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.collision.kernel import collision_ttc_fwd


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("block_s", "block_a", "interpret"))
def collision_ttc(
    ego_pos: jax.Array,  # (S, 2)
    ego_vel: jax.Array,  # (S, 2)
    ego_radius: jax.Array,  # (S,)
    agent_pos: jax.Array,  # (S, A, 2)
    agent_vel: jax.Array,  # (S, A, 2)
    agent_radius: jax.Array,  # (S, A)
    *,
    block_s: int = 256,
    block_a: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Signed distance, TTC and collision flag per ego-agent pair.

    Returns ``(dist (S,A) f32, ttc (S,A) f32, hit (S,A) bool)``."""
    S, A = agent_radius.shape
    bs = min(block_s, _ceil_to(S, 8))
    ba = min(block_a, _ceil_to(A, 128))
    Sp, Ap = _ceil_to(S, bs), _ceil_to(A, ba)

    def pad_ego(x):
        return jnp.zeros((Sp, 1), jnp.float32).at[:S, 0].set(x.astype(jnp.float32))

    def pad_agent(x):
        return jnp.zeros((Sp, Ap), jnp.float32).at[:S, :A].set(x.astype(jnp.float32))

    ego = (
        pad_ego(ego_pos[:, 0]), pad_ego(ego_pos[:, 1]),
        pad_ego(ego_vel[:, 0]), pad_ego(ego_vel[:, 1]),
        pad_ego(ego_radius),
    )
    agents = (
        pad_agent(agent_pos[..., 0]), pad_agent(agent_pos[..., 1]),
        pad_agent(agent_vel[..., 0]), pad_agent(agent_vel[..., 1]),
        pad_agent(agent_radius),
    )
    dist, ttc, hit = collision_ttc_fwd(
        ego, agents, n_valid_agents=A, block_s=bs, block_a=ba, interpret=interpret
    )
    return dist[:S, :A], ttc[:S, :A], hit[:S, :A].astype(bool)
