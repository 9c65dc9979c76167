"""Jitted public wrapper for the flash-attention kernel.

Accepts model-layout tensors (B, S, H, D) and handles layout, block-size
selection and the interpret/compiled switch.  Used by
``models.attention.attend`` when ``attention_impl='flash'``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, S, Hq, D) — model layout
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_fwd(
        qt, kt, vt, causal=causal, block_q=block_q, block_k=block_k, interpret=interpret
    )
    return jnp.swapaxes(out, 1, 2)


def flash_attention_reference(q, k, v, *, causal=True):
    """(B,S,H,D)-layout oracle, for tests."""
    out = attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), causal=causal
    )
    return jnp.swapaxes(out, 1, 2)
