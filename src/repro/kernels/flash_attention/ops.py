"""Flash attention with a backward, in the model's (B, S, H, D) layout.

A thin wrapper over the splash-attention Pallas kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward kernel and
dq / dkv backward kernels under one ``custom_vjp``, so ``jax.grad`` passes
through.  Scores live in VMEM, one (block, block) tile at a time; no score
matrix is written to HBM.  Causal masking skips the blocks above the
diagonal, and positions come from the kernel's indices (query i sees keys
0..i), so callers pass positions 0..S-1.

GQA: splash's MQA form takes G query heads over one K/V head; it is vmapped
over batch and KV heads.  The forward kernel's output and logsumexp carry
the ``checkpoint_name`` ``RESIDUALS``, so a remat policy that saves that
name keeps them for the backward instead of rerunning the forward kernel
(``models.transformer._maybe_remat``); outside a ``jax.checkpoint`` the
name changes nothing.  q is scaled by ``1/sqrt(D)`` in its own dtype and
nothing is cast up before a dot: the MXU takes bf16 operands and
accumulates in f32.  Used by ``models.attention.attend``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.common import default_interpret
from repro.kernels.flash_attention.ref import attention_ref

# splash tiles are multiples of the 128-lane vreg
BLOCK_SIZES = (512, 256, 128)
# checkpoint name of the forward kernel's output and logsumexp
RESIDUALS = "flash_attention_residuals"


def block_size(seq_len: int) -> int:
    """The largest of ``BLOCK_SIZES`` that divides ``seq_len``."""
    for b in BLOCK_SIZES:
        if seq_len % b == 0:
            return b
    raise ValueError(f"flash attention needs a length divisible by 128, got {seq_len}")


@functools.lru_cache(maxsize=64)  # a few prefill buckets and the train length
def _kernel(seq_len: int, group: int, causal: bool, interpret: bool):
    """The splash kernel for G query heads over one K/V head; its mask info
    is built once per (S, G, causal) at trace time and kept."""
    b = block_size(seq_len)
    one = (splash.CausalMask if causal else splash.FullMask)((seq_len, seq_len))
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b,
    )
    # built eagerly even when first asked for inside a trace, so the cached
    # mask arrays are constants and not that trace's tracers
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * group), block_sizes=sizes,
            residual_checkpoint_name=RESIDUALS, interpret=interpret,
        )


def flash_attention(
    q: jax.Array,  # (B, S, Hq, D) — model layout
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    kernel = _kernel(S, G, causal, interpret)
    with jax.named_scope("flash_attention"):
        # query head h reads KV head h // G, as in attention._repeat_kv
        qt = (q * jnp.asarray(D ** -0.5, q.dtype)).reshape(B, S, Hkv, G, D)
        qt = qt.transpose(0, 2, 3, 1, 4)  # (B, Hkv, G, S, D)
        kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, D)
        vt = v.transpose(0, 2, 1, 3)
        out = jax.vmap(jax.vmap(kernel))(qt, kt, vt)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D)


def flash_attention_reference(q, k, v, *, causal=True):
    """(B,S,H,D)-layout oracle, for tests."""
    out = attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), causal=causal
    )
    return jnp.swapaxes(out, 1, 2)
