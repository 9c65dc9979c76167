"""Jitted public wrapper for paged GQA decode attention.

Accepts model-layout tensors (``q``/``k_new`` as ``(B, 1, H, hd)``) plus
the page pool and block tables, and routes:

* TPU — the Pallas kernel, compiled, gathering pages via scalar-prefetch
  block tables (``use_kernel=True`` forces the kernel elsewhere, in
  interpret mode — the tests' path).
* anywhere else — ``ref.paged_decode_ref``: a page gather + the existing
  ``sdpa_decode_readonly`` einsum path (interpret-mode Pallas is orders
  of magnitude slower than XLA on CPU, so the fallback is the *runtime*
  path there, not just the oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    paged_decode_fwd,
    paged_decode_qtok_fwd,
)
from repro.kernels.decode_attention.ref import (
    paged_decode_qtok_ref,
    paged_decode_ref,
)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def paged_decode_attention(
    q: jax.Array,  # (B, Q, Hq, hd) — Q-token window starting at seq_len
    k_pages: jax.Array,  # (P, page, Hkv, hd) — pool; last page is the null page
    v_pages: jax.Array,
    k_new: jax.Array,  # (B, Q, Hkv, hd) window tokens (not yet in the pool)
    v_new: jax.Array,
    block_tables: jax.Array,  # (B, n_pages) int32
    seq_lens: jax.Array,  # (B,) int32 live tokens strictly below the window
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, Q, Hq, hd) attention over [paged cache | causal window].

    ``Q == 1`` is classic decode (one current token merged analytically);
    ``Q > 1`` is the fast-path window — speculative verification and/or a
    chunked-prefill slab — where window token ``j`` attends the cache plus
    window tokens ``j' <= j``.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    Q = q.shape[1]
    if not use_kernel:
        if Q == 1:
            return paged_decode_ref(
                q, k_pages, v_pages, k_new, v_new, block_tables, seq_lens
            )
        return paged_decode_qtok_ref(
            q, k_pages, v_pages, k_new, v_new, block_tables, seq_lens
        )
    B, _, Hq, hd = q.shape
    Hkv = k_pages.shape[2]
    G = Hq // Hkv
    if Q == 1:
        qg = q.reshape(B, Hkv, G, hd)  # heads grouped under their kv head
        out = paged_decode_fwd(
            qg,
            k_pages,
            v_pages,
            k_new[:, 0],
            v_new[:, 0],
            block_tables.astype(jnp.int32),
            seq_lens.astype(jnp.int32),
            interpret=interpret,
        )
        return out.reshape(B, 1, Hq, hd)
    # window-major rows per kv head: (B, Hkv, Q*G, hd), row r = j*G + g
    qg = q.reshape(B, Q, Hkv, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, Hkv, Q * G, hd)
    out = paged_decode_qtok_fwd(
        qg,
        k_pages,
        v_pages,
        k_new,
        v_new,
        block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        group=G,
        interpret=interpret,
    )
    out = out.reshape(B, Hkv, Q, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Q, Hq, hd)
