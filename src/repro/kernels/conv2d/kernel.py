"""Direct-convolution Pallas kernel (paper §2.3/§4.3, 10-20x conv claim).

The 2017 OpenCL kernel tiles the output plane across work-groups; the TPU
re-derivation stages a whole (padded) input image in VMEM, tiles output
channels across the grid, and turns the KHxKW spatial taps into KH*KW
shifted (H*W, CI) x (CI, BCO) MXU matmuls accumulated in VMEM — an im2col
GEMM without materializing the im2col buffer in HBM.

Grid = (batch, out-channel blocks); weights are re-read per batch element,
input is re-read per channel block (both stream from HBM once per grid step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret


def _conv_kernel(
    x_ref,  # (1, H+KH-1, W+KW-1, CI) padded input
    w_ref,  # (KH, KW, CI, BCO)
    b_ref,  # (BCO,)
    o_ref,  # (1, H, W, BCO)
    *,
    H: int,
    W: int,
    KH: int,
    KW: int,
):
    CI = x_ref.shape[3]
    BCO = w_ref.shape[3]
    acc = jnp.zeros((H * W, BCO), jnp.float32)
    for kh in range(KH):
        for kw in range(KW):
            xs = x_ref[0, kh : kh + H, kw : kw + W, :].astype(jnp.float32)
            xs = xs.reshape(H * W, CI)
            wk = w_ref[kh, kw].astype(jnp.float32)  # (CI, BCO)
            acc = acc + jax.lax.dot(xs, wk, preferred_element_type=jnp.float32)
    acc = acc + b_ref[...].astype(jnp.float32)[None, :]
    o_ref[0] = acc.reshape(H, W, BCO).astype(o_ref.dtype)


def conv2d_fwd(
    x: jax.Array,  # (N, H, W, CI) — already SAME-padded by the wrapper
    w: jax.Array,  # (KH, KW, CI, CO)
    b: jax.Array,  # (CO,)
    *,
    out_h: int,
    out_w: int,
    block_co: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    N = x.shape[0]
    KH, KW, CI, CO = w.shape
    bco = min(block_co, CO)
    assert CO % bco == 0
    nco = CO // bco

    kernel = functools.partial(_conv_kernel, H=out_h, W=out_w, KH=KH, KW=KW)
    return pl.pallas_call(
        kernel,
        grid=(N, nco),
        in_specs=[
            pl.BlockSpec(
                (1, out_h + KH - 1, out_w + KW - 1, CI), lambda n, c: (n, 0, 0, 0)
            ),
            pl.BlockSpec((KH, KW, CI, bco), lambda n, c: (0, 0, 0, c)),
            pl.BlockSpec((bco,), lambda n, c: (c,)),
        ],
        out_specs=pl.BlockSpec((1, out_h, out_w, bco), lambda n, c: (n, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, out_h, out_w, CO), x.dtype),
        interpret=interpret,
    )(x, w, b)
