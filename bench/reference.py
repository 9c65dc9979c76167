"""Plain float32 reference: the part every model family shares.

Written in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``, with no kernels, cache or
batching.  It imports nothing of the program; its weights are
``weights.generate``'s leaves, upcast to float32.

What differs by architecture sits in the configuration's family file,
``bench/families/<family>.py`` (``spec.family``), which provides:

* ``leaf_specs(cfg)``: leaf name -> (shape, kind) for ``weights``, named by
  their path in the program's parameter tree, layers stacked on axis 0;
* ``model_config(cfg)``: the program's ``ModelConfig``;
* ``hidden(cfg, w, tokens, quant)``: the final-normed hidden states (S, d)
  of one sequence ``tokens`` (S,);
* ``logits(cfg, w, h, quant)``: the logits (n, vocab_size) of hidden rows h;
* ``layer_flops(cfg, ctx)`` and ``head_flops(cfg)``: the forward operations
  of all layers for tokens that attend over ``ctx`` positions each, and of
  the head for one token (``flops``).

A family builds ``hidden`` and ``logits`` from the helpers here (``mm``,
``rms``, ``rope``, ``attention``, ``scan_layers``), which carry the
control's precision, and does not copy them.  ``loss``, ``grad``,
``adamw_steps`` and ``served_gaps`` run the family's functions under the
precision above.  Training adds the z-loss ``z_loss_coef *
mean(logsumexp ** 2)`` to the cross-entropy, the objective the program
trains; AdamW decays every leaf.

``quant="fp8"`` is the control: the same computation with every matrix
multiplication in float8, the precision step below the bfloat16 the
configurations state, as float8 training does it: the inputs rounded to
float8_e4m3fn and, in the backward pass, the incoming gradient rounded to
float8_e5m2, each under one scale per tensor.  The rounding passes gradients
straight through, so a gradient is never lost to an unscaled float8 type.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512


def _round(x, dtype, top):
    """``x`` rounded to ``dtype`` under one scale per tensor that maps its
    largest magnitude to ``top``, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_grad(y):
    return y


_fp8_grad.defvjp(lambda y: (y, None),
                 lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


def _q(x, quant):
    """A matmul input at the control's precision (identity for f32)."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(quant)
    return _fp8(x)


def _qg(y, quant):
    """A matmul output whose gradient is taken at the control's precision."""
    return y if quant is None else _fp8_grad(y)


def mm(a, b, quant):
    return _qg(jnp.matmul(_q(a, quant), _q(b, quant)), quant)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, H, hd), pos (S,): rotate pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def attention(q, k, v, quant):
    """Causal GQA over one sequence: q (S, Hq, hd), k/v (S, Hkv, hd)."""
    S, hq, hd = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kq, vq = _q(k, quant), _q(v, quant)
    blk = min(Q_BLOCK, S)
    nb = S // blk

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=0)
        s = _qg(jnp.einsum("qhd,khd->hqk", _q(qi, quant), kq), quant) * hd ** -0.5
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(S)[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _qg(jnp.einsum("hqk,khd->qhd", _q(p, quant), vq), quant)

    out = jax.lax.map(one, jnp.arange(nb))  # (nb, blk, Hq, hd)
    return out.reshape(S, hq, hd)


def scan_layers(layer, x: jax.Array, w: dict, prefix: str) -> jax.Array:
    """``x`` through the stack of leaves named ``<prefix>.*`` (stacked on
    axis 0), ``layer(x, leaves)`` one layer at a time, recomputed in the
    backward pass."""
    n = len(prefix) + 1
    stacked = {k[n:]: v for k, v in w.items() if k.startswith(prefix + ".")}
    x, _ = jax.lax.scan(jax.checkpoint(lambda x, lw: (layer(x, lw), None)), x, stacked)
    return x


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def served_gaps(fam, cfg_json: str, w, seq, pos, quant=None):
    """For one served request: ``seq`` (S,) is the prompt and served tokens,
    ``pos`` (n,) the positions whose next token was served; ``cfg_json`` is
    the configuration as JSON, a static argument.  Returns (reference best
    logit - reference logit of the served token) at each position and, for
    a control, the gap of the token the control puts first there, both
    (n,)."""
    cfg = json.loads(cfg_json)
    served = seq[jnp.minimum(pos + 1, seq.shape[0] - 1)]
    with jax.default_matmul_precision("highest"):
        ref = fam.logits(cfg, w, fam.hidden(cfg, w, seq)[pos])
        best = ref.max(axis=-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]
        if quant is None:
            return gap, gap
        hc = fam.hidden(cfg, w, seq, quant)
        pick = jnp.argmax(fam.logits(cfg, w, hc[pos], quant), axis=-1)
    ctrl = best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return gap, ctrl


def loss(fam, cfg: dict, tcfg: dict, w: dict, rows: jax.Array,
         quant=None) -> jax.Array:
    """Mean next-token cross-entropy plus z-loss over rows (B, S + 1),
    taken one row at a time, through the family ``fam``."""

    def one(row):
        with jax.default_matmul_precision("highest"):
            h = fam.hidden(cfg, w, row[:-1], quant)
            lg = fam.logits(cfg, w, h, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        nll = lse - jnp.take_along_axis(lg, row[1:, None], axis=1)[:, 0]
        return jnp.mean(nll) + tcfg["z_loss_coef"] * jnp.mean(lse * lse)

    return jnp.mean(jax.lax.map(jax.checkpoint(one), rows))


def grad(fam, cfg: dict, tcfg: dict, w: dict, rows: jax.Array, quant=None):
    """(loss, gradient) over rows, accumulated one row at a time."""
    def one(acc, row):
        l, g = jax.value_and_grad(lambda w_: loss(fam, cfg, tcfg, w_, row[None], quant))(w)
        return jax.tree.map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(one, zero, rows)
    n = rows.shape[0]
    return l / n, jax.tree.map(lambda x: x / n, g)


def adamw_steps(fam, cfg: dict, tcfg: dict, w0: dict, batches: list, quant=None):
    """Clipped AdamW from ``w0`` (float32) over ``batches``: per-step losses,
    the first clipped gradient, and the final weights."""
    b1, b2, eps, wd = tcfg["beta1"], tcfg["beta2"], tcfg["eps"], tcfg["weight_decay"]
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, first = [], None
    step_fn = jax.jit(lambda w_, r: grad(fam, cfg, tcfg, w_, r, quant))
    for t, rows in enumerate(batches):
        l, g = step_fn(w, rows)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, tcfg["grad_clip"] / norm), g)
        if first is None:
            first = g
        if t >= tcfg["warmup_steps"]:
            raise ValueError("the reference follows warm-up steps only")
        lr = tcfg["learning_rate"] * (t + 1.0) / tcfg["warmup_steps"]
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        w = jax.tree.map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p),
            w, m, v,
        )
        losses.append(float(l))
    return losses, first, w
