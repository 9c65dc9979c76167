"""Plain float32 reference for the benchmark's dense Qwen configurations.

Written from the published descriptions (Qwen2: arXiv:2407.10671 and
``Qwen/Qwen2-0.5B`` config.json; Qwen3: ``Qwen/Qwen3-4B`` config.json) in
straightforward ``jax.numpy``, under ``default_matmul_precision("highest")``,
with no kernels, cache or batching.  It imports nothing of the program; its
weights are ``weights.generate``'s leaves, upcast to float32.

Per layer: RMSNorm, q/k/v projections (biases where the config has
``attention_bias``), RMSNorm over head_dim of q and k where it has
``qk_norm``, rotary position embedding, causal grouped-query attention with a
softmax scaled by head_dim ** -0.5, output projection, residual; RMSNorm and
a SwiGLU MLP, residual.  A final RMSNorm and the tied embedding give the
logits over ``vocab_size``.

Departures, each deliberate:

* Rotary pairs dimensions (2i, 2i+1), as the RoPE paper (arXiv:2104.09864)
  defines it.  The published checkpoints pair (i, i + head_dim/2)
  (``rotate_half`` in transformers), which is the same map after a fixed
  permutation of q and k's output columns; with seeded random weights the
  two are equally the model.
* Attention is computed in blocks of query rows, so that the scores of a
  long sequence fit; the mathematics is unchanged.
* Training adds the z-loss ``z_loss_coef * mean(logsumexp ** 2)`` to the
  cross-entropy, the objective the program trains; AdamW decays every leaf.

``quant="fp8"`` is the control: the same computation with every matrix
multiplication in float8, the precision step below the bfloat16 the
configurations state, as float8 training does it: the inputs rounded to
float8_e4m3fn and, in the backward pass, the incoming gradient rounded to
float8_e5m2, each under one scale per tensor.  The rounding passes gradients
straight through, so a gradient is never lost to an unscaled float8 type.
"""

from __future__ import annotations

import functools

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


def _mm(a, b, quant):
    return _qg(jnp.matmul(_q(a, quant), _q(b, quant)), quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, hd), pos (S,): rotate pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _attention(q, k, v, quant):
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


def _layer(cfg, quant, x, lw):
    """One decoder layer over one sequence x (S, d); lw: this layer's leaves."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    S = x.shape[0]
    lw = {k: v.astype(F32) for k, v in lw.items()}
    h = _rms(x, lw["ln1.scale"], eps)

    def proj(name, heads):
        y = _mm(h, lw[f"attn.{name}.w"], quant)
        if f"attn.{name}.b" in lw:
            y = y + lw[f"attn.{name}.b"]
        return y.reshape(S, heads, hd)

    q, k, v = proj("q", hq), proj("k", hkv), proj("v", hkv)
    if cfg["qk_norm"]:
        q = _rms(q, lw["attn.q_norm.scale"], eps)
        k = _rms(k, lw["attn.k_norm.scale"], eps)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    o = _attention(q, k, v, quant).reshape(S, hq * hd)
    x = x + _mm(o, lw["attn.o.w"], quant)
    h = _rms(x, lw["ln2.scale"], eps)
    g = jax.nn.silu(_mm(h, lw["mlp.gate.w"], quant)) * _mm(h, lw["mlp.up.w"], quant)
    return x + _mm(g, lw["mlp.down.w"], quant)


def hidden(cfg: dict, w: dict, tokens: jax.Array, quant=None) -> jax.Array:
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    with jax.default_matmul_precision("highest"):
        x = w["embed.embedding"].astype(F32)[tokens]
        stacked = {k[len("layers."):]: v for k, v in w.items() if k.startswith("layers.")}
        body = jax.checkpoint(lambda x, lw: (_layer(cfg, quant, x, lw), None))
        x, _ = jax.lax.scan(body, x, stacked)
        return _rms(x, w["final_norm.scale"].astype(F32), cfg["rms_norm_eps"])


def logits(cfg: dict, w: dict, h: jax.Array, quant=None) -> jax.Array:
    """Tied-embedding logits (n, vocab_size) of hidden rows h (n, d)."""
    with jax.default_matmul_precision("highest"):
        return _mm(h, w["embed.embedding"].astype(F32).T, quant)


MODEL_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta", "vocab_size",
    "attention_bias", "qk_norm",
)


def model_items(cfg: dict) -> tuple:
    """The model's sizes as a hashable static argument."""
    return tuple((k, cfg[k]) for k in MODEL_KEYS)


@functools.partial(jax.jit, static_argnums=(0, 4))
def served_gaps(cfg_items, w, seq, pos, quant=None):
    """For one served request: ``seq`` (S,) is the prompt and served tokens,
    ``pos`` (n,) the positions whose next token was served.  Returns
    (reference best logit - reference logit of the served token) at each
    position and, for a control, the gap of the token the control puts
    first there, both (n,)."""
    cfg = dict(cfg_items)
    served = seq[jnp.minimum(pos + 1, seq.shape[0] - 1)]
    h = hidden(cfg, w, seq)
    ref = logits(cfg, w, h[pos])
    best = ref.max(axis=-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]
    if quant is None:
        return gap, gap
    hc = hidden(cfg, w, seq, quant)
    pick = jnp.argmax(logits(cfg, w, hc[pos], quant), axis=-1)
    ctrl = best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return gap, ctrl


def loss(cfg: dict, tcfg: dict, w: dict, rows: jax.Array, quant=None) -> jax.Array:
    """Mean next-token cross-entropy plus z-loss over rows (B, S + 1),
    taken one row at a time."""

    def one(row):
        h = hidden(cfg, w, row[:-1], quant)
        lg = logits(cfg, w, h, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        nll = lse - jnp.take_along_axis(lg, row[1:, None], axis=1)[:, 0]
        return jnp.mean(nll) + tcfg["z_loss_coef"] * jnp.mean(lse * lse)

    return jnp.mean(jax.lax.map(jax.checkpoint(one), rows))


def grad(cfg: dict, tcfg: dict, w: dict, rows: jax.Array, quant=None):
    """(loss, gradient) over rows, accumulated one row at a time."""
    def one(acc, row):
        l, g = jax.value_and_grad(lambda w_: loss(cfg, tcfg, w_, row[None], quant))(w)
        return jax.tree.map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(one, zero, rows)
    n = rows.shape[0]
    return l / n, jax.tree.map(lambda x: x / n, g)


def adamw_steps(cfg: dict, tcfg: dict, w0: dict, batches: list, quant=None):
    """Clipped AdamW from ``w0`` (float32) over ``batches``: per-step losses,
    the first clipped gradient, and the final weights."""
    b1, b2, eps, wd = tcfg["beta1"], tcfg["beta2"], tcfg["eps"], tcfg["weight_decay"]
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, first = [], None
    step_fn = jax.jit(lambda w_, r: grad(cfg, tcfg, w_, r, quant))
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
