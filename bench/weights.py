"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so the reference can make
the very same values again from the seed.  The configuration's family
(``fam``, ``bench/families/<family>.py``) lists the leaves in ``leaf_specs``:
named by their path in the model (``layers.attn.q.w``), stacked over layers
on axis 0, each with a kind (matrix | bias | norm | embed).  Matrices and
biases are generated in bfloat16, the type they are served in, and norm
scales in float32.  ``program_tree`` lays them out as the program's parameter
tree and pads every vocabulary axis to the program's padded vocabulary with
zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def _leaf(key, shape, kind):
    if kind == "norm":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return (0.2 * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)
    fan_in = shape[-1] if kind == "embed" else shape[-2]
    scale = jnp.asarray(fan_in ** -0.5, jnp.bfloat16)
    return jax.random.normal(key, shape, jnp.bfloat16) * scale


def generate(fam, cfg: dict, key: jax.Array) -> dict[str, jax.Array]:
    """Traceable: every leaf from its own fold of ``key``."""
    specs = fam.leaf_specs(cfg)
    return {
        name: _leaf(jax.random.fold_in(key, i), shape, kind)
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items()))
    }


def make(fam, cfg: dict, seed: int) -> dict[str, jax.Array]:
    """The flat leaves on the default device, in one jitted call."""
    return jax.jit(lambda k: generate(fam, cfg, k))(seed_key(seed))


def _path_name(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def program_tree(fam, cfg: dict, program_specs, seed: int):
    """The same leaves, laid out as the program's parameter tree
    (``program_specs``: its tree of ShapeDtypeStruct), in one jitted call.
    A leaf the program has and the benchmark does not make is an error."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(program_specs)
    names = [_path_name(p) for p, _ in flat]
    want = fam.leaf_specs(cfg)
    missing = sorted(set(names) - set(want))
    extra = sorted(set(want) - set(names))
    if missing or extra:
        raise ValueError(f"parameter trees differ: program only {missing}, "
                         f"benchmark only {extra}")

    vocab = cfg["vocab_size"]

    def build(key):
        leaves = generate(fam, cfg, key)
        out = []
        for name, (_, sds) in zip(names, flat):
            x = leaves[name]
            if x.shape != sds.shape:  # the program pads the vocabulary
                if len(x.shape) != len(sds.shape) or any(
                        a != b and not (a == vocab < b) for a, b in zip(x.shape, sds.shape)):
                    raise ValueError(f"{name}: {x.shape} vs program {sds.shape}")
                x = jnp.pad(x, [(0, b - a) for a, b in zip(x.shape, sds.shape)])
            out.append(x.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
