"""Logical-axis sharding rules (t5x-style).

Every parameter and activation in the model zoo is annotated with a tuple of
*logical* axis names (e.g. ``("embed", "ffn")``).  A rule table maps logical
axes to mesh axes; :func:`logical_to_spec` resolves a logical tuple into a
``PartitionSpec`` for a concrete mesh, dropping mesh axes that do not exist
(so the same annotations drive the 1-device test mesh, the 16x16 pod and the
2x16x16 multi-pod mesh).
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rule table.  Values may be a mesh axis name, a tuple of mesh axes
# (a logical dim sharded over several mesh axes), or None (replicated).
LOGICAL_RULES: dict[str, Any] = {
    # weights
    "vocab": "model",
    "embed": None,          # set to 'data' by the weights_2d (ZeRO-3-ish) mode
    "heads": "model",       # q/kv head output dims of attention projections
    "ffn": "model",
    "experts": "expert",    # resolved to 'model' when shard_mode == 'expert'
    "expert_ffn": None,     # resolved to 'model' when shard_mode == 'ffn'
    "ssm_heads": "model",   # mamba head axis (weights)
    "ssm_hd": None,         # mamba head_dim within d_inner
    "ssm_heads_act": "model",  # mamba head axis (activations/state)
    "ssm_hd_act": None,        # mamba head_dim axis (model-sharded when H % tp != 0)
    "cache_heads": "model",    # KV-cache head dim (when kv_heads % tp == 0)
    "cache_hd": None,          # KV-cache head_dim (model-sharded otherwise)
    "lora": None,
    "frontend": None,
    # activations
    "batch": ("pod", "data"),
    "seq": None,            # 'data' for long-context decode; 'model' w/ seq_shard
    "act_embed": None,
    "act_heads": "model",
    "act_ffn": "model",
    "kv_seq": None,
    "vocab_act": "model",    # logits vocab dim
    "moe_cap": "data",       # expert capacity bins sharded over data
    "moe_groups": None,      # 'data' under grouped-local dispatch (n_groups>0)
    "expert_ffn_act": None,  # set to 'model' under shard_mode == 'ffn'
    "kv_seq_data": "data",  # long-context (batch=1) decode: seq sharded over data
    "batch_rep": None,      # batch too small to shard (long-context decode)
    "layers": None,
    "sites": None,
    "pos3": None,
    # optimizer (ZeRO-1): first shardable dim additionally over data axes
    "zero": ("data",),
}


def resolve_rules(
    *,
    weights_2d: bool = False,
    moe_shard_mode: str = "expert",
    ssm_shard: str = "heads",  # heads | head_dim (head_dim when H % tp != 0)
    cache_shard: str = "heads",  # heads | hd (hd when kv_heads % tp != 0)
    seq_axis: str | None = None,
    extra: Mapping[str, Any] | None = None,
) -> dict[str, Any]:
    """Build a concrete rule table for one run."""
    rules = dict(LOGICAL_RULES)
    rules["experts"] = "model" if moe_shard_mode == "expert" else None
    rules["expert_ffn"] = "model" if moe_shard_mode == "ffn" else None
    rules["expert_ffn_act"] = "model" if moe_shard_mode == "ffn" else None
    if ssm_shard == "head_dim":
        rules.update(
            ssm_heads=None, ssm_hd="model", ssm_heads_act=None, ssm_hd_act="model"
        )
    if cache_shard == "hd":
        rules.update(cache_heads=None, cache_hd="model")
    if weights_2d:
        rules["embed"] = "data"
    if seq_axis is not None:
        rules["seq"] = seq_axis
        rules["kv_seq"] = seq_axis
    if extra:
        rules.update(extra)
    return rules


def rules_for_model(cfg, mesh: Mesh, *, weights_2d: bool = False, extra=None) -> dict:
    """Arch-aware rule table: picks SSM/cache sharding dims that divide on
    this mesh (in_shardings require exact divisibility; see DESIGN.md §5)."""
    tp = mesh.shape.get("model", 1)
    moe_mode = cfg.moe.shard_mode if cfg.moe is not None else "expert"
    ssm_shard = "heads"
    if cfg.ssm is not None:
        n_heads = (cfg.ssm.expand * cfg.d_model) // cfg.ssm.head_dim
        if n_heads % tp != 0:
            ssm_shard = "head_dim"
    cache_shard = "heads" if cfg.num_kv_heads % tp == 0 else "hd"
    return resolve_rules(
        weights_2d=weights_2d,
        moe_shard_mode=moe_mode,
        ssm_shard=ssm_shard,
        cache_shard=cache_shard,
        extra=extra,
    )


def sanitize_specs(spec_tree: Any, struct_tree: Any, mesh: Mesh) -> Any:
    """Drop mesh axes from PartitionSpecs wherever the dim is not evenly
    divisible (pjit in_shardings reject padding, unlike constraints)."""

    def one(spec, struct):
        if not isinstance(spec, P):
            return spec
        shape = struct.shape
        entries = list(spec)
        out = []
        for i, e in enumerate(entries):
            if e is None or i >= len(shape):
                out.append(None if i >= len(shape) else e)
                continue
            axes = e if isinstance(e, tuple) else (e,)
            extent = 1
            for a in axes:
                extent *= mesh.shape.get(a, 1)
            out.append(e if extent and shape[i] % extent == 0 else None)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    return jax.tree.map(
        one, spec_tree, struct_tree, is_leaf=lambda x: isinstance(x, P)
    )


def _mesh_axes(mesh: Mesh) -> set[str]:
    return set(mesh.axis_names)


def logical_to_spec(
    logical: Sequence[str | None] | None,
    mesh: Mesh,
    rules: Mapping[str, Any] | None = None,
) -> P:
    """Resolve a tuple of logical axis names into a PartitionSpec."""
    if logical is None:
        return P()
    rules = rules or LOGICAL_RULES
    present = _mesh_axes(mesh)
    used: set[str] = set()
    out: list[Any] = []
    for name in logical:
        target = rules.get(name) if name is not None else None
        if target is None:
            out.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        axes = tuple(a for a in target if a in present and a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def make_shardings(
    logical_tree: Any,
    mesh: Mesh,
    rules: Mapping[str, Any] | None = None,
) -> Any:
    """Map a pytree of logical tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda lg: NamedSharding(mesh, logical_to_spec(lg, mesh, rules)),
        logical_tree,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)),
    )


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    """Map a pytree of PartitionSpecs to NamedShardings."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def constrain(x, logical, rules=None):
    """with_sharding_constraint by logical names.

    No-op outside a mesh context; axes that are not Auto on the current
    abstract mesh (e.g. everything inside shard_map, where axes are Manual)
    are dropped from the spec."""
    mesh = get_abstract_mesh()
    if mesh is None:
        return x
    auto = {
        name
        for name, t in zip(mesh.axis_names, mesh.axis_types)
        if t == jax.sharding.AxisType.Auto
    }
    if not auto:
        return x
    spec = logical_to_spec(logical, mesh, rules)
    entries = []
    for e in spec:
        if e is None:
            entries.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a in auto)
            entries.append(kept if kept else None)
        else:
            entries.append(e if e in auto else None)
    while entries and entries[-1] is None:
        entries.pop()
    if not entries:
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))


def get_abstract_mesh():
    """The mesh visible at trace time, or None.

    Inside shard_map/use_mesh the *abstract* mesh is set (axis types matter
    there: Manual axes must not be constrained).  Under a plain ``with
    mesh:`` context (the pjit path) only the thread-local *physical* mesh is
    populated — fall back to it, with all axes treated as Auto."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.axis_names:
        return mesh
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    if mesh.axis_names:
        return mesh.abstract_mesh
    return None


def zero1_spec(
    param_spec: P,
    shape: tuple[int, ...],
    mesh: Mesh,
    dp_axes: tuple[str, ...],
    logical: tuple | None = None,
) -> P:
    """ZeRO-1: additionally shard an optimizer-state array over the data axes.

    Picks the first dim that is divisible by the dp extent and not already
    sharded; falls back to the param spec when nothing fits.  Stacked scan
    dims (logical 'layers'/'sites') are never chosen, so the sharding is
    identical at any depth (the roofline lowers rely on this).
    """
    dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    if not dp_axes:
        return param_spec
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))
    used = set()
    for e in entries:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a:
                used.add(a)
    if any(a in used for a in dp_axes):
        return param_spec
    stacked_dims = set()
    if logical is not None:
        stacked_dims = {
            i for i, name in enumerate(logical) if name in ("layers", "sites")
        }
    for i, (dim, e) in enumerate(zip(shape, entries)):
        if i in stacked_dims:
            continue
        if e is None and dim % dp == 0 and dim > 0:
            entries[i] = dp_axes[0] if len(dp_axes) == 1 else dp_axes
            while entries and entries[-1] is None:
                entries.pop()
            return P(*entries)
    return param_spec
