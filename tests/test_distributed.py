"""Sharding rules, sanitization, collectives, multi-device subprocess tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import MeshConfig, get_arch
from repro.distributed import collectives
from repro.distributed.sharding import (
    LOGICAL_RULES,
    logical_to_spec,
    resolve_rules,
    rules_for_model,
    sanitize_specs,
    zero1_spec,
)
from repro.distributed.mesh import single_device_mesh

from conftest import run_subprocess


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_logical_to_spec_basic():
    mesh = _FakeMesh({"data": 4, "model": 2})
    assert logical_to_spec(("batch", None, "act_ffn"), mesh) == P("data", None, "model")
    # pod axis absent -> dropped from the tuple
    assert logical_to_spec(("batch",), mesh) == P("data")


def test_logical_to_spec_no_duplicate_axes():
    mesh = _FakeMesh({"data": 4, "model": 2})
    spec = logical_to_spec(("act_heads", "act_ffn"), mesh)
    # both map to 'model'; second use must be dropped
    assert spec == P("model")


def test_sanitize_drops_indivisible():
    mesh = _FakeMesh({"data": 4, "model": 16})
    specs = {"a": P(None, "model"), "b": P("data", "model")}
    structs = {
        "a": jax.ShapeDtypeStruct((24, 24), jnp.float32),   # 24 % 16 != 0
        "b": jax.ShapeDtypeStruct((8, 32), jnp.float32),    # both divide
    }
    out = sanitize_specs(specs, structs, mesh)
    assert out["a"] == P()
    assert out["b"] == P("data", "model")


def test_zero1_spec_skips_stacked_dims():
    mesh = _FakeMesh({"data": 4, "model": 2})
    spec = zero1_spec(P(None, None, "model"), (16, 64, 8), mesh, ("data",),
                      logical=("layers", "embed", "ffn"))
    assert spec == P(None, "data", "model")  # dim0 skipped despite divisibility


def test_rules_for_model_picks_head_dim_for_mamba130():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = rules_for_model(get_arch("mamba2-130m"), mesh)
    assert rules["ssm_heads"] is None and rules["ssm_hd"] == "model"
    rules2 = rules_for_model(get_arch("zamba2-2.7b"), mesh)
    assert rules2["ssm_heads"] == "model"


def test_rules_for_model_cache_hd_for_small_kv():
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert rules_for_model(get_arch("qwen2-0.5b"), mesh)["cache_hd"] == "model"
    assert rules_for_model(get_arch("stablelm-1.6b"), mesh)["cache_heads"] == "model"


def test_int8_quantization_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3
    q, scale = collectives._quantize_int8(x)
    back = collectives._dequantize_int8(q, scale, jnp.float32)
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) / 2 + 1e-6


def test_wire_bytes():
    tree = {"a": jnp.zeros((4, 4), jnp.float32), "b": jnp.zeros((8,), jnp.bfloat16)}
    assert collectives.wire_bytes(tree, compressed=False) == 16 * 4 + 8 * 2
    assert collectives.wire_bytes(tree, compressed=True) == 16 + 8


@pytest.mark.subprocess
def test_psum_and_compressed_reduce_agree():
    run_subprocess(
        """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed import collectives
from repro.distributed.mesh import auto_mesh
mesh = auto_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

def f(gl):
    tree = {"g": gl[0]}
    plain = collectives.psum_mean(tree, ("data",))
    comp, res = collectives.compressed_psum_mean(tree, collectives.init_residual(tree), ("data",))
    return plain["g"], comp["g"], res["g"]

plain, comp, res = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)(g)
import numpy as np
err = float(jnp.max(jnp.abs(plain - comp)))
scale = float(jnp.max(jnp.abs(plain)))
assert err < 0.02 * scale + 1e-4, (err, scale)
# error feedback residual carries exactly the quantization error
print("OK", err)
""",
        devices=8,
    )


@pytest.mark.subprocess
def test_hierarchical_equals_flat_psum():
    run_subprocess(
        """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed import collectives
from repro.distributed.mesh import auto_mesh
mesh = auto_mesh((2, 4), ("pod", "data"))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 32))

def f(gl):
    tree = {"g": gl[0, 0]}
    flat = collectives.psum_mean(tree, ("pod", "data"))
    hier = collectives.hierarchical_psum_mean(tree, ("data",), ("pod",))
    return flat["g"], hier["g"]

flat, hier = jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(), check_vma=False)(g)
import numpy as np
np.testing.assert_allclose(np.asarray(flat), np.asarray(hier), rtol=1e-6)
print("OK")
""",
        devices=8,
    )


@pytest.mark.subprocess
def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (4,) data mesh, restore onto (8,) — elastic resize."""
    run_subprocess(
        """
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.tiered_store import TieredStore
from repro.training.checkpoint import CheckpointManager

from repro.distributed.mesh import auto_mesh
devs = jax.devices()
mesh4 = auto_mesh((4,), ("data",), devices=devs[:4])
mesh8 = auto_mesh((8,), ("data",), devices=devs)
x = jnp.arange(64.0).reshape(8, 8)
x4 = jax.device_put(x, NamedSharding(mesh4, P("data")))
with tempfile.TemporaryDirectory() as d:
    store = TieredStore(d, mem_capacity=1 << 30)
    ck = CheckpointManager(store)
    ck.save({"x": jax.device_get(x4)}, 1, durable=True)
    like = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    sh = {"x": NamedSharding(mesh8, P("data"))}
    restored, _ = ck.restore(like, shardings=sh)
    assert restored["x"].sharding.num_devices == 8
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
    store.close()
print("OK")
""",
        devices=8,
    )
