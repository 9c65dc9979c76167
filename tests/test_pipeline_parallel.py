"""GPipe pipeline parallelism: pipelined == serial, fwd and grad."""

import pytest

from conftest import run_subprocess

pytestmark = pytest.mark.subprocess


def test_pipeline_forward_and_grad_match_serial():
    run_subprocess(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.mesh import auto_mesh
from repro.distributed.pipeline_parallel import (
    bubble_fraction, mlp_stage_fn, pipeline_apply, serial_reference)

S, M, mb, d = 4, 6, 2, 16
mesh = auto_mesh((S,), ("stage",))
ks = jax.random.split(jax.random.PRNGKey(0), 3)
params = {
    "w1": jax.random.normal(ks[0], (S, d, 32)) * 0.3,
    "w2": jax.random.normal(ks[1], (S, 32, d)) * 0.3,
}
x = jax.random.normal(ks[2], (M, mb, d))
fn = mlp_stage_fn(d)

out = pipeline_apply(fn, params, x, mesh)
ref = serial_reference(fn, params, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

# pipelined backward == serial backward
def loss_pp(p):
    return jnp.sum(pipeline_apply(fn, p, x, mesh) ** 2)

def loss_serial(p):
    return jnp.sum(serial_reference(fn, p, x) ** 2)

g_pp = jax.grad(loss_pp)(params)
g_s = jax.grad(loss_serial)(params)
for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_s)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)

assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
print("OK pipeline parallel")
""",
        devices=4,
        timeout=900,
    )
