"""Subprocess: EP MoE == dense MoE when capacity is generous (no drops)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
import jax
import jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.models import moe_ep

mesh = make_mesh((4, 2), ("data", "model"))
m = MoEConfig(n_experts=4, n_shared=0, top_k=2, d_ff_expert=16,
              capacity_factor=16.0)   # generous: nothing drops either way
d = 8
p = moe_mod.init_moe(jax.random.PRNGKey(0), m, d, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, d))
y_dense, aux_dense = moe_mod.moe_apply(p, m, x)
with mesh:
    y_ep, aux_ep = jax.jit(lambda p, x: moe_ep.moe_apply_ep(p, m, x, mesh))(p, x)
err = float(jnp.max(jnp.abs(y_dense - y_ep)))
print("max err", err, "aux", float(aux_dense), float(aux_ep))
assert err < 1e-4, err
# aux estimators differ (global-mean vs mean of per-shard products) — both
# positive load-balance signals of the same scale
assert 0 < float(aux_ep) < 10 * float(aux_dense) + 1e-3
# gradients flow
def loss(p):
    with mesh:
        y, aux = moe_ep.moe_apply_ep(p, m, x, mesh)
    return jnp.sum(y ** 2) + aux
g = jax.jit(jax.grad(loss))(p)
assert float(jnp.abs(g["w_gate"]).sum()) > 0
assert float(jnp.abs(g["router"]["w"]).sum()) > 0
print("EP_MOE_OK")
