"""Subprocess helper for test_dist_backend.py — needs its own process so
xla_force_host_platform_device_count doesn't leak into other tests.

Runs the SPMD train step on a (2,2,2) pod/data/model mesh with a REAL
reduced model and real arrays, and checks:
 1. every strategy (bsp/gaia/fedavg/dgc/dpsgd/adpsgd) executes with
    finite loss,
 2. the distributed Gaia update == the simulation-backend Gaia update
    (same arithmetic, two backends; the full per-strategy equivalence
    matrix lives in launch_gossip_script.py),
 3. serve_step executes on the mesh.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import CommConfig
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   param_shardings, train_state_shardings)
from repro.launch.steps import (gossip_operands, make_serve_step,
                                make_train_step, make_train_state)
from repro.models.model import init_cache, init_model
from repro.models.shard_hints import activation_sharding
from repro.topology.graphs import ring


def main():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("qwen3-0.6b").reduced()
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    B_per_pod, T = 4, 32
    tokens = jax.random.randint(key, (2, B_per_pod, T), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, B_per_pod, T), 0,
                                cfg.vocab)
    batch = {"tokens": tokens, "labels": labels}

    losses = {}
    states = {}
    fabric = ring(2)
    for strategy in ("bsp", "gaia", "fedavg", "dgc", "dpsgd", "adpsgd"):
        comm = CommConfig(strategy=strategy, gaia_t0=0.01,
                          iter_local=1, dgc_sparsity=0.75, max_staleness=1)
        state = make_train_state(params, comm, 2)
        with mesh, activation_sharding(mesh):
            s_shard = train_state_shardings(
                jax.eval_shape(lambda: state), mesh)
            b_shard = batch_shardings(batch, mesh, pod_stacked=True)
            step = make_train_step(cfg, comm, mesh=mesh, lr=1e-2,
                                   remat=False, chunk=16)
            if strategy in ("dpsgd", "adpsgd"):
                mix = gossip_operands(
                    fabric, 0,
                    staleness=1 if strategy == "adpsgd" else None,
                    max_staleness=comm.max_staleness)
                jitted = jax.jit(step,
                                 in_shardings=(s_shard, b_shard, None,
                                               None))
                new_state, metrics = jitted(state, batch, jnp.int32(0),
                                            mix)
            else:
                jitted = jax.jit(step,
                                 in_shardings=(s_shard, b_shard, None))
                new_state, metrics = jitted(state, batch, jnp.int32(0))
            loss = float(metrics["loss"])
        assert np.isfinite(loss), (strategy, loss)
        losses[strategy] = loss
        states[strategy] = jax.device_get(new_state)
        print(f"dist {strategy}: loss={loss:.4f} OK", flush=True)

    # --- cross-backend check: dist gaia == hand-computed reference ---
    # recompute per-pod grads with plain jax (no mesh) and apply Algorithm 1
    from repro.models.model import loss_fn

    def pod_loss(p, b):
        l, _ = loss_fn(p, cfg, b, remat=False, chunk=16)
        return l
    g0 = jax.grad(pod_loss)(params, {"tokens": tokens[0], "labels": labels[0]})
    g1 = jax.grad(pod_loss)(params, {"tokens": tokens[1], "labels": labels[1]})
    tmap = jax.tree_util.tree_map
    lr, t0 = 1e-2, 0.01
    vel = tmap(lambda a, b: jnp.stack([-lr * a.astype(jnp.float32),
                                       -lr * b.astype(jnp.float32)]), g0, g1)
    p_stack = tmap(lambda l: jnp.stack([l.astype(jnp.float32)] * 2), params)
    p_local = tmap(lambda w, u: w + u, p_stack, vel)
    acc = vel

    def exchange(w, v):
        mask = (jnp.abs(v) > t0 * jnp.abs(w)).astype(v.dtype)
        sel = v * mask
        total = jnp.sum(sel, axis=0, keepdims=True)
        return w + (total - sel), v * (1 - mask)
    pairs = tmap(exchange, p_local, acc)
    p_ref = tmap(lambda pr: pr[0], pairs,
                 is_leaf=lambda x: isinstance(x, tuple))

    got = states["gaia"]["params"]
    ref_leaves = jax.tree_util.tree_leaves(p_ref)
    got_leaves = jax.tree_util.tree_leaves(got)
    worst = 0.0
    for r, g in zip(ref_leaves, got_leaves):
        diff = np.max(np.abs(np.asarray(r, np.float32)
                             - np.asarray(g, np.float32)))
        scale = np.max(np.abs(np.asarray(r, np.float32))) + 1e-6
        worst = max(worst, float(diff / scale))
    assert worst < 5e-2, f"dist vs ref gaia mismatch: {worst}"
    print(f"gaia dist==ref OK (worst rel diff {worst:.2e})", flush=True)

    # --- serve step on the mesh ---
    with mesh, activation_sharding(mesh):
        p_shard = param_shardings(jax.eval_shape(lambda: params), mesh)
        cache = init_cache(cfg, 8, 64)
        c_shard = cache_shardings(jax.eval_shape(lambda: cache), mesh,
                                  batch_sharded=True)
        sbatch = {"token": jnp.zeros((8,), jnp.int32),
                  "t": jnp.zeros((8,), jnp.int32)}
        b_shard = batch_shardings(sbatch, mesh, pod_stacked=False)
        serve = jax.jit(make_serve_step(cfg),
                        in_shardings=(p_shard, c_shard, b_shard))
        tok, _ = serve(params, cache, sbatch)
        assert tok.shape == (8,)
    print("serve OK", flush=True)
    print("ALL_DIST_OK")


if __name__ == "__main__":
    main()
