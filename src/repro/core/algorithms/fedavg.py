"""FederatedAveraging (McMahan et al., AISTATS 2017) — Algorithm 2.

Each node runs ``iter_local`` local momentum-SGD steps, then all node models
are averaged (all_reduce) into the next round's starting point.  Following
the paper's Appendix A, all K partitions participate every round
(deterministic variant).  ``iter_local`` is dynamic: the sync happens when
``step_idx % iter_local == 0``, so SkewScout can retune it live."""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.algorithms.base import (ModelFns, Params, pernode_grads,
                                        tree_mean0, tree_size, tmap)


class FedAvg:
    name = "fedavg"

    def __init__(self, fns: ModelFns, n_nodes: int, *, momentum: float = 0.9,
                 weight_decay: float = 0.0, iter_local: int = 20):
        self.fns, self.K = fns, n_nodes
        self.m, self.wd = momentum, weight_decay
        self.iter_local = iter_local

    def init(self, params: Params, mstate: Params) -> Dict[str, Params]:
        stack = lambda l: jnp.broadcast_to(l, (self.K,) + l.shape)
        return {
            "params": tmap(stack, params),
            "mstate": tmap(stack, mstate),
            "vel": tmap(lambda l: jnp.zeros((self.K,) + l.shape, l.dtype),
                        params),
        }

    @partial(jax.jit, static_argnums=0)
    def step(self, state, batch, lr, step_idx, iter_local=None
             ) -> Tuple[Dict, Dict]:
        il = jnp.asarray(self.iter_local if iter_local is None else iter_local,
                         jnp.int32)
        with jax.named_scope("local_step"):
            losses, grads, new_ms = pernode_grads(
                self.fns, state["params"], state["mstate"], batch,
                params_stacked=True)
            vel = tmap(lambda w, g, u: self.m * u - lr * (g + self.wd * w),
                       state["params"], grads, state["vel"])
            params = tmap(lambda w, u: w + u, state["params"], vel)

        do_sync = (step_idx % il) == (il - 1)

        # divergence probe: mean |w_k - w_avg| / |w_avg| at sync points
        avg = tree_mean0(params)
        delta = _mean_rel_dev(params, avg)

        def sync(p):
            a = tree_mean0(p)
            return tmap(lambda l, m_: jnp.broadcast_to(m_, l.shape), p, a)

        with jax.named_scope("exchange"):
            params = jax.lax.cond(do_sync, sync, lambda p: p, params)
            new_ms = jax.lax.cond(do_sync, sync, lambda s: s, new_ms)
        comm = jnp.where(do_sync,
                         float(tree_size(avg)), 0.0).astype(jnp.float32)
        metrics = {"loss": jnp.mean(losses), "comm_floats": comm,
                   "local_delta": delta, "synced": do_sync}
        return ({"params": params, "mstate": new_ms, "vel": vel}, metrics)

    def eval_params(self, state):
        return tree_mean0(state["params"]), tree_mean0(state["mstate"])

    def node_params(self, state, k: int):
        return (tmap(lambda l: l[k], state["params"]),
                tmap(lambda l: l[k], state["mstate"]))


def _mean_rel_dev(stacked, avg):
    num = sum(jnp.sum(jnp.abs(s - a[None]))
              for s, a in zip(jax.tree_util.tree_leaves(stacked),
                              jax.tree_util.tree_leaves(avg)))
    den = sum(jnp.sum(jnp.abs(a)) * s.shape[0]
              for s, a in zip(jax.tree_util.tree_leaves(stacked),
                              jax.tree_util.tree_leaves(avg)))
    return num / jnp.maximum(den, 1e-12)
