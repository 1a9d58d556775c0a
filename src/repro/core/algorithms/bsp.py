"""BSP (Valiant 1990): full synchronization every step — the paper's model-
quality target.  All node gradients are averaged each minibatch; a single
global model exists at all times.  Per-node BatchNorm still normalizes with
*local* minibatch statistics — which is exactly why BSP alone cannot fix the
non-IID problem for BN models (paper §5)."""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.algorithms.base import (ModelFns, Params, pernode_grads,
                                        tree_mean0, tree_size, tmap)
from repro.optim.sgd import init_momentum


class BSP:
    name = "bsp"

    def __init__(self, fns: ModelFns, n_nodes: int, *, momentum: float = 0.9,
                 weight_decay: float = 0.0):
        self.fns, self.K = fns, n_nodes
        self.m, self.wd = momentum, weight_decay

    def init(self, params: Params, mstate: Params) -> Dict[str, Params]:
        return {
            "params": params,
            "mstate": tmap(lambda l: jnp.broadcast_to(l, (self.K,) + l.shape),
                           mstate),
            "vel": init_momentum(params),
        }

    @partial(jax.jit, static_argnums=0)
    def step(self, state, batch, lr, step_idx) -> Tuple[Dict, Dict]:
        with jax.named_scope("local_step"):
            losses, grads, new_ms = pernode_grads(
                self.fns, state["params"], state["mstate"], batch,
                params_stacked=False)
        with jax.named_scope("exchange"):
            g = tree_mean0(grads)

        def upd(w, gl, u):
            gl = gl + self.wd * w
            return self.m * u - lr * gl
        with jax.named_scope("local_step"):
            vel = tmap(upd, state["params"], g, state["vel"])
            params = tmap(lambda w, u: w + u, state["params"], vel)
        new_state = {"params": params, "mstate": new_ms, "vel": vel}
        metrics = {"loss": jnp.mean(losses),
                   "comm_floats": jnp.asarray(
                       float(tree_size(state["params"])), jnp.float32)}
        return new_state, metrics

    def eval_params(self, state):
        return state["params"], tree_mean0(state["mstate"])

    def node_params(self, state, k: int):
        return state["params"], tmap(lambda l: l[k], state["mstate"])
