"""Gaia (Hsieh et al., NSDI 2017) — Algorithm 1.

Each node runs local momentum SGD, accumulates weight updates v, and shares
only *significant* updates: those with |v/w| > T.  Shared updates are applied
by every other node and cleared locally.  T decays with the learning rate
(update_threshold).  Under non-IID partitions the insignificant residuals
let each node's model specialize — the paper's §4.3 failure mode, which our
divergence probes expose.

``t0`` is a *dynamic* hyper-parameter (traced scalar) so SkewScout can retune
it without recompilation.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.algorithms.base import (ModelFns, Params, pernode_grads,
                                        tree_mean0, tmap)
from repro.kernels import ops


class Gaia:
    name = "gaia"

    def __init__(self, fns: ModelFns, n_nodes: int, *, momentum: float = 0.9,
                 weight_decay: float = 0.0, t0: float = 0.10,
                 lr0: float = None):
        self.fns, self.K = fns, n_nodes
        self.m, self.wd = momentum, weight_decay
        self.t0 = t0
        self.lr0 = lr0  # reference lr for threshold decay (None => constant T)

    def init(self, params: Params, mstate: Params) -> Dict[str, Params]:
        stack = lambda l: jnp.broadcast_to(l, (self.K,) + l.shape)
        return {
            "params": tmap(stack, params),     # per-node replicas
            "mstate": tmap(stack, mstate),
            "vel": tmap(lambda l: jnp.zeros((self.K,) + l.shape, l.dtype),
                        params),
            "acc": tmap(lambda l: jnp.zeros((self.K,) + l.shape, l.dtype),
                        params),               # accumulated updates v
        }

    @partial(jax.jit, static_argnums=0)
    def step(self, state, batch, lr, step_idx, t0=None) -> Tuple[Dict, Dict]:
        t0 = self.t0 if t0 is None else t0
        # threshold decays with the learning rate (Algorithm 1, line 16)
        thresh = t0 * (lr / self.lr0) if self.lr0 is not None else t0

        with jax.named_scope("local_step"):
            losses, grads, new_ms = pernode_grads(
                self.fns, state["params"], state["mstate"], batch,
                params_stacked=True)
            vel = tmap(lambda w, g, u: self.m * u - lr * (g + self.wd * w),
                       state["params"], grads, state["vel"])
            params = tmap(lambda w, u: w + u, state["params"], vel)
            acc = tmap(lambda v, u: v + u, state["acc"], vel)

        with jax.named_scope("exchange"):
            # significance filter: |v / w| > thresh — the fused select
            # kernel (or its dispatched jnp twin) returns (v * mask,
            # count) per leaf, so the mask itself never materializes: the
            # shared part is cleared exactly via acc - shared (shared =
            # acc * mask).
            leaves_v, treedef = jax.tree_util.tree_flatten(acc)
            leaves_w = treedef.flatten_up_to(params)
            picked = [ops.gaia_select(v, w, thresh)
                      for v, w in zip(leaves_v, leaves_w)]
            shared = jax.tree_util.tree_unflatten(treedef,
                                                  [p[0] for p in picked])
            total = tmap(lambda s: jnp.sum(s, axis=0, keepdims=True), shared)
            # apply everyone else's significant updates; clear own shared
            # part
            params = tmap(lambda w, t, s: w + (t - s), params, total, shared)
            acc = tmap(lambda v, s: v - s, acc, shared)

        comm = sum(p[1].astype(jnp.float32) for p in picked) / self.K
        metrics = {"loss": jnp.mean(losses), "comm_floats": comm,
                   "resid_delta": _mean_rel(acc, params)}
        return ({"params": params, "mstate": new_ms, "vel": vel, "acc": acc},
                metrics)

    def eval_params(self, state):
        return tree_mean0(state["params"]), tree_mean0(state["mstate"])

    def node_params(self, state, k: int):
        return (tmap(lambda l: l[k], state["params"]),
                tmap(lambda l: l[k], state["mstate"]))


def _mean_rel(acc, params):
    num = sum(jnp.sum(jnp.abs(a)) for a in jax.tree_util.tree_leaves(acc))
    den = sum(jnp.sum(jnp.abs(p)) for p in jax.tree_util.tree_leaves(params))
    return num / jnp.maximum(den, 1e-12)
