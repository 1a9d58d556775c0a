"""DeepGradientCompression (Lin et al., ICLR 2018) — Algorithm 3.

Per step each node: scales its gradient by -eta, clips (global norm),
applies momentum correction (u = m*u + g), accumulates v += u, and exchanges
only the top-s% magnitude entries of v per tensor.  Exchanged entries are
cleared from BOTH v and u (momentum factor masking).  A warm-up schedule
raises s over epochs: 75%, 93.75%, 98.4375%, 99.6%, 99.9%.

``sparsity`` is dynamic (traced), so both the warm-up schedule and SkewScout
retuning require no recompilation.

``compressor="randk"`` swaps the exact top-s% selection for seeded
rand-k (the classic baseline top-k is measured against): the keep mask
is a pure function of (seed, step, leaf, flat index) generated inside
the select kernel (``kernels/rng.py``) — no materialized random arrays —
and the same (seed, counter) stream masks ``v`` and ``u`` consistently.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.algorithms.base import (ModelFns, Params, pernode_grads,
                                        tree_mean0, tree_sum0, tmap)
from repro.kernels import ops
from repro.optim.sgd import global_norm

WARMUP_SPARSITIES = (0.75, 0.9375, 0.984375, 0.996, 0.999)


def warmup_sparsity(epoch: int, e_warm: int) -> float:
    """Paper §3: s follows the warm-up schedule, e_warm epochs per level."""
    idx = min(epoch // max(e_warm, 1), len(WARMUP_SPARSITIES) - 1)
    return WARMUP_SPARSITIES[idx]


class DGC:
    name = "dgc"

    def __init__(self, fns: ModelFns, n_nodes: int, *, momentum: float = 0.9,
                 weight_decay: float = 0.0, clip: float = 1.0,
                 sparsity: float = 0.999, compressor: str = "topk",
                 seed: int = 0):
        if compressor not in ("topk", "randk"):
            raise ValueError(f"compressor={compressor!r}; expected "
                             "'topk' or 'randk'")
        self.fns, self.K = fns, n_nodes
        self.m, self.wd = momentum, weight_decay
        self.clip = clip
        self.sparsity = sparsity
        self.compressor = compressor
        self.seed = seed

    def init(self, params: Params, mstate: Params) -> Dict[str, Params]:
        stack = lambda l: jnp.broadcast_to(l, (self.K,) + l.shape)
        zeros = lambda l: jnp.zeros((self.K,) + l.shape, l.dtype)
        return {
            "params": params,                 # ONE global model
            "mstate": tmap(stack, mstate),
            "vel": tmap(zeros, params),       # u (per node)
            "acc": tmap(zeros, params),       # v (per node)
        }

    @partial(jax.jit, static_argnums=0)
    def step(self, state, batch, lr, step_idx, sparsity=None
             ) -> Tuple[Dict, Dict]:
        s = self.sparsity if sparsity is None else sparsity
        with jax.named_scope("local_step"):
            losses, grads, new_ms = pernode_grads(
                self.fns, state["params"], state["mstate"], batch,
                params_stacked=False)

            # g = -eta * grad, with per-node gradient clipping
            def clip_node(g):
                n = global_norm(g)
                scale = jnp.minimum(1.0, self.clip / jnp.maximum(n, 1e-12))
                return tmap(lambda l: l * scale, g)
            grads = jax.vmap(clip_node)(grads)
            g = tmap(lambda gl, w: -lr * (gl + self.wd * w[None]),
                     grads, state["params"])

            vel = tmap(lambda u, gl: self.m * u + gl, state["vel"], g)
            acc = tmap(lambda v, u: v + u, state["acc"], vel)

        with jax.named_scope("exchange"):
            if self.compressor == "randk":
                # seeded rand-k: each (step, leaf) gets its own counter
                # stream, and replaying the stream on ``vel`` clears exactly
                # the exchanged coordinates (momentum factor masking without
                # a materialized mask).
                keep = 1.0 - s
                leaves_v, treedef = jax.tree_util.tree_flatten(acc)
                leaves_u = treedef.flatten_up_to(vel)
                sh, cl, counts = [], [], []
                for li, (v, u) in enumerate(zip(leaves_v, leaves_u)):
                    leaf_seed = (jnp.asarray(step_idx, jnp.int32) * 1009
                                 + self.seed * 131 + li)
                    sv, cnt = ops.rand_k_sparsify(v, keep, leaf_seed)
                    su, _ = ops.rand_k_sparsify(u, keep, leaf_seed)
                    sh.append(sv)
                    cl.append(su)
                    counts.append(cnt)
                shared = jax.tree_util.tree_unflatten(treedef, sh)
                total = tree_sum0(shared)                    # sum over nodes
                params = tmap(lambda w, t: w + t, state["params"], total)
                acc = tmap(lambda v, sv: v - sv, acc, shared)
                vel = jax.tree_util.tree_unflatten(
                    treedef, [u - su for u, su in zip(leaves_u, cl)])
                comm = sum(c.astype(jnp.float32) for c in counts) / self.K
            else:
                # per-tensor, per-node top-(1-s) magnitude threshold
                def threshold(v):
                    flat = jnp.abs(v.reshape(v.shape[0], -1))
                    return jnp.quantile(flat, s, axis=1)     # (K,)
                def select(v):
                    t = threshold(v)
                    return (jnp.abs(v) > t.reshape((-1,) + (1,) * (v.ndim - 1))
                            ).astype(v.dtype)
                mask = tmap(select, acc)
                shared = tmap(lambda v, m_: v * m_, acc, mask)
                total = tree_sum0(shared)                    # sum over nodes
                params = tmap(lambda w, t: w + t, state["params"], total)
                # momentum factor masking: clear exchanged entries from v AND u
                acc = tmap(lambda v, m_: v * (1 - m_), acc, mask)
                vel = tmap(lambda u, m_: u * (1 - m_), vel, mask)
                comm = sum(jnp.sum(m_)
                           for m_ in jax.tree_util.tree_leaves(mask)) / self.K
        metrics = {"loss": jnp.mean(losses), "comm_floats": comm,
                   "resid_delta": _mean_rel(acc, params)}
        return ({"params": params, "mstate": new_ms, "vel": vel, "acc": acc},
                metrics)

    def eval_params(self, state):
        return state["params"], tree_mean0(state["mstate"])

    def node_params(self, state, k: int):
        return state["params"], tmap(lambda l: l[k], state["mstate"])


def _mean_rel(acc, params):
    num = sum(jnp.sum(jnp.abs(a)) for a in jax.tree_util.tree_leaves(acc))
    den = sum(jnp.sum(jnp.abs(p)) * acc_l.shape[0]
              for p, acc_l in zip(jax.tree_util.tree_leaves(params),
                                  jax.tree_util.tree_leaves(acc)))
    return num / jnp.maximum(den, 1e-12)
