"""D-PSGD (Lian et al., NeurIPS 2017): decentralized parallel SGD.

Each node holds a model replica, takes a local momentum-SGD step, then
*gossip-averages* with its graph neighbors: ``x_k <- sum_j W[k,j] x_j``
restricted to the round's edges, with W the symmetric doubly-stochastic
mixing matrix.  On the complete graph (W = 1/K) this is exact averaging
and the trajectory coincides with BSP; on sparse graphs (ring, torus,
expander, D-Cliques) each step only moves the model toward consensus at
the rate of the spectral gap, trading accuracy-under-skew for per-node
bandwidth of ``degree * |model|`` instead of a full all-reduce.

The fabric is a :class:`~repro.topology.graphs.TopologySchedule`: round
``t`` mixes with ``schedule.at(t)``'s neighbors.  The padded neighbor
indices/weights are *runtime operands* of the jitted step — padded to
the schedule-wide max degree so every round (and every rung of a
SkewScout topology ladder, via :meth:`DPSGD.set_schedule`) shares one
operand shape and the step compiles exactly once per run
(``trace_count`` asserts this in tests).

The mixing itself runs as one fused gather-scale-accumulate over the
flattened parameter stack via ``ops.neighbor_mix`` — the backend-aware
dispatcher (``kernels/dispatch.py``) routes it to the Pallas kernel on
TPU and to whichever of {Pallas, jnp padded-scatter oracle} measured
faster elsewhere.  ``use_kernel=False`` bypasses ops entirely for a
locally-built dense ``W @ X`` (debug path).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithms.base import (ModelFns, Params, pernode_grads,
                                        tree_mean0, tree_size, tmap)
from repro.kernels import ops
from repro.topology.graphs import Topology, TopologySchedule, as_schedule


class DPSGD:
    name = "dpsgd"

    def __init__(self, fns: ModelFns, n_nodes: int, *,
                 topology: Union[Topology, TopologySchedule],
                 momentum: float = 0.9, weight_decay: float = 0.0,
                 use_kernel: bool = True,
                 pad_degree: Optional[int] = None,
                 participation=None):
        """``pad_degree`` widens the neighbor operand shape beyond this
        schedule's max degree — set it to the max over a SkewScout
        topology ladder so rung switches don't change operand shapes
        (and hence never retrace the step).

        ``participation``: optional
        :class:`~repro.topology.links.Participation` sampler.  Each
        round its seeded node mask zeroes the mixing weight of every
        edge with a sampled-out endpoint (slack returns to the self
        weight, so rows still sum to 1 and sampled-out nodes keep their
        own model).  Masking changes operand *values* only — shapes are
        untouched, so the step still compiles exactly once."""
        schedule = as_schedule(topology)
        assert schedule.n_nodes == n_nodes, (schedule.n_nodes, n_nodes)
        self.fns, self.K = fns, n_nodes
        self.m, self.wd = momentum, weight_decay
        self.use_kernel = use_kernel
        self.participation = participation
        # how many times the jitted step body was traced; 1 after any
        # number of rounds == "schedules don't retrigger compilation"
        self.trace_count = 0
        self._pad_degree = max(schedule.max_degree, 1)
        if pad_degree is not None:
            self._pad_degree = max(self._pad_degree, pad_degree)
        self._operand_cache: Dict[int, tuple] = {}
        self.set_schedule(schedule)

    # ---- schedule plumbing ----
    def set_schedule(self, fabric: Union[Topology, TopologySchedule]
                     ) -> None:
        """Swap the fabric mid-run (SkewScout topology rung switch).
        Keeps the operand padding monotone so the jitted step's operand
        shapes — and its compilation — survive the switch."""
        schedule = as_schedule(fabric)
        assert schedule.n_nodes == self.K, (schedule.n_nodes, self.K)
        # widening the pad after the step compiled would change the
        # operand shape and silently retrace — refuse instead (growing
        # the pad is only safe while nothing has been traced yet)
        assert schedule.max_degree <= self._pad_degree or \
            self.trace_count == 0, \
            (f"schedule {schedule.name!r} needs degree "
             f"{schedule.max_degree} > pad {self._pad_degree}; construct "
             f"DPSGD with pad_degree=max over the ladder")
        self._pad_degree = max(self._pad_degree, schedule.max_degree)
        self.schedule = schedule
        self._operand_cache.clear()

    @property
    def topology(self) -> Topology:
        """Round-0 graph — the full graph for constant schedules (kept
        for one-graph-per-run callers)."""
        return self.schedule.at(0)

    def mix_operands(self, t: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
        """Round ``t``'s (nbr_idx, nbr_w, self_w) device arrays, cached
        per unique graph of the period, all padded to one shape.  With a
        participation sampler, round ``t``'s node mask is applied to the
        cached host arrays (same shapes, masked values) before upload;
        a full-participation round returns the cached device operands
        untouched."""
        i = id(self.schedule.at(t))
        ent = self._operand_cache.get(i)
        if ent is None:
            idx, w, sw = self.schedule.neighbor_arrays(
                t, pad_degree=self._pad_degree)
            ent = ((idx, w),
                   (jnp.asarray(idx), jnp.asarray(w), jnp.asarray(sw)))
            self._operand_cache[i] = ent
        (idx_np, w_np), ops_t = ent
        if self.participation is None:
            return ops_t
        m = self.participation.mask(int(t))
        if m.all():
            return ops_t
        # w'_ij = w_ij * m_i * m_j (symmetric), slack to the diagonal:
        # rows still sum to 1 and sampled-out nodes mix with nobody
        w2 = np.where(m[idx_np] & m[:, None], w_np, 0.0) \
            .astype(np.float32)
        sw2 = (1.0 - w2.sum(axis=1)).astype(np.float32)
        return ops_t[0], jnp.asarray(w2), jnp.asarray(sw2)

    def init(self, params: Params, mstate: Params) -> Dict[str, Params]:
        stack = lambda l: jnp.broadcast_to(l, (self.K,) + l.shape)
        return {
            "params": tmap(stack, params),
            "mstate": tmap(stack, mstate),
            "vel": tmap(lambda l: jnp.zeros((self.K,) + l.shape, l.dtype),
                        params),
        }

    def _flatten(self, stacked: Params):
        """Per-node model stack -> one (K, N) float32 matrix (+ the
        structure needed to split back)."""
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        flat = jnp.concatenate(
            [l.reshape(self.K, -1).astype(jnp.float32) for l in leaves],
            axis=1)
        return flat, treedef, leaves

    def _unflatten(self, mixed: jnp.ndarray, treedef, leaves) -> Params:
        out, off = [], 0
        for l in leaves:
            n = l[0].size
            out.append(mixed[:, off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    def _mix(self, stacked: Params, nbr_idx, nbr_w, self_w) -> Params:
        """Gossip-average every leaf: flatten the per-node model stack to
        one (K, N) matrix, mix once, split back."""
        flat, treedef, leaves = self._flatten(stacked)
        if self.use_kernel:
            mixed = ops.neighbor_mix(flat, nbr_idx, nbr_w, self_w)
        else:
            # dense oracle path: rebuild W from the same runtime operands
            # (padding rows carry weight 0, so they scatter nothing)
            K = self.K
            W = jnp.zeros((K, K), jnp.float32).at[
                jnp.arange(K)[:, None], nbr_idx].add(nbr_w)
            W = W + jnp.diag(self_w)
            mixed = jnp.matmul(W, flat)
        return self._unflatten(mixed, treedef, leaves)

    def step(self, state, batch, lr, step_idx) -> Tuple[Dict, Dict]:
        """One local step + gossip round.  ``step_idx`` selects the
        round's graph; the neighbor operands enter the jitted body as
        traced arguments, so a schedule rotating its edge set reuses one
        compilation."""
        nbr_idx, nbr_w, self_w = self.mix_operands(int(step_idx))
        return self._step(state, batch, lr, step_idx,
                          nbr_idx, nbr_w, self_w)

    def _local_update(self, state, batch, lr):
        """Per-node momentum-SGD step (pre-gossip), shared with ADPSGD."""
        losses, grads, new_ms = pernode_grads(
            self.fns, state["params"], state["mstate"], batch,
            params_stacked=True)
        vel = tmap(lambda w, g, u: self.m * u - lr * (g + self.wd * w),
                   state["params"], grads, state["vel"])
        params = tmap(lambda w, u: w + u, state["params"], vel)
        return losses, new_ms, vel, params

    def _gossip_metrics(self, losses, params, nbr_w) -> Dict:
        # per-node price: ship the model once to each active neighbor
        # this round (padding entries carry weight 0, so counting
        # positive weights recovers the round graph's mean degree)
        model_floats = float(tree_size(params)) / self.K
        mean_degree = jnp.sum(nbr_w > 0).astype(jnp.float32) / self.K
        comm = mean_degree * model_floats
        # consensus distance: mean |w_k - w_avg| / |w_avg|
        avg = tree_mean0(params)
        num = sum(jnp.sum(jnp.abs(s - a[None]))
                  for s, a in zip(jax.tree_util.tree_leaves(params),
                                  jax.tree_util.tree_leaves(avg)))
        den = sum(jnp.sum(jnp.abs(a)) * self.K
                  for a in jax.tree_util.tree_leaves(avg))
        return {"loss": jnp.mean(losses), "comm_floats": comm,
                "consensus_delta": num / jnp.maximum(den, 1e-12)}

    @partial(jax.jit, static_argnums=0)
    def _step(self, state, batch, lr, step_idx, nbr_idx, nbr_w, self_w
              ) -> Tuple[Dict, Dict]:
        self.trace_count += 1          # Python side effect: trace-time only
        with jax.named_scope("local_step"):
            losses, new_ms, vel, params = self._local_update(state, batch,
                                                             lr)
        with jax.named_scope("exchange"):
            params = self._mix(params, nbr_idx, nbr_w, self_w)
        metrics = self._gossip_metrics(losses, params, nbr_w)
        return ({"params": params, "mstate": new_ms, "vel": vel}, metrics)

    def eval_params(self, state):
        return tree_mean0(state["params"]), tree_mean0(state["mstate"])

    def node_params(self, state, k: int):
        return (tmap(lambda l: l[k], state["params"]),
                tmap(lambda l: l[k], state["mstate"]))
