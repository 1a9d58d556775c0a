"""AD-PSGD (Lian et al., NeurIPS 2018): asynchronous decentralized SGD.

Same local momentum-SGD + gossip-averaging loop as :class:`DPSGD`, but
nodes do not wait for each round's slowest link: each node mixes with
the *last delivered* version of its neighbors' parameters, which may be
up to ``max_staleness`` rounds old.  The simulation models this with a
**bounded-staleness snapshot buffer**: ``state["snaps"]`` holds the
flattened per-node parameter stack of the last ``max_staleness + 1``
rounds (slot 0 = this round's post-gradient params, slot ``s`` = the
stack from ``s`` rounds ago), and every neighbor read gathers from slot
``staleness`` instead of slot 0.  ``staleness = 0`` is bit-identical to
synchronous D-PSGD; the *bound* is structural — a read deeper than the
buffer cannot be expressed.

The mixing reuses the dispatched ``ops.neighbor_mix`` (src-gather
variant; Pallas on TPU, measured winner elsewhere): the buffer
is stacked into one ``((S + 1) * K, N)`` source matrix and the round's
padded neighbor indices are offset by ``staleness * K`` — staleness
values therefore ride inside the same *runtime* index operand as the
schedule's neighbor sets, so rotating schedules, SkewScout rung
switches, **and** staleness changes (``set_staleness``) all reuse one
compilation per run (``trace_count`` asserts this in tests).

Why it matters here: under a geo-WAN fabric the synchronous ledger
prices every round at the slowest link — one straggler gates all nodes.
With stale reads the slow link keeps ``staleness + 1`` deliveries in
flight and its latency amortizes away (see ``CommLedger`` async mode),
while accuracy stays within noise of the synchronous run — the
communication-structure-vs-skew trade the paper's SkewScout controller
climbs, now with staleness as a rung.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithms.base import ModelFns
from repro.core.algorithms.dpsgd import DPSGD
from repro.kernels import ops
from repro.topology.graphs import Topology, TopologySchedule


class ADPSGD(DPSGD):
    name = "adpsgd"

    def __init__(self, fns: ModelFns, n_nodes: int, *,
                 topology: Union[Topology, TopologySchedule],
                 momentum: float = 0.9, weight_decay: float = 0.0,
                 use_kernel: bool = True,
                 pad_degree: Optional[int] = None,
                 max_staleness: int = 2,
                 staleness: Optional[int] = None,
                 participation=None):
        """``max_staleness`` sizes the snapshot buffer (the hard bound a
        controller may move within); ``staleness`` is the current rung,
        defaulting to the bound (fully asynchronous)."""
        assert max_staleness >= 0, max_staleness
        self.max_staleness = int(max_staleness)
        s = self.max_staleness if staleness is None else int(staleness)
        assert 0 <= s <= self.max_staleness, (s, self.max_staleness)
        self.staleness = s
        self._stale_cache: Dict = {}
        super().__init__(fns, n_nodes, topology=topology,
                         momentum=momentum, weight_decay=weight_decay,
                         use_kernel=use_kernel, pad_degree=pad_degree,
                         participation=participation)

    # ---- staleness plumbing ----
    def set_schedule(self, fabric) -> None:
        super().set_schedule(fabric)
        self._stale_cache = {}

    def set_staleness(self, staleness: int) -> None:
        """Move the staleness rung (SkewScout).  The buffer depth is
        fixed at ``max_staleness + 1``, so any rung within the bound
        changes only the *values* of the runtime index operand — never
        the operand shapes, hence never the compilation."""
        s = int(staleness)
        assert 0 <= s <= self.max_staleness, \
            (f"staleness {s} outside the bound [0, {self.max_staleness}] "
             "fixed by the snapshot buffer at construction")
        if s != self.staleness:
            self.staleness = s
            self._stale_cache = {}

    def _stale_operand(self, t: int) -> jnp.ndarray:
        """(K, D) int32 per-read staleness slots for round ``t``: the
        current rung on real neighbor slots, 0 on padding (padding
        weights are 0, so the slot is irrelevant — 0 keeps the gather
        index in range without widening the buffer)."""
        key = (id(self.schedule.at(t)), self.staleness)
        op = self._stale_cache.get(key)
        if op is None:
            _, w, _ = self.schedule.neighbor_arrays(
                t, pad_degree=self._pad_degree)
            op = jnp.asarray(np.where(w > 0, self.staleness, 0)
                             .astype(np.int32))
            self._stale_cache[key] = op
        return op

    def edge_staleness(self, t: int) -> np.ndarray:
        """Per-edge staleness bound for round ``t``'s active edges,
        aligned with ``schedule.at(t).edges`` — what the async ledger
        uses to amortize each link's latency."""
        return np.full(len(self.schedule.at(int(t)).edges),
                       self.staleness, np.int64)

    # ---- state ----
    def init(self, params, mstate) -> Dict:
        state = super().init(params, mstate)
        flat, _, _ = self._flatten(state["params"])
        state["snaps"] = jnp.broadcast_to(
            flat, (self.max_staleness + 1,) + flat.shape)
        return state

    def step(self, state, batch, lr, step_idx) -> Tuple[Dict, Dict]:
        """One local step + stale gossip round.  Neighbor indices,
        weights, and staleness slots are all runtime operands of the one
        jitted body."""
        nbr_idx, nbr_w, self_w = self.mix_operands(int(step_idx))
        stale = self._stale_operand(int(step_idx))
        return self._step_stale(state, batch, lr, step_idx,
                                nbr_idx, nbr_w, self_w, stale)

    @partial(jax.jit, static_argnums=0)
    def _step_stale(self, state, batch, lr, step_idx,
                    nbr_idx, nbr_w, self_w, stale) -> Tuple[Dict, Dict]:
        self.trace_count += 1          # Python side effect: trace-time only
        with jax.named_scope("local_step"):
            losses, new_ms, vel, params = self._local_update(state, batch,
                                                             lr)
        with jax.named_scope("exchange"):
            flat, treedef, leaves = self._flatten(params)
            # push this round's post-gradient stack into slot 0; slot s
            # now holds the stack from s rounds ago (pre-mix, like slot 0)
            snaps = jnp.concatenate([flat[None], state["snaps"][:-1]],
                                    axis=0)
            src = snaps.reshape(-1, flat.shape[1])     # ((S+1)*K, N)
            gidx = stale * self.K + nbr_idx            # slot-offset gather
            if self.use_kernel:
                mixed = ops.neighbor_mix(flat, gidx, nbr_w, self_w, src=src)
            else:
                # dense oracle: scatter the runtime weights into
                # (K, (S+1)K)
                W = jnp.zeros((self.K, src.shape[0]), jnp.float32).at[
                    jnp.arange(self.K)[:, None], gidx].add(nbr_w)
                mixed = jnp.matmul(W, src) + self_w[:, None] * flat
            params = self._unflatten(mixed, treedef, leaves)

        metrics = self._gossip_metrics(losses, params, nbr_w)
        nbr_mask = (nbr_w > 0).astype(jnp.float32)
        reads = jnp.maximum(jnp.sum(nbr_mask), 1.0)
        metrics["mean_staleness"] = jnp.sum(stale * nbr_mask) / reads
        metrics["max_staleness_used"] = jnp.max(stale * nbr_mask
                                                .astype(jnp.int32))
        return ({"params": params, "mstate": new_ms, "vel": vel,
                 "snaps": snaps}, metrics)
