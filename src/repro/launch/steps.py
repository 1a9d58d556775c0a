"""Distributed train/serve steps with the paper's communication strategies
as a first-class stage.

The decentralized-site axis is the mesh ``pod`` axis.  Training state holds
*pod-stacked* model replicas — leaf shapes (n_pods, ...) sharded
P('pod', ...) — so each pod trains its own replica on its own data shard
(vmap over the stacked axis keeps all intra-pod collectives pod-local), and
the cross-pod exchange is an explicit reduction over axis 0, which GSPMD
lowers to collectives on the scarce cross-pod links:

  bsp:    grads averaged across pods every step (the quality target)
  gaia:   |accumulated update / weight| > T  -> masked psum (Algorithm 1);
          T decays with the learning rate, T = t0 * lr/lr0 (lr0 defaults
          to the construction-time lr), exactly like
          core/algorithms/gaia.py
  fedavg: params averaged across pods every Iter_local steps (Algorithm 2)
  dgc:    per-pod global-norm clip, momentum correction, then top-s%
          magnitude of the accumulated -lr*grad momentum via a 256-bin
          histogram threshold — the TPU-native replacement for sort-based
          selection (Algorithm 3); ``sparsity`` is a runtime operand so
          the warm-up schedule never recompiles
  dpsgd:  gossip averaging over a TopologySchedule fabric: a ring of
          ``n_pods - 1`` static ppermute rotations over the ``pod`` axis
          (shard_map; every other mesh axis keeps its GSPMD sharding),
          with the round's padded neighbor idx/weights entering as
          *runtime* operands — the SPMD twin of the Pallas
          ``neighbor_mix`` self-weight + padded-neighbor-gather
          arithmetic, and the same compile-once contract that
          ``DPSGD.trace_count`` asserts in the simulation
  adpsgd: same ring, but neighbor reads gather from a pod-stacked
          bounded-staleness snapshot buffer in the train state
          (``state["snaps"]``, slot s = the stack from s rounds ago);
          per-read staleness slots ride in a fourth runtime operand, so
          schedule rotation AND staleness moves reuse one compilation.
          Staleness 0 is bit-identical to dpsgd.

This is the *same arithmetic* as repro.core.algorithms — asserted by
tests/test_launch_gossip.py, which steps both backends on identical
inputs and compares the updates strategy by strategy.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, ModelConfig
from repro.models.model import (decode_step, forward, init_cache,
                                init_model, loss_fn)
from repro.topology.graphs import Topology, TopologySchedule, as_schedule

Params = Any
tmap = jax.tree_util.tree_map

#: strategies whose cross-pod exchange is gossip over a topology fabric —
#: their train_step takes the round's mix operands (see gossip_operands)
GOSSIP_STRATEGIES = ("dpsgd", "adpsgd")


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------

def make_train_state(params: Params, comm: CommConfig, n_pods: int) -> Dict:
    """Stack replicas over the pod axis; fp32 master velocity.

    adpsgd additionally carries the bounded-staleness snapshot buffer:
    per leaf ``(max_staleness + 1, n_pods, ...)`` in the leaf's own dtype
    (slot 0 always holds the current round's post-gradient stack, so a
    staleness-0 read is exactly the fresh dpsgd read)."""
    stack = lambda l: jnp.broadcast_to(l, (n_pods,) + l.shape)
    state = {
        "params": tmap(stack, params),
        "vel": tmap(lambda l: jnp.zeros((n_pods,) + l.shape, jnp.float32),
                    params),
    }
    if comm.strategy in ("gaia", "dgc"):
        state["acc"] = tmap(
            lambda l: jnp.zeros((n_pods,) + l.shape, jnp.float32), params)
    if comm.strategy == "adpsgd":
        state["snaps"] = tmap(
            lambda l: jnp.broadcast_to(l,
                                       (comm.max_staleness + 1,) + l.shape),
            state["params"])
    return state


def param_shape(cfg: ModelConfig):
    """Abstract parameter pytree (the serve/prefill state) — the one
    shape source the dryrun sweep and the jaxpr audit both trace from."""
    return jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))


def cache_shape(cfg: ModelConfig, global_batch: int, seq_len: int,
                long_mode: bool = False):
    """Abstract decode-cache pytree for :func:`make_serve_step`."""
    return jax.eval_shape(
        lambda: init_cache(cfg, global_batch, seq_len, long_mode))


def train_state_shape(cfg: ModelConfig, comm: CommConfig, n_pods: int
                      ) -> Dict:
    return jax.eval_shape(
        lambda p: make_train_state(p, comm, n_pods), param_shape(cfg))


# ---------------------------------------------------------------------------
# Gossip fabric plumbing
# ---------------------------------------------------------------------------

def gossip_operands(fabric: Union[Topology, TopologySchedule], t: int, *,
                    pad_degree: Optional[int] = None,
                    staleness: Optional[int] = None,
                    max_staleness: Optional[int] = None) -> Tuple:
    """Round ``t``'s runtime mix operands for the pod-gossip step.

    Returns ``(nbr_idx, nbr_w, self_w)`` — plus a ``(K, D)`` int32 per-read
    staleness-slot operand when ``staleness`` is given (adpsgd; 0 on
    padding entries, whose weight is 0 anyway) — padded to the
    schedule-wide max degree (or ``pad_degree``, e.g. the max over a
    controller ladder).  Every round of a rotating schedule and every
    staleness move therefore shares one operand *shape*: only the values
    change, and the jitted train step compiles exactly once — the same
    contract ``DPSGD.trace_count`` asserts for the simulation backend."""
    sched = as_schedule(fabric)
    idx, w, sw = sched.neighbor_arrays(int(t), pad_degree=pad_degree)
    ops = (jnp.asarray(idx, jnp.int32), jnp.asarray(w, jnp.float32),
           jnp.asarray(sw, jnp.float32))
    if staleness is None:
        return ops
    # a slot outside the snapshot buffer would be *silently dropped* by
    # the coefficient scatter (jax out-of-bounds updates drop), zeroing
    # the neighbor weights — so the bound is mandatory here, the one
    # place the slot values are constructed
    if max_staleness is None:
        raise ValueError(
            "staleness needs max_staleness (= comm.max_staleness, the "
            "snapshot-buffer depth) so out-of-buffer slots are refused "
            "instead of silently scattering to nowhere")
    if not 0 <= staleness <= max_staleness:
        raise ValueError(
            f"staleness {staleness} outside the snapshot buffer bound "
            f"[0, {max_staleness}] fixed at construction "
            "(comm.max_staleness)")
    stale = np.where(w > 0, int(staleness), 0).astype(np.int32)
    return ops + (jnp.asarray(stale),)


def _pod_mix_fn(strategy: str, mesh, n_pods: int, p_specs,
                snap_specs=None, n_slots: int = 1) -> Callable:
    """Build the shard_map'd gossip exchange over the mesh ``pod`` axis.

    Mirrors the Pallas ``neighbor_mix`` arithmetic (self-weight term +
    padded-neighbor gather, f32 accumulate, cast back to the leaf dtype)
    re-expressed for SPMD: the pod axis is manual and every other mesh
    axis keeps the train state's own sharding (``in_specs`` are the
    leaves' actual PartitionSpecs, so the exchange inserts no reshard),
    and the neighbor gather becomes ``n_pods - 1`` static ppermute
    shifts.  dpsgd rotates the params one hop at a time: at shift ``r``
    pod ``k`` holds pod ``(k - r) % n_pods``'s payload and scales it by
    a coefficient scattered at *runtime* from the padded ``(K, D)``
    neighbor operands.  adpsgd instead contracts at the *source*: each
    pod collapses its ``(S+1)``-slot snapshot stack down to one
    already-weighted model per destination (via a ``(K, K, S+1)``
    runtime coefficient scatter keyed by the per-read staleness operand)
    and ships it with a direct distance-``r`` permute — same cross-pod
    bytes as dpsgd, instead of ``(S+1)x`` for rotating the whole buffer.
    Either way a rotating schedule (or a staleness move) changes operand
    values only, never shapes, and the exchange lowers to
    collective-permutes on the pod axis alone
    (``hlo_analysis.pod_exchange_report`` verifies).
    """
    perm = [(j, (j + 1) % n_pods) for j in range(n_pods)]
    op_specs = (P("pod", None), P("pod", None), P("pod"))

    if strategy == "dpsgd":
        def body(p, nbr_idx, nbr_w, self_w):
            k = jax.lax.axis_index("pod")
            # this pod's mixing-matrix row, from its (1, D) operand slice
            wvec = jnp.zeros((n_pods,), jnp.float32
                             ).at[nbr_idx[0]].add(nbr_w[0])

            def mix_leaf(x):
                y = self_w[0] * x.astype(jnp.float32)
                xr = x
                for r in range(1, n_pods):
                    xr = jax.lax.ppermute(xr, "pod", perm)
                    y = y + wvec[(k - r) % n_pods] * xr.astype(jnp.float32)
                return y.astype(x.dtype)
            return tmap(mix_leaf, p)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(p_specs,) + op_specs,
                             out_specs=p_specs, check_vma=False)

    def body(p, snaps, nbr_idx, nbr_w, self_w, stale):
        k = jax.lax.axis_index("pod")
        # structural staleness bound, as in the simulation ("a read
        # deeper than the buffer cannot be expressed"): a slot past the
        # compiled buffer reads the *oldest* snapshot instead of
        # scattering out of bounds, where jax would silently drop the
        # neighbor weight (gossip_operands refuses declared-bound
        # violations; this guards a bound that lied)
        stale = jnp.clip(stale, 0, n_slots - 1)
        # full (K, K, S+1) coefficient tensor from the *replicated*
        # operands: a source must know each destination's weight and
        # staleness slot for reads of itself, so it can contract its own
        # snapshot stack down to ONE model before shipping — rotating
        # the whole (S+1)-slot buffer around the ring instead would ship
        # (S+1)x the cross-pod bytes actually consumed
        rows = jnp.arange(n_pods)[:, None]
        coeff = jnp.zeros((n_pods, n_pods, n_slots), jnp.float32
                          ).at[rows, nbr_idx, stale].add(nbr_w)

        def mix_leaf(x, sn):
            y = self_w[0] * x.astype(jnp.float32)
            sn32 = sn.astype(jnp.float32)        # (n_slots, 1, ...) local
            for r in range(1, n_pods):
                dest = (k + r) % n_pods
                # already weighted by the destination's coefficients for
                # reads of this pod, so the receiver only adds; shipped
                # in the leaf dtype so the wire bytes equal dpsgd's
                # (for bf16 models that rounds each weighted term, the
                # standard price of bf16 comms; exact for f32)
                payload = jnp.tensordot(coeff[dest, k], sn32, axes=1
                                        ).astype(x.dtype)
                y = y + jax.lax.ppermute(
                    payload, "pod",
                    [(j, (j + r) % n_pods) for j in range(n_pods)]
                ).astype(jnp.float32)
            return y.astype(x.dtype)
        return tmap(mix_leaf, p, snaps)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(p_specs, snap_specs,
                                   P(None, None), P(None, None), P("pod"),
                                   P(None, None)),
                         out_specs=p_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Histogram-quantile threshold (pure jnp twin of kernels/dgc_topk)
# ---------------------------------------------------------------------------

def hist_threshold(v: jnp.ndarray, sparsity: jnp.ndarray,
                   n_bins: int = 256) -> jnp.ndarray:
    a = jnp.abs(v.reshape(-1)).astype(jnp.float32)
    vmax = jnp.maximum(jnp.max(a), 1e-30)
    idx = jnp.clip((a / vmax * n_bins).astype(jnp.int32), 0, n_bins - 1)
    hist = jnp.zeros((n_bins,), jnp.int32).at[idx].add(1)
    cum = jnp.cumsum(hist).astype(jnp.float32)
    target = sparsity * a.shape[0]
    bin_idx = jnp.clip(jnp.searchsorted(cum, target), 0, n_bins - 1)
    return (bin_idx.astype(jnp.float32) + 1.0) / n_bins * vmax


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, comm: CommConfig, *,
                    mesh=None, lr: float = 1e-3,
                    lr0: Optional[float] = None,
                    momentum: float = 0.9, weight_decay: float = 0.0,
                    remat: bool = True, chunk: int = 512) -> Callable:
    """Returns ``train_step(state, batch, step_idx, mix=None, lr=None,
    sparsity=None) -> (state, metrics)``.  ``batch`` leaves are
    (n_pods, b, ...).

    Runtime operands (all optional, so existing 3-argument call sites
    keep working):
      mix       gossip neighbor operands from :func:`gossip_operands` —
                required for dpsgd/adpsgd, which also require ``mesh``
                (a mesh with a ``pod`` axis) at construction
      lr        traced learning-rate override of the static ``lr`` —
                lets one compilation serve a schedule, and drives Gaia's
                threshold decay T = t0 * lr / lr0 (``lr0`` defaults to
                the static ``lr``, matching the core trainer's
                always-decaying wiring; at the static lr the threshold
                is exactly t0)
      sparsity  traced DGC sparsity (the warm-up schedule / a controller)
                overriding ``comm.dgc_sparsity``
    """

    def pod_loss(params, batch):
        loss, parts = loss_fn(params, cfg, batch, remat=remat, chunk=chunk)
        return loss

    grad_fn = jax.value_and_grad(pod_loss)

    lr_static = lr
    mix_fn = None
    model_floats = None
    if comm.strategy in GOSSIP_STRATEGIES:
        if mesh is None or "pod" not in mesh.axis_names:
            raise ValueError(
                f"strategy {comm.strategy!r} gossips over the mesh 'pod' "
                "axis: pass make_train_step(..., mesh=) with a pod axis "
                "(make_production_mesh(multi_pod=True))")
        # in_specs for the manual exchange come from the same sharding
        # rules the callers use for the state, so the shard_map boundary
        # introduces no reshard
        from repro.launch.sharding import train_state_shardings
        n_pods = mesh.shape["pod"]
        state_shape = train_state_shape(cfg, comm, n_pods)
        state_sh = train_state_shardings(state_shape, mesh)
        p_specs = tmap(lambda ns: ns.spec, state_sh["params"])
        snap_specs = (tmap(lambda ns: ns.spec, state_sh["snaps"])
                      if comm.strategy == "adpsgd" else None)
        mix_fn = _pod_mix_fn(comm.strategy, mesh, n_pods, p_specs,
                             snap_specs=snap_specs,
                             n_slots=comm.max_staleness + 1)
        model_floats = float(sum(
            l.size for l in
            jax.tree_util.tree_leaves(state_shape["params"]))) / n_pods

    def local_sgd(params, grads, vel, lr_t):
        """Per-pod momentum step.  Returns (params, vel)."""
        def upd(w, g, u):
            g32 = g.astype(jnp.float32) + weight_decay * w.astype(jnp.float32)
            return momentum * u - lr_t * g32
        vel = tmap(upd, params, grads, vel)
        params = tmap(lambda w, u: (w.astype(jnp.float32) + u
                                    ).astype(w.dtype), params, vel)
        return params, vel

    def train_step(state, batch, step_idx, mix=None, lr=None,
                   sparsity=None):
        lr_t = lr_static if lr is None else lr
        losses, grads = jax.vmap(grad_fn)(state["params"], batch)
        metrics = {"loss": jnp.mean(losses)}

        if comm.strategy in GOSSIP_STRATEGIES:
            if mix is None:
                raise ValueError(
                    f"{comm.strategy} needs the round's "
                    "gossip_operands(...) as the mix argument")
            want = 4 if comm.strategy == "adpsgd" else 3
            if len(mix) != want:
                raise ValueError(
                    f"{comm.strategy} takes {want} mix operands, got "
                    f"{len(mix)} — build them with gossip_operands("
                    + ("..., staleness=, max_staleness=) so the "
                       "per-read staleness slots are included"
                       if comm.strategy == "adpsgd" else
                       "...) without staleness (dpsgd reads are fresh)"))
            # a schedule over the wrong node count would silently
            # mis-split over the pod axis (and scatter out of bounds)
            if mix[0].shape[0] != n_pods:
                raise ValueError(
                    f"gossip operands are for {mix[0].shape[0]} nodes "
                    f"but the mesh has {n_pods} pods — build the "
                    "schedule over the pod count")
            params, vel = local_sgd(state["params"], grads, state["vel"],
                                    lr_t)
            nbr_w = mix[1]
            # per-pod *algorithmic* price: one model per active neighbor
            # (padding entries carry weight 0) — the same currency the
            # simulation ledger books, NOT the wire bytes: the static
            # ring ships n_pods-1 permutes per round regardless of the
            # round's degree, and dryrun's pod_exchange reports those
            # physical bytes from the HLO
            mean_degree = (jnp.sum(nbr_w > 0).astype(jnp.float32)
                           / nbr_w.shape[0])
            metrics["mean_degree"] = mean_degree
            metrics["comm_floats"] = mean_degree * model_floats
            if comm.strategy == "dpsgd":
                nbr_idx, nbr_w_, self_w = mix
                params = mix_fn(params, nbr_idx, nbr_w_, self_w)
                return {"params": params, "vel": vel}, metrics
            nbr_idx, nbr_w_, self_w, stale = mix
            # push this round's post-gradient stack into slot 0; slot s
            # now holds the stack from s rounds ago (pre-mix, like the
            # simulation's snapshot buffer)
            snaps = tmap(lambda s, x: jnp.concatenate(
                [x[None].astype(s.dtype), s[:-1]], axis=0),
                state["snaps"], params)
            params = mix_fn(params, snaps, nbr_idx, nbr_w_, self_w, stale)
            nbr_mask = (nbr_w_ > 0).astype(jnp.float32)
            reads = jnp.maximum(jnp.sum(nbr_mask), 1.0)
            metrics["mean_staleness"] = jnp.sum(stale * nbr_mask) / reads
            return {"params": params, "vel": vel, "snaps": snaps}, metrics

        if comm.strategy == "bsp":
            g = tmap(lambda x: jnp.mean(x, axis=0, keepdims=True), grads)
            g = tmap(lambda x, p: jnp.broadcast_to(x, p.shape), g,
                     state["params"])
            params, vel = local_sgd(state["params"], g, state["vel"], lr_t)
            return {"params": params, "vel": vel}, metrics

        if comm.strategy == "fedavg":
            params, vel = local_sgd(state["params"], grads, state["vel"],
                                    lr_t)
            il = comm.iter_local
            do_sync = (step_idx % il) == (il - 1)

            def sync(p):
                return tmap(lambda l: jnp.broadcast_to(
                    jnp.mean(l, axis=0, keepdims=True), l.shape), p)
            params = jax.lax.cond(do_sync, sync, lambda p: p, params)
            return {"params": params, "vel": vel}, metrics

        if comm.strategy == "gaia":
            params, vel = local_sgd(state["params"], grads, state["vel"],
                                    lr_t)
            acc = tmap(lambda v, u: v + u, state["acc"], vel)
            # threshold decays with the learning rate (Algorithm 1 line
            # 16), matching core/algorithms/gaia.py; the reference lr
            # defaults to the static lr, so a runtime lr schedule decays
            # T at every call site without opt-in
            thresh = comm.gaia_t0 * (
                lr_t / (lr_static if lr0 is None else lr0))

            def exchange(w, v):
                mask = (jnp.abs(v) > thresh * jnp.abs(w.astype(jnp.float32))
                        ).astype(v.dtype)
                sel = v * mask
                total = jnp.sum(sel, axis=0, keepdims=True)   # cross-pod
                w_new = (w.astype(jnp.float32) + (total - sel)
                         ).astype(w.dtype)
                return w_new, v * (1 - mask)
            pairs = tmap(exchange, params, acc)
            params = tmap(lambda pr: pr[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
            acc = tmap(lambda pr: pr[1], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
            return {"params": params, "vel": vel, "acc": acc}, metrics

        if comm.strategy == "dgc":
            # per-pod global-norm gradient clip (Algorithm 3 line 2)
            sq = sum(jnp.sum(l.astype(jnp.float32) ** 2,
                             axis=tuple(range(1, l.ndim)))
                     for l in jax.tree_util.tree_leaves(grads))
            scale = jnp.minimum(
                1.0, comm.dgc_clip / jnp.maximum(jnp.sqrt(sq), 1e-12))
            grads_c = tmap(lambda l: l * scale.reshape(
                (-1,) + (1,) * (l.ndim - 1)).astype(l.dtype), grads)
            # g = -lr * (clipped grad + wd * w); momentum correction
            g = tmap(lambda x, w: -lr_t * (x.astype(jnp.float32)
                                           + weight_decay
                                           * w.astype(jnp.float32)),
                     grads_c, state["params"])
            vel = tmap(lambda u, gl: momentum * u + gl, state["vel"], g)
            acc = tmap(lambda v, u: v + u, state["acc"], vel)
            # runtime sparsity operand: the warm-up schedule (and any
            # controller) retunes without recompiling, like the
            # simulation DGC
            s = comm.dgc_sparsity if sparsity is None else sparsity

            def exchange(w, v, u):
                t = jax.vmap(lambda vv: hist_threshold(vv, s))(v)  # per pod
                t = t.reshape((-1,) + (1,) * (v.ndim - 1))
                mask = (jnp.abs(v) > t).astype(v.dtype)
                sel = v * mask
                total = jnp.sum(sel, axis=0)                  # cross-pod
                w_new = (w.astype(jnp.float32) + total[None]
                         ).astype(w.dtype)
                return w_new, v * (1 - mask), u * (1 - mask)
            triples = tmap(exchange, state["params"], acc, vel)
            params = tmap(lambda tr: tr[0], triples,
                          is_leaf=lambda x: isinstance(x, tuple))
            acc = tmap(lambda tr: tr[1], triples,
                       is_leaf=lambda x: isinstance(x, tuple))
            vel = tmap(lambda tr: tr[2], triples,
                       is_leaf=lambda x: isinstance(x, tuple))
            return {"params": params, "vel": vel, "acc": acc}, metrics

        raise ValueError(comm.strategy)

    return train_step


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, *, chunk: int = 512) -> Callable:
    """Prefill step.  Audited alongside the train graphs (jaxpr + HLO
    passes): donation is optional for serve-side graphs, host callbacks
    and off-pod-axis collectives are not."""
    def prefill_step(params, batch):
        logits, _ = forward(params, cfg, batch, remat=False, chunk=chunk)
        return logits[:, -1]                       # next-token logits
    return prefill_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, batch):
        logits, new_cache = decode_step(params, cfg, batch, cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache
    return serve_step
