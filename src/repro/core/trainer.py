"""Decentralized training driver (simulation backend, CPU-scale).

This is the harness behind every paper experiment: pick a CNN, a
partitioning, an algorithm + θ, (optionally) SkewScout — train, track
communication, and report validation accuracy of the global model.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import CommConfig
from repro.configs.cnn_zoo import CNNConfig
from repro.core.algorithms.adpsgd import ADPSGD
from repro.core.algorithms.base import ModelFns, tree_size
from repro.core.algorithms.bsp import BSP
from repro.core.algorithms.dgc import DGC, warmup_sparsity
from repro.core.algorithms.dpsgd import DPSGD
from repro.core.algorithms.fedavg import FedAvg
from repro.core.algorithms.gaia import Gaia
from repro.core.skewscout import SkewScout
from repro.data.pipeline import DecentralizedLoader
from repro.models.cnn import cnn_apply, init_cnn
from repro.topology import (LABEL_AWARE_TOPOLOGIES, LINK_PROFILES,
                            CommLedger, Participation, Topology,
                            TopologySchedule, as_schedule, build_schedule,
                            make_link_model, topology_ladder)


# ---------------------------------------------------------------------------
# CNN adapter
# ---------------------------------------------------------------------------

def make_cnn_fns(cfg: CNNConfig) -> Tuple[ModelFns, Callable]:
    def loss_fn(params, mstate, batch):
        logits, new_ms = cnn_apply(params, mstate, cfg, batch["x"],
                                   train=True)
        labels = batch["y"]
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        return nll, new_ms

    def loss_and_grad(params, mstate, batch):
        (loss, new_ms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mstate, batch)
        return loss, grads, new_ms

    @jax.jit
    def eval_acc(params, mstate, x, y):
        logits, _ = cnn_apply(params, mstate, cfg, x, train=False)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    def eval_acc_np(params, mstate, x, y, batch: int = 512):
        accs, ns = [], []
        for i in range(0, len(x), batch):
            xb = jnp.asarray(x[i:i + batch])
            yb = jnp.asarray(y[i:i + batch])
            accs.append(float(eval_acc(params, mstate, xb, yb)))
            ns.append(len(xb))
        return float(np.average(accs, weights=ns))

    return ModelFns(loss_and_grad=loss_and_grad), eval_acc_np


#: gossip-averaging strategies that run over a TopologySchedule fabric
GOSSIP_ALGOS = ("dpsgd", "adpsgd")


def make_algorithm(name: str, fns: ModelFns, n_nodes: int,
                   comm: CommConfig, *, momentum: float = 0.9,
                   weight_decay: float = 5e-4, lr0: Optional[float] = None,
                   topology: Optional[Topology | TopologySchedule] = None,
                   seed: int = 0, pad_degree: Optional[int] = None,
                   staleness: Optional[int] = None,
                   participation: Optional[Participation] = None):
    if name == "bsp":
        return BSP(fns, n_nodes, momentum=momentum, weight_decay=weight_decay)
    if name == "gaia":
        return Gaia(fns, n_nodes, momentum=momentum,
                    weight_decay=weight_decay, t0=comm.gaia_t0, lr0=lr0)
    if name == "fedavg":
        return FedAvg(fns, n_nodes, momentum=momentum,
                      weight_decay=weight_decay, iter_local=comm.iter_local)
    if name == "dgc":
        return DGC(fns, n_nodes, momentum=momentum,
                   weight_decay=weight_decay, clip=comm.dgc_clip,
                   sparsity=comm.dgc_sparsity,
                   compressor=getattr(comm, "dgc_compressor", "topk"),
                   seed=seed)
    if name in GOSSIP_ALGOS:
        if topology is None:
            # standalone fallback; label-aware topologies need the label
            # histograms only train_decentralized can supply — refuse to
            # silently build a label-blind graph in their place
            if comm.fabric.topology in LABEL_AWARE_TOPOLOGIES:
                raise ValueError(
                    f"comm.fabric.topology={comm.fabric.topology!r} is "
                    "label-aware: it needs per-node label histograms to "
                    "assemble cliques. Build it with build_schedule(..., "
                    "label_hist=...) and pass topology= explicitly "
                    "(train_decentralized does this from the partitions)")
            topology = build_schedule(comm.fabric.topology, n_nodes,
                                      seed=seed)
        if name == "adpsgd":
            return ADPSGD(fns, n_nodes, topology=topology,
                          momentum=momentum, weight_decay=weight_decay,
                          pad_degree=pad_degree,
                          max_staleness=comm.max_staleness,
                          staleness=staleness,
                          participation=participation)
        return DPSGD(fns, n_nodes, topology=topology, momentum=momentum,
                     weight_decay=weight_decay, pad_degree=pad_degree,
                     participation=participation)
    raise ValueError(name)


@dataclass
class RunResult:
    name: str
    val_acc: float
    val_acc_curve: List[Tuple[int, float]]
    loss_curve: List[Tuple[int, float]]
    comm_total_floats: float
    bsp_equiv_floats: float
    comm_savings: float
    skewscout_history: List = field(default_factory=list)
    extras: Dict[str, Any] = field(default_factory=dict)
    # link-level accounting (repro.topology.CommLedger)
    topology: str = "full"
    comm_lan_floats: float = 0.0
    comm_wan_floats: float = 0.0
    sim_time_s: float = 0.0


def train_decentralized(cnn_cfg: CNNConfig, algo_name: str,
                        parts: Sequence[Tuple[np.ndarray, np.ndarray]],
                        val: Tuple[np.ndarray, np.ndarray], *,
                        comm: CommConfig = CommConfig(),
                        steps: int = 400, batch: int = 20,
                        lr_schedule: Callable = None, lr: float = 0.05,
                        momentum: float = 0.9, weight_decay: float = 5e-4,
                        eval_every: int = 100, seed: int = 0,
                        theta_start_index: Optional[int] = None
                        ) -> RunResult:
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every} "
                         "(with steps < eval_every the final step still "
                         "evaluates, but eval_every itself must be valid)")
    K = len(parts)
    fns, eval_acc = make_cnn_fns(cnn_cfg)
    params, mstate = init_cnn(jax.random.PRNGKey(seed), cnn_cfg)

    # communication fabric: per-round graph schedule + link-level cost.
    # Label histograms feed the label-aware builders — needed for a
    # dcliques-family topology, and for the SkewScout topology ladder
    # (whatever fabric the run starts on, the controller must be able to
    # climb to the label-aware rung)
    label_hist = None
    if comm.fabric.topology in LABEL_AWARE_TOPOLOGIES or \
            (comm.skewscout and algo_name == "dpsgd"):
        n_classes = int(max(int(y.max()) for _, y in parts)) + 1
        label_hist = np.stack([np.bincount(np.asarray(y, np.int64),
                                           minlength=n_classes)
                               for _, y in parts])
    sched = build_schedule(comm.fabric.topology, K, label_hist=label_hist,
                           seed=seed)

    # topology as a SkewScout rung (dpsgd): the theta ladder is a list
    # of schedules ordered densest first; training starts on the rung
    # matching the configured topology when there is one, and the
    # neighbor operands are padded to the ladder-wide max degree so rung
    # switches never retrace the step
    ladder = None
    pad_degree = None
    staleness = None
    start_index = theta_start_index
    if comm.skewscout and algo_name == "dpsgd":
        ladder = topology_ladder(K, label_hist=label_hist, seed=seed)
        # the configured fabric is always a rung: replace the same-named
        # rung with the exact built schedule, or insert it, then re-sort
        # densest-first (hill climbing needs the ladder monotone in cost)
        names = [s.name for s in ladder]
        if sched.name in names:
            ladder[names.index(sched.name)] = sched
        else:
            ladder.append(sched)
        ladder.sort(key=TopologySchedule.mean_round_edges, reverse=True)
        if start_index is None:
            start_index = ladder.index(sched)
        elif not 0 <= start_index < len(ladder):
            raise ValueError(
                f"theta_start_index={start_index} out of range for the "
                f"{len(ladder)}-rung topology ladder "
                f"({[s.name for s in ladder]})")
        sched = ladder[start_index]
        pad_degree = max(s.max_degree for s in ladder)
    elif comm.skewscout and algo_name == "adpsgd":
        # staleness as a SkewScout rung (adpsgd): most synchronous rung
        # first (staleness 0 pays full per-round latency -> the costly
        # end of the ladder under the async time-priced C(theta)).
        # A sync ledger ignores staleness, so every rung would have the
        # same C(theta) and the controller would drift on noise —
        # refuse instead of silently mis-steering
        if not comm.async_gossip:
            raise ValueError(
                "skewscout over the adpsgd staleness ladder needs "
                "async_gossip=True: a synchronous ledger prices every "
                "staleness rung identically (C(theta) is float-based), "
                "so the controller's cost term would be degenerate")
        ladder = list(range(comm.max_staleness + 1))
        if start_index is None:
            start_index = len(ladder) - 1     # start fully asynchronous
        elif not 0 <= start_index < len(ladder):
            raise ValueError(
                f"theta_start_index={start_index} out of range for the "
                f"{len(ladder)}-rung staleness ladder ({ladder})")
        staleness = ladder[start_index]

    # stochastic links: one seeded LinkModel for the run.  Its draws are
    # keyed streams of (seed, edge, activation) — the link seed cannot
    # perturb the clique assignment or anything else the run seed feeds
    profile = LINK_PROFILES[comm.fabric.profile]
    links = make_link_model(comm.fabric.link, profile, seed=seed)
    # partial participation: one seeded per-round node sampler shared by
    # the ledger (masked pricing), the gossip mixing operands, and the
    # SkewScout probes — tag-disjoint from the link streams, so toggling
    # participation never perturbs a link draw
    part = (Participation(K, comm.fabric.participation, seed=seed)
            if comm.fabric.participation < 1.0 else None)
    ledger = CommLedger(sched, profile, config=comm.fabric,
                        async_mode=comm.async_gossip,
                        link_model=links,
                        participation=part)

    algo = make_algorithm(algo_name, fns, K, comm, momentum=momentum,
                          weight_decay=weight_decay, lr0=lr, topology=sched,
                          seed=seed, pad_degree=pad_degree,
                          staleness=staleness, participation=part)
    state = algo.init(params, mstate)
    loader = DecentralizedLoader(parts, batch, seed=seed)
    lr_fn = lr_schedule or (lambda s: lr)

    def _cm_pin(fabric) -> float:
        # CM pinned to one full-model exchange on the given fabric, in
        # the unit the scout prices C(theta) with: wall-clock for an
        # async ledger, bandwidth-seconds for a sync one
        led = CommLedger(fabric, profile).view()
        m = float(tree_size(params))
        return led.full_exchange_time(m) if comm.async_gossip \
            else led.full_exchange_cost(m)

    scout = None
    if comm.skewscout and algo_name == "dpsgd":
        # densest rung pins the denominator so C(theta)/CM stays
        # comparable as the controller changes fabrics.  Under a link
        # model the constants are a fiction: pin the *fabric* instead
        # and let the scout re-price CM from the ledger's per-edge EWMA
        # measured costs at every probe
        cm = (dict(cm_fabric=ladder[0]) if links is not None
              else dict(cm_ref=_cm_pin(ladder[0])))
        scout = SkewScout(comm, algo_name, tree_size(params), eval_acc,
                          start_index=start_index, seed=seed,
                          ledger=ledger, ladder=ladder,
                          participation=part, **cm)
    elif comm.skewscout and algo_name == "adpsgd":
        cm = (dict(cm_fabric=sched) if links is not None
              else dict(cm_ref=_cm_pin(sched)))
        scout = SkewScout(comm, algo_name, tree_size(params), eval_acc,
                          start_index=start_index, seed=seed,
                          ledger=ledger, ladder=ladder,
                          participation=part, **cm)
    elif comm.skewscout and algo_name != "bsp":
        scout = SkewScout(comm, algo_name, tree_size(params), eval_acc,
                          start_index=theta_start_index, seed=seed,
                          ledger=ledger, participation=part)

    loss_curve, acc_curve, gap_curve, stale_curve = [], [], [], []
    # host time of each round's ``trainer.dispatch`` plus its
    # ``trainer.wait``, not one wall interval: a pipelined round's
    # interval also holds the next round's host work.  The wait in round
    # t is for the state of the round it finishes (t - 1 when pipelined;
    # the last round also waits for its own).  Round 0's dispatch
    # includes tracing and compiling the step
    step_s: List[float] = []
    comm_total = 0.0
    steps_per_epoch = loader.steps_per_epoch
    # One round of software pipelining: round t is dispatched before
    # round t - 1's scalars are read, so the host's loading, puts and
    # dispatch overlap the chip's previous step.  Only a SkewScout
    # controller feeds a round's results into the next round's inputs
    # (theta, the topology or staleness rung), so a run with one keeps
    # lock-step
    lag = 1 if scout is None else 0
    scalars = ("comm_floats", "loss") + \
        (("mean_staleness",) if algo_name == "adpsgd" else ())
    pending: List[Tuple[int, Any, Dict]] = []
    overlapped = 0

    def finish(u: int, st, metrics) -> None:
        """Round ``u``'s host side once its step is dispatched: wait for
        its state, read its scalars, price it, steer and evaluate."""
        nonlocal comm_total
        t_wait = time.perf_counter()
        with obs.span("trainer.wait"):
            jax.block_until_ready(st)
        step_s[-1] += time.perf_counter() - t_wait
        with obs.span("trainer.sync"):
            cf = float(metrics["comm_floats"])
            loss = float(metrics["loss"])
            if algo_name == "adpsgd":
                stale_curve.append((u, float(metrics["mean_staleness"])))
        if obs.active():
            obs.count("d2h_syncs", len(scalars))
        comm_total += cf
        with obs.span("trainer.ledger"):
            if algo_name in GOSSIP_ALGOS:
                # round u's active edge set prices this gossip exchange;
                # an async algorithm also reports its per-edge staleness
                # bound so the ledger can amortize link latency
                # accordingly
                stale = algo.edge_staleness(u) \
                    if algo_name == "adpsgd" else None
                ledger.record_gossip(float(tree_size(params)), t=u,
                                     staleness=stale)
                gap_curve.append(
                    (u, float(algo.schedule.round_spectral_gap(u))))
            elif cf > 0:
                ledger.record_exchange(cf)
        if scout:
            with obs.span("trainer.scout"):
                scout.record_step(cf)
                rep = scout.maybe_travel(
                    u, algo, st,
                    lambda node, _t=u: loader.sample_train_subset(
                        node, 256, seed=_t))
            if rep is not None:
                # model traveling overhead: the scout booked each
                # probe's shipment on the edge it crossed
                comm_total += rep.probe_floats
                if algo_name == "dpsgd" and rep.new_theta is not rep.theta:
                    # topology rung switch: re-wiring is charged by the
                    # ledger on the next gossip round, inside the new
                    # rung's C(θ) window
                    algo.set_schedule(rep.new_theta)
                    ledger.switch_schedule(rep.new_theta)
                elif algo_name == "adpsgd" and rep.new_theta != rep.theta:
                    # staleness rung switch: same fabric, new bound —
                    # runtime operand values only, no re-wiring
                    algo.set_staleness(rep.new_theta)
        if (u + 1) % eval_every == 0 or u == steps - 1:
            with obs.span("trainer.eval"):
                p, s = algo.eval_params(st)
                acc = eval_acc(p, s, val[0], val[1])
            acc_curve.append((u + 1, acc))
        loss_curve.append((u, loss))

    for t in range(steps):
        obs.set_round(t)
        with obs.span("trainer.round"):
            with obs.span("trainer.load"):
                xs, ys = loader.next_stacked()
            with obs.span("trainer.put"):
                sbatch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
            lr = lr_fn(t)
            with obs.span("trainer.put"):
                # NumPy scalars of the step's dtypes (not weak-typed, so
                # the step compiles as for device scalars): the jitted
                # call transfers each with its arguments, or drops one
                # its step does not read, and no eager program runs
                lr_t = np.float32(lr)
                kw: Dict[str, Any] = {}
                if algo_name == "gaia":
                    kw["t0"] = np.float32(
                        scout.theta if scout else comm.gaia_t0)
                elif algo_name == "fedavg":
                    kw["iter_local"] = np.int32(
                        scout.theta if scout else comm.iter_local)
                elif algo_name == "dgc":
                    epoch = t // steps_per_epoch
                    s = (scout.theta if scout
                         else warmup_sparsity(epoch, comm.dgc_warmup_epochs))
                    kw["sparsity"] = np.float32(s)
            if obs.active():
                # this round's puts: x, y, lr_t, the strategy's scalar
                # and the round index (4 bytes each); all but the batch
                # are host scalars, which the step call itself transfers
                obs.count("h2d_puts", 4 + len(kw))
                obs.count("h2d_bytes", sbatch["x"].nbytes +
                          sbatch["y"].nbytes + 4 * (2 + len(kw)))
                obs.count("host_scalars", 2 + len(kw))
            if pending:
                # dispatched while the previous round's scalars are unread
                overlapped += 1
                if obs.active():
                    obs.count("rounds_ahead")
            t_step = time.perf_counter()
            with obs.span("trainer.dispatch"):
                state, metrics = algo.step(state, sbatch, lr_t,
                                           np.int32(t), **kw)
            step_s.append(time.perf_counter() - t_step)
            # the scalars' copies queue behind this step, ahead of the
            # next, and have landed by the time they are read
            for k in scalars:
                metrics[k].copy_to_host_async()
            pending.append((t, state, metrics))
            # the last round drains what is still in flight
            while len(pending) > (lag if t < steps - 1 else 0):
                finish(*pending.pop(0))

    if not acc_curve:
        raise RuntimeError(
            f"no evaluation happened in {steps} steps (eval_every="
            f"{eval_every}); acc_curve is empty — check the schedule")
    # Mosaic kernel calls in the step as lowered for this backend (0
    # where Pallas kernels run in interpret mode): the last step's own
    # operands, with its round index static as the gossip steps need
    mosaic_calls = jax.jit(algo.step, static_argnums=3).lower(
        state, sbatch, lr_t, t, **kw).as_text().count("tpu_custom_call")
    bsp_equiv = float(tree_size(params)) * steps
    # the fabric the run *ended* on (rung switches may have moved it)
    final_sched = as_schedule(algo.schedule) \
        if algo_name in GOSSIP_ALGOS else sched
    ledger_view = ledger.view()
    return RunResult(
        name=f"{cnn_cfg.name}/{algo_name}",
        val_acc=acc_curve[-1][1],
        val_acc_curve=acc_curve,
        loss_curve=loss_curve,
        comm_total_floats=comm_total,
        bsp_equiv_floats=bsp_equiv,
        comm_savings=bsp_equiv / max(comm_total, 1.0),
        skewscout_history=list(scout.history) if scout else [],
        extras={"ledger": ledger.summary(),
                "step_s": step_s,
                "rounds_overlapped": overlapped,
                "mosaic_calls": mosaic_calls,
                "spectral_gap": final_sched.spectral_gap(),
                "spectral_gap_curve": gap_curve,
                "schedule_period": final_sched.period,
                # per-node clock accounting (async: who ran ahead; sync:
                # who sat waiting on the slowest link)
                "node_clock_skew_s": ledger_view.clock_skew_s,
                "node_busy_s": [float(b) for b in ledger_view.node_busy_s],
                "node_idle_s": [float(i) for i in ledger_view.node_idle_s],
                # stochastic-link extras: straggler/jitter exposure of
                # the run (activations, slow fraction, knob values)
                **({"link_model": links.summary()}
                   if links is not None else {}),
                **({"staleness_curve": stale_curve,
                    "max_staleness": algo.max_staleness}
                   if algo_name == "adpsgd" else {}),
                **({"topology_ladder": [s.name for s in ladder]}
                   if ladder is not None and algo_name == "dpsgd" else {}),
                **({"staleness_ladder": list(ladder)}
                   if ladder is not None and algo_name == "adpsgd"
                   else {})},
        topology=final_sched.name,
        comm_lan_floats=ledger.lan_floats,
        comm_wan_floats=ledger.wan_floats,
        sim_time_s=ledger.sim_time_s,
    )
