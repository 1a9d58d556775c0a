"""The trainer's pipelined loop (round t dispatched before round t - 1's
scalars are read) against a plain lock-step loop written here: every
strategy's results bit for bit, and the rounds the loop ran ahead.  And
the scalars each round hands ``algo.step``: host NumPy scalars with the
avals of ``jnp.asarray``, so that no eager program converts them."""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import CommConfig
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core import partition_label_skew, train_decentralized, trainer
from repro.core.algorithms.base import tree_size
from repro.core.algorithms.dgc import warmup_sparsity
from repro.core.trainer import GOSSIP_ALGOS, make_algorithm, make_cnn_fns
from repro.data.pipeline import DecentralizedLoader
from repro.data.synthetic import synth_images
from repro.models.cnn import init_cnn
from repro.topology import (LINK_PROFILES, CommLedger, build_schedule,
                            make_link_model)

CFG = CNN_ZOO["bn-lenet"]
STEPS, BATCH, EVAL_EVERY, LR, SEED = 6, 4, 4, 0.05, 3
#: FedAvg averages and DGC's warm-up moves within the run's few rounds
COMM = CommConfig(iter_local=2, dgc_warmup_epochs=1)


@pytest.fixture(scope="module")
def task():
    ds = synth_images(240, seed=0)
    val = synth_images(48, seed=9)
    idx = partition_label_skew(ds.y, 5, 1.0, seed=1)
    return [(ds.x[i], ds.y[i]) for i in idx], (val.x, val.y)


def lock_step(algo_name, parts, val, comm):
    """The loop as it reads with no pipelining: loader, ``algo.step``,
    ``block_until_ready``, ``float``; then the ledger and the
    evaluation."""
    fns, eval_acc = make_cnn_fns(CFG)
    params, mstate = init_cnn(jax.random.PRNGKey(SEED), CFG)
    sched = build_schedule(comm.fabric.topology, len(parts), seed=SEED)
    profile = LINK_PROFILES[comm.fabric.profile]
    ledger = CommLedger(sched, profile, config=comm.fabric,
                        async_mode=comm.async_gossip,
                        link_model=make_link_model(comm.fabric.link, profile,
                                                   seed=SEED))
    algo = make_algorithm(algo_name, fns, len(parts), comm, lr0=LR,
                          topology=sched, seed=SEED)
    state = algo.init(params, mstate)
    loader = DecentralizedLoader(parts, BATCH, seed=SEED)
    losses, accs, stale, total = [], [], [], 0.0
    for t in range(STEPS):
        xs, ys = loader.next_stacked()
        kw = {}
        if algo_name == "gaia":
            kw["t0"] = jnp.asarray(comm.gaia_t0, jnp.float32)
        elif algo_name == "fedavg":
            kw["iter_local"] = jnp.asarray(comm.iter_local, jnp.int32)
        elif algo_name == "dgc":
            kw["sparsity"] = jnp.asarray(warmup_sparsity(
                t // loader.steps_per_epoch, comm.dgc_warmup_epochs),
                jnp.float32)
        state, m = algo.step(state, {"x": jnp.asarray(xs),
                                     "y": jnp.asarray(ys)},
                             jnp.asarray(LR, jnp.float32),
                             jnp.asarray(t, jnp.int32), **kw)
        jax.block_until_ready(state)
        cf = float(m["comm_floats"])
        losses.append((t, float(m["loss"])))
        total += cf
        if algo_name in GOSSIP_ALGOS:
            if algo_name == "adpsgd":
                stale.append((t, float(m["mean_staleness"])))
            ledger.record_gossip(
                float(tree_size(params)), t=t,
                staleness=algo.edge_staleness(t)
                if algo_name == "adpsgd" else None)
        elif cf > 0:
            ledger.record_exchange(cf)
        if (t + 1) % EVAL_EVERY == 0 or t == STEPS - 1:
            accs.append((t + 1, eval_acc(*algo.eval_params(state), *val)))
    return {"loss_curve": losses, "val_acc_curve": accs,
            "comm_total_floats": total, "ledger": ledger.summary(),
            "staleness_curve": stale}


CASES = [(a, False) for a in ("bsp", "gaia", "fedavg", "dgc", "dpsgd",
                              "adpsgd")] + [("gaia", True), ("dpsgd", True)]


@pytest.mark.parametrize("algo_name,scout", CASES,
                         ids=[a + ("-scout" if s else "") for a, s in CASES])
def test_pipelined_loop_matches_lock_step(task, algo_name, scout):
    parts, val = task
    # with a scout: Gaia's t0 ladder, D-PSGD's topology ladder, probed
    # every other round
    comm = dataclasses.replace(COMM, skewscout=True, travel_every=2) \
        if scout else COMM
    r = train_decentralized(CFG, algo_name, parts, val, comm=comm,
                            steps=STEPS, batch=BATCH, lr=LR,
                            eval_every=EVAL_EVERY, seed=SEED)
    if scout:
        # the scout reads each round's results before the next dispatch
        assert r.skewscout_history
        assert r.extras["rounds_overlapped"] == 0
        return
    assert r.extras["rounds_overlapped"] == STEPS - 1
    want = lock_step(algo_name, parts, val, comm)
    assert r.loss_curve == want["loss_curve"]
    assert r.val_acc_curve == want["val_acc_curve"]
    assert r.comm_total_floats == want["comm_total_floats"]
    assert r.extras["ledger"] == want["ledger"]
    assert r.extras.get("staleness_curve", []) == want["staleness_curve"]
    assert len(r.extras["step_s"]) == STEPS


#: the dtype that each scalar argument of ``algo.step`` has on the chip
SCALAR_DTYPES = {"lr": jnp.float32, "step_idx": jnp.int32,
                 "t0": jnp.float32, "iter_local": jnp.int32,
                 "sparsity": jnp.float32}
STRATEGY_SCALAR = {"bsp": (), "gaia": ("t0",), "fedavg": ("iter_local",),
                   "dgc": ("sparsity",), "dpsgd": (), "adpsgd": ()}


@pytest.mark.parametrize("algo_name", list(STRATEGY_SCALAR))
def test_round_scalars_reach_the_step_as_host_values(task, algo_name):
    parts, val = task
    calls, make = [], trainer.make_algorithm

    def tapped(*a, **k):
        algo = make(*a, **k)
        step = algo.step

        def call(state, batch, lr, step_idx, **kw):
            calls.append(dict(kw, lr=lr, step_idx=step_idx))
            # nothing the dispatch does may fetch from the device: the
            # gossip steps' int(step_idx) reads a host value.  (The CPU
            # backend lets any fetch through; a chip refuses it here)
            with jax.transfer_guard_device_to_host("disallow"):
                return step(state, batch, lr, step_idx, **kw)

        algo.step = call
        return algo

    with mock.patch.object(trainer, "make_algorithm", tapped), \
            obs.Recorder() as rec:
        train_decentralized(CFG, algo_name, parts, val, comm=COMM,
                            steps=STEPS, batch=BATCH, lr=LR,
                            eval_every=EVAL_EVERY, seed=SEED)
    # the rounds' calls, then the lowering that counts Mosaic calls
    assert len(calls) == STEPS + 1
    want = {"lr", "step_idx", *STRATEGY_SCALAR[algo_name]}
    for t, args in enumerate(calls[:STEPS]):
        assert set(args) == want
        assert args["step_idx"] == t and args["lr"] == np.float32(LR)
        for name, v in args.items():
            assert isinstance(v, np.generic), (name, type(v))
            assert jax.typeof(v) == jax.typeof(
                jnp.asarray(v, SCALAR_DTYPES[name])), name
            assert not jax.typeof(v).weak_type and np.shape(v) == ()
    assert rec.summary()["counts"]["host_scalars"] == [len(want)] * STEPS
