"""``bench/round_split.py`` on the CPU at a tiny size: the stretches, the
split's arithmetic, and the compiled program's scopes."""
import collections

import numpy as np
import pytest

import round_split
from benchlib import layers, traffic as tgen
from cpu_run import small_cell

RECORDED = 6


@pytest.fixture(scope="module")
def split():
    cell = small_cell("bn-lenet.gaia.k5")
    return round_split.split(cell, 2 ** 40 + 11, seconds=0.5,
                             recorded=RECORDED, traced=3)


def test_recorded_stretch_holds_whole_rounds_of_every_span(split):
    assert split["rounds"]["recorded"] == RECORDED
    assert set(split["self_ms"]) == {
        "trainer.round", "trainer.load", "trainer.put", "trainer.dispatch",
        "trainer.wait", "trainer.sync", "trainer.ledger"}
    assert all(v > 0 for v in split["self_ms"].values())
    # Gaia: x, y, lr, t0 and the round index; loss and comm floats
    assert split["counts"]["h2d_puts"] == 5
    assert split["counts"]["d2h_syncs"] == 2
    assert split["counts"]["h2d_bytes"] == 5 * 20 * 32 * 32 * 3 * 4 + \
        5 * 20 * 4 + 3 * 4


def test_split_checks_add_up(split):
    c = split["checks"]
    assert 0.9 < c["covered_median"] <= 1.0
    # host time beside the step: the spans outside it and what the round
    # leaves uncovered
    host = sum(split["self_ms"][k] for k in round_split.HOST) + \
        split["self_ms"]["trainer.round"]
    assert c["host_ms"] == pytest.approx(host)
    assert c["dispatch_wait_ms"] == pytest.approx(
        split["self_ms"]["trainer.dispatch"] +
        split["self_ms"]["trainer.wait"])
    for k in ("plain_host_ms", "plain_step_ms", "host_ms_median",
              "plain_host_ms_median"):
        assert c[k] > 0
    assert set(split["round_ms_median"]) == {
        "plain", "recorded", "traced_annotated", "traced"}
    assert split["stalls"] == [] or all(
        s["held_by"] in split["self_ms"] for s in split["stalls"])
    assert "device_ms" not in split          # no trace was asked for


def test_exchange_kinds_beside_their_hbm_floor():
    kinds = {"kernel": {"n": 64, "seconds": 8e-6, "read": 2000,
                        "written": 1000},
             "other": {"n": 20, "seconds": 6e-5, "read": 4000,
                       "written": 3000}}
    got = round_split.with_floor(kinds, traced=2, bw=1e9)
    # the kernels' floor counts what they read and write; the other ops'
    # what they write
    assert got == {"kernel": pytest.approx({"events": 32, "ms": 4e-3,
                                            "floor_ms": 1.5e-3}),
                   "other": pytest.approx({"events": 10, "ms": 3e-2,
                                           "floor_ms": 1.5e-3})}


def test_compiled_step_scopes_cover_most_instructions():
    cell = small_cell("bn-lenet.gaia.k5")
    drv = cell.driver()
    parts, val = tgen.image_task(cell.traffic, cell.config, 5)
    from repro.core import trainer
    with round_split.jitted_calls() as box, drv.precision(cell.config):
        trainer.train_decentralized(
            drv._cnn_config(cell.config), "gaia", parts,
            (val[0][:64], val[1][:64]), steps=2, eval_every=2,
            **drv._train_kw(cell.traffic, 5))
        texts = round_split.step_texts(box)
    assert set(box["calls"]) == {"step"}
    module, paths = layers.hlo_scopes(texts[0])
    assert module == "jit_step"
    got = collections.Counter(layers.scope_of(p) for p in paths.values())
    assert got["local_step"] > 0 and got["exchange"] > 0
    assert np.sum([got[k] for k in layers.SCOPES]) > got[None]
    # every instruction of the step is sized, and those under the exchange
    # move bytes
    sizes = layers.hlo_bytes(texts[0])
    assert set(paths) <= set(sizes)
    assert sum(sum(sizes[k]) for k, p in paths.items()
               if layers.scope_of(p) == "exchange") > 0
