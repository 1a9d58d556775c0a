"""A configuration, a traffic mix, a cell's limits, a per-layer metric and
a strategy's reference dropped into their directories are found by name,
with no edit to the harness or to the files already there."""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from benchlib.registry import BENCH_DIR, ROOT, load_cell
from cpu_run import run_cell

#: FedAvg's exchange as a later benchmark would add it: every
#: ``iter_local``-th round the sites' models are averaged
FEDAVG_REFERENCE = '''
import jax.numpy as jnp

STACKED = {"params": True, "grad0": True}


def init_state(params, n_sites, comm):
    stack = lambda t: {k: jnp.broadcast_to(v, (n_sites,) + v.shape)
                       for k, v in t.items()}
    return {"params": stack(params),
            "vel": stack({k: jnp.zeros_like(v) for k, v in params.items()})}


def step(state, rnd, *, grads, sgd, comm, fault):
    losses, g = grads(state["params"], stacked=True)
    vel = {k: sgd(state["params"][k], g[k], state["vel"][k], rnd["lr"])
           for k in g}
    params = {k: state["params"][k] + vel[k] for k in g}
    il = rnd["kw"]["iter_local"]
    if fault != "no_exchange":
        sync = rnd["t"] % il == il - 1
        params = {k: jnp.where(sync, jnp.broadcast_to(
            jnp.mean(v, axis=0), v.shape), v) for k, v in params.items()}
    return {"params": params, "vel": vel}, jnp.mean(losses)


def grad0(vel, lr):
    return {k: -v / lr for k, v in vel.items()}
'''


def checkout(tmp_path):
    """A copy of the benchmark and ``BENCHMARK.json``; the bytes of every
    file in it, to show later that none was edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    b = root / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    return root, b, before


def add_cell(root, name, config, traffic, limits):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(name)
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "cells" / f"{name}.json").write_text(
        json.dumps({"limits": limits}))


def test_new_files_are_found_by_name(tmp_path):
    root, b, before = checkout(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())

    config = json.loads((b / "configs" / "bn-lenet.json").read_text())
    config.update(name="lenet-wide", conv_channels=[64, 64, 128])
    (b / "configs" / "lenet-wide.json").write_text(json.dumps(config))
    mix = json.loads((b / "traffic" / "bsp-label-skew-k5.json").read_text())
    mix["sites"] = 8
    (b / "traffic" / "bsp-label-skew-k8.json").write_text(json.dumps(mix))
    (b / "cells" / "lenet-wide.bsp.k8.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.5}}))
    (b / "metrics" / "rounds_seen.cnn.py").write_text(
        "def read(run):\n    return run.counters.get('rounds')\n")

    bench["configs"].append({"name": "lenet-wide", "source": "x",
                             "file": "bench/configs/lenet-wide.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "lenet-wide.bsp.k8",
                               "config": "lenet-wide",
                               "traffic": "bsp-label-skew-k8", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("lenet-wide.bsp.k8")
    bench["per_layer"].append({
        "name": "rounds_seen.cnn", "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "trainer loop (core/trainer.py)",
        "moves": "train_images_per_s", "workloads": ["lenet-wide.bsp.k8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("lenet-wide.bsp.k8", root=str(root), bench_dir=str(b))
    assert cell.config["conv_channels"] == [64, 64, 128]
    assert cell.traffic["sites"] == 8
    assert cell.limits["limits"] == {"loss_gap": 0.5}
    assert [m["name"] for m in cell.per_layer] == ["rounds_seen.cnn"]
    assert {m["name"] for m in cell.end_to_end} == {
        "train_images_per_s", "round_ms_p95", "setup_s"}
    reader = cell.reader("rounds_seen.cnn")
    assert reader.read(SimpleNamespace(counters={"rounds": 7})) == 7
    assert cell.driver().__name__ == "bench_driver_cnn_decentralized"
    # every file that was there is byte for byte what it was
    for p, data in before.items():
        assert p.read_bytes() == data


@pytest.mark.parametrize("reference", ["sound", "exchange_left_out"])
def test_new_strategy_is_new_files(monkeypatch, tmp_path, reference):
    """A FedAvg cell: a traffic mix whose ``comm`` the driver passes to the
    program as it stands, and FedAvg's reference in its own file, run at
    a tiny size.  It is correct against the sound reference, and not
    correct against one that leaves the averaging out."""
    root, b, before = checkout(tmp_path)
    src = FEDAVG_REFERENCE
    if reference == "exchange_left_out":
        src = src.replace('if fault != "no_exchange":', "if False:")
    (b / "reference" / "cnn_exchange" / "fedavg.py").write_text(src)
    mix = json.loads((b / "traffic" / "gaia-label-skew-k5.json").read_text())
    mix["comm"] = {"strategy": "fedavg", "iter_local": 2}
    (b / "traffic" / "fedavg2-label-skew-k5.json").write_text(json.dumps(mix))
    add_cell(root, "bn-lenet.fedavg2.k5", "bn-lenet",
             "fedavg2-label-skew-k5",
             json.loads((b / "cells" / "bn-lenet.gaia.k5.json").read_text())
             ["limits"])

    res = run_cell(monkeypatch, "bn-lenet.fedavg2.k5", root=str(root),
                   bench_dir=str(b))
    assert res["correct"] == (reference == "sound"), res["checks"]
    for p, data in before.items():
        assert p.read_bytes() == data
