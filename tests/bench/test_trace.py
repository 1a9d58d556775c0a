"""The reduction from trace events to busy, idle, per-op and collective
time."""
import gzip
import json
import os

import numpy as np
import pytest

from benchlib import trace
from benchlib.registry import BENCH_DIR

MS = 1e6    # ns


def events(device_ops, host=None):
    host = host or []
    return {"devices": {"/device:TPU:0": device_ops},
            "host": [["bench.window", 0.0, 100 * MS, "main"]] + host}


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    ops = [["fusion.1", 10 * MS, 20 * MS, "jit_step"],
           ["fusion.2", 20 * MS, 20 * MS, "jit_step"],     # overlaps .1
           ["copy.3", 90 * MS, 30 * MS, "jit_step"],       # runs past end
           ["fusion.4", -5 * MS, 10 * MS, "jit_step"]]     # starts before
    s = trace.reduce(events(ops), 1)
    assert s["window_s"] == pytest.approx(0.1)
    # [0,5] + [10,40] + [90,100] = 45 ms
    assert s["busy_s"] == pytest.approx(0.045)
    assert s["idle_share"] == pytest.approx(0.55)
    assert s["ops_s"]["fusion.1"] == pytest.approx(0.020)
    assert s["ops_s"]["copy.3"] == pytest.approx(0.010)


def test_exposed_collective_is_what_no_compute_overlaps():
    ops = [["fusion.1", 0.0, 30 * MS, "m"],
           ["collective-permute-start.1", 20 * MS, 30 * MS, "m"],
           ["collective-permute-done.1", 50 * MS, 5 * MS, "m"],
           ["all-reduce.2", 70 * MS, 10 * MS, "m"],
           ["fusion.3", 75 * MS, 10 * MS, "m"]]
    s = trace.reduce(events(ops), 1)
    # the permute and its wait [20,55] less compute [0,30] = 25; the
    # all-reduce [70,80] less [75,85] = 5
    assert s["collective_s"] == pytest.approx(0.045)
    assert s["collective_exposed_s"] == pytest.approx(0.030)


def test_device_quantities_are_averaged_over_chips():
    ev = events([["fusion.1", 0.0, 40 * MS, "m"]])
    ev["devices"]["/device:TPU:1"] = [["fusion.1", 0.0, 20 * MS, "m"]]
    s = trace.reduce(ev, 2)
    assert s["busy_s"] == pytest.approx(0.030)
    assert s["ops_s"]["fusion.1"] == pytest.approx(0.030)
    with pytest.raises(RuntimeError):
        trace.reduce(ev, 3)


def test_idle_gaps_go_to_the_innermost_host_span():
    ops = [["fusion.1", 0.0, 10 * MS, "m"], ["fusion.2", 60 * MS, 40 * MS,
                                             "m"]]
    host = [["bench.round", 0.0, 50 * MS, "main"],
            ["loader", 12 * MS, 20 * MS, "main"],
            ["bench.round", 50 * MS, 50 * MS, "main"]]
    s = trace.reduce(events(ops, host), 1)
    # gap [10,60], middle 35: inside the first round span only
    assert s["gaps"] == {"bench.round": pytest.approx(0.050)}
    host[1] = ["loader", 30 * MS, 20 * MS, "main"]
    s = trace.reduce(events(ops, host), 1)
    assert s["gaps"] == {"loader": pytest.approx(0.050)}
    b = trace.breakdown(s)
    assert b["idle_gaps"] == [["loader", pytest.approx(0.050)]]
    assert [n for n, _ in b["device_ops"]] == ["fusion.2", "fusion.1"]


def test_one_window_span_is_required():
    ev = events([])
    ev["host"].append(["bench.window", 0.0, 1.0, "main"])
    with pytest.raises(RuntimeError):
        trace.reduce(ev, 1)


RECORDED = os.path.join(BENCH_DIR, "testdata",
                        "bn-lenet.gaia.k5.trace40ms.json.gz")


def test_recorded_chip_trace_against_a_direct_count():
    """The first 40 ms of a traced window of ``bn-lenet.gaia.k5`` on a TPU
    v5 lite (host spans and the chip's XLA ops), reduced as a run reduces
    it, against sums taken here on a 100 ns grid and event by event."""
    with gzip.open(RECORDED, "rt") as f:
        ev = json.load(f)
    s = trace.reduce(ev, 1)
    t0, t1 = trace.window_of(ev["host"])
    (plane,) = ev["devices"].values()
    grid = np.zeros(int((t1 - t0) / 100) + 1, bool)
    ops = {}
    for name, start, dur, _ in plane:
        a, b = max(start, t0), min(start + dur, t1)
        if start + dur > t0 and start < t1:
            grid[int((a - t0) / 100):int((b - t0) / 100)] = True
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
    assert s["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert s["busy_s"] == pytest.approx(grid.mean() * s["window_s"],
                                        abs=2e-4)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    assert s["ops_s"] == pytest.approx(ops)
    assert sum(s["gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    # one chip: no collectives; Gaia's select kernel is in the step
    assert s["collective_s"] == 0 == s["collective_exposed_s"]
    assert any(n.startswith("_gaia_pallas") for n in s["ops_s"])
    assert "bench.round" in {n for n, *_ in ev["host"]}
