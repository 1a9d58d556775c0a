"""Helpers that drive a benchmark cell on the CPU at a tiny data size:
the harness's look for a chip is skipped, everything else runs."""
import json
import os
import sys
import time
from types import SimpleNamespace

from benchlib import device, registry

BENCH_DIR = registry.BENCH_DIR
SMALL = dict(n_train=1500, n_val=600)


def small_cell(name, *, root=registry.ROOT, bench_dir=BENCH_DIR,
               **traffic):
    cell = registry.load_cell(name, root=root, bench_dir=bench_dir)
    cell.traffic["data"].update(SMALL)
    cell.traffic.update(warm_rounds=6, **traffic)
    return cell


def driver_ctx(cell, seed=2 ** 40 + 11, seconds=1.0):
    return SimpleNamespace(cell=cell, seed=seed, seconds=seconds,
                           trace_dir=None, t_start=time.perf_counter(),
                           log=lambda *a: None,
                           compiles=device.CompileCounter(), peaks=None)


def run_cell(monkeypatch, name, seed=2 ** 40 + 11, seconds=1.0, **where):
    """``bench/run.py``'s ``run_cell`` on the CPU for a shrunk cell (found
    under ``where``'s ``root`` and ``bench_dir``, if given); the
    persistent compile cache and the dispatch cache stay off."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import run as bench_run
    monkeypatch.setenv("REPRO_DISPATCH_CACHE", "")
    monkeypatch.setattr(device, "use_compile_cache", lambda: None)
    monkeypatch.setattr(bench_run, "load_cell",
                        lambda n: small_cell(n, **where))
    args = SimpleNamespace(workload=name, seed=seed, seconds=seconds,
                           trace=0)
    res = bench_run.run_cell(args, require_tpu=False)
    json.dumps(res)            # the result line is plain JSON
    return res


def limits(name):
    with open(os.path.join(BENCH_DIR, "cells", name + ".json")) as f:
        return json.load(f)["limits"]
