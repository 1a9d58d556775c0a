"""Find every piece of a cell by its name in ``BENCHMARK.json``.

Nothing here names a configuration, a traffic mix or a metric.  A later
cell or metric is new files and new entries:

* ``bench/configs/<config>.json`` (the file ``BENCHMARK.json`` gives),
  which names its ``driver``;
* ``bench/traffic/<mix>.json``;
* ``bench/drivers/<driver>.py``, with ``run(ctx)``;
* ``bench/metrics/<metric>.py``, with ``read(run)``;
* ``bench/cells/<workload>.json``, the limits of the comparison that
  decides ``correct``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """A cell that cannot run as written."""


def _json(path: str) -> Dict:
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str

    def driver(self):
        drv = self.config["driver"]
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        drv + ".py"), f"bench_driver_{drv}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(bench_dir, "traffic",
                                 w["traffic"] + ".json"))
    limits = _json(os.path.join(bench_dir, "cells", name + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, bench_dir=bench_dir)
