"""Benchmark entry point — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick pass (default)
  PYTHONPATH=src python -m benchmarks.run --full     # full paper-scale runs
  PYTHONPATH=src python -m benchmarks.run --only fig1,fig8
  PYTHONPATH=src python -m benchmarks.run --json out # + BENCH_*.json per
                                                     # bench (CI artifact)

Prints ``name,us_per_call,derived`` CSV.  For kernel benches us_per_call is
the measured call time; for experiment benches us_per_call is the total
wall time of the run and ``derived`` carries the headline metric
(accuracy / savings / divergence), full rows land in experiments/results/.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (fig1_accuracy, fig2_flickr, fig4_bn_divergence,
                        fig5_groupnorm, fig6_skew_degree, fig8_skewscout,
                        fig_topology, kernels_bench, tab678_hparams)

BENCHES = {  # priority order: cheap + headline results first
    "kernels": (kernels_bench, "pallas kernels vs oracles"),
    "fig4": (fig4_bn_divergence, "BN minibatch-mean divergence"),
    "fig8": (fig8_skewscout, "SkewScout vs BSP vs Oracle"),
    "fig1": (fig1_accuracy, "4 CNN x 4 algo x IID/non-IID accuracy"),
    "fig5": (fig5_groupnorm, "GroupNorm vs BatchNorm rescue"),
    "fig6": (fig6_skew_degree, "degree-of-skew sweep"),
    "fig2": (fig2_flickr, "geo-skew (Flickr-Mammal analogue)"),
    "fig_topology": (fig_topology, "D-PSGD topology x skew sweep"),
    "tab678": (tab678_hparams, "theta sensitivity"),
}


def _headline(name, rows):
    if not rows:
        return ""
    if name == "kernels":
        return ""
    if name == "fig4":
        import numpy as np
        by = {}
        for r in rows:
            by.setdefault(r["setting"], []).append(r["mu_divergence"])
        return ";".join(f"{k}:mean_div={np.mean(v):.3f}"
                        for k, v in by.items())
    if name == "fig8":
        return ";".join(
            f"skew{r['skew']}:ss={r['skewscout_savings']:.1f}x,"
            f"oracle={r['oracle_savings']:.1f}x" for r in rows)
    if "val_acc" in rows[0]:
        worst = min(rows, key=lambda r: r["val_acc"])
        keys = [k for k in ("model", "algo", "skew", "setting", "theta")
                if k in worst]
        tag = "/".join(str(worst[k]) for k in keys)
        return f"n={len(rows)};worst_acc={worst['val_acc']:.3f}@{tag}"
    return f"n={len(rows)}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="emit a machine-readable BENCH_<name>.json per "
                         "bench into DIR (the CI perf-trajectory "
                         "artifact: commit, timestamp, wall time, "
                         "headline, full rows)")
    ap.add_argument("--use-cache", action="store_true",
                    help="reuse experiments/results/*.json if present")
    ap.add_argument("--cache-only", action="store_true",
                    help="with --use-cache: skip experiment benches whose "
                         "results are missing instead of re-running")
    args = ap.parse_args(argv)
    names = list(BENCHES) if not args.only else args.only.split(",")

    print("name,us_per_call,derived")
    for name in names:
        mod, _desc = BENCHES[name]
        t0 = time.perf_counter()
        if args.use_cache and name != "kernels":
            from benchmarks.common import load_rows
            rows = load_rows(name)
            if rows is None:
                if args.cache_only:
                    print(f"{name},0,SKIPPED(no cached result)")
                    continue
                rows = mod.run(quick=not args.full)
        else:
            rows = mod.run(quick=not args.full)
        dt_us = (time.perf_counter() - t0) * 1e6
        if name == "kernels":
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},")
        else:
            print(f"{name},{dt_us:.0f},{_headline(name, rows)}")
        if args.json:
            from benchmarks.common import save_bench_json
            save_bench_json(name, rows, derived=_headline(name, rows),
                            us_per_call=dt_us, out_dir=args.json)
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
