"""Readings that the limits of ``correct`` are set from, and their proof.

  python3 bench/control.py --workload <cell> --seeds 1,2,3

On the chip the cell asks for, for each seed, the cell's driver runs the
program's checked rounds (no window) and reads each compared number for
the program, for the control (the plain reference one precision down, in
the program's place) and for each fault planted in the reference.  Each
reading is held to the cell's limits by the predicate that decides
``correct`` in ``bench/run.py``.  One JSON line per seed, with each
variant's numbers and its ``correct``, then the largest program reading
and the smallest reading of each variant.  The script exits non-zero
when the program comes out not correct or the control or a fault comes
out correct on any seed.
"""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from benchlib import device  # noqa: E402
from benchlib.compare import passes  # noqa: E402
from benchlib.registry import ROOT, BenchError, load_cell  # noqa: E402

NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("REPRO_DISPATCH_CACHE", "")
    cell = load_cell(args.workload)
    device.use_compile_cache()
    try:
        dev = device.find_device(cell.chips)
    except BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    print("device", dev.as_dict(), file=sys.stderr, flush=True)
    drv = cell.driver()
    worst, wrong = {}, []
    for seed in (int(s) for s in args.seeds.split(",")):
        line = {"seed": seed}
        for v, g in drv.readings(cell, seed).items():
            ok = passes(drv.limit_checks(cell, g))
            line[v] = dict({n: g[n] for n in NUMBERS}, correct=ok)
            if ok != (v == "program"):
                wrong.append((seed, v))
            pick = max if v == "program" else min
            for n in NUMBERS:
                worst[(v, n)] = pick(worst.get((v, n), g[n]), g[n])
        print(json.dumps(line), flush=True)
    summary = {}
    for (v, n), x in sorted(worst.items()):
        summary.setdefault(v, {})[n] = x
    print(json.dumps({"summary": summary,
                      "limits": cell.limits["limits"],
                      "wrong_verdicts": wrong}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
