"""Run one benchmark cell on the chip and print its result.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell's configuration, traffic mix, driver, limits and per-layer
metric readers are found by name from ``BENCHMARK.json`` (see
``benchlib/registry.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the driver traces a short
stretch after its untraced window, and the result carries the per-layer
metrics, read from the window's counters and from that trace, with the
trace's ``busy_s`` and ``window_s`` and a ``breakdown``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown``), then ``checks``,
each number compared with its limit.  The checks are also the last lines
of stderr.  The run exits non-zero and prints no result when JAX finds
no TPU or fewer chips than the cell asks for, when a
``REPRO_KERNEL_DISPATCH*`` override is set, or when the program is not
beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib import device, trace  # noqa: E402
from benchlib.compare import passes  # noqa: E402
from benchlib.registry import ROOT, BenchError, load_cell  # noqa: E402


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _per_layer(cell, out, summary, pk):
    run = SimpleNamespace(trace=summary, counters=out["counters"],
                          config=cell.config, traffic=cell.traffic,
                          peaks=pk, n_devices=cell.chips)
    metrics = {}
    for m in cell.per_layer:
        v = cell.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return metrics


def run_cell(args, *, require_tpu: bool = True) -> dict:
    """Everything but the printing; raises BenchError where the cell
    cannot run."""
    forced = sorted(k for k in os.environ
                    if k.startswith("REPRO_KERNEL_DISPATCH"))
    if forced:
        raise BenchError(f"{', '.join(forced)} set: a dispatch override "
                         "could take the kernels off the device path")
    cell = load_cell(args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError("the program (src/repro) is not beside the "
                         "benchmark")
    if src not in sys.path:
        sys.path.insert(0, src)
    # kernel dispatch decisions stay in memory: no run reads another's
    os.environ.setdefault("REPRO_DISPATCH_CACHE", "")
    device.use_compile_cache()
    compiles = device.CompileCounter()
    dev = device.find_device(cell.chips, require_tpu)
    pk = device.peaks(dev.kind) if require_tpu else None
    log(f"device {dev.platform} {dev.kind} x{dev.count}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    ctx = SimpleNamespace(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace_dir=tdir, t_start=T_START, log=log,
                          compiles=compiles, peaks=pk)
    try:
        out = cell.driver().run(ctx)
        log(f"compiles_in_window {out['compiles_in_window']}")
        res_dev = dict(dev.as_dict(),
                       memory_peak_bytes=out["memory_peak_bytes"])
        breakdown = None
        if args.trace:
            summary = trace.reduce(trace.load(tdir), cell.chips)
            res_dev["busy_s"] = summary["busy_s"]
            res_dev["window_s"] = summary["window_s"]
            metrics = _per_layer(cell, out, summary, pk)
            breakdown = trace.breakdown(summary)
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            missing = sorted(set(units) - set(out["metrics"]))
            if missing:
                raise BenchError(f"driver reported no {missing}")
            metrics = {k: {"value": float(out["metrics"][k]),
                           "unit": units[k]} for k in units}
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    checks = out["checks"]
    res = {"correct": passes(checks), "attempted": int(out["attempted"]),
           "failed": int(out["failed"]), "metrics": metrics,
           "device": res_dev}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return res


def main(argv=None) -> int:
    args = parse(argv)
    try:
        res = run_cell(args)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    for name, c in res["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
