"""Split a round of a cell's training loop by layer, on the chip.

  python3 bench/round_split.py --workload <cell> --seed <n> \
      [--keep <file.json.gz>]

One call of the program's ``train_decentralized`` on the cell's data, set
up as the cell's driver sets it up (configuration, traffic, precision,
warm-up call), runs four stretches, switched from the loop's per-round
hook (its ``lr_schedule``), which stamps the host clock as the driver's
does:

1. plain: about ``SECONDS`` of rounds with nothing on, after the
   driver's checked rounds: the round and the trainer's ``step_s`` as
   the benchmark's window sees them;
2. recorded: ``RECORDED`` whole rounds under a ``repro.obs`` recorder,
   with no profiler: each span's self time and each counter, by round;
3. traced: the driver's ``TRACE_LEAD`` rounds after the profiler starts,
   then its ``TRACED`` rounds inside the ``bench.window`` span under a
   recorder that annotates: the chip's busy time under each named scope
   and inside each program span, on the trace's clock
   (``benchlib/layers.py``);
4. ``TRACED`` more rounds under the profiler with no recorder, which
   price the annotations.

A round in which the recorder starts or stops is left out.  The one
JSON line on stdout holds, in ms a round, each span's mean self time
over the recorded stretch, the chip's time by scope and inside each span
over the traced one, the exchange's kernel events and other ops each
beside the time its bytes take at the chip's HBM bandwidth, and the
checks of the split: how much of a round its child spans cover, the
host's share against the plain stretch's (round - ``step_s``), dispatch +
wait against ``step_s``, the spans of the traced rounds that the trace
lacks, and the recorder's cost.  ``--keep`` writes the first ``KEEP_MS``
of the traced window's events as gzipped JSON, the form ``layers.load``
gives.

The driver's window knows none of this yet: this tool is to be folded
into ``bench/drivers/cnn_decentralized.py`` (PERF.md section 7).
"""
import argparse
import contextlib
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from unittest import mock

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from benchlib import device, layers, traffic as tgen  # noqa: E402
from benchlib.registry import ROOT, BenchError, load_cell  # noqa: E402
from benchlib.trace import WINDOW, Spans, window_of  # noqa: E402

#: seconds of the plain stretch, and rounds of the recorded one
SECONDS, RECORDED = 10.0, 1000
#: the host spans that run outside the device step, beside the round's
#: own uncovered time
HOST = ("trainer.load", "trainer.put", "trainer.sync", "trainer.ledger")
KEEP_MS = 80.0


def _ms(xs) -> float:
    return float(np.mean(xs) * 1e3)


@contextlib.contextmanager
def jitted_calls():
    """The trainer, with the jitted methods of the algorithm its next
    call builds wrapped so as to keep their first call's arguments;
    yields a dict that then holds the algorithm under ``"algo"`` and
    those arguments by method name under ``"calls"``."""
    from repro.core import trainer
    box, make = {"calls": {}}, trainer.make_algorithm

    def keep(name, fn):
        def call(*a, **k):
            box["calls"].setdefault(name, (a, k))
            return fn(*a, **k)
        return call

    def wrapped(*a, **k):
        algo = box["algo"] = make(*a, **k)
        for name in dir(type(algo)):
            if hasattr(getattr(type(algo), name), "lower"):
                setattr(algo, name, keep(name, getattr(algo, name)))
        return algo

    with mock.patch.object(trainer, "make_algorithm", wrapped):
        yield box


def step_texts(box):
    """The compiled HLO text of each jitted method the call ran, lowered
    again from its first arguments (the same program, found in the
    compilation cache)."""
    algo = box["algo"]
    return [getattr(type(algo), name).lower(algo, *a, **k).compile()
            .as_text() for name, (a, k) in box["calls"].items()]


def split(cell, seed, seconds=SECONDS, recorded=RECORDED, traced=None,
          trace_dir=None, keep=None):
    """The split of one call's rounds; see the module's docstring.
    ``traced`` defaults to the driver's ``TRACED``."""
    from repro import obs
    from repro.core import trainer
    drv = cell.driver()
    traced = traced or drv.TRACED
    config, tr = cell.config, cell.traffic
    algo, lr = drv.strategy(tr), tr["optimizer"]["lr"]
    cfg = drv._cnn_config(config)
    parts, val = tgen.image_task(tr, config, seed)
    kw = drv._train_kw(tr, seed)

    stamps = []
    n_warm = min(len(val[1]), 512 + len(val[1]) % 512)
    with drv.precision(config):
        trainer.train_decentralized(
            cfg, algo, parts, (val[0][:n_warm], val[1][:n_warm]),
            steps=tr["warm_rounds"], eval_every=tr["warm_rounds"],
            lr_schedule=lambda t: stamps.append(time.perf_counter()) or lr,
            **kw)
    round_s = float(np.median(np.diff(stamps)[drv.CHECKED:]))

    # switch rounds: recorder on at r0, off at r1 (profiler on); the
    # annotating recorder and the window from a0 to a1; profiler off at b1
    r0 = max(drv.CHECKED + 4, int(seconds / round_s) + drv.CHECKED)
    r1 = r0 + recorded + 1
    a0 = r1 + drv.TRACE_LEAD
    a1 = a0 + traced
    b1 = a1 + traced
    rec, ann = obs.Recorder(), obs.Recorder(annotate=True)
    spans = Spans(trace_dir)
    stamps.clear()

    def hook(t):
        stamps.append(time.perf_counter())
        if t == r0:
            obs.start(rec)
        elif t == r1:
            obs.stop()
            spans.start()
        elif t == a0:
            spans.open_window()
            obs.start(ann)
        elif t == a1:
            obs.stop()
            spans.close_window()
        elif t == b1:
            spans.stop()
        return lr

    try:
        with jitted_calls() as box, drv.precision(config):
            r = trainer.train_decentralized(
                cfg, algo, parts, val, steps=b1 + 1, eval_every=b1 + 1,
                lr_schedule=hook, **kw)
            texts = step_texts(box) if trace_dir else []
    finally:
        obs.stop()
        spans.stop()

    st = np.asarray(stamps)
    gaps = np.diff(st)
    step_s = np.asarray(r.extras["step_s"])
    plain = slice(drv.CHECKED, r0)
    s = rec.summary()
    self_ms = {k: _ms(v) for k, v in s["self_s"].items()}
    total = {k: np.asarray(v) for k, v in s["total_s"].items()}
    rnd = total["trainer.round"]
    cover = 1.0 - np.asarray(s["self_s"]["trainer.round"]) / rnd
    host_ms = sum(self_ms.get(k, 0.0) for k in HOST) + \
        self_ms["trainer.round"]
    plain_host = gaps[plain] - step_s[plain]
    step = total["trainer.dispatch"] + total["trainer.wait"]
    slow = np.flatnonzero(rnd > 5 * np.median(rnd))
    stalls = [{"round": s["rounds"][i], "ms": float(rnd[i] * 1e3),
               "held_by": max(s["self_s"], key=lambda k: s["self_s"][k][i])}
              for i in slow]

    out = {"cell": cell.name, "seed": seed, "strategy": algo,
           "rounds": {"plain": r0 - drv.CHECKED,
                      "recorded": len(s["rounds"]), "traced": traced},
           "round_ms_median": {
               "plain": float(np.median(gaps[plain]) * 1e3),
               "recorded": float(np.median(gaps[r0 + 1:r1 - 1]) * 1e3),
               "traced_annotated": float(np.median(gaps[a0:a1]) * 1e3),
               "traced": float(np.median(gaps[a1 + 1:b1]) * 1e3)},
           "self_ms": self_ms,
           "self_ms_median": {k: float(np.median(v) * 1e3)
                              for k, v in s["self_s"].items()},
           "counts": {k: float(np.mean(v)) for k, v in s["counts"].items()},
           "checks": {"covered_median": float(np.median(cover)),
                      "host_ms": host_ms, "plain_host_ms": _ms(plain_host),
                      "host_ms_median": float(np.median(rnd - step) * 1e3),
                      "plain_host_ms_median": float(
                          np.median(plain_host) * 1e3),
                      "dispatch_wait_ms": _ms(step),
                      "plain_step_ms": _ms(step_s[plain])},
           "stalls": stalls}
    if trace_dir:
        ev = layers.load(layers.newest_xplane(trace_dir))
        for text in texts:
            layers.attach(ev, *layers.hlo_scopes(text))
        sc = layers.by_scope(ev)
        ins = layers.inside(ev)
        a = ann.summary()
        whole = set(a["rounds"])
        mine = [(n, t) for n, t, _, e, _ in ann.spans
                if t in whole and e is not None]
        out["device_ms"] = {k: v / traced * 1e3 for k, v in sc.items()}
        out["inside_ms"] = {k: v / traced * 1e3 for k, v in ins.items()}
        out["unscoped_share"] = sc["unscoped"] / sc["busy"] \
            if sc["busy"] else None
        out["wait_idle_ms"] = _ms(total["trainer.wait"]) - \
            out["inside_ms"].get("trainer.wait", 0.0)
        out["checks"]["spans_traced"] = len(mine)
        out["checks"]["spans_without_twin"] = layers.twins(mine, ev["host"])
        import jax
        bw = device.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
        ks = layers.kernel_split(ev, {k: v for text in texts for k, v in
                                      layers.hlo_bytes(text).items()})
        out["exchange_ms"] = with_floor(ks.get("exchange", {}), traced, bw)
        out["checks"]["kernel_events_outside_exchange"] = sum(
            v["kernel"]["n"] for k, v in ks.items()
            if k != "exchange" and "kernel" in v)
        if keep:
            write_slice(ev, keep)
    return out


def with_floor(kinds, traced, bw):
    """Per round, for the kernel events and the other ops of one scope
    (``layers.kernel_split``): their events and summed time, and
    ``floor``, the time their bytes take at ``bw`` bytes/s: what the
    kernels read and write (each streams its operands once), and what the
    other ops write (a slice reads only part of its operand)."""
    out = {}
    for kind, v in kinds.items():
        moved = v["written"] + (v["read"] if kind == "kernel" else 0)
        out[kind] = {"events": v["n"] / traced,
                     "ms": v["seconds"] / traced * 1e3,
                     "floor_ms": moved / bw / traced * 1e3}
    return out


def write_slice(ev, path):
    """The first ``KEEP_MS`` of the window: ops and the program's and the
    bench's spans that start in it, under a window of that length."""
    t0, _ = window_of(ev["host"])
    t1 = t0 + KEEP_MS * 1e6
    keep = {"devices": {p: [o for o in ops if t0 <= o[1] < t1]
                        for p, ops in ev["devices"].items()},
            "host": [[WINDOW, t0, t1 - t0, None]] + [
                h for h in ev["host"] if t0 <= h[1] < t1
                and h[0].startswith((layers.SPAN_PREFIX, "bench.round"))]}
    with gzip.open(path, "wt") as f:
        json.dump(keep, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("REPRO_DISPATCH_CACHE", "")
    cell = load_cell(args.workload)
    device.use_compile_cache()
    try:
        dev = device.find_device(cell.chips)
    except BenchError as e:
        print(f"round_split: {e}", file=sys.stderr)
        return 2
    print("device", dev.as_dict(), file=sys.stderr, flush=True)
    tdir = tempfile.mkdtemp(prefix="round_split_")
    try:
        out = split(cell, args.seed, trace_dir=tdir, keep=args.keep)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
