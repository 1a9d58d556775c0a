"""Driver: decentralized CNN training through the program's own loop.

The window drives ``repro.core.trainer.train_decentralized`` itself,
host work included: the loader, the step, the scalar syncs and the
ledger's pricing.  The loop's one per-round host hook is its
``lr_schedule`` callable; the driver passes one that returns the
traffic's constant learning rate and stamps the host clock at the top of
every round, so a round is the interval between two stamps.

* A first, short call (the traffic's ``warm_rounds``) compiles and warms
  up every shape and gives the steady round time.
* A second call is sized to about ``--seconds`` of rounds.  Its first
  three rounds feed the correctness check; the window runs from round
  3's stamp to the window's last stamp.  The call's step count is its
  ``eval_every``, so the final evaluation falls after the window.
* With a trace asked for, the same call runs ``TRACED`` more rounds
  after the window under the profiler.  The window itself is never
  traced, so the host-clock counters it gives are those of an untraced
  loop.

The traffic's ``comm`` object is the program's ``CommConfig``, field by
field (nested objects for nested configs), so a mix may set any
strategy and its knobs.

For the check, the driver taps the algorithm that this second call
builds: the tap keeps the inputs of rounds 0-2 and the state after round
0 and after round 2 on the host, then gets out of the way.  The plain
reference (``bench/reference/cnn_train.py``, with the strategy's own
exchange from ``bench/reference/cnn_exchange/<strategy>.py``) replays
those rounds from the same seed once the window has closed and the
program's state is freed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import typing
from unittest import mock

import numpy as np

from benchlib import compare, traffic as tgen
from benchlib.registry import BenchError, load_module
from benchlib.trace import Spans

#: rounds of the second call before the window opens (checked rounds)
CHECKED = 3
#: rounds traced after the window when a trace is asked for (about 3 s of
#: traced rounds on a TPU v5e), and rounds let pass between the window's
#: close and the first traced round while the profiler starts
TRACED, TRACE_LEAD = 100, 2


def _named(tree):
    """The program's parameter tree as the reference's flat names."""
    out = {}
    for i, (c, n) in enumerate(zip(tree["conv"], tree["norm"])):
        out[f"conv{i}.w"], out[f"conv{i}.b"] = c["w"], c["b"]
        out[f"norm{i}.scale"], out[f"norm{i}.bias"] = n["scale"], n["bias"]
    for j, f in enumerate(tree["fc"]):
        out[f"fc{j}.w"], out[f"fc{j}.b"] = f["w"], f["b"]
    out["out.w"], out["out.b"] = tree["out"]["w"], tree["out"]["b"]
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


class StepTap:
    """Wraps ``algo.step``: keeps what the check needs from the first
    ``CHECKED`` calls, and only passes calls through after that."""

    def __init__(self, step):
        self.step, self.calls = step, 0
        self.inputs, self.params0, self.vel0, self.params_end = [], None, \
            None, None

    def __call__(self, state, batch, lr, t, **kw):
        if self.calls >= CHECKED:
            return self.step(state, batch, lr, t, **kw)
        import jax
        if self.calls == 0:
            self.params0 = _named(jax.device_get(state["params"]))
        self.inputs.append({"x": np.asarray(batch["x"]),
                            "y": np.asarray(batch["y"]), "lr": float(lr),
                            "t": int(t), "kw": {k: np.asarray(v).item()
                                                for k, v in kw.items()}})
        state, metrics = self.step(state, batch, lr, t, **kw)
        if self.calls == 0:
            self.vel0 = _named(jax.device_get(state["vel"]))
        if self.calls == CHECKED - 1:
            self.params_end = _named(jax.device_get(state["params"]))
        self.calls += 1
        return state, metrics


@contextlib.contextmanager
def tapped_trainer():
    """The trainer, with a ``StepTap`` on the algorithm its next call
    builds; yields a dict that then holds the tap under ``"tap"``."""
    from repro.core import trainer
    box, make = {}, trainer.make_algorithm

    def tapped(*a, **k):
        algo = make(*a, **k)
        algo.step = box.setdefault("tap", StepTap(algo.step))
        return algo

    with mock.patch.object(trainer, "make_algorithm", tapped):
        yield box


def _cnn_config(config):
    from repro.configs.cnn_zoo import CNN_ZOO
    cfg = dataclasses.replace(CNN_ZOO[config["zoo_name"]],
                              image_size=config["image_size"])
    for key in ("conv_channels", "kernel_sizes", "pool_after", "fc_dims",
                "n_classes", "in_channels", "norm"):
        got = getattr(cfg, key)
        want = config[key]
        if (list(got) if isinstance(got, tuple) else got) != want:
            raise BenchError(f"the program's {config['zoo_name']} has "
                             f"{key}={got}, the configuration {want}")
    return cfg


def precision(config):
    """The matmul precision the configuration states, around every call
    into the program: the program sets none, and a TPU otherwise runs
    float32 convolutions as one bfloat16 pass."""
    import jax
    return jax.default_matmul_precision(config["matmul_precision"])


def _dataclass(cls, fields):
    """``cls`` from a JSON object; a nested object fills a field whose type
    is itself a dataclass."""
    hints = typing.get_type_hints(cls)
    kw = {k: (_dataclass(hints[k], v)
              if isinstance(v, dict) and dataclasses.is_dataclass(
                  hints.get(k)) else v)
          for k, v in fields.items()}
    try:
        return cls(**kw)
    except TypeError as e:
        raise BenchError(f"traffic's comm: {e}") from None


def strategy(tr):
    return tr["comm"]["strategy"]


def _train_kw(tr, seed):
    from repro.configs.base import CommConfig
    opt = tr["optimizer"]
    return dict(comm=_dataclass(CommConfig, tr["comm"]), batch=tr["batch"],
                lr=opt["lr"], momentum=opt["momentum"],
                weight_decay=opt["weight_decay"],
                seed=tgen.derive_seed(seed, "program"))


def run(ctx):
    from repro.core import trainer

    config, tr = ctx.cell.config, ctx.cell.traffic
    algo, opt = strategy(tr), tr["optimizer"]
    cfg = _cnn_config(config)
    t_data = time.perf_counter()
    parts, val = tgen.image_task(tr, config, ctx.seed)
    kw = _train_kw(tr, ctx.seed)
    t_warm = time.perf_counter()

    # warm-up: compiles the step and both shapes the final evaluation
    # takes (the trainer evaluates in batches of 512 and a remainder)
    stamps = []
    n_warm = min(len(val[1]), 512 + len(val[1]) % 512)
    warm_val = (val[0][:n_warm], val[1][:n_warm])
    with precision(config):
        trainer.train_decentralized(
            cfg, algo, parts, warm_val, steps=tr["warm_rounds"],
            eval_every=tr["warm_rounds"],
            lr_schedule=lambda t: stamps.append(time.perf_counter()) or
            opt["lr"], **kw)
    round_s = float(np.median(np.diff(stamps)[CHECKED:]))
    t_call = time.perf_counter()
    # the window: stamps CHECKED .. last; then, traced, rounds
    # trace0 .. trace1 - 1 between the stamps of trace0 and trace1
    last = max(CHECKED + 4, int(ctx.seconds / round_s) + CHECKED + 1) - 1
    trace0 = last + 1 + TRACE_LEAD
    trace1 = trace0 + TRACED
    n_rounds = trace1 + 1 if ctx.trace_dir else last + 1

    spans = Spans(ctx.trace_dir)
    stamps.clear()
    marks = {}

    def hook(t):
        stamps.append(time.perf_counter())
        if t == CHECKED:
            marks["compiles0"] = ctx.compiles.n
        elif t == last:
            marks["compiles1"] = ctx.compiles.n
            spans.start()
        elif t == trace0:
            spans.open_window()
        elif t == trace1:
            spans.close_window()
            spans.stop()
        if trace0 <= t < trace1:
            spans.round(t)
        return opt["lr"]

    try:
        with tapped_trainer() as tap, precision(config):
            r = trainer.train_decentralized(
                cfg, algo, parts, val, steps=n_rounds,
                eval_every=n_rounds, lr_schedule=hook, **kw)
    finally:
        spans.stop()
    from benchlib.device import memory_peak_bytes
    mem = memory_peak_bytes(1)

    st = np.asarray(stamps)
    window_s = float(st[last] - st[CHECKED])
    rounds = np.diff(st[CHECKED:last + 1])          # rounds 3 .. last - 1
    step_s = np.asarray(r.extras["step_s"])[CHECKED:last]
    losses = np.asarray([l for _, l in r.loss_curve])
    per_round = tr["sites"] * tr["batch"]
    metrics = {
        "setup_s": float(st[CHECKED] - ctx.t_start),
        "train_images_per_s": len(rounds) * per_round / window_s,
        "round_ms_p95": float(np.percentile(rounds, 95) * 1e3),
    }
    counters = {"window_s": window_s, "rounds": len(rounds),
                "round_s": rounds.tolist(), "step_s": step_s.tolist(),
                "images_per_round": per_round,
                "mosaic_calls": r.extras["mosaic_calls"],
                "strategy": algo}
    if ctx.trace_dir:
        counters["traced_rounds"] = TRACED
        traced = np.diff(st[trace0:trace1 + 1])
        ctx.log(f"traced rounds {TRACED} round_ms_median "
                f"{np.median(traced) * 1e3:.4f} (untraced "
                f"{np.median(rounds) * 1e3:.4f})")
    slow = np.flatnonzero(rounds > 5 * np.median(rounds))
    ctx.log("rounds over 5x the median (round, s into the window, round "
            "ms, step ms): " + ", ".join(
                f"({CHECKED + i}, {st[CHECKED + i] - st[CHECKED]:.3f}, "
                f"{rounds[i] * 1e3:.3f}, {step_s[i] * 1e3:.3f})"
                for i in slow))
    ctx.log(f"setup: imports {t_data - ctx.t_start:.3f} s, data "
            f"{t_warm - t_data:.3f} s, warm-up call {t_call - t_warm:.3f} s, "
            f"checked rounds {st[CHECKED] - t_call:.3f} s")
    ctx.log(f"rounds {len(rounds)} window_s {window_s:.4f} round_ms_median "
            f"{np.median(rounds) * 1e3:.4f} step_ms_median "
            f"{np.median(step_s) * 1e3:.4f} round_ms_max "
            f"{rounds.max() * 1e3:.4f} rounds_over_5x_median "
            f"{len(slow)} mosaic_calls "
            f"{r.extras['mosaic_calls']} val_acc {r.val_acc:.4f}")

    checks = check(ctx, tap["tap"], losses[:CHECKED])
    return {"metrics": metrics, "counters": counters,
            "attempted": len(rounds),
            "failed": int(np.sum(~np.isfinite(losses[CHECKED:last]))),
            "checks": checks, "memory_peak_bytes": mem,
            "compiles_in_window": marks["compiles1"] - marks["compiles0"]}


def reference(cell):
    return load_module(os.path.join(cell.bench_dir,
                                    cell.config["reference"]),
                       "bench_reference_cnn")


def program_readings(tap, losses, exch):
    return {"losses": [float(l) for l in losses],
            "grad0": exch.grad0(tap.vel0, tap.inputs[0]["lr"]),
            "params0": tap.params0, "params_end": tap.params_end}


def gaps(cell, seed, tap, losses, variants=None):
    """Readings of the three numbers, by name: ``program`` (``tap``,
    ``losses``) against the reference, and each of ``variants`` (name ->
    ``precision``/``dtype``/``fault`` keywords): the reference so computed
    put in the program's place."""
    tr = cell.traffic
    kw = dict(momentum=tr["optimizer"]["momentum"],
              weight_decay=tr["optimizer"]["weight_decay"])
    ref_mod, pseed = reference(cell), tgen.derive_seed(seed, "program")
    exch = ref_mod.exchange(strategy(tr))
    run_ref = lambda **v: ref_mod.run(cell.config, tr["comm"], pseed,
                                      tap.inputs, **v, **kw)
    ref = run_ref()
    out = {"program": compare.training_gaps(
        program_readings(tap, losses, exch), ref, stacked=exch.STACKED)}
    for name, v in (variants or {}).items():
        out[name] = compare.training_gaps(run_ref(**v), ref,
                                          stacked=exch.STACKED)
    return out


def check(ctx, tap, losses):
    return limit_checks(ctx.cell, gaps(ctx.cell, ctx.seed, tap, losses)
                        ["program"], ctx.log)


def limit_checks(cell, g, log=None):
    """The compared numbers of one reading, each beside its limit."""
    if log:
        log(f"worst leaves: grad {g['grad_leaf']} change "
            f"{g['change_leaf']}; left out of change: {g['still_leaves']}")
    lim = cell.limits["limits"]
    return [{"name": k, "value": g[k], "limit": lim[k]}
            for k in ("loss_gap", "grad_gap", "change_gap")]


def first_rounds(cell, seed):
    """The program's checked rounds alone, with no window: the tap and the
    losses of a ``CHECKED``-round call on the cell's data from ``seed``."""
    from repro.core import trainer
    tr = cell.traffic
    parts, val = tgen.image_task(tr, cell.config, seed)
    with tapped_trainer() as tap, precision(cell.config):
        r = trainer.train_decentralized(
            _cnn_config(cell.config), strategy(tr), parts,
            (val[0][:512], val[1][:512]), steps=CHECKED,
            eval_every=CHECKED, **_train_kw(tr, seed))
    return tap["tap"], np.asarray([l for _, l in r.loss_curve])


#: what the control script reads beside the program: the control (the
#: reference one precision down from the configuration's float32 at
#: ``highest``: three bf16 passes) and the faults planted in the reference
VARIANTS = {"control_high": dict(precision="high"),
            "half_batch": dict(fault="half_batch"),
            "no_exchange": dict(fault="no_exchange")}


def readings(cell, seed):
    """Each number's reading for the program and for every variant."""
    tap, losses = first_rounds(cell, seed)
    return gaps(cell, seed, tap, losses, VARIANTS)
