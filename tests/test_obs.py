"""The training loop's spans and counters (``repro.obs``): the off path,
the recorder's arithmetic, and the trainer under a recorder."""
import glob
import os
import re
import tracemalloc
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import CommConfig
from repro.configs.cnn_zoo import CNN_ZOO
from repro.core import partition_label_skew, train_decentralized
from repro.data.synthetic import synth_images


@pytest.fixture(autouse=True)
def no_recorder():
    obs.stop()
    yield
    obs.stop()


@pytest.fixture
def clock(monkeypatch):
    """A fake nanosecond clock: each read returns the next of ``ticks``."""
    ticks = []
    fake = SimpleNamespace(perf_counter_ns=lambda: ticks.pop(0))
    monkeypatch.setattr(obs, "time", fake)
    return ticks


def _refuse(*a, **k):
    raise AssertionError("called on the off path")


def test_off_path_records_nothing(monkeypatch):
    monkeypatch.setattr(obs, "time",
                        SimpleNamespace(perf_counter_ns=_refuse))
    monkeypatch.setattr(obs.profiler, "TraceAnnotation", _refuse)
    monkeypatch.setattr(obs, "_Span", _refuse)
    first = obs.span("trainer.load")
    for t in range(3):
        obs.set_round(t)
        with obs.span("trainer.round") as s:
            assert s is first
            obs.count("h2d_puts", 4)


def test_off_path_keeps_no_memory():
    def loop(n):
        for t in range(n):
            obs.set_round(t)
            with obs.span("trainer.round"):
                with obs.span("trainer.load"):
                    obs.count("h2d_bytes", 1 << 20)
    loop(10)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        loop(2000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    mine = tracemalloc.Filter(True, obs.__file__)
    grown = after.filter_traces([mine]).compare_to(
        before.filter_traces([mine]), "filename")
    assert sum(d.size_diff for d in grown) <= 0, grown


def test_nesting_parents_and_self_time(clock):
    rec = obs.Recorder()
    # round 0: round [0, 100] holding load [10, 30] and put [40, 90],
    # the put holding a sync [50, 60]
    clock.extend([0, 10, 30, 40, 50, 60, 90, 100])
    with rec:
        obs.set_round(0)
        with obs.span("trainer.round"):
            with obs.span("trainer.load"):
                pass
            with obs.span("trainer.put"):
                with obs.span("trainer.sync"):
                    pass
    assert [(n, p) for n, _, _, _, p in rec.spans] == [
        ("trainer.round", -1), ("trainer.load", 0), ("trainer.put", 0),
        ("trainer.sync", 2)]
    s = rec.summary()
    assert s["rounds"] == [0]
    ns = {k: v[0] * 1e9 for k, v in s["self_s"].items()}
    assert ns == pytest.approx({"trainer.round": 30, "trainer.load": 20,
                                "trainer.put": 40, "trainer.sync": 10})
    assert s["total_s"]["trainer.put"][0] * 1e9 == pytest.approx(50)


def test_spans_of_one_name_sum_within_a_round(clock):
    rec = obs.Recorder()
    clock.extend([0, 1, 3, 5, 9, 10])
    with rec:
        obs.set_round(4)
        with obs.span("trainer.round"):
            with obs.span("trainer.put"):
                pass
            with obs.span("trainer.put"):
                pass
    s = rec.summary()
    assert s["self_s"]["trainer.put"][0] * 1e9 == pytest.approx(6)
    assert s["self_s"]["trainer.round"][0] * 1e9 == pytest.approx(4)


def _round(t, n_puts=1):
    obs.set_round(t)
    with obs.span("trainer.round"):
        with obs.span("trainer.put"):
            obs.count("h2d_puts", n_puts)
        hook(t)
        with obs.span("trainer.wait"):
            obs.count("h2d_puts", 1)
            obs.count("d2h_syncs", 2)


HOOK = {}


def hook(t):
    """Stands for the bench's per-round hook, which switches the recorder
    in the middle of a round."""
    if t in HOOK:
        HOOK[t]()


def test_only_whole_rounds_count(monkeypatch):
    rec = obs.Recorder()
    monkeypatch.setitem(HOOK, 1, lambda: obs.start(rec))
    monkeypatch.setitem(HOOK, 4, obs.stop)
    for t in range(6):
        _round(t, n_puts=t)
    # on in round 1 and off in round 4: rounds 2 and 3 are whole
    s = rec.summary()
    assert s["rounds"] == [2, 3]
    assert s["counts"] == {"h2d_puts": [3, 4], "d2h_syncs": [2, 2]}
    assert set(s["self_s"]) == {"trainer.round", "trainer.put",
                                "trainer.wait"}
    assert all(len(v) == 2 for v in s["self_s"].values())
    # what was kept of rounds 1 and 4 is left out, not dropped
    assert {t for _, t, _, _, _ in rec.spans} == {1, 2, 3, 4}


def test_counters_sum_per_round():
    with obs.Recorder() as rec:
        for t in range(3):
            _round(t, n_puts=2 * t)
    s = rec.summary()
    assert s["rounds"] == [0, 1, 2]
    assert s["counts"]["h2d_puts"] == [1, 3, 5]
    assert s["counts"]["d2h_syncs"] == [2, 2, 2]


def test_one_recorder_at_a_time():
    with obs.Recorder():
        with pytest.raises(RuntimeError):
            obs.start(obs.Recorder())


def test_annotating_recorder_enters_a_trace_annotation(monkeypatch):
    notes = []

    class Note:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            notes.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            notes.append(("exit", self.name, self.kw))

    monkeypatch.setattr(obs.profiler, "TraceAnnotation", Note)
    with obs.Recorder():
        _round(0)
    assert notes == []
    with obs.Recorder(annotate=True):
        _round(7)
    r = {"round": 7}
    assert notes == [("enter", "trainer.round", r),
                     ("enter", "trainer.put", r), ("exit", "trainer.put", r),
                     ("enter", "trainer.wait", r), ("exit", "trainer.wait", r),
                     ("exit", "trainer.round", r)]


# ------------------------------------------------------------- the trainer

ROUNDS = 6
PER_ROUND = {"trainer.round": 1, "trainer.load": 1, "trainer.put": 2,
             "trainer.dispatch": 1, "trainer.wait": 1, "trainer.sync": 1,
             "trainer.ledger": 1}
#: the spans of the round a round finishes (the one dispatched before it)
FINISHING = ("trainer.wait", "trainer.sync", "trainer.ledger")


@pytest.fixture(scope="module")
def task():
    ds = synth_images(400, seed=0)
    val = synth_images(64, seed=9)
    idx = partition_label_skew(ds.y, 5, 1.0, seed=1)
    return [(ds.x[i], ds.y[i]) for i in idx], (val.x, val.y)


def _train(task, algo="gaia", lr_schedule=None):
    parts, val = task
    return train_decentralized(CNN_ZOO["bn-lenet"], algo, parts, val,
                               comm=CommConfig(), steps=ROUNDS, batch=4,
                               eval_every=ROUNDS, lr_schedule=lr_schedule)


def _losses(r):
    return [l for _, l in r.loss_curve]


@pytest.mark.parametrize("algo", ["gaia", "bsp"])
def test_trainer_rounds_under_a_recorder(task, algo):
    plain = _train(task, algo)
    with obs.Recorder() as rec:
        r = _train(task, algo)
    assert _losses(r) == _losses(plain)           # bit for bit
    assert rec.whole_rounds() == list(range(ROUNDS))
    for t in range(ROUNDS):
        names = [n for n, rt, _, _, _ in rec.spans if rt == t]
        # each round finishes the one before it: round 0 has none to
        # finish, and the last round finishes itself too
        finishing = 0 if t == 0 else 2 if t == ROUNDS - 1 else 1
        want = dict(PER_ROUND, **{k: finishing for k in FINISHING},
                    **({"trainer.eval": 1} if t == ROUNDS - 1 else {}))
        want = {k: n for k, n in want.items() if n}
        assert {n: names.count(n) for n in set(names)} == want, t
    s = rec.summary()
    puts = 4 + (algo == "gaia")        # x, y, lr, the round, Gaia's t0
    assert s["counts"]["h2d_puts"] == [puts] * ROUNDS
    assert s["counts"]["d2h_syncs"] == [0] + [2] * (ROUNDS - 2) + [4]
    assert s["counts"]["rounds_ahead"] == [0] + [1] * (ROUNDS - 1)
    assert r.extras["rounds_overlapped"] == ROUNDS - 1
    x = task[0][0][0]
    assert s["counts"]["h2d_bytes"] == [5 * 4 * x[0].nbytes + 5 * 4 * 4
                                        + 4 * (puts - 2)] * ROUNDS
    # the trainer's own step time is what its dispatch and wait spans hold
    step = np.asarray(r.extras["step_s"])
    inner = np.add(s["total_s"]["trainer.dispatch"],
                   s["total_s"]["trainer.wait"])
    assert np.all(inner <= step) and np.all(inner > 0.5 * step)


def test_trainer_with_no_recorder_feeds_no_span_or_counter(task,
                                                         monkeypatch):
    monkeypatch.setattr(obs, "count", _refuse)
    monkeypatch.setattr(obs, "_Span", _refuse)
    assert len(_train(task).loss_curve) == ROUNDS


def test_recorded_spans_have_twins_in_the_profile(task, tmp_path):
    from jax.profiler import ProfileData
    rec = obs.Recorder(annotate=True)

    def hook(t):
        if t == 1:
            jax.profiler.start_trace(str(tmp_path))
            obs.start(rec)
        elif t == ROUNDS - 1:
            obs.stop()
            jax.profiler.stop_trace()
        return 0.05

    _train(task, lr_schedule=hook)
    whole = set(rec.whole_rounds())
    assert whole == set(range(2, ROUNDS - 1))
    mine = sorted((n, t) for n, t, _, e, _ in rec.spans
                  if t in whole and e is not None)
    assert len(mine) == len(whole) * sum(PER_ROUND.values())
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    got = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if e.name.startswith("trainer.") and "round" in st:
                    got.append((e.name, int(st["round"])))
    # every whole round's span is there once; the partial rounds 1 and 5
    # add theirs besides
    assert not set(mine) - set(got)
    assert all(got.count(k) == mine.count(k) for k in set(mine))


# ------------------------------------------------- scopes are metadata only

_META = re.compile(r",? metadata=\{[^}]*\}")


def _compiled(algo_name, task):
    from repro.core.trainer import make_algorithm, make_cnn_fns
    from repro.models.cnn import init_cnn
    cfg = CNN_ZOO["bn-lenet"]
    fns, _ = make_cnn_fns(cfg)
    algo = make_algorithm(algo_name, fns, 5, CommConfig(), lr0=0.05)
    state = algo.init(*init_cnn(jax.random.PRNGKey(0), cfg))
    xs = np.stack([p[0][:4] for p in task[0]])
    ys = np.stack([p[1][:4] for p in task[0]])
    text = type(algo).step.lower(
        algo, state, {"x": xs, "y": ys}, np.float32(0.05),
        np.int32(0)).compile().as_text()
    # the program less its metadata and the table of source lines that the
    # metadata points into
    head, body = text.split("\n", 1)
    body = body[re.search(r"^(%|ENTRY)", body, re.M).start():]
    return _META.sub("", head + "\n" + body)


@pytest.mark.parametrize("algo", ["gaia", "bsp"])
def test_named_scopes_change_no_compiled_op(task, algo, monkeypatch):
    scoped = _compiled(algo, task)
    monkeypatch.setattr(jax, "named_scope", lambda name: _Nothing())
    assert _compiled(algo, task) == scoped


class _Nothing:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
