"""Spans and counters of the training loop, kept in memory.

The loop marks its layers with ``span(name)`` and counts its transfers
with ``count(name, n)``; ``set_round(t)`` gives the round that the spans
and counts which follow belong to.  Nothing is kept unless a caller has
started a :class:`Recorder`::

    rec = obs.Recorder()
    with rec:                    # or obs.start(rec) ... obs.stop()
        train_decentralized(...)
    rec.summary()

With no recorder active, ``span`` returns one shared null context and
``count`` returns at once: no clock read, no allocation, no profiler
annotation.  A recorder made with ``annotate=True`` also enters a
``jax.profiler.TraceAnnotation(name, round=t)`` for every span, so that
while a profile is taken the spans sit in its ``.xplane.pb`` beside the
device's ops, on the profiler's clock.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from jax import profiler


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
_active: Optional["Recorder"] = None
_round = -1


class Recorder:
    """Spans as ``[name, round, start_ns, end_ns, parent index]`` (parent
    -1 at the top; end ``None`` while open) and counts by round.

    A round is whole when ``set_round`` began it while the recorder was
    active and no span was open when the recorder stopped in it; only
    whole rounds enter :meth:`summary`."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: List[list] = []
        self.counts: Dict[int, Dict[str, int]] = {}
        self.begun: List[int] = []
        self.partial: set = set()
        self._open: List[int] = []

    def __enter__(self) -> "Recorder":
        start(self)
        return self

    def __exit__(self, *exc) -> bool:
        stop()
        return False

    def whole_rounds(self) -> List[int]:
        return [t for t in self.begun if t not in self.partial]

    def summary(self) -> Dict:
        """Per whole round, in the order of ``rounds``: each span name's
        self time (its spans' durations less what their child spans
        cover) and total time, in seconds, and each counter's sum.  A
        span or counter absent from a round reads 0 there."""
        rounds = self.whole_rounds()
        at = {t: i for i, t in enumerate(rounds)}
        covered = [0] * len(self.spans)
        for name, t, s, e, parent in self.spans:
            if e is not None and parent >= 0:
                covered[parent] += e - s
        self_s: Dict[str, List[float]] = {}
        total_s: Dict[str, List[float]] = {}
        for i, (name, t, s, e, _) in enumerate(self.spans):
            if t not in at or e is None:
                continue
            for out, ns in ((total_s, e - s), (self_s, e - s - covered[i])):
                out.setdefault(name, [0.0] * len(rounds))[at[t]] += ns * 1e-9
        counts: Dict[str, List[int]] = {}
        for t, by_name in self.counts.items():
            if t in at:
                for name, n in by_name.items():
                    counts.setdefault(name, [0] * len(rounds))[at[t]] += n
        return {"rounds": rounds, "self_s": self_s, "total_s": total_s,
                "counts": counts}


class _Span:
    __slots__ = ("rec", "name", "i", "note")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        rec = self.rec
        if rec.annotate:
            self.note = profiler.TraceAnnotation(self.name, round=_round)
            self.note.__enter__()
        self.i = len(rec.spans)
        rec.spans.append([self.name, _round, time.perf_counter_ns(), None,
                          rec._open[-1] if rec._open else -1])
        rec._open.append(self.i)
        return self

    def __exit__(self, *exc):
        rec = self.rec
        if _active is rec and rec._open and rec._open[-1] == self.i:
            rec.spans[self.i][3] = time.perf_counter_ns()
            rec._open.pop()
        if rec.annotate:
            self.note.__exit__(None, None, None)
        return False


def start(rec: Recorder) -> None:
    """Make ``rec`` the active recorder."""
    global _active
    if _active is not None:
        raise RuntimeError("a recorder is already active")
    _active = rec


def stop() -> None:
    """Stop the active recorder; the round it stops in is not whole if
    a span is still open."""
    global _active
    rec = _active
    if rec is not None:
        if rec._open:
            rec.partial.add(_round)
            rec._open.clear()
        _active = None


def set_round(t: int) -> None:
    """Begin round ``t``: the spans and counts that follow carry it."""
    global _round
    _round = t
    if _active is not None:
        _active.begun.append(t)


def active() -> bool:
    """Whether a recorder is active: work that only feeds a counter is
    skipped without one."""
    return _active is not None


def span(name: str):
    """A context that records ``name`` as a span of the current round."""
    if _active is None:
        return _NULL
    return _Span(_active, name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the current round."""
    if _active is None:
        return
    by_name = _active.counts.setdefault(_round, {})
    by_name[name] = by_name.get(name, 0) + n
