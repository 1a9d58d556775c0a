"""From the profiler's trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists of events: device ops (one list per chip, from each device plane's
``XLA Ops`` line) and host spans.  ``reduce`` works on those lists only,
so a small trace recorded on the chip and kept as JSON
(``bench/testdata``) checks it on any machine:

* the window is the host span ``bench.window`` that the driver opens and
  closes around the traced rounds; everything is clipped to it;
* busy time is the union of a chip's op intervals, averaged over chips;
  the idle share is one less busy over window;
* per-op time is the sum of each op's device durations, by HLO
  instruction name;
* collective time is the summed interval of the collective ops; the
  exposed part is what of it no compute op on the same chip overlaps.

``Spans`` writes the driver's host spans into the same trace
(``jax.profiler.TraceAnnotation``), so each idle gap can be laid against
what the host was doing.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
ROUND = "bench.round"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv", re.I)
#: op names that mean the chip waits rather than works
WAITS = re.compile(r"-done|^wait|barrier", re.I)


class Spans:
    """The driver's host spans, written only while a trace is taken."""

    def __init__(self, trace_dir: Optional[str]):
        self.dir = trace_dir
        self._on = False
        self._window = self._round = None

    def start(self) -> None:
        if self.dir and not self._on:
            import jax
            opts = jax.profiler.ProfileOptions()
            # no Python tracer.  Host tracer level 1 records the bench's
            # own annotations and also the runtime's level-1 spans (the
            # per-round ``Transpose`` of the input batch, the allocator),
            # thousands a round: they slow a traced round of the CNN loop
            # about 2.4x, which is why the driver traces only a few rounds
            # after an untraced window
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._on = True

    def stop(self) -> None:
        if self._on:
            import jax
            self.close_window()
            jax.profiler.stop_trace()
            self._on = False

    def open_window(self) -> None:
        if self.dir:
            import jax
            self._window = jax.profiler.TraceAnnotation(WINDOW)
            self._window.__enter__()

    def _close_round(self) -> None:
        if self._round is not None:
            self._round.__exit__(None, None, None)
            self._round = None

    def round(self, t: int) -> None:
        """Close the previous round's span and open round ``t``'s."""
        if self.dir:
            import jax
            self._close_round()
            self._round = jax.profiler.TraceAnnotation(ROUND, round=t)
            self._round.__enter__()

    def close_window(self) -> None:
        if self.dir and self._window is not None:
            self._close_round()
            self._window.__exit__(None, None, None)
            self._window = None


# ------------------------------------------------------------------ load

def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def op_name(name: str) -> str:
    """The HLO instruction name of a device event, whose name may be the
    whole instruction text (``%fusion.12 = f32[5,32] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Dict:
    """Plain events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append([op_name(e.name), float(e.start_ns),
                                float(e.duration_ns),
                                str(_stat(e, "hlo_module") or "")])
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), line.name])
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------- reduce

def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def _minus(a_set, b_union) -> float:
    """Length of the union ``a_set`` less what ``b_union`` covers."""
    total, j = 0.0, 0
    for a, b in a_set:
        covered = 0.0
        while j < len(b_union) and b_union[j][1] <= a:
            j += 1
        k = j
        while k < len(b_union) and b_union[k][0] < b:
            covered += min(b, b_union[k][1]) - max(a, b_union[k][0])
            k += 1
        total += (b - a) - covered
    return total


def window_of(host) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d, _ in host if n == WINDOW]
    if len(spans) != 1:
        raise RuntimeError(f"{len(spans)} {WINDOW} spans in the trace")
    return spans[0]


def reduce(events: Dict, n_devices: int) -> Dict:
    """Busy, idle, per-op and collective times of the traced window, in
    seconds, each device quantity averaged over ``n_devices`` chips."""
    t0, t1 = window_of(events["host"])
    planes = sorted(events["devices"])[:n_devices]
    if len(planes) < n_devices:
        raise RuntimeError(f"{len(planes)} device planes with ops, "
                           f"{n_devices} expected")
    busy = coll = exposed = 0.0
    ops: Dict[str, float] = {}
    gaps = []
    for p in planes:
        evs = [(n, s, s + d, m) for n, s, d, m in events["devices"][p]
               if s + d > t0 and s < t1]
        u = _union(_clip([(s, e) for _, s, e, _ in evs], t0, t1))
        busy += _length(u)
        compute = _union(_clip([(s, e) for n, s, e, _ in evs
                                if not COLLECTIVE.search(n)
                                and not WAITS.search(n)], t0, t1))
        cu = _union(_clip([(s, e) for n, s, e, _ in evs
                           if COLLECTIVE.search(n)], t0, t1))
        coll += _length(cu)
        exposed += _minus(cu, compute)
        for n, s, e, _ in evs:
            ops[n] = ops.get(n, 0.0) + min(e, t1) - max(s, t0)
        edges = [t0] + [x for iv in u for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = float(len(planes))
    window = (t1 - t0) / 1e9
    return {"window_s": window, "busy_s": busy / n / 1e9,
            "idle_share": 1.0 - busy / n / (t1 - t0),
            "ops_s": {k: v / n / 1e9 for k, v in ops.items()},
            "collective_s": coll / n / 1e9,
            "collective_exposed_s": exposed / n / 1e9,
            "gaps": _attribute(gaps, events["host"], t0, t1, n)}


def _attribute(gaps, host, t0, t1, n_planes) -> Dict[str, float]:
    """Idle time by the innermost host span (other than the window and
    round spans, unless nothing else covers it) around each gap's
    middle, in seconds averaged over the chips."""
    spans = sorted((s, s + d, name) for name, s, d, _ in host
                   if s + d > t0 and s < t1)
    out: Dict[str, float] = {}
    active, i = [], 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= mid]
        inner = [sp for sp in active if sp[2] not in (WINDOW, ROUND)]
        pick = min(inner or active, key=lambda sp: sp[1] - sp[0],
                   default=None)
        name = pick[2] if pick else "host: no span"
        out[name] = out.get(name, 0.0) + (b - a) / n_planes / 1e9
    return out


def breakdown(summary: Dict, top: int = 10) -> Dict:
    ops = sorted(summary["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
