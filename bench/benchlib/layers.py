"""From the program's own spans and scopes to a round's time by layer.

The program marks its layers on both sides of the chip:

* on the host, ``repro.obs`` spans (``trainer.load``, ``trainer.put``,
  ``trainer.dispatch``, ``trainer.wait``, ...), kept in memory by a
  recorder and, with ``annotate=True`` under the profiler, written into
  the trace as ``TraceAnnotation`` events that carry their round;
* on the device, ``jax.named_scope("local_step")`` and
  ``jax.named_scope("exchange")`` in each strategy's step, which XLA
  keeps in each instruction's ``metadata={op_name=...}``.

A TPU trace's op events carry no such path: an event of the ``XLA Ops``
line is named by its HLO instruction, and the ``XLA Modules`` line says
which program ran when.  So ``load`` gives each op its module, and
``hlo_scopes`` reads each instruction's path from the compiled program's
text (``jax.stages.Compiled.as_text()``); ``attach`` joins the two.

``load`` turns an ``.xplane.pb`` into plain lists, and the reductions
work on those lists only, so a small trace recorded on the chip and kept
as JSON (``bench/testdata``) checks them on any machine:

* ``by_scope``: the chip's busy time under each scope (the union of its
  ops' intervals), what no scope covers, and all of it;
* ``inside``: the chip's busy time inside each program span;
* ``kernel_split``: under each scope, the program's Pallas kernels' op
  events apart from the other ops: their summed time and the bytes their
  instructions read and write (``hlo_bytes``, from the same text), to
  set beside the chip's HBM bandwidth;
* ``twins``: the recorder's spans that have no twin (same name and
  round) among the trace's host events.

Everything is clipped to the host span ``bench.window``, and device
quantities are averaged over chips, as in ``benchlib/trace.py``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import Counter
from typing import Dict, Iterable, List, Optional, Tuple

from benchlib.trace import (OPS_LINE, _clip, _length, _minus, _union,
                            op_name, window_of)

MODULES_LINE = "XLA Modules"

SCOPES = ("local_step", "exchange")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=$|[/)])")
#: the program's span names start so
SPAN_PREFIX = "trainer."
#: the ``name=`` of each ``pallas_call`` in ``src/repro/kernels``: their
#: op events are ``<name>.N`` (``abs_histogram`` also heads
#: ``abs_histogram_fused``)
KERNELS = ("gaia_select", "neighbor_mix", "rand_k_select", "dgc_select",
           "abs_histogram", "flash_attention", "group_norm")


def scope_of(path: str) -> Optional[str]:
    """The outermost of ``SCOPES`` in an op's path of scopes
    (``jit(step)/exchange/jit(_gaia_pallas)/...``), or None."""
    m = _SCOPE.search(path or "")
    return m.group(1) if m else None


def _stats(ev) -> Dict:
    return {k: v for k, v in ev.stats}


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _module_of(ops, modules) -> None:
    """Append to each op the name of the module whose run holds its
    start ("" where none does)."""
    modules.sort()
    starts = [m[0] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        op.append(modules[i][2] if i >= 0 and op[1] < modules[i][1]
                  else "")


def load(path: str) -> Dict:
    """Plain events of one ``.xplane.pb``: device ops as ``[name, start_ns,
    dur_ns, scope path, module]`` by device plane (the path from the
    event's ``tf_op`` stat, "" where it has none), and host events as
    ``[name, start_ns, dur_ns, round]`` (round None where the event has
    none)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                for e in line.events:
                    if line.name == OPS_LINE:
                        ops.append([op_name(e.name), float(e.start_ns),
                                    float(e.duration_ns),
                                    str(_stats(e).get("tf_op") or "")])
                    elif line.name == MODULES_LINE:
                        modules.append((float(e.start_ns),
                                        float(e.start_ns + e.duration_ns),
                                        e.name.split("(", 1)[0]))
            _module_of(ops, modules)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        r = _stats(e).get("round")
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns),
                                     None if r is None else int(r)])
    return {"devices": devices, "host": host}


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%(\S+) = .*?op_name="([^"]*)"', re.M)


def hlo_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """A compiled program's module name and each instruction's path of
    scopes, from its HLO text."""
    m = re.search(r"^HloModule ([^\s,]+)", text, re.M)
    if not m:
        raise ValueError("no HloModule line in the program's text")
    return m.group(1), dict(_INSTR.findall(text))


_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+) = (\(.*?\)|\S+) [\w-]+\(([^)]*)\)", re.M)
_ARRAY = re.compile(r"\b(pred|bf16|[fsuc]\d+)\[([\d,]*)\]")


def _nbytes(shape: str) -> int:
    n = 0
    for dtype, dims in _ARRAY.findall(shape):
        size = 1 if dtype == "pred" else int(dtype.lstrip("bfsuc")) // 8
        for d in filter(None, dims.split(",")):
            size *= int(d)
        n += size
    return n


def hlo_bytes(text: str) -> Dict[str, Tuple[int, int]]:
    """The bytes each instruction of a compiled program's text reads (the
    results of its operands, each in full) and writes (its own result),
    by instruction name."""
    lines = _LINE.findall(text)
    out = {name: _nbytes(shape) for name, shape, _ in lines}
    return {name: (sum(out.get(o, 0)
                       for o in re.findall(r"%([^\s,]+)", args)), out[name])
            for name, _, args in lines}


def attach(events: Dict, module: str, paths: Dict[str, str]) -> None:
    """Give the ops of ``module`` that have no path the one ``paths``
    holds for their instruction."""
    for ops in events["devices"].values():
        for op in ops:
            if not op[3] and op[4] == module:
                op[3] = paths.get(op[0], "")


def _planes(events, n_devices):
    planes = sorted(events["devices"])[:n_devices]
    if len(planes) < n_devices:
        raise RuntimeError(f"{len(planes)} device planes with ops, "
                           f"{n_devices} expected")
    return planes


def by_scope(events: Dict, n_devices: int = 1) -> Dict[str, float]:
    """Seconds of chip busy time in the window under each of ``SCOPES``,
    under none (``unscoped``) and in all (``busy``), averaged over
    chips."""
    t0, t1 = window_of(events["host"])
    planes = _planes(events, n_devices)
    out = dict.fromkeys(SCOPES + ("unscoped", "busy"), 0.0)
    for p in planes:
        ivs: Dict[Optional[str], List[Tuple[float, float]]] = {}
        for _, s, d, path, *_ in events["devices"][p]:
            ivs.setdefault(scope_of(path), []).append((s, s + d))
        scoped = []
        for sc in SCOPES:
            u = _union(_clip(ivs.get(sc, []), t0, t1))
            out[sc] += _length(u)
            scoped += u
        busy = _union(_clip([iv for v in ivs.values() for iv in v], t0, t1))
        out["busy"] += _length(busy)
        out["unscoped"] += _minus(busy, _union(scoped))
    return {k: v / len(planes) / 1e9 for k, v in out.items()}


def inside(events: Dict, n_devices: int = 1,
           prefix: str = SPAN_PREFIX) -> Dict[str, float]:
    """Seconds of chip busy time in the window that fall inside the host
    spans of each name that starts with ``prefix``, averaged over
    chips."""
    t0, t1 = window_of(events["host"])
    planes = _planes(events, n_devices)
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for name, s, d, _ in events["host"]:
        if name.startswith(prefix):
            spans.setdefault(name, []).append((s, s + d))
    out = dict.fromkeys(spans, 0.0)
    for p in planes:
        busy = _union(_clip([(o[1], o[1] + o[2])
                             for o in events["devices"][p]], t0, t1))
        for name, ivs in spans.items():
            out[name] += _length(busy) - _minus(busy, _union(ivs))
    return {k: v / len(planes) / 1e9 for k, v in out.items()}


def kernel_split(events: Dict, sizes: Dict[str, Tuple[int, int]],
                 n_devices: int = 1) -> Dict[Optional[str], Dict]:
    """Under each scope (None for none), the op events of ``KERNELS``
    (``kernel``) and of the other ops (``other``) that start in the
    window: ``n`` events, their summed device ``seconds``, and the bytes
    their instructions ``read`` and ``written`` by ``sizes``
    (``hlo_bytes``), averaged over chips."""
    t0, t1 = window_of(events["host"])
    planes = _planes(events, n_devices)
    out: Dict[Optional[str], Dict] = {}
    for p in planes:
        for name, s, d, path, *_ in events["devices"][p]:
            if not t0 <= s < t1:
                continue
            kind = "kernel" if name.startswith(KERNELS) else "other"
            got = out.setdefault(scope_of(path), {}).setdefault(
                kind, dict.fromkeys(("n", "seconds", "read", "written"), 0))
            read, written = sizes.get(name, (0, 0))
            got["n"] += 1
            got["seconds"] += d / 1e9
            got["read"] += read
            got["written"] += written
    return {sc: {k: {q: x / len(planes) for q, x in v.items()}
                 for k, v in kinds.items()} for sc, kinds in out.items()}


def twins(spans: Iterable[Tuple[str, int]], host) -> List[Tuple[str, int]]:
    """The ``(name, round)`` spans, one entry each, that the trace's host
    events do not hold as often."""
    have = Counter((n, r) for n, _, _, r in host if r is not None)
    missing = Counter(spans) - have
    return sorted(missing.elements())
