"""Graph auditor: invariant checks over the partitioned step HLO.

Extends the single pod-exchange check ``launch/dryrun.py`` has enforced
since PR 4 into a general audit of the compiled train-step graph.  The
incidents behind each rule are real: gossip once leaked off the pod
axis, and adpsgd's payload silently widened bf16 to f32 on the wire
until PR 4 pinned the leaf dtype.

Rules:

* **GA201 off-pod-axis** — a cross-pod collective-permute pair does not
  preserve the intra-pod device coordinate: gossip is leaking off the
  ``pod`` mesh axis.
* **GA202 wire-dtype-widening** — a cross-pod transfer ships a floating
  dtype wider than the model's leaf dtype (expected wire dtype inferred
  as the narrowest float among ENTRY parameters unless given): bf16
  payloads must not widen to f32 on the wire.
* **GA203 host-callback** — a host callback (``custom-call`` into a
  Python/host target, or infeed/outfeed) inside the step graph: a
  device->host round-trip per step that no profiler of device time will
  show.
* **GA204 donation-drift** — the entry's ``input_output_alias`` map is
  missing (donation silently lost) or an aliased output's type no
  longer matches its donated parameter (step ``t``'s output cannot feed
  step ``t+1`` without a realloc/reshard).
* **GA205 unclassified-collective** — a collective the pod classifier
  cannot attribute (send/recv, broadcast, unparseable groups):
  cross-pod byte totals would silently understate the exchange.

``audit_hlo`` returns findings plus a machine-readable summary — the
CLI (``python -m repro.analysis``) lands both in ``out/AUDIT.json``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.analysis.base import Finding
from repro.analysis import hlo

RULES = {
    "GA201": "off-pod-axis",
    "GA202": "wire-dtype-widening",
    "GA203": "host-callback",
    "GA204": "donation-drift",
    "GA205": "unclassified-collective",
}

_FLOAT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                "f8e4m3fn": 1, "f8e5m2": 1}

_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\}")
_PARAM_NUM_RE = re.compile(r"parameter\((\d+)\)")
_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')

#: custom-call targets that round-trip through the host per step
_HOST_TARGET_HINTS = ("callback", "host", "py_", "python")


def _first_dtype(type_str: str) -> Optional[str]:
    m = hlo._SHAPE_PIECE.search(type_str)
    return m.group(1) if m else None


def _strip_layout(type_str: str) -> str:
    """Drop layout annotations and inline ``/*index=N*/`` comments:
    ``/*index=5*/f32[1,2]{1,0}`` -> ``f32[1,2]``."""
    s = re.sub(r"/\*.*?\*/", "", type_str)
    return re.sub(r"\]\{[\d,]*\}", "]", s).strip()


def _split_tuple(type_str: str) -> List[str]:
    """Top-level elements of a tuple type string (non-tuples: [self])."""
    s = type_str.strip()
    if not s.startswith("("):
        return [s]
    s = s[1:-1] if s.endswith(")") else s[1:]
    out, depth, start = [], 0, 0
    for i, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(s[start:i].strip())
            start = i + 1
    tail = s[start:].strip()
    if tail:
        out.append(tail)
    return out


def _navigate(type_str: str, index_path: List[int]) -> Optional[str]:
    """Element type at a nested tuple index path (``[]`` = whole)."""
    cur = type_str
    for i in index_path:
        elems = _split_tuple(cur)
        if i >= len(elems):
            return None
        cur = elems[i]
    return cur


def parse_alias_map(text: str) -> Optional[List[Tuple[List[int], int,
                                                      List[int]]]]:
    """The module's ``input_output_alias`` entries as
    (output index path, param number, param index path), or None when
    the module declares no aliasing at all."""
    # the alias map lives on the HloModule header line; the map nests
    # braces ({0}: (0, {}, may-alias)), so extract the balanced span
    hdr = next((ln for ln in text.splitlines()
                if "input_output_alias=" in ln), None)
    if hdr is None:
        return None
    start = hdr.find("input_output_alias=")
    open_i = hdr.find("{", start)
    if open_i < 0:
        return None
    depth = 0
    close_i = open_i
    for i in range(open_i, len(hdr)):
        depth += hdr[i] == "{"
        depth -= hdr[i] == "}"
        if depth == 0:
            close_i = i
            break
    body = hdr[open_i + 1:close_i]
    entries = []
    for out_idx, pnum, pidx in _ALIAS_ENTRY_RE.findall(body):
        entries.append((
            [int(x) for x in out_idx.replace(" ", "").split(",") if x],
            int(pnum),
            [int(x) for x in pidx.replace(" ", "").split(",") if x]))
    return entries


@dataclass
class GraphAudit:
    """Findings + the machine-readable summary for AUDIT.json."""
    tag: str
    combo: Optional[str] = None
    findings: List[Finding] = field(default_factory=list)
    pod_exchange: Optional[hlo.PodExchange] = None
    expected_wire_dtype: Optional[str] = None
    cross_pod_dtype_bytes: Dict[str, float] = field(default_factory=dict)
    host_callbacks: List[str] = field(default_factory=list)
    donated_pairs: int = 0
    n_params: int = 0

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> Dict:
        pex = None
        if self.pod_exchange is not None:
            p = self.pod_exchange
            pex = {
                "devices_per_pod": p.devices_per_pod,
                "permute_cross_bytes": p.permute_cross_bytes,
                "permute_local_bytes": p.permute_local_bytes,
                "reduce_cross_bytes": p.reduce_cross_bytes,
                "reduce_local_bytes": p.reduce_local_bytes,
                "pod_axis_only": p.pod_axis_only,
                "unparsed": p.unparsed,
            }
        return {
            "tag": self.tag, "combo": self.combo, "ok": self.ok,
            "pod_exchange": pex,
            "expected_wire_dtype": self.expected_wire_dtype,
            "cross_pod_dtype_bytes": self.cross_pod_dtype_bytes,
            "host_callbacks": self.host_callbacks,
            "donated_pairs": self.donated_pairs,
            "n_params": self.n_params,
            "findings": [f.to_json() for f in self.findings],
        }


def _entry(comps: Dict[str, hlo.Computation]
           ) -> Optional[hlo.Computation]:
    return next((c for c in comps.values() if c.is_entry), None)


def infer_wire_dtype(comps: Dict[str, hlo.Computation]) -> Optional[str]:
    """Narrowest floating dtype among ENTRY parameters — the model's
    leaf dtype, i.e. the widest thing that should legitimately cross
    pods in a gossip exchange."""
    ent = _entry(comps)
    if ent is None:
        return None
    best: Optional[str] = None
    for ins in ent.instrs:
        if ins.op != "parameter":
            continue
        for m in hlo._SHAPE_PIECE.finditer(ins.type_str):
            dt = m.group(1)
            if dt in _FLOAT_BYTES and (
                    best is None
                    or _FLOAT_BYTES[dt] < _FLOAT_BYTES[best]):
                best = dt
    return best


def audit_hlo(text: str, *, tag: str = "<hlo>",
              combo: Optional[str] = None,
              devices_per_pod: Optional[int] = None,
              expected_wire_dtype: Optional[str] = None,
              check_wire_dtype: bool = True,
              check_pod_axis: bool = True,
              expect_donation: bool = False) -> GraphAudit:
    """Audit one partitioned HLO module.

    ``combo`` labels the sweep row (``shape/strategy/topology``) this
    module came from — the coverage matrix in AUDIT.json keys on it.
    ``devices_per_pod`` enables the pod-axis / cross-pod rules (GA201,
    GA202 restricted to cross-pod transfers, GA205); without it GA202
    considers every collective-permute a wire transfer.
    ``check_pod_axis=False`` disables GA201 while keeping the
    pod-exchange report and GA205: the coordinate-preservation
    invariant is a *gossip-exchange* contract — non-gossip strategies
    legitimately let GSPMD reshard with arbitrary cross-pod permutes.
    ``expect_donation`` turns a missing ``input_output_alias`` map into
    a GA204 finding (train steps donate their state; serve/prefill
    don't have to).
    """
    rep = GraphAudit(tag=tag, combo=combo)
    comps = hlo.parse_module(text)
    mult = hlo._multiplicities(comps)

    def emit(rule: str, message: str, source: str) -> None:
        rep.findings.append(Finding(rule=rule, path=tag, line=0,
                                    message=message, source=source))

    # ---- pod-axis classification (GA201 / GA205) ----
    if devices_per_pod is not None:
        pex = hlo.pod_exchange_report(text, devices_per_pod)
        rep.pod_exchange = pex
        if check_pod_axis and not pex.pod_axis_only:
            emit("GA201",
                 "cross-pod collective-permute pair does not preserve "
                 "the intra-pod device coordinate — gossip is leaking "
                 "off the pod axis", "pod_axis_only")
        if pex.unparsed:
            emit("GA205",
                 f"{pex.unparsed} collective(s) the pod classifier "
                 "cannot attribute (send/recv, broadcast, or "
                 "unparseable replica groups) — cross-pod bytes would "
                 "silently understate the exchange", "unparsed")

    # ---- wire dtype (GA202) ----
    expected = expected_wire_dtype or infer_wire_dtype(comps)
    rep.expected_wire_dtype = expected if check_wire_dtype else None
    if check_wire_dtype and expected in _FLOAT_BYTES:
        exp_b = _FLOAT_BYTES[expected]
        for comp in comps.values():
            m = mult.get(comp.name, 0.0)
            if m == 0.0:
                continue
            for ins in comp.instrs:
                base = ins.op[:-6] if ins.op.endswith("-start") else ins.op
                if base != "collective-permute" or ins.op.endswith("-done"):
                    continue
                if devices_per_pod is not None:
                    pairs = hlo._parse_pairs(ins.rest)
                    cross = pairs and any(
                        a // devices_per_pod != t // devices_per_pod
                        for a, t in pairs)
                    if not cross:
                        continue
                dt = _first_dtype(ins.type_str)
                if dt is None:
                    continue
                b = m * hlo._collective_bytes(ins)
                rep.cross_pod_dtype_bytes[dt] = \
                    rep.cross_pod_dtype_bytes.get(dt, 0.0) + b
                if dt in _FLOAT_BYTES and _FLOAT_BYTES[dt] > exp_b:
                    emit("GA202",
                         f"cross-pod transfer `{ins.name}` ships {dt} "
                         f"but the leaf dtype is {expected} — the "
                         "payload widened on the wire "
                         f"({hlo._collective_bytes(ins)} bytes/step)",
                         ins.name)

    # ---- host callbacks (GA203) ----
    for comp in comps.values():
        if mult.get(comp.name, 0.0) == 0.0:
            continue
        for ins in comp.instrs:
            if ins.op in ("infeed", "outfeed"):
                rep.host_callbacks.append(ins.op)
                emit("GA203",
                     f"`{ins.op}` in the step graph: a device<->host "
                     "transfer every step", ins.name)
            elif ins.op == "custom-call":
                tm = _CC_TARGET_RE.search(ins.rest)
                target = tm.group(1) if tm else ""
                if any(h in target.lower() for h in _HOST_TARGET_HINTS):
                    rep.host_callbacks.append(target)
                    emit("GA203",
                         f"host callback `{target}` in the step graph "
                         "— a Python round-trip per step that device "
                         "profiles never show", ins.name)
            elif ins.op in ("send", "recv") and \
                    "is_host_transfer=true" in ins.rest:
                rep.host_callbacks.append(ins.op)
                emit("GA203",
                     f"host-transfer `{ins.op}` in the step graph",
                     ins.name)

    # ---- donation / resharding drift (GA204) ----
    ent = _entry(comps)
    if ent is not None:
        params = {}
        for ins in ent.instrs:
            if ins.op == "parameter":
                pm = _PARAM_NUM_RE.search(ins.rest)
                if pm:
                    params[int(pm.group(1))] = ins.type_str
        rep.n_params = len(params)
        root = next((i for i in ent.instrs if i.is_root),
                    ent.instrs[-1] if ent.instrs else None)
        alias = parse_alias_map(text)
        if alias is None:
            if expect_donation:
                emit("GA204",
                     "module declares no input_output_alias: the donated "
                     "state buffers were silently lost — every step "
                     "reallocates the whole train state", "no-alias-map")
        elif root is not None:
            rep.donated_pairs = len(alias)
            for out_path, pnum, p_path in alias:
                out_t = _navigate(root.type_str, out_path)
                par_t = params.get(pnum)
                if par_t is not None and p_path:
                    par_t = _navigate(par_t, p_path)
                if out_t is None or par_t is None:
                    continue
                if _strip_layout(out_t) != _strip_layout(par_t):
                    emit("GA204",
                         f"donated buffer drift: output {out_path or [0]}"
                         f" is `{_strip_layout(out_t)}` but aliased "
                         f"parameter {pnum} is `{_strip_layout(par_t)}` "
                         "— step t's output cannot feed step t+1 "
                         "without a realloc/reshard", f"alias:{pnum}")
    return rep
