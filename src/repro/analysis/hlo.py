"""Trip-count-aware HLO cost analysis (home of the repo's HLO parser).

Moved here from ``repro.launch.hlo_analysis`` so the static-analysis
subsystem (``repro.analysis.graph_audit``) and the launch tooling share
one parser; ``repro.launch.hlo_analysis`` remains as a re-export shim
for external callers.

``Compiled.cost_analysis()`` visits while-loop bodies ONCE, so any
scan-over-layers / scan-over-chunks program is undercounted by ~n_layers.
This module parses the optimized HLO text instead:

- builds a per-computation symbol table (instruction name -> shape),
- walks the call graph from ENTRY, multiplying while bodies by their
  ``known_trip_count`` backend config (nested loops compose),
- FLOPs: 2 * prod(output) * prod(lhs contracting dims) for every
  dot / dot-general (wherever it lives, incl. inside fusions),
- bytes: operands + outputs at fusion/instruction boundaries (fusion
  internals are one kernel => free),
- collective bytes: output sizes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute, per kind,
  trip-multiplied.

All numbers are per-device (the input text is the SPMD-partitioned module).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
    "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_PIECE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+)$")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*{\s*$")
_CALLED_RE = re.compile(r"(?:calls|body|to_apply|branch_computations)="
                        r"[{]?%?([\w\.\-]+(?:,\s*%[\w\.\-]+)*)[}]?")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_PIECE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _tuple_elements(type_str: str) -> List[str]:
    """Top-level element types of a tuple type string."""
    out, depth, start = [], 0, 1
    for i, ch in enumerate(type_str):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if (ch == "," and depth == 1) or depth == 0:
            out.append(type_str[start:i].strip())
            start = i + 1
        if depth == 0:
            break
    return out


def _collective_bytes(ins: "Instr") -> int:
    """Bytes one collective moves.  An async ``collective-permute-start``
    is typed ``(operand, result, u32[], u32[])`` on TPU (``(operand,
    result)`` elsewhere): only its operand goes over the wire."""
    if ins.op == "collective-permute-start" and ins.type_str.startswith("("):
        return _shape_bytes(_tuple_elements(ins.type_str)[0])
    return _shape_bytes(ins.type_str)


def _shape_dims(type_str: str) -> List[List[int]]:
    """All array shapes in a (possibly tuple) type string."""
    out = []
    for m in _SHAPE_PIECE.finditer(type_str):
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append(dims)
    return out


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str
    is_root: bool = False


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    is_entry: bool = False
    is_fusion: bool = False


_OP_TOKEN = re.compile(r"^([a-z][\w\-]*)\(")


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR.match(line.strip())
        if hdr and line.strip().endswith("{"):
            name = hdr.group(2)
            cur = Computation(name=name, is_entry=bool(hdr.group(1)),
                              is_fusion=name.startswith("fused_"))
            comps[name] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        is_root = line.lstrip().startswith("ROOT")
        name, rhs = m.group(1), m.group(2)
        # rhs = "<type> <op>(...), ..."
        # type may be tuple: ( ... ) — find op token after the type
        rhs_strip = rhs
        if rhs_strip.startswith("("):
            depth = 0
            for i, ch in enumerate(rhs_strip):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            type_str = rhs_strip[:i + 1]
            tail = rhs_strip[i + 1:].strip()
        else:
            sp = rhs_strip.find(" ")
            type_str = rhs_strip[:sp]
            tail = rhs_strip[sp + 1:].strip()
        om = _OP_TOKEN.match(tail)
        op = om.group(1) if om else tail.split("(")[0].strip()
        cur.instrs.append(Instr(name=name, type_str=type_str, op=op,
                                rest=tail, is_root=is_root))
    return comps


def _multiplicities(comps: Dict[str, Computation]) -> Dict[str, float]:
    """Execution count per computation, walking ENTRY -> callees."""
    entry = next((c.name for c in comps.values() if c.is_entry), None)
    mult: Dict[str, float] = {}

    def visit(name: str, m: float):
        if name not in comps:
            return
        mult[name] = mult.get(name, 0.0) + m
        for ins in comps[name].instrs:
            cm = _CALLED_RE.search(ins.rest)
            if not cm:
                continue
            callees = [c.strip().lstrip("%")
                       for c in cm.group(1).split(",")]
            child_m = m
            if ins.op == "while":
                tm = _TRIP_RE.search(ins.rest)
                trip = float(tm.group(1)) if tm else 1.0
                child_m = m * trip
            for c in callees:
                visit(c, child_m)
    if entry:
        visit(entry, 1.0)
    return mult


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "while", "conditional", "call", "after-all",
             "partition-id", "replica-id", "domain", "opt-barrier",
             "get-dimension-size", "iota"}


_PAIR_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_PAIR_ITEM_RE = re.compile(r"\{(\d+),(\d+)\}")
_GROUPS_RE = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")
_GROUP_ITEM_RE = re.compile(r"\{([\d,]*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")

_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _opname_bucket(rest: str) -> str:
    """Coarse attribution bucket from HLO metadata op_name."""
    m = _OPNAME_RE.search(rest)
    if not m:
        return "(none)"
    name = m.group(1)
    # e.g. jit(train_step)/while/body/remat/.../dot_general -> keep the
    # most informative middle segments
    parts = [p for p in name.split("/") if p and not p.startswith("jit(")]
    return "/".join(parts[:4]) if parts else "(root)"


@dataclass
class HLOCost:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    coll_by_op: Dict[str, float] = field(default_factory=dict)
    bytes_by_op: Dict[str, float] = field(default_factory=dict)

    @property
    def coll_total(self) -> float:
        return sum(self.collective_bytes.values())

    def top_collectives(self, n: int = 12):
        return sorted(self.coll_by_op.items(), key=lambda kv: -kv[1])[:n]

    def top_bytes(self, n: int = 12):
        return sorted(self.bytes_by_op.items(), key=lambda kv: -kv[1])[:n]


def _dus_update_bytes(ins: Instr, comps: Dict[str, Computation],
                      symtab: Dict[str, str]) -> Optional[float]:
    """If ``ins`` is (or is a fusion rooted in) a dynamic-update-slice whose
    output aliases its buffer operand, return the modeled in-place traffic:
    2x update-slice bytes + non-buffer operand bytes.  Else None."""
    if ins.op == "dynamic-update-slice":
        paren = ins.rest.find("(")
        close = ins.rest.find(")", paren)
        ops = _OPERAND_RE.findall(ins.rest[paren + 1:close])
        if len(ops) >= 2 and ops[1] in symtab:
            return 2.0 * _shape_bytes(symtab[ops[1]])
        return None
    if ins.op != "fusion":
        return None
    cm = _CALLED_RE.search(ins.rest)
    if not cm:
        return None
    callee = comps.get(cm.group(1).strip().lstrip("%"))
    if callee is None or not callee.instrs:
        return None
    root = callee.instrs[-1]
    # XLA:CPU legalizes bf16 by wrapping compute in f32 converts; on the
    # TPU target the DUS is native — see through trailing convert/bitcast
    inner_tab0 = {i.name: i.type_str for i in callee.instrs}
    seen = 0
    while root.op in ("convert", "bitcast", "copy") and seen < 4:
        paren = root.rest.find("(")
        close = root.rest.find(")", paren)
        ops = _OPERAND_RE.findall(root.rest[paren + 1:close])
        nxt = next((i for i in callee.instrs if ops and i.name == ops[0]),
                   None)
        if nxt is None:
            break
        root = nxt
        seen += 1
    if root.op == "dynamic-slice" or (
            callee.instrs and any(i.op == "dynamic-slice"
                                  for i in callee.instrs)
            and all(i.op in _LEGAL_OPS | {"dynamic-slice"}
                    for i in callee.instrs)):
        # slice-read fusion: traffic = slice out + slice in, not the buffer
        return 2.0 * _shape_bytes(ins.type_str)
    if root.op != "dynamic-update-slice":
        return None
    # update operand of the root DUS, resolved in the fused computation
    inner_tab = {i.name: i.type_str for i in callee.instrs}
    paren = root.rest.find("(")
    close = root.rest.find(")", paren)
    ops = _OPERAND_RE.findall(root.rest[paren + 1:close])
    upd = 0.0
    if len(ops) >= 2 and ops[1] in inner_tab:
        upd = _shape_bytes(inner_tab[ops[1]])
    else:
        return None
    # non-buffer outer operands (buffer = operand with same type as output)
    paren = ins.rest.find("(")
    close = ins.rest.find(")", paren)
    outer_ops = _OPERAND_RE.findall(ins.rest[paren + 1:close])
    extra = 0.0
    buffer_skipped = False
    for o in outer_ops:
        t = symtab.get(o)
        if t is None:
            continue
        if not buffer_skipped and _shape_bytes(t) == _shape_bytes(
                ins.type_str):
            buffer_skipped = True        # the aliased buffer: free
            continue
        extra += _shape_bytes(t)
    return 2.0 * upd + extra


_LEGAL_OPS = {"parameter", "constant", "convert", "bitcast", "copy",
              "reshape", "transpose"}


def _is_legalization_fusion(ins: Instr, comps: Dict[str, Computation]
                            ) -> bool:
    if ins.op != "fusion":
        return False
    cm = _CALLED_RE.search(ins.rest)
    if not cm:
        return False
    callee = comps.get(cm.group(1).strip().lstrip("%"))
    if callee is None:
        return False
    return all(i.op in _LEGAL_OPS for i in callee.instrs)


def _is_legalization_convert(ins: Instr, symtab: Dict[str, str]) -> bool:
    """Standalone bf16<->f32 convert of a whole buffer: XLA:CPU keeps
    loop carries in f32; native bf16 on TPU."""
    if ins.op != "convert":
        return False
    t_out = ins.type_str
    paren = ins.rest.find("(")
    close = ins.rest.find(")", paren)
    ops = _OPERAND_RE.findall(ins.rest[paren + 1:close])
    if not ops or ops[0] not in symtab:
        return False
    t_in = symtab[ops[0]]
    kinds = {t_out.split("[")[0], t_in.split("[")[0]}
    return kinds == {"f32", "bf16"}


def _scatter_inplace_bytes(ins: Instr, comps: Dict[str, Computation],
                           symtab: Dict[str, str]) -> Optional[float]:
    """Scatter updates the buffer in place: traffic = indices + 2x updates,
    not the whole buffer.  Handles bare scatter and fusion-wrapped scatter
    (``wrapped_scatter``)."""
    root = ins
    if ins.op == "fusion":
        cm = _CALLED_RE.search(ins.rest)
        callee = comps.get(cm.group(1).strip().lstrip("%")) if cm else None
        if callee is None or not any(i.op == "scatter" for i in callee.instrs):
            return None
        if not all(i.op in _LEGAL_OPS | {"scatter"} for i in callee.instrs):
            return None
    elif ins.op != "scatter":
        return None
    # operands: (buffer, indices, updates) — buffer matches output size
    paren = ins.rest.find("(")
    close = ins.rest.find(")", paren)
    ops = _OPERAND_RE.findall(ins.rest[paren + 1:close])
    out_bytes = _shape_bytes(ins.type_str)
    total = 0.0
    buffer_skipped = False
    for o in ops:
        t = symtab.get(o)
        if t is None:
            continue
        bb = _shape_bytes(t)
        if not buffer_skipped and bb == out_bytes:
            buffer_skipped = True
            continue
        total += bb
    return 2.0 * total if buffer_skipped else None


def _parse_pairs(rest: str) -> Optional[List[Tuple[int, int]]]:
    """collective-permute source_target_pairs, or None when absent."""
    m = _PAIR_RE.search(rest)
    if not m:
        return None
    return [(int(a), int(b)) for a, b in _PAIR_ITEM_RE.findall(m.group(1))]


def _parse_replica_groups(rest: str) -> Optional[List[List[int]]]:
    """Device groups of a reduction collective.  Handles the literal
    ``{{0,1},{2,3}}`` form and the iota v2 form ``[g,s]<=[dims]T(perm)``
    (arange over prod(dims), reshaped to dims, transposed by perm,
    flattened, then split into g groups of s).  ``{{}}``/missing groups
    mean all devices; returns None only when the attribute is present
    but unparseable."""
    m = _GROUPS_RE.search(rest)
    if m:
        groups = [[int(x) for x in g.split(",") if x]
                  for g in _GROUP_ITEM_RE.findall(m.group(1))]
        return [g for g in groups if g]
    m = _GROUPS_IOTA_RE.search(rest)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",") if d]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",") if p]
            ids = ids.transpose(perm)
        return ids.reshape(g, s).tolist()
    if "replica_groups=" in rest:
        return None
    return []           # no groups attribute: all devices


#: reduction-style collectives whose replica_groups decide pod crossing
_REDUCE_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all")


@dataclass
class PodExchange:
    """Where a multi-pod program's collective traffic actually flows.

    The gossip/exchange contract for the pod-stacked train step: the
    model exchange must be collective-permutes whose cross-pod pairs move
    along the ``pod`` axis *only* (source and target share their
    intra-pod coordinate), and cross-pod reduction traffic must stay
    small relative to the permute exchange (GSPMD reshard noise aside,
    gossip that leaks into reduction collectives is a regression — the
    dryrun gossip gate enforces the ratio).  Bytes are per-device,
    trip-multiplied, using the same conventions as :func:`analyze`.
    """
    devices_per_pod: int
    permute_cross_bytes: float = 0.0     # collective-permute across pods
    permute_local_bytes: float = 0.0     # collective-permute inside a pod
    reduce_cross_bytes: float = 0.0      # reductions whose groups span pods
    reduce_local_bytes: float = 0.0      # reductions inside a single pod
    pod_axis_only: bool = True           # every cross-pod permute pair
    #                                      preserves the intra-pod coord
    unparsed: int = 0                    # collectives we could not classify

    @property
    def cross_pod_bytes(self) -> float:
        return self.permute_cross_bytes + self.reduce_cross_bytes


def pod_exchange_report(text: str, devices_per_pod: int) -> PodExchange:
    """Classify every collective in the partitioned HLO by whether it
    crosses the pod boundary (device ids are pod-major: pod p owns ids
    ``[p*devices_per_pod, (p+1)*devices_per_pod)``)."""
    comps = parse_module(text)
    mult = _multiplicities(comps)
    rep = PodExchange(devices_per_pod=devices_per_pod)
    dpp = devices_per_pod
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m == 0.0:
            continue
        for ins in comp.instrs:
            if ins.op.endswith("-done"):
                continue                 # bytes counted at the -start
            base = ins.op[:-6] if ins.op.endswith("-start") else ins.op
            b = m * _collective_bytes(ins)
            if base == "collective-permute":
                pairs = _parse_pairs(ins.rest)
                if pairs is None:
                    rep.unparsed += 1
                    continue
                cross = [(a, t) for a, t in pairs if a // dpp != t // dpp]
                if cross:
                    rep.permute_cross_bytes += b
                    if any(a % dpp != t % dpp for a, t in cross):
                        rep.pod_axis_only = False
                else:
                    rep.permute_local_bytes += b
            elif base in _REDUCE_COLLECTIVES:
                groups = _parse_replica_groups(ins.rest)
                if groups is None:
                    rep.unparsed += 1
                    rep.reduce_cross_bytes += b   # conservative
                    continue
                if not groups:                    # all devices
                    rep.reduce_cross_bytes += b
                elif any(len({g // dpp for g in grp}) > 1
                         for grp in groups):
                    rep.reduce_cross_bytes += b
                else:
                    rep.reduce_local_bytes += b
            elif base in ("collective-broadcast", "send", "recv",
                          "ragged-all-to-all"):
                # a collective kind this report can't classify: surface
                # it instead of silently under-stating cross-pod traffic
                rep.unparsed += 1
    return rep


def analyze(text: str) -> HLOCost:
    comps = parse_module(text)
    mult = _multiplicities(comps)
    cost = HLOCost(collective_bytes={k: 0.0 for k in COLLECTIVES})

    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m == 0.0:
            continue
        symtab = {i.name: i.type_str for i in comp.instrs}
        for ins in comp.instrs:
            # ---- flops: dots (count even inside fusions) ----
            if ins.op == "dot":
                out_dims_list = _shape_dims(ins.type_str)
                out_elems = 1
                for d in (out_dims_list[0] if out_dims_list else []):
                    out_elems *= d
                cmatch = _CONTRACT_RE.search(ins.rest)
                k = 1
                if cmatch:
                    ops = _OPERAND_RE.findall(
                        ins.rest[ins.rest.find("(") + 1:ins.rest.find(")")])
                    if ops and ops[0] in symtab:
                        lhs_dims = _shape_dims(symtab[ops[0]])
                        if lhs_dims:
                            for ci in cmatch.group(1).split(","):
                                if ci:
                                    ci = int(ci)
                                    if ci < len(lhs_dims[0]):
                                        k *= lhs_dims[0][ci]
                cost.flops += m * 2.0 * out_elems * k
            if ins.op in ("convolution",):
                # rough: 2 * out_elems * kernel_elems (per out channel set)
                out_dims_list = _shape_dims(ins.type_str)
                out_elems = 1
                for d in (out_dims_list[0] if out_dims_list else []):
                    out_elems *= d
                cost.flops += m * 2.0 * out_elems  # lower bound
            # ---- collectives ----
            for kind in COLLECTIVES:
                if ins.op == kind or ins.op == kind + "-start":
                    b = m * _collective_bytes(ins)
                    cost.collective_bytes[kind] += b
                    bucket = f"{kind}:{_opname_bucket(ins.rest)}"
                    cost.coll_by_op[bucket] = (
                        cost.coll_by_op.get(bucket, 0.0) + b)
            # ---- bytes at kernel boundaries ----
            if comp.is_fusion:
                continue                      # internals are one kernel
            if ins.op in _FREE_OPS or ins.op.endswith("-done"):
                continue
            out_b = _shape_bytes(ins.type_str)
            in_b = 0
            paren = ins.rest.find("(")
            close = ins.rest.find(")", paren)
            operands = []
            if paren >= 0 and close > paren:
                operands = _OPERAND_RE.findall(ins.rest[paren + 1:close])
                for opnd in operands:
                    if opnd in symtab:
                        in_b += _shape_bytes(symtab[opnd])
            # in-place dynamic-update-slice (scan carries / ys-stacking):
            # XLA updates the buffer in place; real traffic is the slice,
            # not the whole buffer.  Model that instead of buffer*2.
            dus_update = _dus_update_bytes(ins, comps, symtab)
            scatter_b = _scatter_inplace_bytes(ins, comps, symtab)
            if dus_update is not None:
                b = m * dus_update
            elif scatter_b is not None:
                b = m * scatter_b
            elif _is_legalization_fusion(ins, comps) or \
                    _is_legalization_convert(ins, symtab):
                # pure convert/bitcast = XLA:CPU bf16 legalization;
                # free on the TPU target this analysis models
                b = 0.0
            else:
                b = m * (out_b + in_b)
            cost.bytes_accessed += b
            bucket = _opname_bucket(ins.rest)
            if bucket == "(none)":
                bucket = f"(none):{ins.op}"
            cost.bytes_by_op[bucket] = cost.bytes_by_op.get(bucket, 0.0) + b
    return cost
