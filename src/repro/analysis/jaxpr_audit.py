"""Jaxpr-level dataflow audit: the pre-lowering half of the graph gate.

The HLO auditor (:mod:`repro.analysis.graph_audit`) sees the step graph
*after* XLA has folded it — by which point constant-folding and fusion
can have erased exactly the hazards it was meant to catch (a host sync
folded into a fused loop, a widening convert absorbed into a collective
lowering).  This pass walks the **closed jaxpr** of every step builder
instead — ``jax.make_jaxpr`` output, recursing into ``pjit`` / ``scan``
/ ``while`` / ``cond`` / ``shard_map`` sub-jaxprs — so the whole
strategy x topology matrix can be audited without ever invoking XLA:
tracing is ~0.5 s per combo where compiling is ~10x that.

Rules (JA4xx; suppressible only via the fingerprint baseline — jaxprs
have no source lines to carry ``# repro-allow:`` markers):

* **JA400 step-trace-failure** — a combo in the audit matrix failed to
  trace at all.  Emitted by :func:`audit_combos` so a broken builder is
  a finding, never a silently-unaudited row in the coverage matrix.
* **JA401 host-callback-in-step** — a host callback (``pure_callback``,
  ``io_callback``, ``debug_callback`` — i.e. ``jax.debug.print`` —
  infeed/outfeed) or an IO effect reachable from a train/serve step:
  a device->host round-trip per step, caught before XLA can disguise
  it as a fused custom-call.
* **JA402 widen-into-collective** — a collective ships a floating dtype
  wider than the narrowest float leaf it dataflow-traces back to, with
  the widening ``convert_element_type`` named when found on the path:
  the adpsgd bf16->f32 wire bug (PR 4) caught *before* lowering.  The
  legitimate accumulate-in-f32-then-narrow pattern does not fire — the
  wire operand itself must be wide.
* **JA403 off-pod-axis-collective** — a collective whose ``axis_name``
  is not the pod axis: gossip exchange belongs on the scarce cross-pod
  links; every other mesh axis is GSPMD's to schedule.
* **JA404 large-closed-constant** — a constant above the size threshold
  closed over into the jaxpr (any scope).  Baked-in arrays silently
  bloat every executable and force a recompile whenever their value
  changes — they belong in the step's runtime operands.
* **JA405 rng-key-not-from-args** — an RNG primitive whose key does not
  dataflow-trace back to a step argument: the step resamples the same
  stream every call (or bakes entropy at trace time).  The trace-level
  twin of AST rule RA101's unkeyed-randomness check.

The audit itself imports no JAX — it duck-types jaxpr objects (``eqns``
/ ``invars`` / ``primitive``), so ``repro.analysis`` stays importable
without jax and tests can feed it hand-built traces.  Only
:func:`audit_combos` (the sweep driver) touches the launch stack.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.base import Finding

RULES = {
    "JA400": "step-trace-failure",
    "JA401": "host-callback-in-step",
    "JA402": "widen-into-collective",
    "JA403": "off-pod-axis-collective",
    "JA404": "large-closed-constant",
    "JA405": "rng-key-not-from-args",
}

#: primitives that round-trip through the host (device->host per step)
HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "outside_call", "host_callback_call",
})

#: cross-device communication primitives (named-axis collectives)
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pshuffle", "psum", "pmax", "pmin", "pmean",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "pgather", "pbroadcast",
})

#: primitives that mint or consume PRNG state
RNG_PRIMS = frozenset({
    "random_seed", "random_bits", "random_wrap", "random_fold_in",
    "random_gamma", "threefry2x32", "rng_bit_generator", "rng_uniform",
})

#: default JA404 threshold: anything above 1 MiB baked into the graph
#: is a deliberate decision, not an incidental table
CONST_THRESHOLD_BYTES = 1 << 20

_FLOAT_BYTES = {"float64": 8, "float32": 4, "float16": 2, "bfloat16": 2,
                "float8_e4m3fn": 1, "float8_e5m2": 1}


def _float_bytes(dtype) -> Optional[int]:
    return _FLOAT_BYTES.get(getattr(dtype, "name", str(dtype)))


def _is_literal(v: Any) -> bool:
    # jax.core.Literal carries .val; Var / DropVar do not
    return hasattr(v, "val")


def _is_jaxpr(x: Any) -> bool:
    return hasattr(x, "eqns") and hasattr(x, "invars")


def _as_open(x: Any) -> Optional[Tuple[Any, List[Any]]]:
    """(open jaxpr, consts) for a Jaxpr or ClosedJaxpr, else None.  The
    ClosedJaxpr test comes first: jax 0.9's ClosedJaxpr also exposes
    ``eqns`` and ``invars``, and taking it for an open jaxpr would drop
    its consts."""
    inner = getattr(x, "jaxpr", None)
    if inner is not None and _is_jaxpr(inner):
        return inner, list(getattr(x, "consts", []))
    if _is_jaxpr(x):
        return x, []
    return None


def _sub_jaxprs(eqn) -> List[Tuple[Any, List[Any]]]:
    """Every (open jaxpr, consts) hanging off this eqn's params."""
    out = []
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            pair = _as_open(x)
            if pair is not None:
                out.append(pair)
    return out


@dataclass
class _EqnRec:
    """One equation, flattened out of its (possibly nested) scope."""
    eqn: Any
    scope: str                  # e.g. "pjit/scan" ("" = top level)

    @property
    def name(self) -> str:
        return self.eqn.primitive.name

    @property
    def site(self) -> str:
        return f"{self.name}@{self.scope}" if self.scope else self.name


class _Graph:
    """The whole-trace dataflow graph: eqns from every scope, forward
    var->var edges (cross-scope boundaries wired through), producers,
    and the consts closed over at each level."""

    def __init__(self):
        self.eqns: List[_EqnRec] = []
        self.fwd: Dict[int, Set[int]] = {}
        self.vars: Dict[int, Any] = {}          # id -> var (keepalive)
        self.producer: Dict[int, _EqnRec] = {}
        self.consts: List[Tuple[str, Any]] = []  # (scope, const value)
        self.arg_ids: List[int] = []             # top-level invars

    def _edge(self, src: Any, dst: Any) -> None:
        if _is_literal(src):
            return
        self.vars[id(src)] = src
        self.vars[id(dst)] = dst
        self.fwd.setdefault(id(src), set()).add(id(dst))

    def _link(self, outers: Sequence[Any], inners: Sequence[Any]) -> None:
        """Wire outer operands to inner invars (or inner outvars to
        outer results): positional when the arities match, else the
        conservative all-to-all."""
        if len(outers) == len(inners):
            pairs: Iterable = zip(outers, inners)
        else:
            pairs = ((o, i) for o in outers for i in inners)
        for o, i in pairs:
            self._edge(o, i)


def _build(closed_jaxpr) -> _Graph:
    g = _Graph()

    def rec(jaxpr, consts, scope):
        for cv, c in zip(getattr(jaxpr, "constvars", []), consts):
            g.vars[id(cv)] = cv
            g.consts.append((scope, c))
        for eqn in jaxpr.eqns:
            r = _EqnRec(eqn, scope)
            g.eqns.append(r)
            live_in = [v for v in eqn.invars if not _is_literal(v)]
            for o in eqn.outvars:
                g.vars[id(o)] = o
                g.producer[id(o)] = r
                for v in live_in:
                    g._edge(v, o)
            subs = _sub_jaxprs(eqn)
            if not subs:
                continue
            inner_scope = f"{scope}/{r.name}" if scope else r.name
            name = r.name
            if name == "cond":
                # invars = [branch index, *operands]; each branch takes
                # the operands and yields the eqn outputs
                for sub, sc in subs:
                    g._link(eqn.invars[1:], sub.invars)
                    g._link(sub.outvars, eqn.outvars)
                    rec(sub, sc, inner_scope)
            elif name == "while":
                cn = eqn.params.get("cond_nconsts", 0)
                bn = eqn.params.get("body_nconsts", 0)
                carry = list(eqn.invars[cn + bn:])
                cond_j, cond_c = _as_open(eqn.params["cond_jaxpr"])
                body_j, body_c = _as_open(eqn.params["body_jaxpr"])
                g._link(list(eqn.invars[:cn]) + carry, cond_j.invars)
                g._link(list(eqn.invars[cn:cn + bn]) + carry, body_j.invars)
                g._link(body_j.outvars, eqn.outvars)
                # loop feedback: iteration t's carry feeds iteration t+1
                g._link(body_j.outvars, body_j.invars[bn:])
                g._link(body_j.outvars, cond_j.invars[cn:])
                rec(cond_j, cond_c, inner_scope)
                rec(body_j, body_c, inner_scope)
            else:
                # pjit / closed_call / remat / custom_* / shard_map /
                # scan: operands map positionally onto the sub-jaxpr
                # (scan: consts+carry+xs line up 1:1 with the body's
                # consts+carry+x-slices); unknown arities degrade to
                # the conservative all-to-all link
                for sub, sc in subs:
                    g._link(eqn.invars, sub.invars)
                    g._link(sub.outvars, eqn.outvars)
                    if name == "scan":
                        ncon = eqn.params.get("num_consts", 0)
                        ncar = eqn.params.get("num_carry", 0)
                        g._link(sub.outvars[:ncar],
                                sub.invars[ncon:ncon + ncar])
                    rec(sub, sc, inner_scope)

    top, consts = _as_open(closed_jaxpr)
    g.arg_ids = [id(v) for v in top.invars]
    for v in top.invars:
        g.vars[id(v)] = v
    rec(top, consts, "")
    return g


def _closure(start: Iterable[int], adj: Dict[int, Set[int]]) -> Set[int]:
    seen = set(start)
    stack = list(seen)
    while stack:
        for nxt in adj.get(stack.pop(), ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append(nxt)
    return seen


def _reverse(adj: Dict[int, Set[int]]) -> Dict[int, Set[int]]:
    rev: Dict[int, Set[int]] = {}
    for src, dsts in adj.items():
        for d in dsts:
            rev.setdefault(d, set()).add(src)
    return rev


def _axis_names(eqn) -> List[str]:
    names = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if isinstance(names, str):
        names = (names,)
    return [n for n in names if isinstance(n, str)]


def _aval_str(aval) -> str:
    dt = getattr(getattr(aval, "dtype", None), "name", "?")
    return f"{dt}{list(getattr(aval, 'shape', ()))}"


# ---------------------------------------------------------------- audit

@dataclass
class JaxprAudit:
    """Findings + the machine-readable summary for one traced step."""
    tag: str
    findings: List[Finding] = field(default_factory=list)
    n_eqns: int = 0
    n_collectives: int = 0
    collective_axes: List[str] = field(default_factory=list)
    max_const_bytes: int = 0
    n_rng_prims: int = 0
    error: Optional[str] = None          # JA400: the trace never ran

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> Dict:
        return {
            "tag": self.tag, "ok": self.ok,
            "n_eqns": self.n_eqns,
            "n_collectives": self.n_collectives,
            "collective_axes": self.collective_axes,
            "max_const_bytes": self.max_const_bytes,
            "n_rng_prims": self.n_rng_prims,
            "error": self.error,
            "findings": [f.to_json() for f in self.findings],
        }


def audit_jaxpr(closed_jaxpr, *, tag: str = "<jaxpr>",
                pod_axis: Optional[str] = "pod",
                const_threshold_bytes: int = CONST_THRESHOLD_BYTES
                ) -> JaxprAudit:
    """Audit one closed jaxpr (every nested scope included).

    ``pod_axis`` names the only axis collectives may use (JA403);
    pass ``None`` to skip the axis-discipline rule (e.g. a graph with
    no pod fabric at all)."""
    rep = JaxprAudit(tag=tag)
    g = _build(closed_jaxpr)
    rep.n_eqns = len(g.eqns)

    def emit(rule: str, message: str, source: str) -> None:
        rep.findings.append(Finding(rule=rule, path=tag, line=0,
                                    message=message, source=source))

    # ---- JA401: host callbacks / io effects ----
    for r in g.eqns:
        if r.name in HOST_PRIMS:
            emit("JA401",
                 f"host callback `{r.name}` reachable from the step "
                 f"(scope {r.scope or 'top'}): a device<->host "
                 "round-trip per call that XLA may fold out of sight "
                 "post-lowering", r.site)
    for eff in getattr(closed_jaxpr, "effects", ()) or ():
        en = type(eff).__name__.lower()
        if any(h in en for h in ("io", "callback", "debug")) and \
                not any(f.rule == "JA401" for f in rep.findings):
            emit("JA401",
                 f"step trace carries host-visible effect "
                 f"`{type(eff).__name__}` — something inside the step "
                 "talks to the host", f"effect:{type(eff).__name__}")

    # ---- collectives: JA403 axis discipline, JA402 wire widening ----
    collectives = [r for r in g.eqns if r.name in COLLECTIVE_PRIMS]
    rep.n_collectives = len(collectives)
    axes_seen: Set[str] = set()
    rev = _reverse(g.fwd) if collectives else {}
    arg_id_set = set(g.arg_ids)
    for r in collectives:
        names = _axis_names(r.eqn)
        axes_seen.update(names)
        if pod_axis is not None:
            off = [n for n in names if n != pod_axis]
            if off:
                emit("JA403",
                     f"collective `{r.name}` runs over axis "
                     f"{off if len(off) > 1 else off[0]!r}, not the "
                     f"{pod_axis!r} axis — manual exchange belongs on "
                     "the pod fabric; other axes are GSPMD's", r.site)
        # JA402: for each float operand, walk the dataflow backward to
        # the step-argument leaves it ships; wider-on-the-wire => the
        # payload widened somewhere on the path
        for v in r.eqn.invars:
            if _is_literal(v):
                continue
            wire_b = _float_bytes(getattr(v.aval, "dtype", None))
            if wire_b is None:
                continue
            back = _closure([id(v)], rev)
            leaf_bytes = [
                _float_bytes(g.vars[i].aval.dtype)
                for i in back & arg_id_set
                if _float_bytes(getattr(g.vars[i].aval, "dtype", None))
            ]
            if not leaf_bytes or wire_b <= min(leaf_bytes):
                continue
            widener = next(
                (g.producer[i] for i in back
                 if i in g.producer
                 and g.producer[i].name == "convert_element_type"
                 and _is_widening(g.producer[i].eqn)), None)
            via = (f" (widened by `convert_element_type` in scope "
                   f"{widener.scope or 'top'})" if widener else "")
            emit("JA402",
                 f"collective `{r.name}` ships "
                 f"{_aval_str(v.aval)} but the narrowest float leaf it "
                 f"traces back to is {min(leaf_bytes)} byte(s)/elt — "
                 f"the payload widened on the wire{via}", r.site)
    rep.collective_axes = sorted(axes_seen)

    # ---- JA404: large closed-over constants ----
    for scope, c in g.consts:
        shape = list(getattr(c, "shape", ()))
        # jax 0.9 closes numpy constants over as TypedNdArray, which has
        # shape and dtype but no nbytes
        nb = int(getattr(c, "nbytes", None)
                 or math.prod(shape)
                 * getattr(getattr(c, "dtype", None), "itemsize", 0))
        rep.max_const_bytes = max(rep.max_const_bytes, nb)
        if nb > const_threshold_bytes:
            dt = getattr(getattr(c, "dtype", None), "name", "?")
            emit("JA404",
                 f"{nb} -byte constant ({dt}{shape}) closed over into "
                 f"the jaxpr (scope {scope or 'top'}): baked into every "
                 "executable and a recompile each time its value "
                 "changes — make it a step operand",
                 f"const:{dt}{shape}@{scope or 'top'}")

    # ---- JA405: RNG keys that never touch a step argument ----
    rng = [r for r in g.eqns if r.name in RNG_PRIMS]
    rep.n_rng_prims = len(rng)
    if rng:
        arg_taint = _closure(g.arg_ids, g.fwd)
        rng_taint = _closure(
            [id(o) for r in rng for o in r.eqn.outvars], g.fwd)
        for r in rng:
            live = [id(v) for v in r.eqn.invars if not _is_literal(v)]
            if any(i in arg_taint for i in live):
                continue            # keyed from a step argument: fine
            if any(i in rng_taint for i in live):
                continue            # downstream of the root we flag
            emit("JA405",
                 f"RNG primitive `{r.name}` (scope {r.scope or 'top'}) "
                 "draws from a key that never traces back to a step "
                 "argument — the same stream replays every call; "
                 "thread the key/seed through the step's operands "
                 "(trace-level twin of RA101)", r.site)
    return rep


def _is_widening(eqn) -> bool:
    """convert_element_type eqn that widens float -> wider float."""
    try:
        src = _float_bytes(eqn.invars[0].aval.dtype)
        dst = _float_bytes(eqn.outvars[0].aval.dtype)
    except (AttributeError, IndexError):
        return False
    return src is not None and dst is not None and dst > src


# ------------------------------------------------------------ the sweep

def audit_combos(*, arch: Optional[str] = None,
                 mesh_spec: Optional[str] = None, reduced: bool = True,
                 combos: Optional[Sequence[Tuple]] = None,
                 pod_axis: str = "pod",
                 const_threshold_bytes: int = CONST_THRESHOLD_BYTES,
                 verbose: bool = False) -> List[Tuple[str, JaxprAudit]]:
    """Trace + audit every step builder across the full strategy x
    topology matrix (plus the prefill/serve graphs).

    Returns ``[(combo, JaxprAudit)]`` — one row per combo, ALWAYS: a
    combo whose builder raises gets a JA400 finding instead of silently
    vanishing from the coverage matrix.  Imports the launch stack
    lazily (``repro.launch.dryrun`` first, so XLA_FLAGS is set before
    jax initializes its device count).
    """
    from repro.launch import dryrun  # noqa: F401 — XLA_FLAGS side effect
    arch = arch or dryrun.SWEEP_ARCH
    mesh_spec = mesh_spec or dryrun.SWEEP_MESH
    mesh = dryrun._parse_mesh(mesh_spec)
    out: List[Tuple[str, JaxprAudit]] = []
    for shape_name, strategy, topology in (combos if combos is not None
                                           else dryrun.iter_combos()):
        combo = f"{shape_name}/{strategy or '-'}/{topology or '-'}"
        tag = f"jaxpr:{arch}/{combo}@{mesh_spec}"
        try:
            cj = dryrun.trace_combo(arch, shape_name, strategy=strategy,
                                    topology=topology, mesh=mesh,
                                    reduced=reduced)
            rep = audit_jaxpr(cj, tag=tag, pod_axis=pod_axis,
                              const_threshold_bytes=const_threshold_bytes)
        except Exception as e:  # repro-allow: RA104 — matrix driver: a
            #                     broken builder must become a JA400 row,
            #                     not abort the remaining combos
            rep = JaxprAudit(tag=tag, error=f"{type(e).__name__}: {e}")
            rep.findings.append(Finding(
                rule="JA400", path=tag, line=0,
                message=f"step trace failed: {type(e).__name__}: {e} — "
                        "this combo is unaudited until the builder is "
                        "fixed", source=f"trace:{combo}"))
        if verbose:
            state = ("FAIL" if rep.error else
                     f"{len(rep.findings)} finding(s)" if rep.findings
                     else "ok")
            print(f"[jaxpr-audit] {combo}: {state} "
                  f"({rep.n_eqns} eqns, {rep.n_collectives} collectives)")
        out.append((combo, rep))
    return out
