import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
combination on the production meshes, record memory / cost / collective
analysis for the roofline report.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k [--multi-pod] [--strategy gaia] [--out report.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import sys
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import graph_audit
from repro.analysis import hlo as hlo_analysis
from repro.configs.base import CommConfig, FabricConfig, INPUT_SHAPES
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import analysis
from repro.launch.mesh import (devices_per_pod, make_mesh,
                               make_production_mesh,
                               n_pods as mesh_n_pods)
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   param_shardings,
                                   train_state_shardings)
from repro.launch.specs import input_specs
from repro.launch.steps import (GOSSIP_STRATEGIES, cache_shape,
                                gossip_operands, make_prefill_step,
                                make_serve_step, make_train_step,
                                param_shape, train_state_shape)
from repro.models.shard_hints import activation_sharding
from repro.topology.graphs import build_demo_schedule

SDS = jax.ShapeDtypeStruct

STRATEGIES = ("bsp", "gaia", "fedavg", "dgc") + GOSSIP_STRATEGIES

#: every fabric a gossip strategy can ride — the topology half of the
#: audit matrix (STRATEGIES x GOSSIP_TOPOLOGIES, non-gossip strategies
#: compile the same graph for every fabric so they sweep once)
GOSSIP_TOPOLOGIES = ("ring", "torus", "full", "random", "geo-wan",
                     "dcliques", "tv-dcliques", "random-matching")

#: the all-combos sweep target: the reduced smoke config on the tiny
#: forced-host-device multi-pod mesh CI compiles (2 pods x 2 data x
#: 2 model) — same combo family the dryrun smoke has gated since PR 4
SWEEP_ARCH = "qwen3-0.6b"
SWEEP_SHAPE = "train_4k"
SWEEP_MESH = "2,2,2"

#: which graph-audit findings abort a dryrun: "gossip" (default — hard
#: incidents on the gossip exchange path), "all" (--strict-audit: any
#: strategy, serve/prefill included), "none" (collect only; the
#: analysis CLI applies its own baseline semantics)
AUDIT_FAIL_MODES = ("gossip", "all", "none")


def iter_combos(include_serve: bool = True):
    """The audit matrix: ``(shape_name, strategy, topology)`` rows —
    every strategy x topology combo the launch path can compile, plus
    the prefill/serve graphs (strategy/topology ``None`` there)."""
    for s in STRATEGIES:
        for t in (GOSSIP_TOPOLOGIES if s in GOSSIP_STRATEGIES
                  else (None,)):
            yield (SWEEP_SHAPE, s, t)
    if include_serve:
        yield ("prefill_32k", None, None)
        yield ("decode_32k", None, None)


def _with_shardings(shapes, shardings):
    return jax.tree_util.tree_map(
        lambda sh, ns: SDS(sh.shape, sh.dtype, sharding=ns),
        shapes, shardings)


def _parse_mesh(spec: Optional[str]):
    if not spec:
        return None
    dims = tuple(int(d) for d in spec.split(","))
    if len(dims) not in (2, 3):
        raise ValueError(
            f"--mesh {spec!r}: expected 'pod,data,model' (3 dims) or "
            "'data,model' (2 dims)")
    axes = {3: ("pod", "data", "model"), 2: ("data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def build_step(arch: str, shape_name: str, *,
               strategy: Optional[str] = "gaia",
               topology: Optional[str] = "ring",
               staleness: Optional[int] = None, max_staleness: int = 2,
               chunk: int = 512, remat: bool = True,
               reduced: bool = False, mesh=None) -> Tuple:
    """Construct one combo's ``(step, args, jit_kwargs)`` — the single
    builder behind both graph passes: ``dryrun_one`` jits + lowers +
    compiles it (post-XLA HLO audit), the jaxpr sweep
    (:func:`trace_combo` / ``repro.analysis.jaxpr_audit``) runs
    ``jax.make_jaxpr`` on the raw step (pre-lowering audit).  Must be
    called inside ``with mesh, activation_sharding(mesh)``.

    ``strategy``/``topology`` may be ``None`` for serve-side shapes
    (prefill/decode), where no communication strategy applies."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    shape = INPUT_SHAPES[shape_name]
    pods = mesh_n_pods(mesh)
    comm = CommConfig(strategy=strategy or "bsp",
                      fabric=FabricConfig(topology=topology or "ring"),
                      max_staleness=max_staleness)
    long_mode = shape_name == "long_500k"

    if shape.mode == "train":
        state_shape = train_state_shape(cfg, comm, pods)
        state_shardings = train_state_shardings(state_shape, mesh)
        batch_shapes = input_specs(cfg, shape_name, n_pods=pods)
        b_shardings = batch_shardings(batch_shapes, mesh,
                                      pod_stacked=True)
        step = make_train_step(cfg, comm, mesh=mesh, remat=remat,
                               chunk=chunk)
        args = (_with_shardings(state_shape, state_shardings),
                _with_shardings(batch_shapes, b_shardings),
                SDS((), jnp.int32))
        in_sh: Tuple = (state_shardings, b_shardings, None)
        if strategy in GOSSIP_STRATEGIES:
            # round-0 operands of the real fabric (label-aware
            # builders get the synthetic full-skew histogram): the
            # values are runtime operands, so one compile serves the
            # whole schedule
            sched = build_demo_schedule(topology, pods)
            args += (gossip_operands(
                sched, 0,
                staleness=(max_staleness if staleness is None
                           else staleness)
                if strategy == "adpsgd" else None,
                max_staleness=max_staleness),)
            in_sh += (None,)
        return step, args, {"in_shardings": in_sh,
                            "donate_argnums": (0,)}
    if shape.mode == "prefill":
        p_shape = param_shape(cfg)
        p_shardings = param_shardings(p_shape, mesh)
        batch_shapes = input_specs(cfg, shape_name)
        b_shardings = batch_shardings(batch_shapes, mesh,
                                      pod_stacked=False)
        step = make_prefill_step(cfg, chunk=chunk)
        args = (_with_shardings(p_shape, p_shardings),
                _with_shardings(batch_shapes, b_shardings))
        return step, args, {"in_shardings": (p_shardings, b_shardings)}
    # decode
    p_shape = param_shape(cfg)
    p_shardings = param_shardings(p_shape, mesh)
    c_shape = cache_shape(cfg, shape.global_batch, shape.seq_len,
                          long_mode)
    c_shardings = cache_shardings(
        c_shape, mesh, batch_sharded=shape.global_batch >= 8)
    batch_shapes = input_specs(cfg, shape_name)
    b_shardings = batch_shardings(batch_shapes, mesh,
                                  pod_stacked=False)
    step = make_serve_step(cfg)
    args = (_with_shardings(p_shape, p_shardings),
            _with_shardings(c_shape, c_shardings),
            _with_shardings(batch_shapes, b_shardings))
    return step, args, {"in_shardings": (p_shardings, c_shardings,
                                         b_shardings),
                        "donate_argnums": (1,)}


def trace_combo(arch: str, shape_name: str, *,
                strategy: Optional[str] = None,
                topology: Optional[str] = None,
                staleness: Optional[int] = None, max_staleness: int = 2,
                chunk: int = 512, remat: bool = True,
                reduced: bool = True, mesh=None):
    """Closed jaxpr of one combo's step — the pre-lowering artifact the
    jaxpr audit walks.  Never invokes XLA: tracing the whole audit
    matrix costs less than compiling one combo."""
    mesh = mesh or make_production_mesh(multi_pod=True)
    with mesh, activation_sharding(mesh):
        step, args, _ = build_step(
            arch, shape_name, strategy=strategy, topology=topology,
            staleness=staleness, max_staleness=max_staleness,
            chunk=chunk, remat=remat, reduced=reduced, mesh=mesh)
        return jax.make_jaxpr(step)(*args)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               strategy: Optional[str] = "gaia",
               topology: Optional[str] = "ring",
               staleness: Optional[int] = None, max_staleness: int = 2,
               chunk: int = 512, remat: bool = True, verbose: bool = True,
               reduced: bool = False, mesh=None,
               return_hlo: bool = False,
               audit_fail: str = "gossip") -> Dict:
    if audit_fail not in AUDIT_FAIL_MODES:
        raise ValueError(
            f"audit_fail {audit_fail!r}: expected one of "
            f"{AUDIT_FAIL_MODES}")
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    pods = mesh_n_pods(mesh)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    with mesh, activation_sharding(mesh):
        step, args, jit_kwargs = build_step(
            arch, shape_name, strategy=strategy, topology=topology,
            staleness=staleness, max_staleness=max_staleness,
            chunk=chunk, remat=remat, reduced=reduced, mesh=mesh)
        jitted = jax.jit(step, **jit_kwargs)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):   # older jaxlib: one per device
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()

    n_chips = mesh.devices.size
    per_dev_bytes = None
    mem_summary = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_summary[attr] = int(v)
        per_dev_bytes = (mem_summary.get("argument_size_in_bytes", 0)
                         + mem_summary.get("temp_size_in_bytes", 0)
                         - mem_summary.get("alias_size_in_bytes", 0))
    mf = analysis.model_flops_estimate(cfg, shape, shape.mode)
    roof = analysis.derive_roofline(
        arch, shape_name, mesh_name, n_chips, cost or {}, hlo, mf,
        bytes_per_device=per_dev_bytes)
    pod_exchange = None
    if shape.mode == "train" and pods > 1:
        # where the cross-pod traffic flows: gossip must be pure pod-axis
        # collective-permutes; bsp/gaia/dgc show up as cross-pod reduces
        pex = hlo_analysis.pod_exchange_report(hlo, devices_per_pod(mesh))
        pod_exchange = {
            "permute_cross_gbytes_per_dev": pex.permute_cross_bytes / 1e9,
            "permute_local_gbytes_per_dev": pex.permute_local_bytes / 1e9,
            "reduce_cross_gbytes_per_dev": pex.reduce_cross_bytes / 1e9,
            "reduce_local_gbytes_per_dev": pex.reduce_local_bytes / 1e9,
            "cross_pod_gbytes_per_dev": pex.cross_pod_bytes / 1e9,
            "pod_axis_only": pex.pod_axis_only,
            "unparsed_collectives": pex.unparsed,
        }
        if strategy in GOSSIP_STRATEGIES:
            pod_exchange["topology"] = topology
            if not pex.pod_axis_only:
                raise RuntimeError(
                    f"{strategy} exchange leaked off the pod axis: a "
                    "cross-pod collective-permute pair does not preserve "
                    "the intra-pod device coordinate")
            if pex.permute_cross_bytes <= 0:
                raise RuntimeError(
                    f"{strategy} lowered with no cross-pod "
                    "collective-permute: the gossip exchange vanished")
            # GSPMD reshard noise (e.g. replicated-table all-gathers —
            # the CI smoke carries ~0.6x permute bytes of it from the
            # reduced config's rope-table gather) may legitimately cross
            # pods, but the moment cross-pod reductions *rival* the
            # permute exchange, part of the gossip has fallen back to
            # reduction collectives; if this ever reds on a config tweak
            # rather than a real leak, compare reduce_cross against the
            # bsp baseline before loosening
            if pex.reduce_cross_bytes >= pex.permute_cross_bytes:
                raise RuntimeError(
                    f"{strategy}: cross-pod reduction bytes "
                    f"({pex.reduce_cross_bytes:.0f}) rival the permute "
                    f"exchange ({pex.permute_cross_bytes:.0f}) — the "
                    "gossip is leaking into reduction collectives")
            if pex.unparsed:
                raise RuntimeError(
                    f"{strategy}: {pex.unparsed} collective(s) the pod "
                    "report cannot classify (send/recv, broadcast, or "
                    "unparseable groups) — cross-pod byte totals would "
                    "silently understate the exchange")
    # the general graph audit (repro.analysis.graph_audit): wire
    # dtype, host callbacks, donation drift on top of the pod-axis
    # checks above — now on every mode, serve/prefill included.
    # Gossip strategies hard-fail on any finding (the bf16-widening
    # incident PR 4 fixed is exactly GA202); --strict-audit
    # (audit_fail="all") extends the hard fail to every graph.
    # pod-axis classification (GA201/GA205) and the wire-dtype rule
    # (GA202) only make sense where a gossip exchange could exist: the
    # multi-pod train graph.  Serve/prefill graphs reshard with
    # arbitrary GSPMD permutes, so there we audit host callbacks
    # (GA203) and donation drift (GA204) only.  GA201's
    # coordinate-preservation invariant is narrower still — it is a
    # contract on the *gossip* exchange; reduction-based strategies
    # (bsp/gaia/fedavg/dgc) let GSPMD reshard across pods however it
    # likes, so GA201 is scoped to GOSSIP_STRATEGIES.
    combo = f"{shape_name}/{strategy or '-'}/{topology or '-'}"
    train_graph = shape.mode == "train" and pods > 1
    ga = graph_audit.audit_hlo(
        hlo, tag=f"{arch}/{shape_name}/{strategy or shape.mode}",
        combo=combo,
        devices_per_pod=devices_per_pod(mesh) if train_graph else None,
        check_wire_dtype=train_graph,
        check_pod_axis=strategy in GOSSIP_STRATEGIES,
        expect_donation=shape.mode == "train")
    audit = ga.to_json()
    hard_fail = audit_fail == "all" or (
        audit_fail == "gossip" and shape.mode == "train"
        and strategy in GOSSIP_STRATEGIES)
    if hard_fail and ga.findings:
        raise RuntimeError(
            f"{strategy or shape.mode}: graph audit failed — "
            + "; ".join(f"{f.rule} {f.message}" for f in ga.findings))
    report = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": shape.mode, "strategy": strategy if shape.mode == "train"
        else None,
        "ok": True,
        "pod_exchange": pod_exchange,
        "audit": audit,
        "memory": mem_summary,
        "cost": {k: float(v) for k, v in (cost or {}).items()
                 if isinstance(v, (int, float))},
        "roofline": {
            "t_compute_ms": roof.t_compute * 1e3,
            "t_memory_ms": roof.t_memory * 1e3,
            "t_collective_ms": roof.t_collective * 1e3,
            "bottleneck": roof.bottleneck,
            "hlo_gflops_per_dev": roof.hlo_gflops,
            "hlo_gbytes_per_dev": roof.hlo_gbytes,
            "coll_gbytes_per_dev": roof.coll_gbytes,
            "coll_breakdown_gb": roof.coll_breakdown,
            "model_gflops_per_dev": roof.model_gflops,
            "useful_ratio": roof.useful_ratio,
        },
    }
    if return_hlo:
        report["_hlo"] = hlo
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {mesh_name}: OK  "
              f"bottleneck={roof.bottleneck} "
              f"t=(c {roof.t_compute*1e3:.2f} / m {roof.t_memory*1e3:.2f} / "
              f"x {roof.t_collective*1e3:.2f}) ms  "
              f"useful={roof.useful_ratio:.2f}")
        if mem_summary:
            print(f"         memory: {json.dumps(mem_summary)}")
        if pod_exchange is not None:
            print(f"         cross-pod exchange: "
                  f"{pod_exchange['cross_pod_gbytes_per_dev']:.4f} GB/dev "
                  f"(permute {pod_exchange['permute_cross_gbytes_per_dev']:.4f}"
                  f" / reduce {pod_exchange['reduce_cross_gbytes_per_dev']:.4f}"
                  f", pod_axis_only={pod_exchange['pod_axis_only']})")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--all-combos", action="store_true",
                    help="compile + graph-audit the whole audit matrix "
                         "(iter_combos): every strategy x topology "
                         "combo plus prefill/decode, reduced config on "
                         f"the {SWEEP_MESH} mesh")
    ap.add_argument("--strict-audit", action="store_true",
                    help="fail on ANY graph-audit finding, serve/"
                         "prefill graphs included (default: only "
                         "gossip strategies hard-fail)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="gaia", choices=list(STRATEGIES))
    ap.add_argument("--topology", default="ring",
                    help="gossip fabric over the pod set (dpsgd/adpsgd): "
                         "ring | torus | full | random | geo-wan | "
                         "dcliques | tv-dcliques | random-matching")
    ap.add_argument("--staleness", type=int, default=None,
                    help="adpsgd staleness rung (default: max-staleness)")
    ap.add_argument("--max-staleness", type=int, default=2,
                    help="adpsgd snapshot-buffer depth")
    ap.add_argument("--mesh", default=None,
                    help="override mesh shape, e.g. 2,2,2 (pod,data,model)"
                         " — CI smoke / debugging knob")
    ap.add_argument("--reduced", action="store_true",
                    help="lower the reduced() smoke config instead of the"
                         " full-size arch (CI smoke)")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--outdir", default=None,
                    help="per-combo JSON dir; existing results are skipped")
    ap.add_argument("--save-hlo", action="store_true",
                    help="gzip the partitioned HLO next to each JSON")
    args = ap.parse_args(argv)
    try:
        mesh_override = _parse_mesh(args.mesh)
    except ValueError as e:
        ap.error(str(e))

    # combo rows: (arch, shape, strategy, topology)
    combos = []
    if args.all_combos:
        args.mesh = args.mesh or SWEEP_MESH
        mesh_override = mesh_override or _parse_mesh(args.mesh)
        args.reduced = True
        for sh, st, tp in iter_combos():
            combos.append((SWEEP_ARCH, sh, st, tp))
    elif args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s, args.strategy, args.topology))
    else:
        assert args.arch and args.shape, \
            "--arch/--shape, --all, or --all-combos"
        combos = [(args.arch, args.shape, args.strategy, args.topology)]
    # no communication strategy applies to serve-side graphs
    combos = [(a, s, strat, topo) if INPUT_SHAPES[s].mode == "train"
              else (a, s, None, None) for a, s, strat, topo in combos]

    audit_fail = "all" if args.strict_audit else "gossip"

    def cfg_tag(strategy, topology):
        # the cache tag must carry every report-changing knob, or a
        # cached JSON from a different configuration is silently
        # returned as this run's result (and the gossip pod-axis
        # verification never runs)
        return "__".join(
            [strategy or "serve", "multi" if args.multi_pod else "single"]
            + ([f"mesh{args.mesh.replace(',', 'x')}"] if args.mesh else [])
            + (["reduced"] if args.reduced else [])
            + ([f"chunk{args.chunk}"] if args.chunk != 512 else [])
            + (["noremat"] if args.no_remat else [])
            + (["strict"] if args.strict_audit else [])
            + ([f"{topology}",
                f"s{args.staleness}of{args.max_staleness}"]
               if strategy in GOSSIP_STRATEGIES else []))

    reports, failures = [], []
    for a, s, strat, topo in combos:
        tag = f"{a}__{s}__{cfg_tag(strat, topo)}"
        path = os.path.join(args.outdir, tag + ".json") if args.outdir else None
        if path and os.path.exists(path):
            with open(path) as f:
                rep = json.load(f)
            (reports if rep.get("ok") else failures).append(rep)
            print(f"[dryrun] {tag}: cached ({'ok' if rep.get('ok') else 'FAILED'})")
            continue
        try:
            rep = dryrun_one(
                a, s, multi_pod=args.multi_pod, strategy=strat,
                topology=topo, staleness=args.staleness,
                max_staleness=args.max_staleness,
                reduced=args.reduced, mesh=mesh_override,
                chunk=args.chunk, remat=not args.no_remat,
                return_hlo=args.save_hlo, audit_fail=audit_fail)
            if args.save_hlo and "_hlo" in rep:
                import gzip
                if args.outdir:
                    os.makedirs(args.outdir, exist_ok=True)
                    with gzip.open(os.path.join(
                            args.outdir, tag + ".hlo.gz"), "wt") as f:
                        f.write(rep.pop("_hlo"))
                else:
                    rep.pop("_hlo")
            reports.append(rep)
        except Exception as e:  # repro-allow: RA104 — sweep driver:
            #                     record the failure row and keep going
            traceback.print_exc()
            rep = {"arch": a, "shape": s, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
            failures.append(rep)
        if path:
            os.makedirs(args.outdir, exist_ok=True)
            with open(path, "w") as f:
                json.dump(rep, f, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(reports + failures, f, indent=1)
    print(f"[dryrun] {len(reports)} ok, {len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
