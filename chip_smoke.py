"""Chip smoke test: drive decentralized training once on a TPU and check it.

  python chip_smoke.py               # one chip: the paper's CNN setup
  python chip_smoke.py --four-chips  # four chips: pod-gossip D-PSGD only

One chip.  ``core.trainer.train_decentralized`` at the paper's setup: K=5
sites, batch 20 per site, full label skew, synthetic CIFAR at 32x32, for
gn-lenet and bn-lenet under Gaia, rand-k DGC, D-PSGD (ring) and AD-PSGD
(ring, asynchronous, staleness 2), 20 steps each and a final eval.  Per
run it prints the first/last loss, val accuracy, the first step's time
(trace and compile included) and the median steady step time, each step
ended by ``block_until_ready``.  It also checks that every strategy's
jitted step holds a Mosaic kernel (``tpu_custom_call``), and that each
exchange kernel matches its ``kernels/ref.py`` oracle at the model's
real shapes.

Four chips.  ``launch.steps.make_train_step`` for D-PSGD on a ring over
a (pod=4, data=1, model=1) mesh of ``jax.devices()``, qwen3-0.6b at its
published widths.  One round's exchange is compared with the ring's
mixing matrix applied on the host to the four pre-exchange replicas, and
the compiled exchange must be pod-axis collective-permutes only.

The last line of stdout is one JSON object naming the device.  The script
exits non-zero, with no such line, when JAX finds no TPU, when a
``REPRO_KERNEL_DISPATCH*`` override is set, or when any phase fails.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

K_SITES = 5
BATCH = 20
STEPS = 20
LR = 0.02
MODELS = ("gn-lenet", "bn-lenet")
STRATEGIES = ("gaia", "dgc", "dpsgd", "adpsgd")
KERNEL_RTOL = 1e-5          # max |kernel - oracle| / max |oracle|

# four-chip phase: qwen3-0.6b at published widths and train_4k's sequence;
# the per-pod batch is cut from 64 to 2.  Compiled for v5e, one pod's
# step holds 3.33 GiB of arguments and 12.42 GiB of temporaries at batch
# 2, 15.75 GiB of the chip's 16 GiB; each further sequence adds 5.01 GiB
# of temporaries, most of it its (4096, 151936) f32 logits
LM_ARCH = "qwen3-0.6b"
LM_PODS = 4
LM_SEQ = 4096
LM_BATCH = 2
LM_STEPS = 4


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def log(*a):
    print(*a, flush=True)


def comm_for(strategy):
    from repro.configs.base import CommConfig, FabricConfig
    if strategy == "dgc":
        return CommConfig(strategy="dgc", dgc_compressor="randk")
    if strategy == "dpsgd":
        return CommConfig(strategy="dpsgd",
                          fabric=FabricConfig(topology="ring"))
    if strategy == "adpsgd":
        return CommConfig(strategy="adpsgd",
                          fabric=FabricConfig(topology="ring"),
                          async_gossip=True, max_staleness=2)
    return CommConfig(strategy=strategy)


def cnn_config(name):
    from repro.configs.cnn_zoo import CNN_ZOO
    return dataclasses.replace(CNN_ZOO[name], image_size=32)


def max_rel_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


# ------------------------------------------------------------- one chip

def run_one(model, strategy, parts, val):
    import numpy as np
    from repro.core.trainer import train_decentralized
    r = train_decentralized(cnn_config(model), strategy, parts, val,
                            comm=comm_for(strategy), steps=STEPS,
                            batch=BATCH, lr=LR, eval_every=STEPS, seed=0)
    losses = [l for _, l in r.loss_curve]
    step_s = r.extras["step_s"]
    res = {"model": model, "strategy": strategy,
           "loss_first": losses[0], "loss_last": losses[-1],
           "val_acc": r.val_acc,
           "first_step_s": step_s[0],
           "steady_step_s": float(np.median(step_s[2:])),
           "mosaic_calls": r.extras["mosaic_calls"]}
    log("run", json.dumps(res))
    check(np.isfinite(losses[0]) and np.isfinite(losses[-1]),
          f"{model}/{strategy}: non-finite loss {losses[0]}, {losses[-1]}")
    check(0.0 <= r.val_acc <= 1.0, f"{model}/{strategy}: val_acc "
          f"{r.val_acc}")
    check(res["mosaic_calls"] > 0,
          f"{model}/{strategy}: no tpu_custom_call in the jitted step")
    return res


def check_kernels(model):
    """Each exchange kernel, dispatched as the trainer dispatches it,
    against its oracle at ``model``'s real shapes: neighbor_mix (both
    variants) on the flattened (K, N) model stack, gaia_select and
    rand_k_select on every (K, *leaf) parameter stack."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.algorithms.dgc import WARMUP_SPARSITIES
    from repro.kernels import ops, ref
    from repro.models.cnn import init_cnn
    from repro.topology import build_schedule

    params, _ = init_cnn(jax.random.PRNGKey(0), cnn_config(model))
    leaves = jax.tree_util.tree_leaves(params)
    n = sum(l.size for l in leaves)
    key = jax.random.PRNGKey(1)

    def mosaic(fn, *args):
        return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()

    # gossip: ring operands, D-PSGD fresh and AD-PSGD stale (staleness 2)
    idx, w, sw = (jnp.asarray(a) for a in
                  build_schedule("ring", K_SITES).neighbor_arrays(0))
    x = jax.random.normal(key, (K_SITES, n), jnp.float32)
    src = jax.random.normal(jax.random.fold_in(key, 1), (3 * K_SITES, n))
    gidx = jnp.where(w > 0, 2, 0) * K_SITES + idx
    with jax.default_matmul_precision("highest"):
        want = ref.neighbor_mix_padded_ref(x, idx, w, sw)
        want_src = ref.neighbor_mix_padded_ref(x, gidx, w, sw, src)
    errs = {"neighbor_mix": max_rel_err(ops.neighbor_mix(x, idx, w, sw),
                                        want),
            "neighbor_mix_src": max_rel_err(
                ops.neighbor_mix(x, gidx, w, sw, src=src), want_src)}
    check(mosaic(lambda a: ops.neighbor_mix(a, idx, w, sw), x),
          "neighbor_mix did not dispatch to Mosaic")
    check(mosaic(lambda a, s: ops.neighbor_mix(a, gidx, w, sw, src=s),
                 x, src), "neighbor_mix src variant did not dispatch to "
          "Mosaic")
    for name, e in errs.items():
        check(e <= KERNEL_RTOL, f"{model}: {name} max rel err {e}")

    # selects: bit-exact against the oracle on every leaf shape
    keep = 1.0 - WARMUP_SPARSITIES[0]
    n_exact = 0
    for i, leaf in enumerate(leaves):
        shape = (K_SITES,) + leaf.shape
        kv, kw_ = jax.random.split(jax.random.fold_in(key, 10 + i))
        v = jax.random.normal(kv, shape) * 0.05
        wt = jax.random.normal(kw_, shape) * 0.3
        thresh = jnp.float32(0.1)
        got, cnt = ops.gaia_select(v, wt, thresh)
        exp, ecnt = ref.gaia_select_ref(v, wt, thresh)
        check(np.array_equal(np.asarray(got), np.asarray(exp))
              and int(cnt) == int(ecnt),
              f"{model}: gaia_select differs from its oracle at {shape}")
        seed = jnp.int32(1009 + i)
        got, cnt = ops.rand_k_sparsify(v, keep, seed)
        exp, ecnt = ref.rand_k_select_ref(v, keep, seed)
        check(np.array_equal(np.asarray(got), np.asarray(exp))
              and int(cnt) == int(ecnt),
              f"{model}: rand_k_select differs from its oracle at {shape}")
        n_exact += 2
    check(mosaic(lambda a, b: ops.gaia_select(a, b, 0.1), v, wt),
          "gaia_select did not dispatch to Mosaic")
    check(mosaic(lambda a: ops.rand_k_sparsify(a, keep, 1), v),
          "rand_k_select did not dispatch to Mosaic")
    log("kernels", json.dumps({"model": model, "n": n,
                               "max_rel_err": errs,
                               "bit_exact_leaf_checks": n_exact}))


def one_chip():
    from repro.core import partition_label_skew
    from repro.data.synthetic import synth_images

    ds = synth_images(3000, side=32, seed=0, noise=0.8, class_sep=0.35)
    val = synth_images(1024, side=32, seed=99, noise=0.8, class_sep=0.35)
    idx = partition_label_skew(ds.y, K_SITES, 1.0, seed=1)
    parts = [(ds.x[i], ds.y[i]) for i in idx]
    for model in MODELS:
        check_kernels(model)
        for strategy in STRATEGIES:
            run_one(model, strategy, parts, (val.x, val.y))


# ----------------------------------------------------------- four chips

def lm_setup():
    """Mesh, config, jitted step, state init and batch for the four-site
    pod-gossip step, one site per device of ``jax.devices()``."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import CommConfig, FabricConfig
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import batch_shardings, train_state_shardings
    from repro.launch.steps import (make_train_state, make_train_step,
                                    train_state_shape)
    from repro.models.model import init_model

    n = LM_PODS
    mesh = make_mesh((n, 1, 1), ("pod", "data", "model"))
    cfg = get_config(LM_ARCH)
    comm = CommConfig(strategy="dpsgd", fabric=FabricConfig(topology="ring"))
    state_sh = train_state_shardings(train_state_shape(cfg, comm, n), mesh)
    batch_shape = {k: jax.ShapeDtypeStruct((n, LM_BATCH, LM_SEQ),
                                           jnp.int32)
                   for k in ("tokens", "labels")}
    batch_sh = batch_shardings(batch_shape, mesh, pod_stacked=True)

    @jax.jit
    def init_state(key):
        # one replica per site, each from its own key, so one exchange
        # round moves every weight by a visible amount
        keys = jax.random.split(key, n)
        state = make_train_state(init_model(keys[0], cfg), comm, n)
        state["params"] = jax.vmap(lambda k: init_model(k, cfg))(keys)
        return jax.lax.with_sharding_constraint(state, state_sh)

    @jax.jit
    def make_batch(key):
        tok = jax.random.randint(key, (n, LM_BATCH, LM_SEQ + 1), 0,
                                 cfg.vocab)
        b = {"tokens": tok[..., :-1], "labels": tok[..., 1:]}
        return jax.lax.with_sharding_constraint(b, batch_sh)

    step = jax.jit(make_train_step(cfg, comm, mesh=mesh, lr=1e-3),
                   in_shardings=(state_sh, batch_sh, None, None),
                   out_shardings=(state_sh, None), donate_argnums=(0,))
    return dict(mesh=mesh, cfg=cfg, step=step, init_state=init_state,
                make_batch=make_batch)


#: leaves compared against the host mixing-matrix reference (layer 0 of
#: the stacked body where the leaf has a layer axis, a vocab slice of
#: the embedding)
CHECK_LEAVES = ("['mixer']['wq']", "['ffn']['down']", "['embed']",
                "['final_norm']")
MIX_RTOL = 2.0 ** -7


def four_chips():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.hlo import pod_exchange_report
    from repro.launch.steps import gossip_operands
    from repro.models.shard_hints import activation_sharding
    from repro.topology.graphs import ring

    n = len(jax.devices())
    check(n == LM_PODS, f"--four-chips needs {LM_PODS} devices, found {n}")
    s = lm_setup()
    sched = ring(n)
    mix = gossip_operands(sched, 0)
    # identity operands: self weight 1, neighbor weights 0 — the same
    # compiled step then returns the pre-exchange replicas exactly
    ident = (mix[0], jnp.zeros_like(mix[1]), jnp.ones_like(mix[2]))

    with s["mesh"], activation_sharding(s["mesh"]):
        t0 = time.perf_counter()
        state = s["init_state"](jax.random.PRNGKey(0))
        batch = s["make_batch"](jax.random.PRNGKey(1))
        compiled = s["step"].lower(state, batch, jnp.int32(0),
                                   mix).compile()
        compile_s = time.perf_counter() - t0
        rep = pod_exchange_report(compiled.as_text(), devices_per_pod=1)
        mem = compiled.memory_analysis()

        def named_leaves(st):
            flat = jax.tree_util.tree_flatten_with_path(st["params"])[0]
            out = {}
            for path, leaf in flat:
                name = jax.tree_util.keystr(path)
                if not any(c in name for c in CHECK_LEAVES):
                    continue
                if "['body']" in name:
                    leaf = leaf[:, 0]
                if "['embed']" in name:
                    leaf = leaf[:, :4096]
                out[name] = np.asarray(jax.device_get(leaf), np.float32)
            return out

        pre, m_pre = compiled(state, batch, jnp.int32(0), ident)
        pre_leaves = named_leaves(pre)
        del pre
        state = s["init_state"](jax.random.PRNGKey(0))
        post, m_post = compiled(state, batch, jnp.int32(0), mix)
        post_leaves = named_leaves(post)
        check(float(m_pre["loss"]) == float(m_post["loss"]),
              "the two rounds from one state disagree before the exchange")

        # host reference: y_k = sum_j W[k, j] x_j in float64.  The
        # exchange rounds the mixed value once to the leaf dtype, so a
        # bf16 leaf may differ by half a bf16 ulp (2**-8 relative)
        W = sched.mixing.astype(np.float64)
        errs, moved = {}, {}
        for name, x in pre_leaves.items():
            want = np.tensordot(W, x.astype(np.float64), axes=1)
            errs[name] = max_rel_err(post_leaves[name], want)
            moved[name] = max_rel_err(x, want)
            check(errs[name] <= MIX_RTOL, f"mixing {name}: max rel err "
                  f"{errs[name]} > {MIX_RTOL}")
            # replicas start from different keys, so a mix that did
            # nothing (or the wrong thing) is far outside the tolerance
            check("norm" in name or moved[name] > 10 * MIX_RTOL,
                  f"mixing {name}: the exchange moved the replicas by "
                  f"only {moved[name]}")

        losses, times = [float(m_post["loss"])], []
        state = post
        for t in range(1, LM_STEPS):
            t1 = time.perf_counter()
            state, m = compiled(state, batch, jnp.int32(t), mix)
            jax.block_until_ready(state)
            times.append(time.perf_counter() - t1)
            losses.append(float(m["loss"]))

    res = {"arch": LM_ARCH, "layers": s["cfg"].n_layers,
           "d_model": s["cfg"].d_model, "vocab": s["cfg"].vocab,
           "seq": LM_SEQ, "batch_per_pod": LM_BATCH, "pods": n,
           "compile_s": compile_s, "steady_step_s": float(np.median(times)),
           "losses": losses, "mix_max_rel_err": errs,
           "exchange_moved_rel": moved,
           "permute_cross_bytes": rep.permute_cross_bytes,
           "reduce_cross_bytes": rep.reduce_cross_bytes,
           "pod_axis_only": rep.pod_axis_only,
           "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
           "argument_bytes": getattr(mem, "argument_size_in_bytes", None)}
    log("four_chips", json.dumps(res))
    check(all(np.isfinite(losses)), f"non-finite LM loss {losses}")
    check(rep.pod_axis_only, "cross-pod permute left the pod axis")
    check(rep.permute_cross_bytes > 0, "the gossip exchange vanished")
    check(rep.reduce_cross_bytes < rep.permute_cross_bytes,
          "cross-pod reductions dominate: the exchange fell back to them")
    check(rep.unparsed == 0, f"{rep.unparsed} unparsed collectives")


# ----------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip pod-gossip phase")
    args = ap.parse_args(argv)

    forced = sorted(k for k in os.environ
                    if k.startswith("REPRO_KERNEL_DISPATCH"))
    if forced:
        sys.exit(f"chip_smoke: {', '.join(forced)} set; kernel dispatch "
                 "overrides could hide the device path")
    use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform})")
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
