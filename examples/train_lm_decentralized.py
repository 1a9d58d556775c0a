"""End-to-end driver: decentralized training of a transformer LM with the
production step functions (the same code path the dry-run lowers for the
512-chip mesh), with a reduced model.

The mesh is built from the devices JAX finds: one site (mesh ``pod``)
per device.
The configured strategy controls the cross-pod exchange — Gaia's masked
psum, or the D-PSGD/AD-PSGD gossip ring over a topology fabric
(per-round neighbor operands, so a rotating schedule reuses one
compilation).  Trains a ~10M-param qwen3-family model on synthetic
Markov token streams for a few hundred steps and reports the loss curve.

  PYTHONPATH=src python examples/train_lm_decentralized.py \
      [--steps 200] [--strategy gaia|dpsgd|adpsgd] [--topology ring] \
      [--d-model 256] [--layers 4]

On the CPU, give JAX one host device per site first, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=2``.
"""
import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import CommConfig, FabricConfig
from repro.configs.registry import get_config
from repro.data.synthetic import synth_tokens
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_shardings, train_state_shardings
from repro.launch.steps import (GOSSIP_STRATEGIES, gossip_operands,
                                make_train_state, make_train_step)
from repro.models.model import init_model
from repro.models.shard_hints import activation_sharding
from repro.checkpointing import save
from repro.topology.graphs import build_demo_schedule


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--strategy", default="gaia",
                    choices=["bsp", "gaia", "fedavg", "dgc",
                             "dpsgd", "adpsgd"])
    ap.add_argument("--topology", default="ring",
                    help="gossip fabric across the pods")
    ap.add_argument("--staleness", type=int, default=1,
                    help="adpsgd staleness rung (<= max_staleness=2)")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch-per-pod", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    base = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(
        base, n_layers=args.layers, d_model=args.d_model,
        d_ff=args.d_model * 3, vocab=512,
        attention=dataclasses.replace(
            base.attention, n_heads=4, n_kv_heads=2,
            head_dim=args.d_model // 4))
    n_params = cfg.n_params()
    print(f"arch=qwen3-family reduced  params~{n_params/1e6:.1f}M  "
          f"strategy={args.strategy}")

    pods = len(jax.devices())
    if pods < 2:
        raise SystemExit(
            "one site per device needs >= 2 devices (on the CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    mesh = make_mesh((pods, 1, 1), ("pod", "data", "model"))
    print(f"{pods} sites on {jax.devices()[0].platform}")
    comm = CommConfig(strategy=args.strategy,
                      fabric=FabricConfig(topology=args.topology),
                      gaia_t0=0.05, iter_local=10, dgc_sparsity=0.95)
    params = init_model(jax.random.PRNGKey(0), cfg)
    state = make_train_state(params, comm, pods)

    data = synth_tokens(512, args.seq + 1, vocab=cfg.vocab, seed=0)
    rng = np.random.default_rng(0)

    def next_batch():
        idx = rng.integers(0, data.tokens.shape[0],
                           size=(pods, args.batch_per_pod))
        seqs = data.tokens[idx]
        return {"tokens": jnp.asarray(seqs[..., :-1]),
                "labels": jnp.asarray(seqs[..., 1:])}

    gossip = args.strategy in GOSSIP_STRATEGIES
    # label-aware fabrics get the synthetic full-skew histogram (the
    # Markov stream has no labels to derive one from)
    sched = build_demo_schedule(args.topology, pods) if gossip else None
    with mesh, activation_sharding(mesh):
        s_shard = train_state_shardings(jax.eval_shape(lambda: state), mesh)
        b_shard = batch_shardings(jax.eval_shape(next_batch), mesh,
                                  pod_stacked=True)
        in_sh = (s_shard, b_shard, None) + ((None,) if gossip else ())
        step_fn = jax.jit(
            make_train_step(cfg, comm, mesh=mesh, lr=args.lr, remat=False,
                            chunk=64),
            in_shardings=in_sh,
            # pin the state outputs to the canonical shardings so step t's
            # output is bit-compatible with step t+1's in_shardings (GSPMD
            # may otherwise pick a different layout for e.g. vel)
            out_shardings=(s_shard, None), donate_argnums=(0,))
        t0 = time.time()
        for t in range(args.steps):
            extra = ()
            if gossip:
                # per-round runtime operands: a rotating schedule (and a
                # staleness move) reuses the one compilation
                extra = (gossip_operands(
                    sched, t,
                    staleness=args.staleness
                    if args.strategy == "adpsgd" else None,
                    max_staleness=comm.max_staleness),)
            state, metrics = step_fn(state, next_batch(), jnp.int32(t),
                                     *extra)
            if t % 20 == 0 or t == args.steps - 1:
                print(f"step {t:4d}  loss={float(metrics['loss']):.4f}  "
                      f"({(time.time()-t0):.1f}s)", flush=True)
    final = float(metrics["loss"])
    print(f"done: loss {final:.4f} (random = ln(512) = 6.24)")
    if args.ckpt:
        save(args.ckpt, jax.device_get(state["params"]), step=args.steps)
        print(f"checkpoint written to {args.ckpt}")
    assert final < 5.5, "LM failed to learn Markov structure"


if __name__ == "__main__":
    main()
