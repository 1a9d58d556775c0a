"""Pallas TPU kernel for D-PSGD's sparse neighbor averaging:
``y[k] = W[k,k] * x[k] + sum_d w[k,d] * x[nbr[k,d]]``.

This is the per-step hot-spot of gossip training: the whole stacked model
(K, N) must be re-mixed every step.  A dense ``W @ X`` wastes K**2 * N
MACs when the graph is sparse (ring: degree 2 regardless of K); looping
per node launches K kernels and re-reads X from HBM each time.  This
kernel streams X through VMEM once per (8,128)-aligned column block and,
inside the block, performs the gather-scale-accumulate over the padded
neighbor lists — O(K * max_degree * block) work, one HBM sweep total.

Neighbor structure comes in kernel-friendly padded form (see
``Topology.neighbor_arrays``): ``nbr_idx`` (K, D) int32 padded with the
node's own index and ``nbr_w`` (K, D) float32 padded with zeros, so
padding rows contribute ``0 * x[k]`` and no branching is needed.

``nbr_idx``/``nbr_w`` are *runtime operands*, not trace-time constants:
only their (K, D) shape is baked into the compiled kernel (the k/d loops
unroll over it), while the index values sit in SMEM and pick the
neighbor's row of the VMEM block at run time.  A :class:`TopologySchedule` that
changes the neighbor set every round therefore reuses one compilation,
provided every round pads to the schedule-wide max degree
(``TopologySchedule.neighbor_arrays`` does) — that compile-once contract
is what ``DPSGD.trace_count`` asserts in the tests.

Stale mixing (AD-PSGD): passing ``src`` with M >= K rows gathers the
neighbor terms from ``src`` instead of ``x`` (the self term stays on
``x``).  AD-PSGD stacks its bounded-staleness snapshot buffer into
``src = snaps.reshape((S + 1) * K, N)`` and offsets the neighbor indices
by ``staleness * K`` — the staleness values ride inside the same runtime
index operand, so a controller moving the staleness rung mid-run reuses
the one compilation too.

The pod-scale distributed backend applies the same self-weight +
padded-neighbor-gather arithmetic (both variants) as a shard_map +
ppermute ring over the mesh ``pod`` axis (``launch/steps._pod_mix_fn``);
tests/test_launch_gossip.py holds the two implementations equivalent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _mix_kernel(nbr_ref, w_ref, sw_ref, x_ref, out_ref):
    _mix_src_kernel(nbr_ref, w_ref, sw_ref, x_ref, x_ref, out_ref)


def _mix_src_kernel(nbr_ref, w_ref, sw_ref, x_ref, src_ref, out_ref):
    """out[k] = sw[k] * x[k] + sum_d w[k, d] * src[nbr[k, d]] over one
    (rows, block_rows, 128) block.  Stale-mixing variant: neighbor rows
    gathered from ``src`` (M rows, e.g. a stacked staleness-snapshot
    buffer), self term from ``x``.  The neighbor row is picked by a
    dynamic index on the ref's leading (untiled) axis, read from SMEM."""
    K, D = nbr_ref.shape
    for k in range(K):                            # K, D static: unrolled
        acc = sw_ref[k] * x_ref[k].astype(jnp.float32)
        for d in range(D):
            xn = src_ref[nbr_ref[k, d]].astype(jnp.float32)
            acc = acc + w_ref[k, d] * xn
        out_ref[k] = acc.astype(out_ref.dtype)


def _to_blocks(x: jnp.ndarray, rows_pad: int) -> jnp.ndarray:
    rows, n = x.shape
    xp = jnp.pad(x, ((0, 0), (0, rows_pad * LANES - n)))
    return xp.reshape(rows, rows_pad, LANES)


def neighbor_mix(x: jnp.ndarray, nbr_idx: jnp.ndarray, nbr_w: jnp.ndarray,
                 self_w: jnp.ndarray, *, src: jnp.ndarray = None,
                 block_rows: int = 64,
                 interpret: bool = False) -> jnp.ndarray:
    """x: (K, N) stacked per-node vectors.  nbr_idx/nbr_w: (K, D) padded
    neighbor lists; self_w: (K,) = diag(W).  Returns (K, N) mixed.

    ``src`` (optional, (M, N) with M >= K): gather neighbor terms from
    ``src`` rows instead of ``x`` — AD-PSGD's stale mixing, where
    ``src`` is the flattened (staleness+1, K, N) snapshot buffer and
    ``nbr_idx`` carries ``staleness * K + neighbor`` offsets."""
    K, N = x.shape
    assert nbr_idx.shape == nbr_w.shape and nbr_idx.shape[0] == K
    assert self_w.shape == (K,)
    rows = -(-N // LANES)
    rows_pad = -(-rows // block_rows) * block_rows
    x3 = _to_blocks(x, rows_pad)
    n_blocks = rows_pad // block_rows
    block3 = lambda rows: pl.BlockSpec((rows, block_rows, LANES),
                                       lambda i: (0, i, 0))
    # nbr_idx, nbr_w, self_w: whole arrays in SMEM, read as scalars
    scalars = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
    operands = (jnp.asarray(nbr_idx, jnp.int32),
                jnp.asarray(nbr_w, jnp.float32),
                jnp.asarray(self_w, jnp.float32))

    if src is None:
        out = pl.pallas_call(
            _mix_kernel,
            grid=(n_blocks,),
            in_specs=scalars + [block3(K)],
            out_specs=block3(K),
            out_shape=jax.ShapeDtypeStruct(x3.shape, x.dtype),
            interpret=interpret,
            name="neighbor_mix",
        )(*operands, x3)
    else:
        M = src.shape[0]
        assert src.shape[1] == N, (src.shape, x.shape)
        assert M >= K, (M, K)
        src3 = _to_blocks(src, rows_pad)
        out = pl.pallas_call(
            _mix_src_kernel,
            grid=(n_blocks,),
            in_specs=scalars + [block3(K), block3(M)],
            out_specs=block3(K),
            out_shape=jax.ShapeDtypeStruct(x3.shape, x.dtype),
            interpret=interpret,
            name="neighbor_mix",
        )(*operands, x3, src3)
    return out.reshape(K, rows_pad * LANES)[:, :N]
