"""Pallas TPU kernels for DeepGradientCompression's top-s% sparsification.

GPU DGC implementations use sort/radix-select (warp-shuffle heavy).  TPUs
have no warp shuffles and a full sort is O(n log n) HBM traffic, so we adapt
the *insight* (find a magnitude threshold keeping the top (1-s) fraction) to
a TPU-native two-pass scheme:

  pass 1 — ``abs_histogram_fused``: one kernel launch, two sweeps over
            the blocked layout of |v|: sweep 0 folds the global max
            (the old separate host-side ``jnp.max(|v|)`` pre-pass) into
            SMEM scratch; sweep 1 bins every block against it (per-block
            one-hot matmul-friendly accumulation in VMEM).  The
            max-reduce is order-independent, so the threshold is
            bit-identical to the old two-launch scheme.
  pass 2 — the caller picks the threshold from the cumulative histogram
            (tiny, on host/XLA), then ``dgc_select`` masks v in one more
            fused pass (same structure as gaia_select, absolute threshold).

Histogram quantiles are approximate to one bin width; tests bound the
resulting sparsity error and the benchmark compares against the exact
jnp.quantile oracle.

``rand_k_select`` is the stochastic counterpart (rand-k compression,
the classic baseline top-k is measured against): the keep/drop mask is
generated *inside* the kernel from (seed, flat element index) counters
(``kernels/rng.py``) — no materialized random array crosses HBM, and
the mask is bit-exact against the host generator baseline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rng
from repro.kernels.gaia_select import count_spec, partial_counts

LANES = 128
N_BINS = 256


def _blocked(v: jnp.ndarray, block_rows: int):
    """Flatten + pad any-rank ``v`` into the kernels' (rows_pad, 128)
    lane layout.  Returns (v2, n, n_blocks)."""
    n = v.size
    rows = -(-n // LANES)
    rows_pad = -(-rows // block_rows) * block_rows
    flat = jnp.pad(v.reshape(-1), (0, rows_pad * LANES - n))
    return flat.reshape(rows_pad, LANES), n, rows_pad // block_rows


def _hist_kernel(v_ref, vmax_ref, hist_ref, *, n_bins: int):
    v = jnp.abs(v_ref[...].astype(jnp.float32))         # (rows, 128)
    vmax = jnp.maximum(vmax_ref[0], 1e-30)
    idx = jnp.clip((v / vmax * n_bins).astype(jnp.int32), 0, n_bins - 1)
    # one-hot accumulate: (rows*128, n_bins) -> (n_bins,)
    flat = idx.reshape(-1)
    oh = (flat[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (flat.shape[0], n_bins), 1)).astype(jnp.int32)
    hist_ref[0, :] = jnp.sum(oh, axis=0)


def abs_histogram(v: jnp.ndarray, v_max: jnp.ndarray, *,
                  n_bins: int = N_BINS, block_rows: int = 64,
                  interpret: bool = False) -> jnp.ndarray:
    """256-bin histogram of |v| over [0, v_max].  Padding contributes to
    bin 0; the caller corrects for it (count known statically)."""
    n = v.size
    rows = -(-n // LANES)
    rows_pad = -(-rows // block_rows) * block_rows
    flat = jnp.pad(v.reshape(-1), (0, rows_pad * LANES - n))
    v2 = flat.reshape(rows_pad, LANES)
    n_blocks = rows_pad // block_rows
    vmax_arr = jnp.asarray(v_max, jnp.float32).reshape(1)

    hist = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, n_bins), jnp.int32),
        interpret=interpret,
        name="abs_histogram",
    )(v2, vmax_arr)
    total = jnp.sum(hist, axis=0)
    pad_count = rows_pad * LANES - n
    return total.at[0].add(-pad_count)


def _hist_fused_kernel(v_ref, hist_ref, vmax_ref, mx_scr, *, n_bins: int):
    """Two-sweep grid (sweep, block): sweep 0 reduces the global max of
    |v| into SMEM scratch; sweep 1 bins each block against it.  TPU
    grids run sequentially (and interpret mode mirrors that), so every
    max lands before the first bin is computed."""
    sweep = pl.program_id(0)
    blk = pl.program_id(1)
    v = jnp.abs(v_ref[...].astype(jnp.float32))         # (rows, 128)

    @pl.when((sweep == 0) & (blk == 0))
    def _init():
        mx_scr[0] = 0.0

    @pl.when(sweep == 0)
    def _max():
        mx_scr[0] = jnp.maximum(mx_scr[0], jnp.max(v))
        # the out block is also mapped at sweep 0: write something
        # defined (it is fully overwritten at sweep 1)
        hist_ref[0, :] = jnp.zeros_like(hist_ref[0, :])

    @pl.when(sweep == 1)
    def _bin():
        vmax = jnp.maximum(mx_scr[0], 1e-30)
        idx = jnp.clip((v / vmax * n_bins).astype(jnp.int32), 0, n_bins - 1)
        flat = idx.reshape(-1)
        oh = (flat[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (flat.shape[0], n_bins), 1)).astype(jnp.int32)
        hist_ref[0, :] = jnp.sum(oh, axis=0)
        vmax_ref[0, 0] = mx_scr[0]


def abs_histogram_fused(v: jnp.ndarray, *, n_bins: int = N_BINS,
                        block_rows: int = 64, interpret: bool = False):
    """(histogram of |v| over [0, max|v|], max|v|) in ONE kernel launch —
    the fold of the old host-side ``jnp.max(jnp.abs(v))`` pre-pass into
    the histogram sweep.  Bit-identical histogram/v_max to the separate
    ``jnp.max`` + :func:`abs_histogram` pair (max is order-exact)."""
    v2, n, n_blocks = _blocked(v, block_rows)
    hist, vmax = pl.pallas_call(
        functools.partial(_hist_fused_kernel, n_bins=n_bins),
        grid=(2, n_blocks),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda s, i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, n_bins), lambda s, i: (i, 0)),
            pl.BlockSpec((1, 1), lambda s, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, n_bins), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        name="abs_histogram_fused",
    )(v2)
    total = jnp.sum(hist, axis=0)
    pad_count = v2.size - n
    return total.at[0].add(-pad_count), vmax[0, 0]


def _randk_kernel(v_ref, seed_ref, p_ref, out_ref, cnt_ref, *, n: int):
    """Seeded rand-k mask generated in-kernel: uniform(seed, flat index)
    per element, keep where u < keep_prob — no materialized randoms."""
    blk = pl.program_id(0)
    v = v_ref[...]
    rows, lanes = v.shape
    base = blk * rows * lanes
    idx = base + (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
                  * lanes
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    u = rng.uniform01(seed_ref[0].astype(jnp.uint32), idx)
    keep = (u < p_ref[0]) & (idx < n)          # padding never selects
    out_ref[...] = jnp.where(keep, v, jnp.zeros_like(v))
    cnt_ref[...] = partial_counts(keep)


def rand_k_select(v: jnp.ndarray, keep_prob: jnp.ndarray,
                  seed: jnp.ndarray, *, block_rows: int = 64,
                  interpret: bool = False):
    """Seeded rand-k sparsification: (v * mask, count) with
    ``mask[i] = uniform01(seed, i) < keep_prob``.  ``seed`` and
    ``keep_prob`` are runtime operands (a per-step seed never
    retraces).  Bit-exact vs ``ref.rand_k_select_ref``."""
    orig_shape = v.shape
    v2, n, n_blocks = _blocked(v, block_rows)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1)
    p_arr = jnp.asarray(keep_prob, jnp.float32).reshape(1)
    cnt_block, cnt_shape = count_spec(block_rows, n_blocks)
    out, cnt = pl.pallas_call(
        functools.partial(_randk_kernel, n=n),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # seed scalar
            pl.BlockSpec(memory_space=pltpu.SMEM),   # keep_prob scalar
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            cnt_block,
        ],
        out_shape=[jax.ShapeDtypeStruct(v2.shape, v.dtype), cnt_shape],
        interpret=interpret,
        name="rand_k_select",
    )(v2, seed_arr, p_arr)
    return out.reshape(-1)[:n].reshape(orig_shape), jnp.sum(cnt)


def _select_kernel(v_ref, t_ref, out_ref, cnt_ref):
    v = v_ref[...]
    t = t_ref[0]
    mask = jnp.abs(v.astype(jnp.float32)) > t
    out_ref[...] = jnp.where(mask, v, jnp.zeros_like(v))
    cnt_ref[...] = partial_counts(mask)


def dgc_select(v: jnp.ndarray, threshold: jnp.ndarray, *,
               block_rows: int = 64, interpret: bool = False):
    """Absolute-magnitude select: (v * (|v| > t), count)."""
    orig_shape = v.shape
    v2, n, n_blocks = _blocked(v, block_rows)
    t_arr = jnp.asarray(threshold, jnp.float32).reshape(1)
    cnt_block, cnt_shape = count_spec(block_rows, n_blocks)

    out, cnt = pl.pallas_call(
        _select_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            cnt_block,
        ],
        out_shape=[jax.ShapeDtypeStruct(v2.shape, v.dtype), cnt_shape],
        interpret=interpret,
        name="dgc_select",
    )(v2, t_arr)
    return out.reshape(-1)[:n].reshape(orig_shape), jnp.sum(cnt)


def threshold_from_histogram(hist: jnp.ndarray, v_max: jnp.ndarray,
                             sparsity: jnp.ndarray) -> jnp.ndarray:
    """Pick the bin edge whose cumulative count first reaches ``sparsity``
    of the total — the DGC magnitude threshold."""
    n_bins = hist.shape[0]
    cum = jnp.cumsum(hist)
    total = cum[-1]
    target = sparsity * total.astype(jnp.float32)
    bin_idx = jnp.searchsorted(cum.astype(jnp.float32), target)
    bin_idx = jnp.clip(bin_idx, 0, n_bins - 1)
    return (bin_idx.astype(jnp.float32) + 1.0) / n_bins * v_max
