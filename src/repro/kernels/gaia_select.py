"""Pallas TPU kernel for Gaia's significance filter (Algorithm 1, line 8):
``selected = v * (|v| > T * |w|)`` plus a per-block count of selected
entries.

This is the per-step hot-spot of Gaia at scale: a full HBM sweep of every
accumulated-update tensor.  The kernel fuses compare + mask + popcount into
a single pass over (8, 128)-aligned VMEM tiles, emitting one int32 count
per block (summed cheaply by the caller) instead of an atomic counter — the
TPU-idiomatic replacement for a GPU atomics-based compaction.

Mosaic constraints shape the operands: the threshold scalar lives in SMEM,
and each block's count is an (8, 128) tile of lane-wise partial counts
(:func:`partial_counts`), since a (1, 1) output block breaks the (8, 128)
tiling rule.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def partial_counts(mask: jnp.ndarray) -> jnp.ndarray:
    """(rows, 128) bool -> (8, 128) int32 partial counts of one block:
    whole (8, 128) tiles summed elementwise, which Mosaic lowers to
    plain vector adds.  The caller sums every block's tile."""
    m = mask.astype(jnp.int32)
    return jnp.sum(m.reshape(-1, SUBLANES, LANES), axis=0)


def count_spec(block_rows: int, n_blocks: int):
    """(BlockSpec, ShapeDtypeStruct) of the per-block count output."""
    assert block_rows % SUBLANES == 0, block_rows
    return (pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES), jnp.int32))


def _gaia_kernel(v_ref, w_ref, t_ref, out_ref, cnt_ref):
    v = v_ref[...]
    w = w_ref[...]
    t = t_ref[0]
    mask = jnp.abs(v.astype(jnp.float32)) > t * jnp.abs(w.astype(jnp.float32))
    out_ref[...] = jnp.where(mask, v, jnp.zeros_like(v))
    cnt_ref[...] = partial_counts(mask)


def gaia_select(v: jnp.ndarray, w: jnp.ndarray, threshold: jnp.ndarray, *,
                block_rows: int = 64, interpret: bool = False):
    """v, w: same shape (any rank).  threshold: scalar.
    Returns (selected (same shape), n_selected int32)."""
    assert v.shape == w.shape, (v.shape, w.shape)
    orig_shape = v.shape
    n = v.size
    # lay the tensor out as (rows, 128) lanes, padding the tail
    rows = -(-n // LANES)
    rows_pad = -(-rows // block_rows) * block_rows
    flat_v = jnp.pad(v.reshape(-1), (0, rows_pad * LANES - n))
    flat_w = jnp.pad(w.reshape(-1), (0, rows_pad * LANES - n),
                     constant_values=1.0)  # pad w!=0 so padded v=0 never selects
    v2 = flat_v.reshape(rows_pad, LANES)
    w2 = flat_w.reshape(rows_pad, LANES)
    n_blocks = rows_pad // block_rows
    t_arr = jnp.asarray(threshold, jnp.float32).reshape(1)
    cnt_block, cnt_shape = count_spec(block_rows, n_blocks)

    out, cnt = pl.pallas_call(
        _gaia_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar threshold
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            cnt_block,
        ],
        out_shape=[jax.ShapeDtypeStruct(v2.shape, v.dtype), cnt_shape],
        interpret=interpret,
        name="gaia_select",
    )(v2, w2, t_arr)
    selected = out.reshape(-1)[:n].reshape(orig_shape)
    return selected, jnp.sum(cnt)
