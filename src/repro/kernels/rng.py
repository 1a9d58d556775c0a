"""Counter-based seeded RNG shared by the Pallas kernels, their jnp
oracles, and the topology link model.

Materializing full-size random arrays on the host and shipping them into
a kernel doubles the HBM traffic of every stochastic masking pass and
makes the draw order part of the call site.  Instead every random number
here is a *pure function of (key, counter)* — a 32-bit avalanche hash
(Wellons' lowbias32) of a per-stream key and a per-element counter — so
a kernel can generate exactly the numbers it needs for its block from
``(seed, block-start + lane offsets)`` with no input operand, and any
host-side consumer (the generator "baseline", the link model) reproduces
the same stream element-by-element, in any order.

Guarantees:

* ``uniform_bits``/``uniform01`` are **bit-exact** across the numpy
  path, the jnp path, and the in-kernel path (integer ops only; the
  float conversion keeps 24 bits, exact in float32).  Mask/select
  decisions derived from them are therefore identical everywhere —
  the property the dispatch-equivalence tests assert.
* ``normal01`` (Box–Muller over two counter uniforms) is deterministic
  per library; across numpy/jnp it agrees to float ulps (transcendental
  libm vs XLA), which is why only *uniform-derived* decisions are used
  in kernels and the normal path is host-side (link jitter) only.

Not cryptographic — a statistical-quality hash for masks and link
draws, in the spirit of the in-kernel batched-RNG technique from
Leonana69/pie's ``rand_mv.py`` (Triton weights generated inside the
kernel, bit-exact vs a generator baseline).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_M32 = 0xFFFFFFFF
_GOLD = 0x9E3779B9          # 2**32 / golden ratio: stream-key spreading
_INV24 = float(2.0 ** -24)  # 24-bit mantissa uniform step


def _xp(*arrays):
    """numpy or jnp, by argument type (tracers are jax.Array too)."""
    return jnp if any(isinstance(a, jax.Array) for a in arrays) else np


def _mix(x, u32):
    """lowbias32: full-avalanche 32-bit hash (x is a uint32 array)."""
    x = x ^ (x >> u32(16))
    x = x * u32(0x7FEB352D)
    x = x ^ (x >> u32(15))
    x = x * u32(0x846CA68B)
    x = x ^ (x >> u32(16))
    return x


def _mix_py(x: int) -> int:
    """Python-int twin of :func:`_mix` (host-side key folding)."""
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def fold_key(*parts: int) -> int:
    """Fold any number of integer key components (seed, tag, edge ids,
    ...) into one uint32 stream key.  Order-sensitive, avalanche-mixed
    per component, so (seed, 0, 1) and (seed, 1, 0) are independent."""
    k = 0
    for p in parts:
        k = _mix_py((k * _GOLD + (int(p) & _M32)) & _M32)
    return k


def fold_keys(key: int, *parts) -> np.ndarray:
    """Vectorized continuation of :func:`fold_key`: fold integer *array*
    components into an existing scalar key, elementwise — bit-equal to
    calling ``fold_key(..., parts[0][k], parts[1][k], ...)`` per element
    (uint32 arithmetic wraps exactly like the ``& _M32`` masking).
    Host-side (numpy) only; used to key whole edge sets at once."""
    u32 = np.uint32
    k = None
    for p in parts:
        p = np.asarray(p).astype(u32)
        if k is None:
            # first array part: fold the scalar prefix in exact ints
            k = _mix(u32((int(key) * _GOLD) & _M32) + p, u32)
        else:
            k = _mix(k * u32(_GOLD) + p, u32)
    return k if k is not None else np.asarray(int(key), u32)


def uniform_bits(key, ctr):
    """uint32 hash of (key, counter) — the raw stream.  ``key`` scalar
    (or broadcastable array), ``ctr`` any integer array; numpy in/out
    for numpy inputs, jnp for jnp/tracer inputs (kernel-safe)."""
    xp = _xp(key, ctr)
    u32 = xp.uint32
    key = xp.asarray(key).astype(u32)
    ctr = xp.asarray(ctr).astype(u32)
    return _mix(ctr ^ (key * u32(_GOLD)), u32)


def uniform01(key, ctr):
    """float32 uniforms in [0, 1) from (key, counter) — bit-exact across
    numpy / jnp / in-kernel (top 24 bits of the hash, exact in f32)."""
    xp = _xp(key, ctr)
    bits = uniform_bits(key, ctr)
    # the top 24 bits fit int32 exactly; Mosaic has no uint32 -> float cast
    top = (bits >> xp.uint32(8)).astype(xp.int32)
    return top.astype(xp.float32) * xp.float32(_INV24)


def normal01(key, ctr, dtype=None):
    """Standard normals via Box–Muller over counters (2*ctr, 2*ctr+1).
    Deterministic per library; numpy path (float64 by default) is what
    the link model replays."""
    xp = _xp(key, ctr)
    ctr = xp.asarray(ctr)
    dtype = dtype or (np.float64 if xp is np else jnp.float32)
    u1 = uniform01(key, ctr * 2).astype(dtype)
    u2 = uniform01(key, ctr * 2 + 1).astype(dtype)
    # 1 - u1 in (0, 1]: log never sees 0
    r = xp.sqrt(-2.0 * xp.log1p(-u1))
    return r * xp.cos(dtype(2.0 * np.pi) * u2)
