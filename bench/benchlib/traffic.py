"""Traffic generation: every input a cell feeds the program, from the seed.

One general generator per kind of input, driven by the parameters of a
traffic file (``bench/traffic/<mix>.json``):

* ``images``: a synthetic CIFAR-10 stand-in (smooth class prototypes
  under shift, per-pixel noise and brightness jitter), then a label-skew
  partition over the sites.  The recipe follows the program's
  ``data/synthetic.synth_images`` and ``core/partition.
  partition_label_skew``, written out here in vectorised form so that the
  yardstick cannot move when the program's copies change.

``derive_seed`` maps the benchmark's ``--seed`` (any whole number, far
wider than 32 bits) onto the 31-bit seeds that numpy streams and
``jax.random.PRNGKey`` take without truncation.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the class prototypes ("the world") are fixed; only sampling follows the
#: seed, so every seed trains the same task on different images
CLASS_SEED = 1234


def derive_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one named stream of the run."""
    words = [int(b) for b in tag.encode()]
    ss = np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32, *words])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def _prototypes(rng: np.random.Generator, n_classes: int, side: int,
                channels: int) -> np.ndarray:
    """Smooth class prototypes: 4x4 random fields upsampled bilinearly."""
    coarse = rng.normal(size=(n_classes, 4, 4, channels))
    xs = np.linspace(0, 3, side)
    xi = np.floor(xs).astype(int).clip(0, 2)
    xf = xs - xi
    rows = (coarse[:, xi] * (1 - xf)[None, :, None, None]
            + coarse[:, xi + 1] * xf[None, :, None, None])
    cols = (rows[:, :, xi] * (1 - xf)[None, None, :, None]
            + rows[:, :, xi + 1] * xf[None, None, :, None])
    return (cols * 1.5).astype(np.float32)


def synth_images(n: int, *, side: int, channels: int, n_classes: int,
                 noise: float, class_sep: float, seed: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(x (n, side, side, channels) float32, y (n,) int32)."""
    rng = np.random.default_rng(seed)
    crng = np.random.default_rng(CLASS_SEED)
    protos = _prototypes(crng, n_classes, side, channels)
    if class_sep != 1.0:
        base = _prototypes(crng, 1, side, channels)[0]
        protos = base[None] + class_sep * protos
    # per-class channel offsets: a label-skewed site sees shifted batch
    # statistics, the paper's BatchNorm mechanism (section 5.1)
    protos = protos + crng.normal(
        scale=0.6, size=(n_classes, 1, 1, channels)).astype(np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    # a circular shift of up to 2 pixels each way: every prototype under
    # each of the 25 shifts, then one row gather
    sh = rng.integers(-2, 3, size=(n, 2))
    rolled = np.stack([np.roll(protos, (a, b), axis=(1, 2))
                       for a in range(-2, 3) for b in range(-2, 3)], axis=1)
    which = (y * 25 + (sh[:, 0] + 2) * 5 + sh[:, 1] + 2).astype(np.int32)
    bright = rng.uniform(0.8, 1.2, size=n).astype(np.float32)
    table = jnp.asarray(rolled.reshape((-1,) + protos.shape[1:]))
    key = jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))
    x = np.empty((n,) + protos.shape[1:], np.float32)
    for i in range(0, n, CHUNK):
        m = min(CHUNK, n - i)
        pad = lambda a: np.pad(a[i:i + m], (0, CHUNK - m))
        x[i:i + m] = np.asarray(_noisy_rows(
            table, pad(which), pad(bright), np.float32(noise),
            jax.random.fold_in(key, i)))[:m]
    return x, y


#: rows made on the device per call: one compiled shape, and a few tens
#: of MB on the device however large the data set
CHUNK = 4096


@jax.jit
def _noisy_rows(table, which, bright, noise, key):
    """``(table[which] + noise * N(0, 1)) * bright``, made on the device:
    the hundreds of millions of normal draws are the bulk of the data's
    making, and the host takes seconds for them."""
    x = jnp.take(table, which, axis=0)
    z = jax.random.normal(key, x.shape, jnp.float32)
    return (x + noise * z) * bright[:, None, None, None]


def label_skew_partition(y: np.ndarray, n_sites: int, skew: float,
                         seed: int) -> List[np.ndarray]:
    """Per-site index arrays: a ``skew`` share of the samples is dealt by
    class (class c to site c % n_sites), the rest round-robin."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(y))
    n_skewed = int(round(skew * len(y)))
    skewed, iid = perm[:n_skewed], perm[n_skewed:]
    site_of = y[skewed] % n_sites
    parts = [np.concatenate([skewed[site_of == k], iid[k::n_sites]])
             for k in range(n_sites)]
    out = [np.sort(p).astype(np.int64) for p in parts]
    for k, p in enumerate(out):
        if len(p) == 0:
            raise ValueError(f"site {k} received no data")
    return out


def image_task(traffic: Dict, config: Dict, seed: int):
    """Training partitions and validation set of an ``images`` mix."""
    d = traffic["data"]
    kw = dict(side=config["image_size"], channels=config["in_channels"],
              n_classes=config["n_classes"], noise=d["noise"],
              class_sep=d["class_sep"])
    x, y = synth_images(d["n_train"], seed=derive_seed(seed, "train"), **kw)
    vx, vy = synth_images(d["n_val"], seed=derive_seed(seed, "val"), **kw)
    idx = label_skew_partition(y, traffic["sites"], d["label_skew"],
                               derive_seed(seed, "partition"))
    return [(x[i], y[i]) for i in idx], (vx, vy)
