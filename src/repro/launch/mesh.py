"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips.  The ``pod`` axis is
the decentralized-learning *site* axis: the paper's algorithms (Gaia /
FedAvg / DGC, and the D-PSGD/AD-PSGD gossip ring) control traffic across
it, standard data+tensor parallelism runs inside each pod.

A FUNCTION (not module-level constant) so importing never touches jax
device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``-typed — the one mesh
    constructor of the repo.  The launch path leaves sharding of the
    intermediates to GSPMD (with ``shard_hints`` as hints); jax's default
    ``Explicit`` axes would instead demand an ``out_sharding`` at every
    op that mixes sharded operands."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def n_pods(mesh) -> int:
    return mesh.shape["pod"] if "pod" in mesh.axis_names else 1


def devices_per_pod(mesh) -> int:
    """Chips inside one pod — the device-id stride of the ``pod`` axis
    (mesh axes are ordered pod-major), which is what the HLO pod-traffic
    check keys on."""
    return mesh.devices.size // n_pods(mesh)


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over (within-pod data axis only —
    the pod axis is the explicit site dimension)."""
    return ("data",)
