"""Plain reference of decentralized CNN training: LeNet with BatchNorm,
momentum SGD with L2 weight decay, and each strategy's exchange from its
own file, ``cnn_exchange/<strategy>.py``.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, written
from the paper's description (Hsieh et al., ICML 2020, sections 3-5).
It imports nothing of the program and takes none of its weights: the
initial weights are drawn here from the seed by the same recipe the
configuration states (He-normal convolutions and hidden layers,
LeCun-normal output layer, zero biases, BatchNorm scale 1 and bias 0),
so both sides start from the same numbers.

``precision="high"`` runs its convolutions and matmuls as three
bfloat16 passes (XLA's ``high``), written out as a split of each operand
into bfloat16 high and low parts so that it means the same on any
backend: the control that the comparison must refuse for a configuration
that states float32 at ``highest``.  ``dtype`` runs the whole reference,
state included, in another type.  ``fault`` plants one of the faults the
comparison must catch: ``half_batch`` (the loss averaged over the first
half of each site's batch) and ``no_exchange`` (the exchange left out,
as the strategy's file says).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5


def init_params(seed: int, cfg: Dict) -> Dict[str, jnp.ndarray]:
    convs, fcs = cfg["conv_channels"], cfg["fc_dims"]
    keys = jax.random.split(jax.random.PRNGKey(seed),
                            len(convs) + len(fcs) + 1)
    p = {}
    c_in, side = cfg["in_channels"], cfg["image_size"]
    for i, (c, k) in enumerate(zip(convs, cfg["kernel_sizes"])):
        fan_in = k * k * c_in
        p[f"conv{i}.w"] = (jax.random.normal(keys[i], (k, k, c_in, c))
                           * (2.0 / fan_in) ** 0.5)
        p[f"conv{i}.b"] = jnp.zeros((c,))
        p[f"norm{i}.scale"] = jnp.ones((c,))
        p[f"norm{i}.bias"] = jnp.zeros((c,))
        if cfg["pool_after"][i]:
            side //= 2
        c_in = c
    d = side * side * c_in
    for j, fd in enumerate(fcs):
        p[f"fc{j}.w"] = (jax.random.normal(keys[len(convs) + j], (d, fd))
                         * (2.0 / d) ** 0.5)
        p[f"fc{j}.b"] = jnp.zeros((fd,))
        d = fd
    p["out.w"] = (jax.random.normal(keys[-1], (d, cfg["n_classes"]))
                  * d ** -0.5)
    p["out.b"] = jnp.zeros((cfg["n_classes"],))
    return p


HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def _split(a):
    """``a`` as the sum of two bfloat16 values, each held in a's type."""
    hi = a.astype(jnp.bfloat16).astype(a.dtype)
    return hi, (a - hi).astype(jnp.bfloat16).astype(a.dtype)


def _passes(op, a, b):
    """hi*hi + hi*lo + lo*hi: each product of bfloat16 values exact,
    accumulated in float32."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return op(ah, bh) + (op(ah, bl) + op(al, bh))


def _three_pass(op):
    """The bilinear ``op`` as three bfloat16 passes, forward and backward
    alike (XLA gives the transposed ops of a pass count the same count)."""
    @jax.custom_vjp
    def f(a, b):
        return _passes(op, a, b)

    def fwd(a, b):
        return _passes(op, a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = _passes(lambda gg, bb: jax.vjp(lambda x: op(x, bb), a)[1](gg)[0],
                     g, b)
        db = _passes(lambda gg, aa: jax.vjp(lambda y: op(aa, y), b)[1](gg)[0],
                     g, a)
        return da, db

    f.defvjp(fwd, bwd)
    return f


def forward(p, x, cfg, precision):
    """Logits of one site's batch, BatchNorm on the batch's statistics."""
    conv, dot = ((_conv, _dot) if precision == "highest"
                 else (_three_pass(_conv), _three_pass(_dot)))
    for i in range(len(cfg["conv_channels"])):
        y = conv(x, p[f"conv{i}.w"]) + p[f"conv{i}.b"]
        mu = jnp.mean(y, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(y - mu), axis=(0, 1, 2))
        y = (y - mu) / jnp.sqrt(var + BN_EPS)
        y = jax.nn.relu(y * p[f"norm{i}.scale"] + p[f"norm{i}.bias"])
        if cfg["pool_after"][i]:
            y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID")
        x = y
    x = x.reshape(x.shape[0], -1)
    for j in range(len(cfg["fc_dims"])):
        x = jax.nn.relu(dot(x, p[f"fc{j}.w"]) + p[f"fc{j}.b"])
    return dot(x, p["out.w"]) + p["out.b"]


def site_loss(p, x, y, cfg, precision):
    logp = jax.nn.log_softmax(forward(p, x, cfg, precision))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def exchange(strategy: str):
    """The reference of ``strategy``'s exchange: ``cnn_exchange/<strategy>.py``
    beside this file, with ``STACKED``, ``init_state``, ``step`` and
    ``grad0``.  A strategy is one more file there."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "cnn_exchange", strategy + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"no reference for strategy {strategy!r} "
                         f"({path} is missing)")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_exchange_" + strategy, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_step(cfg: Dict, comm: Dict, *, momentum: float,
              weight_decay: float, precision: str = "highest",
              fault: Optional[str] = None):
    """One round for all K sites: (state, round) -> (state, loss), where a
    round holds ``x`` (K, B, H, W, C), ``y`` (K, B), ``lr``, ``t`` and the
    step's keyword operands ``kw``.  The exchange is ``comm["strategy"]``'s
    module.  Built once per set of arguments, so that repeated runs
    compile once."""
    return _make_step(json.dumps(cfg, sort_keys=True),
                      json.dumps(comm, sort_keys=True), momentum,
                      weight_decay, precision, fault)


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json, comm_json, momentum, weight_decay, precision,
               fault):
    cfg, comm = json.loads(cfg_json), json.loads(comm_json)
    if precision not in ("highest", "high"):
        raise ValueError(f"precision {precision!r}")
    exch = exchange(comm["strategy"])
    grad = jax.value_and_grad(
        lambda p, x, y: site_loss(p, x, y, cfg, precision))

    def sgd(w, g, u, lr):
        return momentum * u - lr * (g + weight_decay * w)

    def step(state, rnd):
        x, y = rnd["x"], rnd["y"]
        if fault == "half_batch":
            half = x.shape[1] // 2
            x, y = x[:, :half], y[:, :half]

        def grads(params, stacked):
            return jax.vmap(grad, in_axes=(0 if stacked else None, 0, 0))(
                params, x, y)
        return exch.step(state, rnd, grads=grads, sgd=sgd, comm=comm,
                         fault=None if fault == "half_batch" else fault)

    return jax.jit(step)


def run(cfg: Dict, comm: Dict, seed: int, rounds, *, momentum: float,
        weight_decay: float, dtype=jnp.float32, precision: str = "highest",
        fault: Optional[str] = None) -> Dict:
    """Drive the reference through the recorded rounds.

    ``rounds`` is a list of dicts, one per round: ``x`` (K, B, H, W, C),
    ``y`` (K, B), ``lr``, ``t`` and ``kw``, the step's other operands.
    Returns what the comparison reads: each round's loss, the gradient as
    the optimizer got it in round 0, and the parameters before round 0
    and after the last.
    """
    exch = exchange(comm["strategy"])
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    n_sites = rounds[0]["x"].shape[0]
    state = cast(exch.init_state(init_params(seed, cfg), n_sites, comm))
    p0 = state["params"]
    step = make_step(cfg, comm, momentum=momentum,
                     weight_decay=weight_decay, precision=precision,
                     fault=fault)
    host = lambda t: {k: np.asarray(v, np.float64) for k, v in t.items()}
    losses, grad0 = [], None
    with jax.default_matmul_precision("highest"):
        for r in rounds:
            state, loss = step(state, {
                "x": jnp.asarray(r["x"], dtype), "y": jnp.asarray(r["y"]),
                "lr": jnp.asarray(r["lr"], dtype),
                "t": jnp.asarray(r["t"], jnp.int32),
                "kw": {k: jnp.asarray(v, dtype if isinstance(v, float)
                                      else None)
                       for k, v in r["kw"].items()}})
            losses.append(float(loss))
            if grad0 is None:
                grad0 = exch.grad0(host(state["vel"]), float(r["lr"]))
    return {"losses": losses, "grad0": grad0, "params0": host(p0),
            "params_end": host(state["params"])}
