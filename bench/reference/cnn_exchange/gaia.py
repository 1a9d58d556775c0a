"""Gaia's exchange (Hsieh et al., NSDI 2017, Algorithm 1), as the plain
reference runs it.

Every site keeps its own model.  After its local momentum-SGD step a
site adds the step to an accumulator per parameter; an entry whose
accumulated update exceeds ``t0`` times the parameter's magnitude is
significant, is sent to every other site, and leaves the accumulator.
Each site adds the significant updates of all the others.  The round's
``t0`` is the one the program's step was given.  ``fault="no_exchange"``
shares nothing.
"""
import jax.numpy as jnp

#: which of the compared trees carry a leading site axis
STACKED = {"params": True, "grad0": True}


def init_state(params, n_sites, comm):
    stack = lambda t: {k: jnp.broadcast_to(v, (n_sites,) + v.shape)
                       for k, v in t.items()}
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"params": stack(params), "vel": stack(zeros),
            "acc": stack(zeros)}


def step(state, rnd, *, grads, sgd, comm, fault):
    losses, g = grads(state["params"], stacked=True)
    vel = {k: sgd(state["params"][k], g[k], state["vel"][k], rnd["lr"])
           for k in g}
    params = {k: state["params"][k] + vel[k] for k in g}
    acc = {k: state["acc"][k] + vel[k] for k in g}
    if fault != "no_exchange":
        t0 = rnd["kw"]["t0"]
        for k in g:
            shared = jnp.where(jnp.abs(acc[k]) > t0 * jnp.abs(params[k]),
                               acc[k], jnp.zeros_like(acc[k]))
            params[k] = params[k] + (jnp.sum(shared, axis=0) - shared)
            acc[k] = acc[k] - shared
    return {"params": params, "vel": vel, "acc": acc}, jnp.mean(losses)


def grad0(vel, lr):
    """The gradient as the optimizer got it in round 0, from the velocity
    after that round (the velocity starts at zero)."""
    return {k: -v / lr for k, v in vel.items()}
