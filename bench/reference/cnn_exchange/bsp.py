"""BSP's exchange, as the plain reference runs it: one global model;
every round each site takes the gradient of its own batch, the sites'
gradients are averaged, and one momentum-SGD step updates the model.
``fault="no_exchange"`` applies site 0's gradient alone.
"""
import jax.numpy as jnp

#: which of the compared trees carry a leading site axis
STACKED = {"params": False, "grad0": False}


def init_state(params, n_sites, comm):
    return {"params": params,
            "vel": {k: jnp.zeros_like(v) for k, v in params.items()}}


def step(state, rnd, *, grads, sgd, comm, fault):
    losses, g = grads(state["params"], stacked=False)
    g = {k: (v[0] if fault == "no_exchange" else jnp.mean(v, axis=0))
         for k, v in g.items()}
    vel = {k: sgd(state["params"][k], g[k], state["vel"][k], rnd["lr"])
           for k in g}
    params = {k: state["params"][k] + vel[k] for k in g}
    return {"params": params, "vel": vel}, jnp.mean(losses)


def grad0(vel, lr):
    """The gradient as the optimizer got it in round 0, from the velocity
    after that round (the velocity starts at zero)."""
    return {k: -v / lr for k, v in vel.items()}
