"""Adam (Kingma and Ba 2015) in plain ``jax.numpy``: the update the
configurations' ``optimizer`` block names, for the reference's replay of
the job's first steps. Bias-corrected moments, epsilon outside the
square root, no weight decay, no clipping; the hyperparameters come
from the configuration file. Imports nothing of the program.
"""

import jax
import jax.numpy as jnp


def init(w):
    return {"count": jnp.zeros((), jnp.int32),
            "mu": jax.tree.map(jnp.zeros_like, w),
            "nu": jax.tree.map(jnp.zeros_like, w)}


def update(w, grads, state, hp: dict):
    """One step: (new weights, new state)."""
    lr, b1, b2, eps = (float(hp[k]) for k in (
        "learning_rate", "b1", "b2", "eps"))
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g,
                      state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g,
                      state["nu"], grads)
    t = count.astype(jnp.float32)
    mu_scale = 1.0 / (1.0 - b1 ** t)
    nu_scale = 1.0 / (1.0 - b2 ** t)
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m * mu_scale)
        / (jnp.sqrt(v * nu_scale) + eps), w, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}
