"""Adam as ``adam.py`` has it (Kingma and Ba 2015: bias-corrected
moments, epsilon outside the square root, no weight decay, no clipping),
with the two moments kept in the host's pinned memory between steps.

For configurations whose replay does not fit a free chip otherwise: the
check holds the weights, the program's first gradients, the reference's
first gradients and the step's gradients on the device, four trees of
the parameters' size in float32, and Adam's two moments would be a fifth
and a sixth (at 680M parameters 16.3 GB of a v5e's 16.9, before a single
temporary). Here a step brings one leaf's moments to the device, updates
that leaf and sends them back before the next leaf's come, so the device
never holds more of them than one leaf's. The numbers are ``adam.py``'s
to the bit: the same expressions in the same order.

Where the configuration's ``optimizer`` block gives ``warmup_steps``, the
learning rate of step t (from 1) is ``learning_rate * min(1, (t - 1) /
warmup_steps)``: linear from 0, so the first step moves nothing. Where
it gives a ``bias_update_speed``, the leaves named ``.../router_b`` (an expert
layer's selection bias) are not Adam's: their "gradient" is the load's
direction and they move against it by that speed, b -= speed * g, the
auxiliary-loss-free balancing rule (Wang et al. 2024; DeepSeek-V3,
section 2.1.2). Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

HOST, DEVICE = jax.memory.Space.Host, jax.memory.Space.Device


def init(w):
    zeros = jax.jit(
        lambda tree: jax.device_put(jax.tree.map(jnp.zeros_like, tree), HOST))
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros(w), "nu": zeros(w)}


def update(w, grads, state, hp: dict):
    """One step: (new weights, new state); traceable, one leaf after
    another."""
    lr, b1, b2, eps = (float(hp[k]) for k in (
        "learning_rate", "b1", "b2", "eps"))
    warmup = float(hp.get("warmup_steps", 0))
    if warmup:
        lr = lr * jnp.minimum(
            1.0, state["count"].astype(jnp.float32) / warmup)
    count = state["count"] + 1
    t = count.astype(jnp.float32)
    mu_scale = 1.0 / (1.0 - b1 ** t)
    nu_scale = 1.0 / (1.0 - b2 ** t)
    speed = hp.get("bias_update_speed")
    named, tree = jax.tree_util.tree_flatten_with_path(w)
    new, mus, nus = [], [], []
    sent = ()
    for (path, p), g, m, v in zip(named, jax.tree.leaves(grads),
                                  jax.tree.leaves(state["mu"]),
                                  jax.tree.leaves(state["nu"])):
        if speed is not None and jax.tree_util.keystr(path).endswith(
                "router_b']"):
            new.append(p - float(speed) * g)
            mus.append(m)
            nus.append(v)
            continue
        # This leaf's moments leave the host only when the last leaf's
        # are back there.
        m, v, _ = jax.lax.optimization_barrier((m, v, sent))
        m, v = jax.device_put((m, v), DEVICE)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        new.append(p - lr * (m * mu_scale) / (jnp.sqrt(v * nu_scale) + eps))
        sent = jax.device_put((m, v), HOST)
        mus.append(sent[0])
        nus.append(sent[1])
    unflatten = lambda xs: jax.tree.unflatten(tree, xs)  # noqa: E731
    return unflatten(new), {"count": count, "mu": unflatten(mus),
                            "nu": unflatten(nus)}
