"""Zoo-contract module of the test size, with the faults that
``tests/test_broken_path.py`` plants underneath the timed path: chosen
by ``$BENCH_TEST_FAULT``, which the worker inherits from the test."""

import os

from benchmark.lib.zoo import contract

symbols = contract(__file__)
FAULT = os.environ.get("BENCH_TEST_FAULT", "")

if FAULT == "frozen_step":
    # The step returns its parameters unchanged.
    def optimizer():
        import optax

        return optax.adam(0.0)
    symbols["optimizer"] = optimizer
elif FAULT == "wrong_update":
    # Adam with another first-moment decay than the configuration states.
    def optimizer():
        import optax

        return optax.adam(1e-3, b1=0.5)
    symbols["optimizer"] = optimizer
elif FAULT == "part_of_batch":
    # The last quarter of every minibatch is left out of the loss.
    sound_loss = symbols["loss"]

    def loss(labels, predictions, mask):
        import jax.numpy as jnp

        rows = mask.shape[0]
        kept = jnp.arange(rows) < rows - max(1, rows // 4)
        return sound_loss(labels, predictions, mask * kept)
    symbols["loss"] = loss
elif FAULT:
    raise ValueError(f"unknown $BENCH_TEST_FAULT {FAULT!r}")

globals().update(symbols)
