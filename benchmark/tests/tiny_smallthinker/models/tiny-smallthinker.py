"""Zoo-contract module of the fifth family's test size, with the faults
that ``tests/test_smallthinker.py`` plants underneath the timed path:
chosen by ``$BENCH_TEST_FAULT``, which the worker (and the check's
process) inherit from the test. (A copy of this file under the real
configuration's name plants the same faults at the cell's size:
PERF.md, section 2.)"""

import os

from benchmark.lib.zoo_smallthinker import contract, program_zoo

symbols = contract(__file__)
FAULT = os.environ.get("BENCH_TEST_FAULT", "")
HYPER = symbols["CONFIG"]["optimizer"]

if FAULT == "frozen_step":
    # The step returns its parameters unchanged.
    symbols["optimizer"] = lambda: program_zoo().optimizer(0.0)
elif FAULT == "wrong_update":
    # Adam at three times the configuration's rate.
    symbols["optimizer"] = lambda: program_zoo().optimizer(
        3 * HYPER["learning_rate"], HYPER["warmup_steps"])
elif FAULT == "half_of_batch":
    # The second half of every minibatch is left out of the loss.
    sound_loss = symbols["loss"]

    def loss(labels, predictions, mask):
        import jax.numpy as jnp

        rows = mask.shape[0]
        return sound_loss(labels, predictions,
                          mask * (jnp.arange(rows) < rows - rows // 2))
    symbols["loss"] = loss
elif FAULT == "full_causal":
    # The window layers run as full causal: every band is as wide as
    # the row, in the kernels' plan and in the dense rule alike.
    from elasticdl_tpu.models import smallthinker
    from elasticdl_tpu.ops.flash_attention import SlidingWindow

    smallthinker.SlidingWindow = lambda length, window: SlidingWindow(
        length, length)
elif FAULT:
    raise ValueError(f"unknown $BENCH_TEST_FAULT {FAULT!r}")

globals().update(symbols)
