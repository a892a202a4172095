"""Zoo-contract module of the fourth family's test size, with the faults
that ``tests/test_sdar_moe.py`` plants underneath the timed path: chosen
by ``$BENCH_TEST_FAULT``, which the worker (and the check's process)
inherit from the test. (A copy of this file under the real
configuration's name plants the same faults at the cell's size:
PERF.md, section 2.)"""

import os

from benchmark.lib.zoo_sdar_moe import contract, program_zoo

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
elif FAULT == "causal_mask":
    # The doubled row under the plain causal mask over its 2L
    # positions: a noised token sees the noised tokens before it and no
    # clean one.
    from elasticdl_tpu.models import sdar_moe
    from elasticdl_tpu.ops import flash_attention as flash

    def under_causal(attend):
        def attention(q, k, v, scale=None, mask=None):
            del mask
            return attend(q, k, v, causal=True, scale=scale)
        return attention

    sdar_moe.dense_attention = under_causal(sdar_moe.dense_attention)
    sdar_moe.flash_attention = under_causal(sdar_moe.flash_attention)
    sdar_moe.flash_supports = lambda shape, mask=None: flash.supports(shape)
elif FAULT:
    raise ValueError(f"unknown $BENCH_TEST_FAULT {FAULT!r}")

globals().update(symbols)
