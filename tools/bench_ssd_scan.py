"""The chunked-scan kernels and the grouped-query attention kernels on
the chip: the compiled kernels against their references at a size the
references can take, then their times at a cell's shapes (host clock
around ``block_until_ready``, the median of ``REPEATS`` calls after one
to compile). Run through the chip tool; prints one JSON line a reading.

    python tools/bench_ssd_scan.py [check] [scan] [attention] [experts]
"""

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.ops.flash_attention import flash_attention  # noqa: E402
from elasticdl_tpu.ops.ring_attention import dense_attention  # noqa: E402
from elasticdl_tpu.ops.ssd_scan import ssd_reference, ssd_scan  # noqa: E402

REPEATS = 5


def say(**reading):
    device = jax.devices()[0]
    print(json.dumps(dict(reading, platform=device.platform,
                          device_kind=device.device_kind)), flush=True)


def scan_inputs(bt, s, h, p, g, n, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (
        jax.random.normal(ks[0], (bt, s, h, p)).astype(dtype),
        jax.nn.softplus(jax.random.normal(ks[1], (bt, s, h)) - 4.0),
        -jnp.exp(jax.random.uniform(ks[2], (h,)) * 2.7),
        jax.random.normal(ks[3], (bt, s, g, n)).astype(dtype),
        jax.random.normal(ks[4], (bt, s, g, n)).astype(dtype),
        jnp.ones((h,)),
    ), jax.random.normal(ks[6], (bt, s, h, p)).astype(dtype)


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        began = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - began)
    return 1e3 * statistics.median(times)


def worst(got, want):
    return max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
              / (jnp.max(jnp.abs(b)) + 1e-30))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def check():
    args, weight = scan_inputs(1, 512, 16, 64, 2, 128, jnp.float32)
    every = tuple(range(6))
    w32 = weight.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
            ssd_scan(*a) * w32), every))(*args)
        want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
            ssd_reference(*a) * w32), every))(*args)
    say(what="ssd_scan compiled, float32, against the recurrence",
        loss_gap=abs(float(got[0] - want[0])) / abs(float(want[0])),
        worst_gradient_gap=worst(got[1], want[1]))
    low = tuple(a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args)
    got = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
        ssd_scan(*a).astype(jnp.float32) * w32), every))(*low)
    say(what="ssd_scan compiled, bfloat16, against the float32 recurrence",
        loss_gap=abs(float(got[0] - want[0])) / abs(float(want[0])),
        worst_gradient_gap=worst(got[1], want[1]))
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, 2048, 32, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2048, 2, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2048, 2, 128), jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, 2048, 32, 128), jnp.float32)
    got = jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v).astype(jnp.float32) * w), (0, 1, 2)))(
            q, k, v)
    want = jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
        dense_attention(q, jnp.repeat(k, 16, 2), jnp.repeat(v, 16, 2))
        .astype(jnp.float32) * w), (0, 1, 2)))(q, k, v)
    say(what="flash_attention 32 over 2 heads, bfloat16, against dense",
        loss_gap=abs(float(got[0] - want[0])) / abs(float(want[0])),
        worst_gradient_gap=worst(
            got[1], jax.tree.map(lambda x: x.astype(jnp.float32), want[1])))


def scan():
    args, weight = scan_inputs(2, 8192, 64, 64, 8, 128)
    forward = jax.jit(lambda *a: ssd_scan(*a))
    both = jax.jit(jax.grad(lambda *a: jnp.sum(
        ssd_scan(*a).astype(jnp.float32) * weight), tuple(range(6))))
    fwd = timed(forward, *args)
    say(what="ssd_scan x(2, 8192, 64, 64), 8 groups, state 128, a layer",
        forward_ms=fwd, forward_and_backward_ms=timed(both, *args))


def attention():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 8192, 32, 128), jnp.bfloat16)
    for kv in (2, 32):
        k = jax.random.normal(ks[1], (2, 8192, kv, 128), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, 8192, kv, 128), jnp.bfloat16)
        forward = jax.jit(lambda q, k, v: flash_attention(q, k, v))
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v).astype(jnp.float32)), (0, 1, 2)))
        say(what=f"flash_attention q(2, 8192, 32, 128) over {kv} key/value "
            "heads, a layer", forward_ms=timed(forward, q, k, v),
            forward_and_backward_ms=timed(both, q, k, v))


def experts():
    """The relu^2 experts' two grouped products, forward and backward,
    at the cell's bound (98,304 rows, 6,144 of them live, 8 groups) and
    three widths: the published 1,856 is 14.5 lane tiles."""
    from elasticdl_tpu.models.mla_moe import relu2
    from elasticdl_tpu.ops.grouped_matmul import grouped_matmul

    bound, live, groups, d = 98304, 6144, 8, 2688
    sizes = jnp.full((groups,), live // groups, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    rows = jax.random.normal(ks[0], (bound, d), jnp.bfloat16)
    in_a_group = (jnp.arange(bound) < live)[:, None]
    for width in (1856, 1920, 2048):
        up = jax.random.normal(ks[1], (groups, d, width), jnp.bfloat16) * 0.02
        down = jax.random.normal(ks[2], (groups, width, d), jnp.bfloat16) * 0.02

        def loss(rows, up, down):
            out = grouped_matmul(
                relu2(grouped_matmul(rows, up, sizes)), down, sizes)
            return jnp.sum(jnp.where(in_a_group, out, 0).astype(jnp.float32))

        both = jax.jit(jax.grad(loss, (0, 1, 2)))
        say(what=f"relu2 experts, {live} live rows of {bound}, 8 groups, "
            f"2688 -> {width} -> 2688, a layer",
            forward_ms=timed(jax.jit(loss), rows, up, down),
            forward_and_backward_ms=timed(both, rows, up, down))


if __name__ == "__main__":
    for part in sys.argv[1:] or ["check", "scan", "attention", "experts"]:
        {"check": check, "scan": scan, "attention": attention,
         "experts": experts}[part]()
