"""The chunked-scan kernels and the grouped-query attention kernels on
the chip: the compiled kernels against their references at a size the
references can take, then their times at a cell's shapes (host clock
around ``block_until_ready``, the median of ``REPEATS`` calls after one
to compile). Run through the chip tool; prints one JSON line a reading.

    python tools/bench_ssd_scan.py [check] [scan] [attention] [experts]

(``bound`` alone is the first part of ``experts``; ``bound <cell> ...``
keeps it to those cells' layers; ``combine [<cell> ...]`` times the
combine of a rung's rows alone.)
"""

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.models import mla_moe  # noqa: E402
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


def _products(rows, sizes, wide, up, down, gate=None):
    """A layer's two grouped products as ``held_experts_part`` runs
    them: the weights cast to bfloat16 and zero-padded to ``wide``
    columns, ``hidden`` left that wide between the products."""
    from flax import linen as nn

    from elasticdl_tpu.models.mla_moe import relu2
    from elasticdl_tpu.ops.grouped_matmul import grouped_matmul, zero_padded

    def widened(w, axis):
        return zero_padded(w.astype(jnp.bfloat16), axis, wide)

    if gate is None:
        hidden = relu2(grouped_matmul(rows, widened(up, 2), sizes))
    else:
        gate_up = grouped_matmul(rows, jnp.concatenate(
            [widened(gate, 2), widened(up, 2)], axis=2), sizes)
        hidden = nn.silu(gate_up[:, :wide]) * gate_up[:, wide:]
    return grouped_matmul(hidden, widened(down, 1), sizes)


def _expert_weights(groups, d, f, gated, params):
    """(w_up, w_down[, w_gate]) of type ``params``."""
    shapes = [(groups, d, f), (groups, f, d)] + [(groups, d, f)] * gated
    keys = jax.random.split(jax.random.PRNGKey(4), len(shapes))
    return [(jax.random.normal(key, shape) * 0.02).astype(params)
            for key, shape in zip(keys, shapes)]


def _expert_layer(bound, live, groups, d, f, wide, gated, params):
    """The products over ``bound`` rows given as they are, dead rows
    selected away. Returns (forward, forward with backward) and their
    arguments."""
    sizes = jnp.full((groups,), live // groups, jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(3), (bound, d), jnp.bfloat16)
    in_a_group = (jnp.arange(bound) < live)[:, None]
    weights = _expert_weights(groups, d, f, gated, params)

    def loss(rows, *weights):
        out = _products(rows, sizes, wide, *weights)
        return jnp.sum(jnp.where(in_a_group, out, 0).astype(jnp.float32))

    every = tuple(range(1 + len(weights)))
    return jax.jit(loss), jax.jit(jax.grad(loss, every)), (rows, *weights)


# The expert cells' layers from the tokens' side: tokens a step, top k,
# the router's width, held experts, hidden size, experts' width, form,
# and whether the cell's seeded router sends every token to one held
# expert and k - 1 absent ones (``members_alike`` and its kin) or to k
# of the router's experts at random.
CELL_LAYERS = {
    "joyai_ep16_steady": (16384, 8, 256, 16, 2048, 768, "silu_gated", False),
    "nemotron3n_ep16_steady": (16384, 6, 128, 8, 2688, 1856, "relu2", False),
    "sdar_ep8_steady": (16384, 8, 128, 16, 2048, 768, "silu_gated", True),
    "smallthinker_ep8_steady": (16384, 6, 64, 8, 2560, 768, "relu_gated",
                                True),
}


def _recomputed_layer(tokens, k, router_width, n, d, f, form, crowd):
    """``held_experts_part`` itself on a routing spread evenly over the
    first ``router_width // crowd`` experts of the router (so the held
    ones get ``crowd`` times a balanced router's share). Returns
    (forward, a recomputed layer whole: forward, then under
    ``jax.checkpoint`` what its backward pass runs), their arguments
    and the held rows."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    rows = jax.random.normal(keys[0], (tokens, d), jnp.bfloat16)
    weigh = jax.random.uniform(keys[1], (tokens, k), jnp.float32)
    pull = jax.random.normal(keys[2], (tokens, d), jnp.float32)
    routed = router_width // crowd
    chosen = (jnp.arange(tokens, dtype=jnp.int32)[:, None]
              + jnp.arange(k, dtype=jnp.int32)[None, :] * (routed // k)
              ) % routed
    up, down, *gate = _expert_weights(n, d, f, form != "relu2", jnp.float32)

    def part(rows, weigh, up, down, *gate):
        return mla_moe.held_experts_part(
            rows, chosen, weigh, gate[0] if gate else None, up, down, 0,
            router_width, form)[0]

    def loss(*moving):
        return jnp.sum(jax.checkpoint(part)(*moving).astype(jnp.float32)
                       * pull)

    args = (rows, weigh, up, down, *gate)
    return (jax.jit(part),
            jax.jit(jax.value_and_grad(loss, tuple(range(len(args))))), args,
            int(jnp.sum(chosen < n)))


def bound(cells=()):
    """What the ladder of row bounds buys (PERF.md section 7, row 28):
    the cells' expert layers (``cells``, or all four) from the tokens'
    side, by the code the cells run. Over every token-choice on one
    path (all the program had before PR 37); then under a two-way
    conditional at 2 and at 4 times the held share (the second PR 37's
    program) and under the ladder's three-way one, on a balanced
    routing, on one that sends the held experts 3 times their share
    (the rung at 4) and on one that sends them 6 times (past every
    rung: the path over every token-choice inside the conditional)."""
    kept = mla_moe.RUNG_FACTORS
    ways = [((), 1), ((2,), 1), ((4,), 1), ((2, 4), 1), ((2,), 3), ((4,), 3),
            ((2, 4), 3), ((4,), 6), ((2, 4), 6)]
    for cell in cells or CELL_LAYERS:
        tokens, k, width, n, d, f, form, _ = CELL_LAYERS[cell]
        for factors, crowd in ways:
            mla_moe.RUNG_FACTORS = factors
            try:
                forward, whole, args, live = _recomputed_layer(
                    tokens, k, width, n, d, f, form, crowd)
                ladder = mla_moe.rows_ladder(tokens * k, n, width)
                say(what=f"{cell}'s expert layer from the tokens' side, "
                    f"{live} live rows, rungs at {factors or 'none'}",
                    ladder=ladder, branches=len(ladder),
                    rows=min(rung for rung in ladder
                             if rung >= live or rung == ladder[-1]),
                    forward_ms=timed(forward, *args),
                    recomputed_layer_ms=timed(whole, *args))
            finally:
                mla_moe.RUNG_FACTORS = kept
            del forward, whole, args


def _combine_case(cell, sizes_of_rows):
    """One routing of ``cell``'s layer, as its seeded router sends it,
    sorted as the layer sorts it, and the combine alone over the first
    ``places`` sorted places for every ``places`` of ``sizes_of_rows``."""
    import numpy as np

    tokens, k, width, n, d, _, _, alike = CELL_LAYERS[cell]
    rng = np.random.default_rng(0)
    if not alike:
        chosen = np.argsort(rng.random((tokens, width)), axis=1)[:, :k]
    else:
        chosen = np.concatenate([
            rng.integers(0, n, (tokens, 1)),
            np.stack([rng.permutation(np.arange(n, width))[:k - 1]
                      for _ in range(tokens)])], axis=1)
    order, inverse, sizes, _ = mla_moe._sorted_choices(
        jnp.asarray(chosen, jnp.int32), 0, n)
    held = int(sizes.sum())

    def added(rows, to):
        return jnp.zeros((tokens, d), jnp.float32).at[to].add(
            rows.astype(jnp.float32)).astype(rows.dtype)

    def gathered(rows, inverse):
        picked = rows.at[inverse].get(mode="fill", fill_value=0)
        return picked.reshape(tokens, k, d).astype(jnp.float32).sum(
            axis=1).astype(rows.dtype)

    for places in sizes_of_rows:
        live = jnp.arange(places) < held
        rows = jnp.where(live[:, None], jax.random.normal(
            jax.random.PRNGKey(1), (places, d), jnp.bfloat16), 0)
        to = order[:places] // k
        say(what=f"{cell}'s combine alone, {held} live rows of width {d}, "
            + ("one held choice a token" if alike
               else "a balanced routing"), rows=places,
            scatter_added_ms=timed(jax.jit(added), rows, to),
            gathered_ms=timed(jax.jit(gathered), rows, inverse))


def combine(cells=()):
    """The combine of a rung's rows into their tokens alone
    (``mla_moe._gather_back`` under a rung, and ``_spread``'s transpose:
    two a layer), which PR 40 found slower over 24,576 rows than over
    49,152 in ``smallthinker_ep8_steady``: the float32 scatter-add as
    the layer runs it and the ``T*k`` gathers by ``inverse`` it
    replaced, over 2 and 4 times the held share and the sizes between
    them. (With the rows of no group sent past the last token and
    dropped the scatter-add gave the same bits in the same time at every
    size: PERF.md section 6, PR 40, call B.)"""
    for cell in cells or CELL_LAYERS:
        tokens, k, width, n, *_ = CELL_LAYERS[cell]
        first, second = (-(-factor * tokens * k * n // (width * 512)) * 512
                         for factor in (2, 4))
        _combine_case(cell, sorted({
            tokens, (tokens + first) // 2, first, (first + second) // 2,
            second}))


def experts():
    """The sweep ``ops/grouped_matmul.py::product_width`` is written
    from: an expert layer's two grouped products over rows given as
    they are, forward and forward with backward; a recomputed layer
    pays the one and then the other. (What surrounds the products, and
    the rows they run over, is :func:`bound`'s.)

    The third family's cell (98,304 rows, 6,144 live, 8 groups, 2,688
    -> f -> 2,688, relu^2): bfloat16 weights of width f itself, then
    what the program does, float32 parameters of the published 1,856
    cast and zero-padded. The second family's cell (131,072 rows, 8,192
    live, 16 groups, 2,048 -> 2 x 768 -> 2,048, silu-gated) at 768 and
    padded to 1,024."""
    bound()
    bf16, f32 = jnp.bfloat16, jnp.float32
    cell = dict(bound=98304, live=6144, groups=8, d=2688)
    joyai = dict(bound=131072, live=8192, groups=16, d=2048)
    readings = [
        (f"weights of width {f}", dict(cell, f=f, wide=f, gated=False,
                                       params=bf16))
        for f in (1856, 1920, 2048, 2304, 2560)
    ] + [
        (f"float32 parameters of width 1856, products at {wide}",
         dict(cell, f=1856, wide=wide, gated=False, params=f32))
        for wide in (1856, 2048)
    ] + [
        (f"float32 parameters of width 768, products at {wide}",
         dict(joyai, f=768, wide=wide, gated=True, params=f32))
        for wide in (768, 1024)
    ]
    for what, shape in readings:
        forward, both, args = _expert_layer(**shape)
        form = "silu-gated" if shape["gated"] else "relu2"
        say(what=f"{form} experts, {shape['live']} live rows of "
            f"{shape['bound']}, {shape['groups']} groups, hidden size "
            f"{shape['d']}, {what}, a layer",
            forward_ms=timed(forward, *args),
            forward_and_backward_ms=timed(both, *args))
        del forward, both, args


if __name__ == "__main__":
    named = [arg for arg in sys.argv[1:] if arg not in CELL_LAYERS]
    for part in named or ["check", "scan", "attention", "experts"]:
        if part in ("bound", "combine"):
            {"bound": bound, "combine": combine}[part](
                [arg for arg in sys.argv[1:] if arg in CELL_LAYERS])
        else:
            {"check": check, "scan": scan, "attention": attention,
             "experts": experts}[part]()
