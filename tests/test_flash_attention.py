"""Flash attention (Pallas interpret mode) vs dense reference: values,
gradients, causal block skipping, the strip walk inside one grid tile
and in the diagonal tiles of a grid of several, bf16."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as flash
from elasticdl_tpu.ops.flash_attention import flash_attention, supports
from elasticdl_tpu.ops.ring_attention import dense_attention

B, S, H, D = 2, 64, 2, 16


def _qkv(seed=0, dtype=jnp.float32, s=S):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, s, H, D), dtype) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (64, 64)])
def test_flash_matches_dense(causal, blocks):
    bq, bk = blocks
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=bq,
                          block_k=bk, interpret=True)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(seed=1)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=16,
                              block_k=16, interpret=True)
        return jnp.sum(out ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_noncausal_gradients():
    q, k, v = _qkv(seed=2)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=False, block_q=32,
                              block_k=16, interpret=True)
        return jnp.sum(out * jnp.cos(out))

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, causal=False)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_bf16_inputs():
    q, k, v = _qkv(seed=3, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=32,
                          block_k=32, interpret=True)
    want = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        rtol=2e-2, atol=2e-2,
    )


def test_supports_gate():
    # Default path: S needs a LANE-ALIGNED (x128) tiling block — the
    # shapes the on-chip lane actually compiles (round-3 block sweep).
    assert supports((2, 256, 4, 16))
    assert supports((2, 1024, 4, 16))
    assert supports((2, 1536, 4, 16))   # 768-blocks tile it
    assert supports((2, 3584, 4, 16))   # 512-blocks tile it
    assert not supports((2, 100, 4, 16))  # not sublane-aligned
    assert not supports((2, 520, 4, 16))  # no x128 divisor block
    assert not supports((2, 200, 4, 16))
    # Small-S models take dense attention (flash has nothing to save).
    assert not supports((2, 32, 4, 16))
    # Explicit blocks keep the raw divisibility rule (interpret tests).
    assert supports((2, 32, 4, 16), block_q=32, block_k=32)
    assert not supports((2, 520, 4, 16), block_q=256, block_k=256)


def test_auto_block_picks_lane_aligned_divisors():
    from elasticdl_tpu.ops.flash_attention import _auto_block

    assert _auto_block(1024, 1024) == 1024
    assert _auto_block(1536, 1024) == 768
    assert _auto_block(3584, 1024) == 896  # largest x128 divisor <= cap
    assert _auto_block(512, 1024) == 512
    assert _auto_block(520, 1024) == 0
    assert _auto_block(32, 1024) == 0


def test_unaligned_seq_raises():
    q, k, v = _qkv(seed=5, s=48)
    with pytest.raises(ValueError, match="must tile"):
        flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)


def test_jit_and_under_vmapless_batch():
    q, k, v = _qkv(seed=4)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16, interpret=True)

    got = f(q, k, v)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- the strip walk inside one grid tile ---------------------------------


@pytest.mark.parametrize(
    "block,sub,computed,total",
    [(1024, 256, 10, 16), (512, 256, 3, 4), (1024, 128, 36, 64),
     (768, 256, 6, 9), (1024, 512, 3, 4)],
)
def test_plan_counts(block, sub, computed, total):
    plan = flash.tile_plan(block, block, sub=sub)
    assert (plan.computed, plan.total) == (computed, total)
    assert (plan.sub_q, plan.sub_k) == (sub, sub)
    assert plan.describe() == (
        f"blocks {block}x{block}, sub-tiles {sub}x{sub}, "
        f"{computed} of {total} computed" + flash.BACKWARD_FORM
    )


@pytest.mark.parametrize(
    "why,kwargs",
    [("non-causal", dict(sq=1024, sk=1024, causal=False)),
     ("a block under two sub-tiles", dict(sq=256, sk=256)),
     ("explicit small blocks", dict(sq=1024, sk=1024, block_q=256,
                                    block_k=256)),
     ("traced offsets", dict(sq=1024, sk=1024, q_offset=jnp.int32(0),
                             k_offset=jnp.int32(0))),
     # Several grid tiles whose diagonal the trace cannot place.
     ("several tiles, non-causal", dict(sq=2048, sk=2048, causal=False)),
     ("several tiles, traced offsets",
      dict(sq=2048, sk=2048, q_offset=jnp.int32(0), k_offset=jnp.int32(0))),
     ("several tiles, unequal blocks",
      dict(sq=2048, sk=2048, block_q=1024, block_k=512)),
     ("several tiles, unequal offsets",
      dict(sq=2048, sk=2048, q_offset=2048, k_offset=0)),
     ("several tiles, unequal lengths", dict(sq=2048, sk=4096)),
     ("several tiles, a block under two sub-tiles",
      dict(sq=1024, sk=1024, block_q=256, block_k=256)),
     ("several tiles, a block of no whole sub-tiles",
      dict(sq=3584, sk=3584))],
)
def test_plan_keeps_the_whole_tile(why, kwargs):
    plan = flash.tile_plan(**kwargs)
    assert plan.rows == (1,) and (plan.computed, plan.total) == (1, 1), why
    assert (plan.sub_q, plan.sub_k) == (plan.block_q, plan.block_k)
    assert plan.tiles == (plan.grid[0] * plan.grid[1], 0, 0), why
    assert plan.describe() == (
        f"blocks {plan.block_q}x{plan.block_k}, sub-tiles "
        f"{plan.block_q}x{plan.block_k}, 1 of 1 computed"
        + flash.BACKWARD_FORM)


@pytest.mark.parametrize(
    "s,kwargs,grid,block,sub,computed,total,tiles,line",
    [(2048, {}, 2, 1024, 256, 10, 16, (1, 2, 1),
      "grid 2x2 of blocks 1024x1024: 1 tile whole and unmasked, 2 "
      "diagonal tiles walked 10 of 16 sub-tiles 256x256, 1 skipped"),
     (4096, {}, 4, 1024, 256, 10, 16, (6, 4, 6),
      "grid 4x4 of blocks 1024x1024: 6 tiles whole and unmasked, 4 "
      "diagonal tiles walked 10 of 16 sub-tiles 256x256, 6 skipped"),
     (1536, {}, 2, 768, 256, 6, 9, (1, 2, 1),
      "grid 2x2 of blocks 768x768: 1 tile whole and unmasked, 2 "
      "diagonal tiles walked 6 of 9 sub-tiles 256x256, 1 skipped"),
     (1536, dict(block_q=512, block_k=512), 3, 512, 256, 3, 4, (3, 3, 3),
      "grid 3x3 of blocks 512x512: 3 tiles whole and unmasked, 3 "
      "diagonal tiles walked 3 of 4 sub-tiles 256x256, 3 skipped"),
     (4096, dict(sub=512, q_offset=4096, k_offset=4096), 4, 1024, 512, 3,
      4, (6, 4, 6),
      "grid 4x4 of blocks 1024x1024: 6 tiles whole and unmasked, 4 "
      "diagonal tiles walked 3 of 4 sub-tiles 512x512, 6 skipped")],
)
def test_plan_walks_the_diagonal_tiles_of_a_grid(s, kwargs, grid, block,
                                                 sub, computed, total,
                                                 tiles, line):
    """Several grid tiles, square, equal offsets known at trace time:
    tile (qi, kt) holds the diagonal iff qi == kt, from its corner, so
    every diagonal tile takes the single tile's plan, the tiles below
    are whole with no mask and the tiles above are skipped."""
    plan = flash.tile_plan(s, s, **kwargs)
    assert plan.grid == (grid, grid)
    assert (plan.block_q, plan.block_k) == (block, block)
    assert (plan.sub_q, plan.sub_k) == (sub, sub)
    assert plan.rows == flash.tile_plan(block, block, sub=sub).rows
    assert (plan.computed, plan.total) == (computed, total)
    assert plan.tiles == tiles and sum(tiles) == grid * grid
    assert plan.describe() == line + flash.BACKWARD_FORM
    assert flash.BACKWARD_FORM == "; backward: one kernel, 5 products a tile"


# (q_offset, k_offset) of a 64-long q block against a 64-long chunk,
# walked in 16-wide sub-tiles.
CHUNK_OFFSETS = {
    "on_diagonal": (0, 0),
    "below": (128, 0),      # every k visible: nothing is masked
    "askew": (64, 48),      # the diagonal misses the sub-tiles' corners
    "half_above": (0, 32),  # the upper strips see nothing
    "above": (0, 64),       # nothing visible: nothing is computed
}


@pytest.mark.parametrize("where", sorted(CHUNK_OFFSETS))
def test_plan_covers_what_the_mask_leaves(where):
    """Sub-tile (i, j) is in the plan exactly when the mask leaves one
    of its scores; rows are prefixes, so a strip is one rectangle."""
    q_off, k_off = CHUNK_OFFSETS[where]
    n, sub = 64, 16
    rows = flash._walk(True, n, n, n, n, q_off, k_off, sub)
    visible = (q_off + np.arange(n)[:, None]) >= (k_off + np.arange(n))
    want = visible.reshape(n // sub, sub, n // sub, sub).any(axis=(1, 3))
    got = np.array([[j < n_k for j in range(n // sub)] for n_k in rows])
    np.testing.assert_array_equal(got, want)


def _chunk_operands(bh=3, n=64, d=16, seed=11):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32) * 0.3
    q, k, v, do = (mk(bh, n, d) for _ in range(4))
    return q, k, v, do, mk(bh, n, 1) + 1.0, mk(bh, n, 1)


@pytest.mark.parametrize("where", sorted(CHUNK_OFFSETS))
def test_chunk_grads_strips_are_the_whole_tile(monkeypatch, where):
    """Python-int offsets walk the tile in strips that stop at the
    diagonal; traced offsets compute the whole tile and mask it. One
    answer; strips wholly above the diagonal write zeros."""
    monkeypatch.setattr(flash, "SUB_TILE", 16)
    ops = _chunk_operands()
    q_off, k_off = CHUNK_OFFSETS[where]
    assert flash.tile_plan(64, 64, q_offset=q_off, k_offset=k_off).total == 16
    strips = flash.flash_chunk_grads(
        *ops, q_off, k_off, causal=True, interpret=True)
    whole = flash.flash_chunk_grads(
        *ops, jnp.int32(q_off), jnp.int32(k_off), causal=True,
        interpret=True)
    for got, want, name in zip(strips, whole, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6,
            err_msg=name)
    dq, dk, dv = (np.asarray(g) for g in strips)
    if where == "above":
        assert not dq.any() and not dk.any() and not dv.any()
    if where == "half_above":
        # q rows 0..31 see no key of the chunk; keys 32..63 no query
        # but the last 32.
        assert not dq[:, :32].any() and dq[:, 32:].any()
        assert dk[:, :32].any() and dv[:, :32].any()


def _value_and_grads(q, k, v):
    return _attend_and_grads(
        q, k, v, lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [512, 1024])
def test_strips_match_dense_and_whole_tile_at_real_blocks(monkeypatch, s, d):
    """The default blocks at S = 512 and 1,024, heads of 64 and 128:
    forward, dq, dk, dv against the dense reference, and against the
    whole-tile kernels (the walk switched off by a sub-tile as large as
    the block). o bit for bit. A q strip's dq is summed k strip by k
    strip since the backward is one kernel, so its last bits move; dk
    and dv contract over the queries, fewer of them in a strip than in
    the tile, and the CPU backend's dot blocks that sum by its length:
    the last bits move at D = 64 here."""
    rng = np.random.RandomState(s + d)
    q, k, v = (jnp.asarray(rng.randn(1, s, 2, d), jnp.float32) * 0.3
               for _ in range(3))
    assert flash.tile_plan(s, s).computed < flash.tile_plan(s, s).total
    strips = _value_and_grads(q, k, v)
    monkeypatch.setattr(flash, "SUB_TILE", s)
    assert flash.tile_plan(s, s).total == 1
    whole = _value_and_grads(q, k, v)

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, causal=True)
        return jnp.sum(out * jnp.cos(out)), out

    grads, out = jax.grad(loss_dense, argnums=(0, 1, 2),
                          has_aux=True)(q, k, v)
    for got, same, want, name in zip(strips, whole, (out, *grads),
                                     ("o", "dq", "dk", "dv")):
        got, same, want = (np.asarray(x) for x in (got, same, want))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"{name} against dense")
        if name == "o":
            np.testing.assert_array_equal(got, same, err_msg=name)
        else:
            np.testing.assert_allclose(
                got, same, rtol=0, atol=1e-6 * np.abs(same).max(),
                err_msg=f"{name} against the whole tile")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "s,block,edge",
    [(64, 64, 16), (64, 64, 32), (96, 96, 32), (96, 96, 16),
     (64, 32, 16)],   # the last: two grid tiles each way, traced diagonal
)
def test_sub_tiled_block_matches_dense(monkeypatch, causal, s, block, edge):
    """One block holds several sub-tiles: values and dq/dk/dv are the
    dense reference's, causal (the walk stops at the diagonal) and not
    (the whole tile, whatever the edge)."""
    monkeypatch.setattr(flash, "SUB_TILE", edge)
    q, k, v = _qkv(seed=7, s=s)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=block,
                              block_k=block, interpret=True)
        return jnp.sum(out * jnp.cos(out)), out

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out)), out

    grad = lambda f: jax.grad(f, argnums=(0, 1, 2), has_aux=True)
    gf, out = grad(loss_flash)(q, k, v)
    gd, ref = grad(loss_dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def _attend_and_grads(q, k, v, attend):
    def loss(q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out * jnp.cos(out)), out

    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("heads", [(16, 16), (24, 16)],
                         ids=["equal_heads", "wider_qk"])
@pytest.mark.parametrize("sub_tiles", [2, 3, 4])
@pytest.mark.parametrize("grid", [2, 3, 4])
def test_grid_walk_matches_dense_and_the_whole_tiles(monkeypatch, grid,
                                                     sub_tiles, heads):
    """A grid of several tiles whose diagonal tiles are walked in strips
    (the tiles below them unmasked, the steps above them dead, naming
    the diagonal's blocks): forward, dq, dk, dv are the dense
    reference's, and those of the same call with the walk switched off
    (a sub-tile as large as the block: whole tiles, masked where the
    traced compare says), to the CPU dot's last bits."""
    sub = 8
    block = sub_tiles * sub
    s = grid * block
    d, dv = heads
    rng = np.random.RandomState(100 * grid + 10 * sub_tiles + d)
    q, k = (jnp.asarray(rng.randn(2, s, 2, d), jnp.float32) * 0.3
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, s, 2, dv), jnp.float32) * 0.3

    def kernels(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, interpret=True)

    monkeypatch.setattr(flash, "SUB_TILE", sub)
    plan = flash.tile_plan(s, s, block_q=block, block_k=block)
    assert plan.grid == (grid, grid)
    assert plan.tiles == (grid * (grid - 1) // 2, grid, grid * (grid - 1) // 2)
    assert (plan.computed, plan.total) == (
        sub_tiles * (sub_tiles + 1) // 2, sub_tiles ** 2)
    walked = _attend_and_grads(q, k, v, kernels)
    monkeypatch.setattr(flash, "SUB_TILE", block)
    assert flash.tile_plan(s, s, block_q=block, block_k=block).rows == (1,)
    whole = _attend_and_grads(q, k, v, kernels)
    dense = _attend_and_grads(
        q, k, v, lambda q, k, v: dense_attention(q, k, v, causal=True))
    for got, same, want, name in zip(walked, whole, dense,
                                     ("o", "dq", "dk", "dv")):
        got, same, want = (np.asarray(x) for x in (got, same, want))
        assert got.shape == want.shape
        np.testing.assert_allclose(
            got, want, rtol=2e-5 if name == "o" else 2e-4, atol=2e-5,
            err_msg=f"{name} against dense")
        np.testing.assert_allclose(
            got, same, rtol=0, atol=1e-6 * np.abs(same).max(),
            err_msg=f"{name} against the whole tiles")


def _count_traces(monkeypatch, *names):
    """How often each named kernel body of the module is traced from
    here on."""
    counts = {}
    for name in names:
        def counting(*args, _real=getattr(flash, name), _name=name, **kw):
            counts[_name] = counts.get(_name, 0) + 1
            return _real(*args, **kw)

        monkeypatch.setattr(flash, name, counting)
    return counts


GRID_KERNELS = ("_fwd_grid_kernel", "_bwd_grid_kernel")


@pytest.mark.parametrize(
    "why,kwargs",
    [("non-causal", dict(causal=False)),
     ("unequal blocks", dict(block_q=24, block_k=12)),
     ("a block under two sub-tiles", dict(block_q=6, block_k=6))],
)
def test_a_call_the_walk_cannot_place_takes_whole_tiles(monkeypatch, why,
                                                        kwargs):
    """Several grid tiles, but no diagonal known through their corners:
    the plan says whole tiles, the grid kernels that walk are not
    traced, and the answer is the dense reference's."""
    monkeypatch.setattr(flash, "SUB_TILE", 6)
    counts = _count_traces(monkeypatch, *GRID_KERNELS)
    kwargs = dict(dict(causal=True, block_q=24, block_k=24), **kwargs)
    s = 48
    assert flash.tile_plan(s, s, **kwargs).rows == (1,), why
    rng = np.random.RandomState(17)
    q, k, v = (jnp.asarray(rng.randn(1, s, 3, 20), jnp.float32) * 0.3
               for _ in range(3))
    got = _attend_and_grads(
        q, k, v, lambda q, k, v: flash_attention(
            q, k, v, interpret=True, **kwargs))
    want = _attend_and_grads(
        q, k, v, lambda q, k, v: dense_attention(
            q, k, v, causal=kwargs["causal"]))
    assert counts == {}, why
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=name)


@pytest.mark.parametrize(
    "why,offsets,walked",
    [("equal ints", (96, 96), True),
     ("traced offsets", (jnp.int32(96), jnp.int32(96)), False),
     ("q_offset != k_offset", (96, 48), False)],
)
def test_chunk_grads_walk_a_grid_only_on_equal_int_offsets(monkeypatch, why,
                                                           offsets, walked):
    """``flash_chunk_grads`` over a 2x2 grid: Python-int offsets that
    are equal put the diagonal through the corners of the diagonal
    tiles, and those are walked; traced or unequal offsets keep whole
    tiles under the traced compare. One answer either way."""
    monkeypatch.setattr(flash, "SUB_TILE", 12)
    counts = _count_traces(monkeypatch, "_bwd_grid_kernel")
    ops = _chunk_operands(bh=2, n=96, d=20, seed=23)
    plan = flash.tile_plan(96, 96, block_q=48, block_k=48,
                           q_offset=offsets[0], k_offset=offsets[1])
    assert plan.grid == (2, 2) and (plan.rows != (1,)) == walked, why
    got = flash.flash_chunk_grads(
        *ops, *offsets, causal=True, block_q=48, block_k=48,
        interpret=True)
    assert counts == ({"_bwd_grid_kernel": 1} if walked else {}), why
    # The same pairing with the walk switched off.
    monkeypatch.setattr(flash, "SUB_TILE", 48)
    want = flash.flash_chunk_grads(
        *ops, *(jnp.int32(o) for o in offsets), causal=True, block_q=48,
        block_k=48, interpret=True)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
            err_msg=name)


def test_s2048_keeps_its_old_results():
    """Several grid tiles at the default blocks: the diagonal tiles are
    walked now (1 tile whole, 2 walked, 1 skipped), and the answer is
    the dense reference's as before."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 2048, 1, 64), jnp.float32) * 0.3
               for _ in range(3))
    assert flash.tile_plan(2048, 2048).tiles == (1, 2, 1)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "q_shape,kwargs,tail",
    [((8, 1024, 16, 64), {},
      "blocks 1024x1024, sub-tiles 256x256, 10 of 16 computed"),
     ((8, 512, 16, 64), {},
      "blocks 512x512, sub-tiles 256x256, 3 of 4 computed"),
     ((2, 2048, 16, 64), {},
      "grid 2x2 of blocks 1024x1024: 1 tile whole and unmasked, 2 "
      "diagonal tiles walked 10 of 16 sub-tiles 256x256, 1 skipped"),
     ((4, 4096, 32, 192), {},
      "grid 4x4 of blocks 1024x1024: 6 tiles whole and unmasked, 4 "
      "diagonal tiles walked 10 of 16 sub-tiles 256x256, 6 skipped"),
     ((2, 2048, 16, 64), {"causal": False},
      "blocks 1024x1024, sub-tiles 1024x1024, 1 of 1 computed"),
     ((2, 4096, 16, 64), {"traced_offsets": True},
      "blocks 1024x1024, sub-tiles 1024x1024, 1 of 1 computed"),
     ((8, 1024, 16, 64), {"causal": False},
      "blocks 1024x1024, sub-tiles 1024x1024, 1 of 1 computed"),
     ((8, 1024, 16, 64), {"traced_offsets": True},
      "blocks 1024x1024, sub-tiles 1024x1024, 1 of 1 computed")],
)
def test_log_line_states_the_sub_tiles(q_shape, kwargs, tail):
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    flash.logger.addHandler(handler)
    flash.log_traced.cache_clear()
    try:
        flash.log_traced(
            "pallas flash kernel",
            "why; " + flash.describe_tiles(q_shape[1], **kwargs), q_shape)
    finally:
        flash.logger.removeHandler(handler)
        flash.log_traced.cache_clear()
    assert records == [
        f"attention: traced pallas flash kernel for q{q_shape}: why; {tail}"
        "; backward: one kernel, 5 products a tile"
    ]


@pytest.mark.parametrize(
    "s,block,kernels",
    [(80, 80, ("_fwd_strips_kernel", "_bwd_strips_kernel")),
     (96, 32, GRID_KERNELS)],
    ids=["one_tile", "grid_of_tiles"],
)
def test_layers_share_one_trace_of_each_kernel(monkeypatch, s, block,
                                               kernels):
    """A model's layers call the kernels with one set of shapes: the
    kernel bodies are traced once for all of them, under ``eval_shape``
    (the worker's state_init) and again under ``grad`` of a ``jit``
    (its first program), not once a layer; the strips kernels of one
    grid tile and the grid kernels that walk the diagonal tiles alike.
    Shapes no other test uses, so the process's trace cache is cold for
    them."""
    counts = _count_traces(monkeypatch, *kernels)
    monkeypatch.setattr(flash, "SUB_TILE", 16)
    assert flash.tile_plan(s, s, block_q=block, block_k=block).rows != (1,)
    rng = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rng.randn(1, s, 3, 24), jnp.float32) * 0.3
               for _ in range(3))

    def layers(q, k, v):
        x = q
        for _ in range(3):
            x = flash_attention(x, k, v, causal=True, block_q=block,
                                block_k=block, interpret=True)
        return jnp.sum(x * x)

    jax.eval_shape(layers, q, k, v)
    assert counts == {kernels[0]: 1}
    jax.jit(jax.grad(layers, argnums=(0, 1, 2)))(q, k, v)
    assert counts == {name: 1 for name in kernels}


# --- the backward as one kernel (PR 35) -----------------------------------

ONE_KERNEL_CASES = {
    # id: (s, block, sub, heads, key/value heads, d, dv, causal, mask)
    "one_walked_tile_d64": (64, 64, 16, 2, 2, 64, 64, True, None),
    "grid_2x2_qk192_v128": (64, 32, 8, 2, 2, 192, 128, True, None),
    "group_of_8": (64, 32, 8, 8, 1, 16, 16, True, None),
    "block_diffusion_2x2": (64, 16, 8, 2, 2, 16, 16, False,
                            flash.BlockDiffusion(32, 4)),
    "non_causal": (64, 32, 8, 2, 2, 16, 16, False, None),
}


@pytest.mark.parametrize("case", sorted(ONE_KERNEL_CASES))
def test_one_backward_kernel_matches_dense(monkeypatch, case):
    """S, P, dP and dS once a tile, feeding dq, dk and dv together: the
    three gradients of every form of the one backward kernel against
    dense attention's in float32: a single walked tile, a causal grid
    with two head sizes, a group of query heads on one key/value head, a
    block-diffusion grid (halves of 32 in tiles of 16: 4 x 4 tiles, each
    half 2 x 2), whole tiles without a mask."""
    s, block, sub, h, hkv, d, dv, causal, mask = ONE_KERNEL_CASES[case]
    monkeypatch.setattr(flash, "SUB_TILE", sub)
    rng = np.random.RandomState(len(case))
    mk = lambda heads, width: jnp.asarray(  # noqa: E731
        rng.randn(2, s, heads, width), jnp.float32) * 0.4
    q, k, v, weight = mk(h, d), mk(hkv, d), mk(hkv, dv), mk(h, dv)

    def kernels(q, k, v):
        return jnp.sum(weight * flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block,
            interpret=True, mask=mask))

    def dense(q, k, v):
        k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
        out = (dense_attention(q, k, v, mask=mask) if mask is not None
               else dense_attention(q, k, v, causal=causal))
        return jnp.sum(weight * out)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(kernels, (0, 1, 2))(q, k, v)
        want = jax.grad(dense, (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(
            a, b, rtol=0, atol=2e-5 * float(np.abs(b).max()) + 1e-6,
            err_msg=name)


def test_one_backward_kernel_on_a_chunk_pair_as_the_ring_sends_it():
    """The ring's backward step: the local queries (the second half of
    the sequence) against each K/V chunk under traced offsets, dq
    summed over the chunks by the caller, dk and dv a chunk each: one
    ``pallas_call`` a pairing, and the sums are dense attention's
    gradients."""
    n, d = 32, 16
    rng = np.random.RandomState(35)
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape), jnp.float32) * 0.4
    q, k, v, do = (mk(1, 2 * n, 3, d) for _ in range(4))
    do = do.at[:, :n].set(0.0)    # the first half's queries are remote
    scale = d ** -0.5
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(3, -1, d)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda q, k, v: dense_attention(q, k, v, causal=True), q, k, v)
        want = [to_bh(x) for x in vjp(do)]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        scores = jnp.where(jnp.tril(jnp.ones((2 * n, 2 * n), bool)),
                           scores, -jnp.inf)
        lse = jax.nn.logsumexp(scores, axis=-1).reshape(3, 2 * n, 1)
        delta = to_bh(do * out).sum(axis=-1, keepdims=True)
        local = lambda x: to_bh(x)[:, n:]   # noqa: E731

        def pair(chunk):
            rows = slice(chunk * n, (chunk + 1) * n)
            grads = lambda q_off, k_off: flash.flash_chunk_grads(  # noqa: E731
                local(q), to_bh(k)[:, rows], to_bh(v)[:, rows], local(do),
                lse[:, n:], delta[:, n:], q_off, k_off, causal=True,
                scale=scale, block_q=16, block_k=16, interpret=True)
            assert count_calls(jax.make_jaxpr(grads)(
                jnp.int32(n), jnp.int32(chunk * n)).jaxpr) == 1
            return jax.jit(grads)(jnp.int32(n), jnp.int32(chunk * n))

        (dq0, dk0, dv0), (dq1, dk1, dv1) = pair(0), pair(1)
    got = (dq0 + dq1, jnp.concatenate([dk0, dk1], axis=1),
           jnp.concatenate([dv0, dv1], axis=1))
    np.testing.assert_allclose(got[0], want[0][:, n:], atol=2e-6)
    np.testing.assert_allclose(got[1], want[1], atol=2e-6)
    np.testing.assert_allclose(got[2], want[2], atol=2e-6)


@pytest.mark.parametrize("form", ["one_tile", "grid", "whole_tiles",
                                  "block_diffusion"])
def test_a_backward_traces_one_kernel_body_and_two_calls_a_gradient(
        monkeypatch, form):
    """``flash_chunk_grads`` issues one ``pallas_call`` whose body is one
    of the four backward bodies, no other of them is traced, and the
    jaxpr of a gradient holds two calls a layer: forward, backward."""
    bodies = {"one_tile": "_bwd_strips_kernel", "grid": "_bwd_grid_kernel",
              "whole_tiles": "_bwd_kernel",
              "block_diffusion": "_bwd_plan_kernel"}
    assert sorted(bodies.values()) == sorted(
        name for name in vars(flash)
        if name.startswith(("_bwd_", "_dq_", "_dkv_"))
        and name.endswith("_kernel"))
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    counts = _count_traces(monkeypatch, *bodies.values())
    s = 160   # shapes no other test traces: the shared traces are cold
    block = {"one_tile": s, "block_diffusion": 40}.get(form, 80)
    kwargs = dict(causal=form != "whole_tiles", block_q=block,
                  block_k=block, interpret=True)
    if form == "block_diffusion":
        kwargs["mask"] = flash.BlockDiffusion(80, 4)
    q, k, v = _qkv(seed=35, s=s)

    def layer(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kwargs) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 2)))(q, k, v)
    assert count_calls(jaxpr.jaxpr) == 2
    # The strips body is also what the grid bodies walk their diagonal
    # and boundary tiles with.
    counts.pop("_bwd_strips_kernel" if form != "one_tile" else "", None)
    assert counts == {bodies[form]: 1}


def test_a_dq_row_too_long_to_stay_resident_is_cut_into_runs(monkeypatch):
    """No model here runs such a length: past ``RESIDENT_DQ_BYTES`` the
    queries are cut into runs of whole blocks, one kernel a run over
    whole tiles against all the keys, dq's rows side by side and dk, dv
    summed; one answer. A masked call has no such form and says so."""
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    ops = _chunk_operands(bh=2, n=96, d=16, seed=5)
    blocks = dict(block_q=16, block_k=16, interpret=True)
    assert flash._resident_rows(16, 16) >= 96
    want = flash.flash_chunk_grads(*ops, 0, 0, causal=True, **blocks)
    monkeypatch.setattr(flash, "RESIDENT_DQ_BYTES", 40 * 16 * 4)
    assert flash._resident_rows(16, 16) == 32
    grads = lambda *a: flash.flash_chunk_grads(  # noqa: E731
        *a, 0, 0, causal=True, **blocks)
    assert count_calls(jax.make_jaxpr(grads)(*ops).jaxpr) == 3
    for a, b, name in zip(grads(*ops), want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
    mask = flash.BlockDiffusion(48, 4)
    assert not supports((1, 96, 2, 16), 16, 16, mask=mask)
    with pytest.raises(ValueError, match="not resident"):
        flash.flash_chunk_grads(*ops, 0, 0, mask=mask, **blocks)
    # A block is the least a run can be.
    monkeypatch.setattr(flash, "RESIDENT_DQ_BYTES", 1)
    assert flash._resident_rows(192, 1024) == 1024


@pytest.mark.parametrize(
    "cell,s,block,d,dv,raised",
    [("whole tiles of 512", 512, 512, 64, 64, False),
     ("s2048_d64", 2048, 1024, 64, 64, True),
     ("joyai_ep16_steady", 4096, 1024, 192, 128, True),
     ("nemotron3n_ep16_steady, sdar_ep8_steady", 8192, 1024, 128, 128,
      True)],
)
def test_the_backwards_vmem_limit_follows_its_blocks(cell, s, block, d, dv,
                                                     raised):
    """The backward over a grid of tiles keeps a b's whole dq row in
    VMEM, so the limit follows the bytes of the blocks, not the head
    size alone: heads of 128 at S 8,192 pass Mosaic's default and raise
    it; small tiles do not."""
    need = flash._grads_vmem_bytes(s, block, block, d, dv, 2)
    params = flash._compiler_params(("parallel",), d, dv, vmem_bytes=need)
    assert (need > flash.DEFAULT_VMEM_BYTES) == raised, cell
    assert params.vmem_limit_bytes == (
        flash.WIDE_HEAD_VMEM_BYTES if raised else None)
    # The longest resident row still fits the raised limit.
    rows = flash._resident_rows(d, 1024)
    assert flash._grads_vmem_bytes(
        rows, 1024, 1024, d, dv, 2) < flash.WIDE_HEAD_VMEM_BYTES


def count_calls(jaxpr, primitive="pallas_call"):
    """Equations of ``primitive`` in a jaxpr and every jaxpr under it."""
    return sum(
        (eqn.primitive.name == primitive)
        + sum(count_calls(sub, primitive)
              for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns
    )


@pytest.mark.parametrize("heads", [(16, 16), (24, 16)],
                         ids=["equal_heads", "wider_qk"])
@pytest.mark.parametrize("s,block", [(32, 32), (64, 16)],
                         ids=["one_tile", "grid_of_tiles"])
def test_a_recomputed_block_runs_the_forward_kernel_once(monkeypatch, s,
                                                         block, heads):
    """A block under ``jax.checkpoint`` with ``remat_policy()`` keeps the
    forward kernel's o and logsumexp: its gradient holds the two
    kernel calls of a block that is not recomputed, where a plain
    checkpoint runs the forward kernel again, and dq, dk, dv are the
    same bits in all three."""
    d, dv = heads
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    assert flash.tile_plan(s, s, block_q=block, block_k=block).rows != (1,)
    rng = np.random.RandomState(s + d)
    q, k = (jnp.asarray(rng.randn(2, s, 2, d), jnp.float32) * 0.3
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, s, 2, dv), jnp.float32) * 0.3
    w = jnp.asarray(rng.randn(dv, dv), jnp.float32)

    def block_fn(q, k, v):
        o = flash_attention(jnp.tanh(q), k, v, causal=True, block_q=block,
                            block_k=block, interpret=True)
        return jnp.sum(jnp.sin(o @ w))

    grads, calls = {}, {}
    for name, fn in [
        ("no_checkpoint", block_fn),
        ("plain", jax.checkpoint(block_fn)),
        ("policy", jax.checkpoint(block_fn, policy=flash.remat_policy())),
    ]:
        grad = jax.grad(fn, argnums=(0, 1, 2))
        calls[name] = count_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)
        grads[name] = jax.jit(grad)(q, k, v)
    assert calls == {"no_checkpoint": 2, "plain": 3, "policy": 2}
    for name in ("plain", "policy"):
        for got, want, leaf in zip(grads[name], grads["no_checkpoint"],
                                   ("dq", "dk", "dv")):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=f"{name} {leaf}")


@pytest.mark.parametrize(
    "v_shape,megabytes",
    [((4, 4096, 32, 128), "136.3"), ((8, 1024, 16, 64), "17.3")],
    ids=["joyai_ep16", "gpt2_medium"],
)
def test_kept_clause_counts_o_and_logsumexp(v_shape, megabytes):
    """(B*H, S, Dv) of o in v's type and (B*H, S) float32 a block."""
    v = jax.ShapeDtypeStruct(v_shape, jnp.bfloat16)
    assert flash.describe_kept(v) == (
        f"under remat the block keeps o and logsumexp ({megabytes} MB), "
        "the forward kernel is not run again")


# Fewer key/value heads than query heads (PR 32): query head h reads
# key/value head h // group in place.

def _grouped_inputs(s, heads, group, d=16, dv=8, seed=0):
    rng = np.random.RandomState(seed + s + group)
    mk = lambda h, w: jnp.asarray(  # noqa: E731
        rng.randn(2, s, h, w), jnp.float32) * 0.5
    return (mk(heads, d), mk(heads // group, d), mk(heads // group, dv),
            mk(heads, dv))


@pytest.mark.parametrize("group", [1, 4, 16])
@pytest.mark.parametrize("s,block,sub", [(64, 64, 16), (128, 32, 8),
                                         (64, 16, 64)],
                         ids=["one_tile_strips", "grid_walked",
                              "whole_tiles"])
def test_grouped_key_value_heads_match_dense(monkeypatch, group, s, block,
                                             sub):
    """Output and all three gradients against dense attention over
    key/value heads repeated ``group`` times, whose own gradient sums
    dk and dv over a group: through the strips kernels, the grid
    kernels that walk the diagonal, and whole tiles."""
    monkeypatch.setattr(flash, "SUB_TILE", sub)
    plan = flash.tile_plan(s, s, block_q=block, block_k=block, group=group)
    assert (plan.rows == (1,)) == (sub == 64) and plan.group == group
    q, k, v, weight = _grouped_inputs(s, 16, group)
    assert k.shape[2] == 16 // group

    def kernel(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=True) * weight)

    def dense(q, k, v):
        return jnp.sum(dense_attention(
            q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
            causal=True) * weight)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


def _index_map_primitives(fn, *args):
    """For every ``pallas_call`` under ``fn``'s jaxpr, the primitives of
    each operand's index map."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    return [
        [[e.primitive.name for e in mapping.index_map_jaxpr.jaxpr.eqns]
         for mapping in eqn.params["grid_mapping"].block_mappings]
        for eqn in calls(jax.make_jaxpr(fn)(*args).jaxpr)
    ]


@pytest.mark.parametrize("s,block", [(64, 64), (128, 32)],
                         ids=["one_tile", "grid_of_tiles"])
def test_equal_head_counts_trace_the_kernels_they_traced(s, block):
    """With as many key/value heads as query heads no index map gains an
    operation (the accepted cells' programs do not move: their jaxprs
    were compared with the parent's, line numbers blanked, in PR 32);
    with fewer, k's and v's maps divide the row and nothing else
    changes."""
    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=True)),
            (0, 1, 2))(q, k, v)

    q, k, v, _ = _grouped_inputs(s, 4, 1)
    equal = _index_map_primitives(grads, q, k, v)
    assert len(equal) == 2
    assert not any("div" in ops for call in equal for ops in call)
    q, k, v, _ = _grouped_inputs(s, 4, 4)
    grouped = _index_map_primitives(grads, q, k, v)
    for call_equal, call_grouped in zip(equal, grouped):
        changed = [i for i, (a, b) in enumerate(zip(call_equal, call_grouped))
                   if a != b]
        # k and v: operands 1 and 2 after the scalar-prefetched offsets
        # (two of them, in the backward kernel's grid of tiles).
        assert len(changed) == 2 and changed[1] == changed[0] + 1
        for i in changed:
            assert call_grouped[i] == ["div"] + call_equal[i]


def test_head_counts_that_do_not_divide_are_refused():
    q, k, v, _ = _grouped_inputs(64, 4, 1)
    with pytest.raises(ValueError, match="4 query heads over 3 key"):
        flash_attention(q, k[:, :, :3], v[:, :, :3], interpret=True)


def test_cost_and_plan_say_the_two_head_counts():
    q = jnp.zeros((32, 64, 16), jnp.bfloat16)
    k = jnp.zeros((2, 64, 16), jnp.bfloat16)
    v = jnp.zeros((2, 64, 8), jnp.bfloat16)
    _, cost = flash._forward_outputs(q, k, v, True)
    # q and o whole, k and v a sixteenth of their rows.
    assert cost.bytes_accessed == int(
        (1 + 1 / 16) * 32 * 64 * (16 + 8) * 2)
    _, equal = flash._forward_outputs(q, q, q[..., :8], True)
    assert equal.bytes_accessed == 2 * 32 * 64 * (16 + 8) * 2
    assert flash.describe_tiles(8192, group=16).endswith(
        "28 skipped; one key/value head read in place by 16 query heads, "
        "dk/dv summed over them; backward: one kernel, 5 products a tile")
    assert "key/value" not in flash.describe_tiles(4096)


# ----------------------------------------------- the block-diffusion mask

def _diffusion_inputs(half, heads, group, d, seed=0, rows=2):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.randn(rows, 2 * half, h, d), jnp.float32) * 0.5
    return mk(heads), mk(heads // group), mk(heads // group)


def _dense_under(mask, q, k, v):
    group = q.shape[2] // k.shape[2]
    return dense_attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
        mask=mask)


def test_block_diffusion_rule_is_the_four_regions():
    """``BlockDiffusion.visible`` against the four rules written out,
    and the count of visible pairs."""
    half, block = 24, 4
    mask = flash.BlockDiffusion(half, block)
    seen = np.asarray(mask.visible(
        jnp.arange(2 * half)[:, None], jnp.arange(2 * half)[None, :]))
    for i in range(2 * half):
        for j in range(2 * half):
            n_i, n_j = i % half // block, j % half // block
            if i < half and j < half:
                want = n_i == n_j
            elif i < half:
                want = n_j < n_i
            elif j < half:
                want = False
            else:
                want = n_j <= n_i
            assert seen[i, j] == want, (i, j)
    assert seen.sum() == mask.pairs == half * (half + block)
    assert flash.BlockDiffusion(4096, 4).pairs == 16793600


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize(
    "half,tile,sub,block",
    [(64, 64, 16, 4), (64, 64, 32, 32), (128, 32, 8, 4), (128, 64, 32, 32),
     (96, 32, 16, 4)],
    ids=["one-tile-b4", "one-tile-b32", "grid-4-b4", "grid-2-b32",
         "grid-3-b4"])
def test_block_diffusion_kernels_match_dense(monkeypatch, half, tile, sub,
                                             block, group, d):
    """Forward and dq, dk, dv under the mask against dense attention
    under the same mask: halves of one and of several grid tiles, blocks
    of 4 and of 32 (a sub-tile's width: the strict rule then drops the
    diagonal sub-tile whole), one and eight query heads to a key/value
    head, head sizes 64 and 128."""
    monkeypatch.setattr(flash, "SUB_TILE", sub)
    mask = flash.BlockDiffusion(half, block)
    q, k, v = _diffusion_inputs(half, 8, group, d, rows=1)
    assert supports(q.shape, tile, tile, mask=mask)

    def kernels(q, k, v):
        return flash_attention(q, k, v, block_q=tile, block_k=tile,
                               interpret=True, mask=mask)

    got = _attend_and_grads(q, k, v, kernels)
    want = _attend_and_grads(
        q, k, v, lambda q, k, v: _dense_under(mask, q, k, v))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-4)


def test_block_diffusion_plan_counts_and_covers_every_visible_pair():
    """At the cell's shape: 12 tiles whole, 12 boundary, 40 dead; and at
    a small one every visible pair lies in a tile the plan multiplies (a
    whole tile, or a live sub-tile of a boundary tile) and no whole tile
    holds a masked pair."""
    plan = flash.tile_plan(8192, 8192, mask=flash.BlockDiffusion(4096, 4))
    assert plan.grid == (8, 8) and plan.tiles == (12, 12, 40)
    assert (plan.plan.own.computed, plan.plan.before.computed,
            plan.plan.clean.computed, plan.total) == (4, 10, 10, 16)
    text = plan.describe()
    assert ("12 tiles whole and unmasked, 12 boundary tiles walked (4 "
            "noised on their own blocks 4, 4 noised on the clean blocks "
            "before 10, 4 clean 10 of 16 sub-tiles 256x256), 40 skipped"
            ) in text
    # 64 grid tiles under a compare against what the mask requires.
    assert round(64 * 16 / (12 * 16 + 4 * 4 + 8 * 10), 1) == 3.6
    half, block, tile, sub = 96, 4, 32, 8
    mask = flash.BlockDiffusion(half, block)
    small = flash.tile_plan(2 * half, 2 * half, block_q=tile, block_k=tile,
                            sub=sub, mask=mask).plan
    n, per = small.n, tile // sub
    covered = np.zeros((2 * half, 2 * half), bool)
    whole = np.zeros_like(covered)
    for qi in range(2 * n):
        place = qi % n
        for kt in range(2 * n):
            rows = slice(qi * tile, (qi + 1) * tile)
            cols = slice(kt * tile, (kt + 1) * tile)
            if kt >= n and kt - n < place:
                covered[rows, cols] = whole[rows, cols] = True
                continue
            walk = (small.own if qi < n and kt == qi else
                    small.before if qi < n and kt == n + place else
                    small.clean if qi >= n and kt == qi else None)
            if walk is None:
                continue
            for i in range(per):
                for j in range(walk.starts[i], walk.rows[i]):
                    covered[qi * tile + i * sub:qi * tile + (i + 1) * sub,
                            kt * tile + j * sub:kt * tile + (j + 1) * sub
                            ] = True
    seen = np.asarray(mask.visible(
        jnp.arange(2 * half)[:, None], jnp.arange(2 * half)[None, :]))
    assert not (seen & ~covered).any()
    assert not (whole & ~seen).any()
    assert small.n * (small.n - 1) == 6 == whole.sum() // tile ** 2


def test_block_diffusion_fetches_nothing_of_the_dead_quadrant(monkeypatch):
    """Noised keys and values poisoned with NaN, where no clean query
    may look: the clean half's rows and their dq are what they were (dk
    and dv of the clean keys are not: the noised queries see them and
    are NaN themselves); and the index maps name, at a dead step, the
    tile a live step of the row names."""
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    half, tile = 64, 32
    mask = flash.BlockDiffusion(half, 4)
    q, k, v = _diffusion_inputs(half, 2, 1, 16, seed=3, rows=1)

    def clean_rows(q, k, v):
        return flash_attention(q, k, v, block_q=tile, block_k=tile,
                               interpret=True, mask=mask)[:, half:]

    def loss(q, k, v):
        return jnp.sum(clean_rows(q, k, v) ** 2)

    want = clean_rows(q, k, v)
    want_dq = jax.grad(loss)(q, k, v)
    k_bad = k.at[:, :half].set(jnp.nan)
    v_bad = v.at[:, :half].set(jnp.nan)
    np.testing.assert_array_equal(clean_rows(q, k_bad, v_bad), want)
    np.testing.assert_array_equal(
        jax.grad(loss)(q, k_bad, v_bad)[:, half:], want_dq[:, half:])
    plan = flash.tile_plan(2 * half, 2 * half, block_q=tile, block_k=tile,
                           mask=mask).plan
    n = plan.n
    for i in range(2 * n):
        live = ({i} | set(range(n, n + i + 1))) if i < n else set(
            range(n, i + 1))
        named = [int(plan.kv_tile(jnp.int32(i), jnp.int32(j)))
                 for j in range(2 * n)]
        assert set(named) == live, (i, named)
        assert all(named[j] == j for j in live)
        # One fetch a live tile: a dead step names what is resident or
        # wanted next, never a tile of its own.
        assert sum(a != b for a, b in zip(named, named[1:])) == len(live) - 1
    for i in range(2 * n):
        live = {i} if i < n else (set(range(i - n, n)) | set(range(i, 2 * n)))
        named = [int(plan.q_tile(jnp.int32(i), jnp.int32(j)))
                 for j in range(2 * n)]
        assert set(named) == live, (i, named)
        assert all(named[j] == j for j in live)


def test_block_diffusion_shapes_without_a_plan_are_refused(monkeypatch):
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    mask = flash.BlockDiffusion(64, 4)
    q, k, v = _diffusion_inputs(64, 2, 1, 16)
    assert supports(q.shape, 32, 32, mask=mask)
    for why, bad, blocks in (
            ("the row is not the two halves", flash.BlockDiffusion(32, 4),
             (32, 32)),
            ("a block that is no power of two", flash.BlockDiffusion(60, 6),
             (32, 32)),
            ("a block wider than a sub-tile", flash.BlockDiffusion(64, 16),
             (32, 32)),
            ("a tile under two sub-tiles", mask, (8, 8)),
            ("tiles that are not square", mask, (32, 64))):
        assert not supports(q.shape, *blocks, mask=bad), why
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1],
                            interpret=True, mask=bad)
    # The default blocks tile a half: 1,024 at the cell's shape, 512
    # for halves of 512.
    assert supports((2, 8192, 32, 128), mask=flash.BlockDiffusion(4096, 4))
    monkeypatch.setattr(flash, "SUB_TILE", 256)
    assert supports((2, 1024, 4, 64), mask=flash.BlockDiffusion(512, 4))
    assert flash.tile_plan(1024, 1024, mask=flash.BlockDiffusion(512, 4)
                           ).grid == (2, 2)
    assert not supports((2, 200, 4, 64), mask=flash.BlockDiffusion(100, 4))


def test_block_diffusion_cost_counts_the_visible_pairs():
    mask = flash.BlockDiffusion(4096, 4)
    cost = flash._cost(64, 8192, 8192, 128, 128, False,
                       [(2, 8192, 128, 2)], mask=mask)
    assert cost.flops == 2 * 64 * (128 + 128) * mask.pairs
    assert cost.transcendentals == 64 * mask.pairs
    causal = flash._cost(64, 8192, 8192, 128, 128, True, [(2, 8192, 128, 2)])
    assert causal.flops == 2 * 64 * 8192 * 8192 * 256 // 2


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,block", [(64, 64), (128, 32)],
                         ids=["one-tile", "grid"])
def test_causal_and_unmasked_calls_trace_the_kernels_they_traced(
        monkeypatch, causal, s, block):
    """A call without ``mask`` traces the kernels it traced before the
    second mask kind: none of the diffusion kernels, and the only mask
    any tile builds is the causal one (``_visible`` asked with True, or
    not at all). (That the operations in them are the parent's was held
    once against the parent's jaxprs with locations blanked: PERF.md,
    PR 34.)"""
    monkeypatch.setattr(flash, "SUB_TILE", 16)
    expected = (("_fwd_strips_kernel", "_bwd_strips_kernel")
                if causal and s == block else
                GRID_KERNELS if causal else ("_fwd_kernel", "_bwd_kernel"))
    counts = _count_traces(
        monkeypatch, "_fwd_plan_kernel", "_bwd_plan_kernel",
        *expected)
    asked = []
    real = flash._visible
    monkeypatch.setattr(
        flash, "_visible",
        lambda shape, q0, k0, kind: asked.append(kind) or real(
            shape, q0, k0, kind))
    # Shapes no other test of this file traces: a shared trace is cached.
    q, k, v, _ = _grouped_inputs(s, 6, 3)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block,
            interpret=True))

    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert {name for name, n in counts.items() if n} == set(expected)
    assert all(kind is True for kind in asked) and bool(asked) == causal


# -------------------------------------------------- the sliding window

def _window_inputs(s, heads, group, d, dv=None, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda h, width: jnp.asarray(  # noqa: E731
        rng.randn(1, s, h, width), jnp.float32) * 0.5
    return mk(heads, d), mk(heads // group, d), mk(heads // group, dv or d)


def test_sliding_window_rule_and_pairs():
    """Query i sees keys i - window + 1 .. i; the pairs of the cell's
    two kinds of layer by hand."""
    mask = flash.SlidingWindow(24, 8)
    seen = np.asarray(mask.visible(
        jnp.arange(24)[:, None], jnp.arange(24)[None, :]))
    for i in range(24):
        for j in range(24):
            assert seen[i, j] == (i - 7 <= j <= i), (i, j)
    assert seen.sum() == mask.pairs == 8 * 9 // 2 + 16 * 8
    assert flash.SlidingWindow(16384, 4096).pairs == 58722304
    assert flash.SlidingWindow(16384, 16384).pairs == 134225920
    assert flash.SlidingWindow(64, 4096).pairs == 64 * 65 // 2


@pytest.mark.parametrize("heads,group", [(7, 7), (4, 1)],
                         ids=["group-7", "group-1"])
@pytest.mark.parametrize(
    "s,tile,sub,window",
    [(128, 32, 8, 32), (128, 32, 8, 64), (128, 32, 8, 128),
     (96, 32, 16, 64), (64, 32, 16, 32)],
    ids=["grid-4-w1", "grid-4-w2", "grid-4-all", "grid-3-w2", "grid-2-w1"])
def test_sliding_window_kernels_match_dense(monkeypatch, s, tile, sub,
                                            window, heads, group):
    """Forward and dq, dk, dv under the band against dense attention
    under the same ``visible``: a grid of several tiles with a window
    of 1, 2 and all tiles, a group of 7 query heads to a key/value head
    and of 1, v's head size unequal to q's and k's."""
    monkeypatch.setattr(flash, "SUB_TILE", sub)
    mask = flash.SlidingWindow(s, window)
    q, k, v = _window_inputs(s, heads, group, 16, dv=8)
    assert supports(q.shape, tile, tile, mask=mask)

    def kernels(q, k, v):
        return flash_attention(q, k, v, block_q=tile, block_k=tile,
                               interpret=True, mask=mask)

    got = _attend_and_grads(q, k, v, kernels)
    want = _attend_and_grads(
        q, k, v, lambda q, k, v: _dense_under(mask, q, k, v))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-4)


def test_a_window_of_all_tiles_is_the_causal_grid_to_the_bit(monkeypatch):
    """The band's diagonal tiles are walked as the causal grid walks
    them and its whole tiles multiplied as the grid's: with no far edge
    inside the sequence the two give the same bits, o and gradients."""
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    q, k, v = _window_inputs(96, 4, 2, 16, seed=5)
    band = _attend_and_grads(q, k, v, lambda q, k, v: flash_attention(
        q, k, v, block_q=32, block_k=32, interpret=True,
        mask=flash.SlidingWindow(96, 96)))
    grid = _attend_and_grads(q, k, v, lambda q, k, v: flash_attention(
        q, k, v, block_q=32, block_k=32, interpret=True))
    for a, b in zip(band, grid):
        np.testing.assert_array_equal(a, b)


def test_sliding_window_plan_counts_and_covers_every_visible_pair():
    """At the cell's shape, from shapes alone: 42 tiles whole, 28
    boundary (16 diagonal, 12 at the window's edge, 10 of 16 sub-tiles
    each), 186 dead; and at a small one every visible pair lies in a
    tile the plan multiplies, no whole tile holds a masked pair, and a
    dead step names a live step's tile."""
    plan = flash.tile_plan(16384, 16384, group=7,
                           mask=flash.SlidingWindow(16384, 4096))
    assert plan.grid == (16, 16) and plan.tiles == (42, 28, 186)
    assert (plan.plan.diagonal.computed, plan.plan.edge.computed,
            plan.total) == (10, 10, 16)
    assert plan.describe() == (
        "grid 16x16 of blocks 1024x1024: 42 tiles whole and unmasked, 28 "
        "boundary tiles walked (16 diagonal, 12 at the window's edge, 10 "
        "of 16 sub-tiles 256x256), 186 skipped; one key/value head read "
        "in place by 7 query heads, dk/dv summed over them; backward: one "
        "kernel, 5 products a tile")
    # A global layer of the same cell: the causal grid.
    assert flash.tile_plan(16384, 16384).tiles == (120, 16, 120)
    assert supports((1, 16384, 28, 128),
                    mask=flash.SlidingWindow(16384, 4096))
    s, tile, sub, window = 160, 32, 8, 64
    mask = flash.SlidingWindow(s, window)
    small = flash.tile_plan(s, s, block_q=tile, block_k=tile, sub=sub,
                            mask=mask).plan
    n, w, per = small.n, small.w, tile // sub
    assert (n, w) == (5, 2)
    covered = np.zeros((s, s), bool)
    whole = np.zeros_like(covered)
    for qi in range(n):
        live = []
        for kt in range(n):
            rows = slice(qi * tile, (qi + 1) * tile)
            cols = slice(kt * tile, (kt + 1) * tile)
            if qi - w < kt < qi:
                covered[rows, cols] = whole[rows, cols] = True
                live.append(kt)
                continue
            walk = (small.diagonal if kt == qi else
                    small.edge if kt == qi - w else None)
            if walk is None:
                continue
            live.append(kt)
            for i in range(per):
                for j in range(walk.starts[i], walk.rows[i]):
                    covered[qi * tile + i * sub:qi * tile + (i + 1) * sub,
                            kt * tile + j * sub:kt * tile + (j + 1) * sub
                            ] = True
        named = [int(small.kv_tile(jnp.int32(qi), jnp.int32(j)))
                 for j in range(n)]
        assert set(named) == set(live) and all(named[j] == j for j in live)
        assert sum(a != b for a, b in zip(named, named[1:])) == len(live) - 1
        # The backward: key column qi is seen by q tiles qi .. qi + w.
        seen_by = [t for t in range(qi, qi + w + 1) if t < n]
        named = [int(small.q_tile(jnp.int32(qi), jnp.int32(j)))
                 for j in range(n)]
        assert set(named) == set(seen_by)
        assert all(named[j] == j for j in seen_by)
    seen = np.asarray(mask.visible(
        jnp.arange(s)[:, None], jnp.arange(s)[None, :]))
    assert not (seen & ~covered).any()
    assert not (whole & ~seen).any()
    assert small.tiles == (whole.sum() // tile ** 2, n + n - w,
                           n * n - whole.sum() // tile ** 2 - 2 * n + w)


def test_sliding_window_shapes_without_a_plan_are_refused(monkeypatch):
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    q, k, v = _window_inputs(128, 2, 1, 16)
    assert supports(q.shape, 32, 32, mask=flash.SlidingWindow(128, 64))
    for why, bad, blocks in (
            ("a window that is no multiple of the block",
             flash.SlidingWindow(128, 48), (32, 32)),
            ("a window under a block", flash.SlidingWindow(128, 16),
             (32, 32)),
            ("another sequence's mask", flash.SlidingWindow(64, 32),
             (32, 32)),
            ("a tile under two sub-tiles", flash.SlidingWindow(128, 64),
             (8, 8)),
            ("tiles that are not square", flash.SlidingWindow(128, 64),
             (32, 64))):
        assert not supports(q.shape, *blocks, mask=bad), why
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1],
                            interpret=True, mask=bad)
    # The dq row of a masked call is resident: S 16,384 at D 128 sits
    # on the limit, twice that is past it.
    assert supports((1, 16384, 28, 128),
                    mask=flash.SlidingWindow(16384, 4096))
    assert not supports((1, 32768, 28, 128),
                        mask=flash.SlidingWindow(32768, 4096))


def test_sliding_window_cost_counts_the_visible_pairs():
    mask = flash.SlidingWindow(16384, 4096)
    cost = flash._cost(28, 16384, 16384, 128, 128, False,
                       [(2, 16384, 128, 2)], mask=mask)
    assert cost.flops == 2 * 28 * (128 + 128) * mask.pairs
    assert cost.transcendentals == 28 * mask.pairs


def test_both_masks_trace_the_one_pair_of_plan_kernels(monkeypatch):
    """No kernel body of the band's own: a block-diffusion call and a
    sliding-window call trace ``_fwd_plan_kernel`` and
    ``_bwd_plan_kernel`` (and the strips bodies they walk a boundary
    tile by) and nothing else; the file has nine bodies."""
    monkeypatch.setattr(flash, "SUB_TILE", 8)
    bodies = [name for name in vars(flash)
              if name.endswith("_kernel") and callable(getattr(flash, name))]
    assert len(bodies) == 9, bodies
    counts = _count_traces(monkeypatch, *bodies)
    # A head size no other test of this file traces: a shared trace is
    # cached.
    for mask in (flash.SlidingWindow(96, 48), flash.BlockDiffusion(48, 4)):
        q, k, v = _window_inputs(96, 3, 3, 24, seed=9)
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, block_q=48, block_k=48, interpret=True, mask=mask)),
            argnums=(0, 1, 2)))(q, k, v)
    # The strips bodies walk the plan kernels' boundary tiles.
    assert {name for name, n in counts.items() if n} == {
        "_fwd_plan_kernel", "_bwd_plan_kernel", "_fwd_strips_kernel",
        "_bwd_strips_kernel"}
