"""TPU kernel-correctness lane: compiled (non-interpret) Pallas kernels
on the REAL chip, asserted against the XLA reference paths.

VERDICT round 1 #3: every other Pallas test runs ``interpret=True`` on
CPU, which cannot catch Mosaic-compilation-only bugs (layout/tiling/DMA
semantics). This lane runs the same numerics compiled on the bench chip:

    make test-tpu    (ELASTICDL_TPU_TESTS=1 pytest -m tpu)

and is a pre-bench gate (`make bench` depends on it). Reference
analogue: ``pkg/kernel/kernel_test.go`` — numeric tolerance against
hand-computed updates, run on the real build, not a simulator.

Ring attention's cross-device collective needs >1 chip; its on-chip
building block (``flash_chunk_update``) is covered here, the collective
path by the virtual-mesh CPU tests (test_ring_attention.py).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def tpu():
    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"needs a TPU device, have {dev.platform}"
    return dev


def _qkv(b=2, s=512, h=4, d=64, dtype="float32", seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(b, s, h, d).astype(np.float32) * 0.3, dtype
    )
    return mk(), mk(), mk()


def _kernels_against_dense(b, h, hkv, s, d, dv, mask):
    """Forward and dq, dk, dv of ``flash_attention`` (bfloat16, ``h``
    query heads over ``hkv`` key/value heads, q/k heads of ``d`` and v
    of ``dv``, causal or under ``mask``) against dense attention in
    float32, a query head at a time (the dense scores of one head are
    268 MB at S = 8,192), in the last row of the batch: o and dq for one
    head of every group (of at most four), dk and dv for the first
    key/value head against the sum over its group."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import flash_attention
    from elasticdl_tpu.ops.ring_attention import dense_attention

    rng = np.random.RandomState(s + d)
    mk = lambda heads, width: jnp.asarray(  # noqa: E731
        rng.randn(b, s, heads, width).astype(np.float32) * 0.5,
        jnp.bfloat16)
    q, k, v = mk(h, d), mk(hkv, d), mk(hkv, dv)
    kwargs = dict(causal=True) if mask is None else dict(mask=mask)
    group = h // hkv

    def total(attend):
        def f(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(out * jnp.cos(out)), out
        return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))

    (dq, dk, dv_), out = total(
        lambda q, k, v: flash_attention(q, k, v, **kwargs))(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)[-1:]  # noqa: E731

    def close(got, want, what):
        # The operands are the same bfloat16 numbers on both sides;
        # the kernels round p and ds to bfloat16 for the MXU.
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        assert err <= 2e-2 * float(np.max(np.abs(want))), (what, err)

    dense = total(lambda q, k, v: dense_attention(q, k, v, **kwargs))

    def of_head(head):
        kv = head // group
        return dense(f32(q[:, :, head:head + 1]), f32(k[:, :, kv:kv + 1]),
                     f32(v[:, :, kv:kv + 1]))

    want_dk, want_dv = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for head in range(0, h, max(group, h // 4)):
            (wq, _, _), want = of_head(head)
            close(f32(out[:, :, head]), want[:, :, 0], ("o", head))
            close(f32(dq[:, :, head]), wq[:, :, 0], ("dq", head))
        for head in range(group):
            (_, wk, wv), _ = of_head(head)
            want_dk, want_dv = want_dk + np.asarray(wk), want_dv + np.asarray(wv)
    close(f32(dk[:, :, 0]), want_dk[:, :, 0], "dk")
    close(f32(dv_[:, :, 0]), want_dv[:, :, 0], "dv")
    for x in (dq, dk, dv_):
        assert np.isfinite(np.asarray(x.astype(jnp.float32))).all()


class TestFlashAttentionOnChip:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_dense_f32(self, tpu, causal):
        import jax

        from elasticdl_tpu.ops.flash_attention import flash_attention
        from elasticdl_tpu.ops.ring_attention import dense_attention

        q, k, v = _qkv()
        got = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=causal)
        )(q, k, v)
        want = dense_attention(q, k, v, causal=causal)
        # On-chip tolerance: TPU matmuls accumulate at MXU default
        # precision (bf16-ish passes), so flash-vs-dense differ by
        # ~1e-3 even in f32 — an order-of-magnitude tighter than any
        # real mask/layout bug (O(1)) this lane exists to catch.
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-2, atol=5e-3
        )

    def test_forward_bf16(self, tpu):
        import jax

        from elasticdl_tpu.ops.flash_attention import flash_attention
        from elasticdl_tpu.ops.ring_attention import dense_attention

        q, k, v = _qkv(dtype="bfloat16")
        got = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
        )(q, k, v)
        want = dense_attention(
            q.astype(np.float32), k.astype(np.float32),
            v.astype(np.float32), causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            rtol=3e-2, atol=3e-2,
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_backward_matches_dense(self, tpu, causal):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.flash_attention import flash_attention
        from elasticdl_tpu.ops.ring_attention import dense_attention

        q, k, v = _qkv(s=256)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

        got = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-2, atol=2e-2,
                err_msg=f"d{name} mismatch on chip",
            )

    @pytest.mark.parametrize(
        "b,h,d", [(16, 8, 128), (8, 16, 64)],
        ids=["transformer_l_d128", "gpt2m_steady_d64"],
    )
    def test_flagship_geometry_bf16_forward_and_backward(self, tpu, b, h,
                                                         d):
        """The shapes the product runs (transformer_l: batch 16 x 8
        heads = 128, S=1024, D=128; the benchmark's cell gpt2m_steady:
        8 x 16 heads of 64; bf16, default 1024x1024 blocks walked in
        256-wide strips to the diagonal) against the float32 dense
        reference. Mosaic can refuse a tile size that every smaller
        test shape passes."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.flash_attention import (
            flash_attention,
            supports,
        )
        from elasticdl_tpu.ops.ring_attention import dense_attention

        q, k, v = _qkv(b=b, s=1024, h=h, d=d, dtype="bfloat16")
        assert supports(q.shape)
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=True)
            return jnp.sum(f32(o) ** 2), o

        def loss_dense(q, k, v):
            o = dense_attention(q, k, v, causal=True)
            return jnp.sum(o ** 2), o

        grad = lambda f: jax.jit(  # noqa: E731
            jax.grad(f, argnums=(0, 1, 2), has_aux=True)
        )
        got, out = grad(loss_flash)(q, k, v)
        want, ref = grad(loss_dense)(f32(q), f32(k), f32(v))
        # bf16 inputs and outputs: errors are a few parts in a
        # thousand of each tensor's largest entry (measured on a v5e:
        # 0.004 forward, <= 0.015 on dv whose entries reach 4.8).
        for g, w, name in zip((out, *got), (ref, *want), "oqkv"):
            w = np.asarray(w)
            np.testing.assert_allclose(
                np.asarray(f32(g)), w, rtol=0,
                atol=2e-2 * float(np.abs(w).max()),
                err_msg=f"{name} mismatch at the flagship geometry",
            )

    @pytest.mark.parametrize(
        "b,h,s,d,dv", [(16, 8, 1024, 128, 128), (8, 16, 1024, 64, 64),
                       (8, 16, 512, 64, 64), (4, 32, 4096, 192, 128),
                       (8, 16, 2048, 64, 64)],
        ids=["transformer_l_d128", "gpt2m_steady_d64", "s512_d64",
             "joyai_ep16_steady_s4096_d192_128", "s2048_d64"],
    )
    def test_strips_are_the_whole_tile_to_the_bit(self, tpu, monkeypatch,
                                                  b, h, s, d, dv):
        """The diagonal walked in strips that stop at it against the
        same tiles computed whole and masked (the walk switched off by a
        sub-tile as large as the block): one grid tile (S up to 1,024),
        and the diagonal tiles of a grid of several (S = 2,048 and the
        second family's S = 4,096 with q/k heads of 192 over v's 128,
        where the tiles below the diagonal also drop the mask and the
        dead steps name the diagonal's blocks). Forward, dq, dk, dv,
        compiled under the limits the kernels set (Mosaic's default
        scoped VMEM; 64 MiB for heads over 128 and where the backward's
        blocks pass the default), are the same bits on the chip for o,
        dk and dv (v5e, PR 28: the rows dropped are exact zeros, and
        adding them moves no sum). dq is not since the backward is one
        kernel (PR 35): a q strip's dq is summed k strip by k strip in
        float32 where one product over all its keys made it, so its last
        bits move: held to a bfloat16 rounding of the largest entry."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops import flash_attention as flash

        q, k, _ = _qkv(b=b, s=s, h=h, d=d, dtype="bfloat16")
        v = _qkv(b=b, s=s, h=h, d=dv, dtype="bfloat16", seed=1)[0]

        def run():
            def loss(q, k, v):
                o = flash.flash_attention(q, k, v, causal=True)
                return jnp.sum(o.astype(jnp.float32) ** 2), o

            grads, out = jax.jit(
                jax.grad(loss, argnums=(0, 1, 2), has_aux=True)
            )(q, k, v)
            return (out, *grads)

        plan = flash.tile_plan(s, s)
        assert plan.computed < plan.total
        assert plan.tiles[1] == s // plan.block_q
        strips = run()
        monkeypatch.setattr(flash, "SUB_TILE", plan.block_q)
        assert flash.tile_plan(s, s).rows == (1,)
        for got, want, name in zip(strips, run(), ("o", "dq", "dk", "dv")):
            got, want = (np.asarray(x.astype(jnp.float32))
                         for x in (got, want))
            np.testing.assert_allclose(
                got, want, rtol=0,
                atol=2 ** -7 * np.abs(want).max() if name == "dq" else 0,
                err_msg=f"{name}: strips against the whole tile",
            )

    @pytest.mark.parametrize("q_offset,k_offset", [(0, 0), (1024, 0),
                                                   (512, 768)])
    def test_chunk_grads_strips_match_whole_tile(self, tpu, q_offset,
                                                 k_offset):
        """Offsets known at trace time walk the tile in strips; traced
        offsets compute the whole tile and mask it. Compiled on the
        chip the two are one answer (dk and dv to the bit; dq, summed k
        strip by k strip in the walked tile, to float32's last bits): a
        chunk on the diagonal, wholly below it, and crossed askew (its
        upper strips wholly above)."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.flash_attention import flash_chunk_grads

        bh, s, d = 8, 1024, 64
        rng = np.random.RandomState(5)
        mk = lambda *shape, dtype="bfloat16": jnp.asarray(  # noqa: E731
            rng.randn(*shape).astype(np.float32) * 0.3, dtype)
        q, k, v, do = (mk(bh, s, d) for _ in range(4))
        lse = mk(bh, s, 1, dtype="float32") + 3.0
        delta = mk(bh, s, 1, dtype="float32")
        strips = jax.jit(lambda *a: flash_chunk_grads(
            *a, q_offset, k_offset, causal=True))
        whole = jax.jit(lambda *a: flash_chunk_grads(
            *a, jnp.int32(q_offset), jnp.int32(k_offset), causal=True))
        for got, want, name in zip(strips(q, k, v, do, lse, delta),
                                   whole(q, k, v, do, lse, delta),
                                   ("dq", "dk", "dv")):
            want = np.asarray(want)
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=0,
                atol=1e-5 * np.abs(want).max() if name == "dq" else 0,
                err_msg=f"{name}: strips against the whole tile",
            )

    @pytest.mark.parametrize("half,block", [(4096, 4), (1024, 32)])
    def test_block_diffusion_mask_matches_dense_at_the_cells_geometry(
            self, tpu, half, block):
        """``sdar_ep8_steady``'s attention, compiled: 32 query heads
        over 4 key/value heads of 128 over the row twice (8,192
        positions; and halves of one grid tile), bfloat16, forward and
        dq, dk, dv against dense attention under the same mask in
        float32."""
        from elasticdl_tpu.ops.flash_attention import BlockDiffusion

        _kernels_against_dense(1, 32, 4, 2 * half, 128, 128,
                               BlockDiffusion(half, block))

    @pytest.mark.parametrize(
        "b,h,hkv,s,d,dv,half",
        [(4, 32, 32, 4096, 192, 128, None), (2, 32, 2, 8192, 128, 128, None),
         (2, 32, 4, 8192, 128, 128, 4096)],
        ids=["joyai_ep16_steady", "nemotron3n_ep16_steady",
             "sdar_ep8_steady"],
    )
    def test_one_backward_kernel_matches_dense_at_the_cells_geometries(
            self, tpu, b, h, hkv, s, d, dv, half):
        """The backward as ONE kernel, compiled under the VMEM limit it
        sets from its blocks (a b's whole float32 dq row resident: 3.1
        MB at S 4,096 x 192, 4.2 MB at S 8,192 x 128, twice with the
        pipeline's second buffer), at the expert cells' attention
        shapes: B*H 128 over a 4 x 4 causal grid with q/k 192 and v 128;
        B*H 64 over 2 key/value heads and, under the block-diffusion
        mask, over 4, both 8 x 8 grids. (``gpt2m_steady``'s one walked
        tile at D 64 is ``test_flagship_geometry...``'s second case.)
        Forward, dq, dk, dv within 2% of dense attention's largest entry
        in float32."""
        from elasticdl_tpu.ops import flash_attention as flash

        mask = half and flash.BlockDiffusion(half, 4)
        assert flash._grads_vmem_bytes(
            s, 1024, 1024, d, dv, 2) > flash.DEFAULT_VMEM_BYTES
        _kernels_against_dense(b, h, hkv, s, d, dv, mask)

    def test_chunk_update_streams_to_full_answer(self, tpu):
        """The ring building block compiled on chip: folding K/V chunks
        through flash_chunk_update must equal one-shot attention."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.flash_attention import flash_chunk_update
        from elasticdl_tpu.ops.ring_attention import dense_attention

        b, s, h, d = 1, 512, 2, 64
        chunk = 256
        q, k, v = _qkv(b=b, s=s, h=h, d=d)
        bh = b * h

        def to_bh(x):
            return x.transpose(0, 2, 1, 3).reshape(bh, s, d)

        @jax.jit
        def run(q, k, v):
            qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
            m = jnp.full((bh, s, 1), -1e30, jnp.float32)
            l = jnp.zeros((bh, s, 1), jnp.float32)
            acc = jnp.zeros((bh, s, d), jnp.float32)
            for off in range(0, s, chunk):
                m, l, acc = flash_chunk_update(
                    qb, kb[:, off:off + chunk], vb[:, off:off + chunk],
                    m, l, acc, q_offset=0, k_offset=off, causal=True,
                )
            return acc / jnp.maximum(l, 1e-30)

        got = run(q, k, v).reshape(b, h, s, d).transpose(0, 2, 1, 3)
        want = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-2, atol=5e-3
        )


class TestEmbeddingKernelsOnChip:
    def _table(self, vocab=1024, dim=128, seed=3):
        rng = np.random.RandomState(seed)
        return rng.randn(vocab, dim).astype(np.float32)

    @pytest.mark.parametrize("dim", [256, 512])
    def test_wide_rows_compile_and_match(self, tpu, dim):
        """D > 128 rows move as chunked (1,128) DMAs — the original
        single-DMA kernels failed Mosaic compilation at D>=256 (sublane
        tiling), caught only by this on-chip lane."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.pallas_embedding import (
            lookup_combine,
            sparse_adam_update,
            sparse_sgd_update,
        )

        rng = np.random.RandomState(9)
        table = jnp.asarray(rng.randn(512, dim).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, 512, (16, 6)), jnp.int32)
        w = jnp.asarray(rng.rand(16, 6), jnp.float32)
        got = jax.jit(lambda t, i, ww: lookup_combine(
            t, i, ww, "mean", force_pallas=True))(table, ids, w)
        want = lookup_combine(table, ids, w, "mean", force_xla=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        uids = jnp.asarray(np.arange(8), jnp.int32)
        grads = jnp.asarray(rng.randn(8, dim).astype(np.float32))
        new = jax.jit(lambda t, i, g: sparse_sgd_update(t, i, g, 0.1))(
            table, uids, grads)
        want_t = np.asarray(table).copy()
        want_t[:8] -= 0.1 * np.asarray(grads)
        np.testing.assert_allclose(np.asarray(new), want_t,
                                   rtol=1e-5, atol=1e-6)

        m = table * 0.01
        v = jnp.abs(table) * 0.01
        jax.block_until_ready(jax.jit(
            lambda t, m_, v_, i, g: sparse_adam_update(
                t, m_, v_, i, g, 0.01, step=3)
        )(table, m, v, uids, grads))

    @pytest.mark.parametrize("combiner", ["sum", "mean", "sqrtn"])
    def test_lookup_combine_pallas_matches_xla(self, tpu, combiner):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.pallas_embedding import lookup_combine

        table = jnp.asarray(self._table())
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, 1024, (64, 10)), jnp.int32)
        weights = jnp.asarray(rng.rand(64, 10), jnp.float32)

        got = jax.jit(
            lambda t, i, w: lookup_combine(
                t, i, w, combiner, force_pallas=True
            )
        )(table, ids, weights)
        want = lookup_combine(table, ids, weights, combiner)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("combiner", ["sum", "mean", "sqrtn"])
    def test_lookup_aligned_matches_xla_on_chip(self, tpu, combiner):
        """The round-4 aligned-tile gather, Mosaic-compiled: the
        (8, D) aligned DMA + sublane select must agree with XLA's
        gather+combine on the real chip (the interpreter cannot see
        Mosaic slice/alignment rules — module docstring)."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.pallas_embedding import (
            lookup_combine,
            lookup_combine_aligned,
        )

        table = jnp.asarray(self._table())
        rng = np.random.RandomState(1)
        ids = jnp.asarray(rng.randint(0, 1024, (64, 10)), jnp.int32)
        weights = jnp.asarray(rng.rand(64, 10), jnp.float32)

        got = jax.jit(
            lambda t, i, w: lookup_combine_aligned(t, i, w, combiner)
        )(table, ids, weights)
        want = lookup_combine(table, ids, weights, combiner)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_sparse_sgd_matches_reference(self, tpu):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.pallas_embedding import sparse_sgd_update

        table = self._table()
        rng = np.random.RandomState(1)
        ids = np.unique(rng.randint(0, 1024, 32)).astype(np.int32)
        grads = rng.randn(len(ids), 128).astype(np.float32)
        lr = 0.1

        got = jax.jit(
            lambda t, i, g: sparse_sgd_update(t, i, g, lr)
        )(jnp.asarray(table), jnp.asarray(ids), jnp.asarray(grads))
        want = table.copy()
        want[ids] -= lr * grads
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-6, atol=1e-6
        )

    def test_sparse_adagrad_matches_reference(self, tpu):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.pallas_embedding import (
            sparse_adagrad_update,
        )

        table = self._table()
        accum = np.abs(self._table(seed=5)) * 0.1
        rng = np.random.RandomState(2)
        ids = np.unique(rng.randint(0, 1024, 32)).astype(np.int32)
        grads = rng.randn(len(ids), 128).astype(np.float32)
        lr, eps = 0.1, 1e-8

        got_t, got_a = jax.jit(
            lambda t, a, i, g: sparse_adagrad_update(t, a, i, g, lr, eps)
        )(jnp.asarray(table), jnp.asarray(accum), jnp.asarray(ids),
          jnp.asarray(grads))
        want_a = accum.copy()
        want_a[ids] += grads * grads
        want_t = table.copy()
        want_t[ids] -= lr * grads / (np.sqrt(want_a[ids]) + eps)
        np.testing.assert_allclose(np.asarray(got_a), want_a,
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_t), want_t,
                                   rtol=1e-5, atol=1e-6)

    def test_sparse_adam_matches_reference(self, tpu):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.embedding.optimizer import Adam
        from elasticdl_tpu.ops.pallas_embedding import sparse_adam_update

        table = self._table()
        m = self._table(seed=7) * 0.01
        v = np.abs(self._table(seed=8)) * 0.01
        rng = np.random.RandomState(6)
        ids = np.unique(rng.randint(0, 1024, 32)).astype(np.int32)
        padded = np.concatenate([ids, [1024, 1024]]).astype(np.int32)
        grads = rng.randn(len(padded), 128).astype(np.float32)
        opt = Adam(lr=0.01)

        got_t, got_m, got_v = jax.jit(
            lambda t, m_, v_, i, g: sparse_adam_update(
                t, m_, v_, i, g, lr=0.01, step=5
            )
        )(jnp.asarray(table), jnp.asarray(m), jnp.asarray(v),
          jnp.asarray(padded), jnp.asarray(grads))
        want_rows, want_slots = opt.apply_rows(
            table[ids], grads[:len(ids)], {"m": m[ids], "v": v[ids]},
            step=5,
        )
        np.testing.assert_allclose(np.asarray(got_t)[ids], want_rows,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_m)[ids],
                                   want_slots["m"], rtol=1e-5, atol=1e-6)
        mask = np.ones(1024, bool)
        mask[ids] = False
        np.testing.assert_array_equal(np.asarray(got_t)[mask],
                                      table[mask])

    def test_sparse_adam_amsgrad_matches_reference(self, tpu):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.embedding.optimizer import AdamAmsgrad
        from elasticdl_tpu.ops.pallas_embedding import (
            sparse_adam_amsgrad_update,
        )

        table = self._table()
        m = self._table(seed=17) * 0.01
        v = np.abs(self._table(seed=18)) * 0.01
        max_v = np.abs(self._table(seed=19)) * 0.01
        rng = np.random.RandomState(16)
        ids = np.unique(rng.randint(0, 1024, 32)).astype(np.int32)
        padded = np.concatenate([ids, [1024, 1024]]).astype(np.int32)
        grads = rng.randn(len(padded), 128).astype(np.float32)
        opt = AdamAmsgrad(lr=0.01)

        got_t, got_m, got_v, got_mv = jax.jit(
            lambda t, m_, v_, mv, i, g: sparse_adam_amsgrad_update(
                t, m_, v_, mv, i, g, lr=0.01, step=5
            )
        )(jnp.asarray(table), jnp.asarray(m), jnp.asarray(v),
          jnp.asarray(max_v), jnp.asarray(padded), jnp.asarray(grads))
        want_rows, want_slots = opt.apply_rows(
            table[ids], grads[:len(ids)],
            {"m": m[ids], "v": v[ids], "max_v": max_v[ids]}, step=5,
        )
        np.testing.assert_allclose(np.asarray(got_t)[ids], want_rows,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_mv)[ids],
                                   want_slots["max_v"],
                                   rtol=1e-5, atol=1e-6)
        mask = np.ones(1024, bool)
        mask[ids] = False
        np.testing.assert_array_equal(np.asarray(got_t)[mask],
                                      table[mask])
        np.testing.assert_array_equal(np.asarray(got_mv)[mask], max_v[mask])

    def test_sparse_momentum_matches_reference(self, tpu):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.embedding.optimizer import Momentum
        from elasticdl_tpu.ops.pallas_embedding import (
            sparse_momentum_update,
        )

        table = self._table()
        vel = self._table(seed=11) * 0.1
        rng = np.random.RandomState(12)
        ids = np.unique(rng.randint(0, 1024, 24)).astype(np.int32)
        grads = rng.randn(len(ids), 128).astype(np.float32)
        opt = Momentum(lr=0.05, momentum=0.9, nesterov=True)

        got_t, got_v = jax.jit(
            lambda t, v, i, g: sparse_momentum_update(
                t, v, i, g, 0.05, momentum=0.9, nesterov=True
            )
        )(jnp.asarray(table), jnp.asarray(vel), jnp.asarray(ids),
          jnp.asarray(grads))
        want_rows, want_slots = opt.apply_rows(
            table[ids], grads, {"momentum": vel[ids]}, step=1
        )
        np.testing.assert_allclose(np.asarray(got_t)[ids], want_rows,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_v)[ids],
                                   want_slots["momentum"],
                                   rtol=1e-5, atol=1e-6)

    def test_sharded_lookup_kernel_compiles_on_chip(self, tpu):
        """lookup_combine_sharded's per-shard kernel inside shard_map
        must lower through Mosaic on real hardware (the CPU-mesh tests
        run the interpreter; Mosaic-only failures are invisible there).
        One chip -> a (1,)-mesh: same shard_map + psum structure."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops.pallas_embedding import (
            lookup_combine,
            lookup_combine_sharded,
        )
        from elasticdl_tpu.parallel.mesh import make_mesh

        mesh = make_mesh((1,), ("tp",), devices=jax.devices()[:1])
        rng = np.random.RandomState(3)
        table = jnp.asarray(rng.randn(512, 256).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, 512, (8, 5)), jnp.int32)
        w = jnp.asarray(rng.rand(8, 5).astype(np.float32))
        got = lookup_combine_sharded(
            table, ids, w, "mean", mesh, "tp", force_pallas=True
        )
        want = lookup_combine(table, ids, w, "mean", force_xla=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
