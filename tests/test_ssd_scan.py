"""``ops/ssd_scan.py``: the chunked scan's Pallas kernels (interpreted
here) against the recurrence taken one position after another, forward
and every gradient; a carried state that a chunk-local scan would lose;
and the real shapes compiled for a described v5e."""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import ssd_scan as ssd
from elasticdl_tpu.ops.ssd_scan import ssd_reference, ssd_scan

NAMES = ("x", "dt", "A", "B", "C", "D")


def inputs(bt, s, h, p, g, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (
        jax.random.normal(ks[0], (bt, s, h, p)).astype(dtype),
        jax.nn.softplus(jax.random.normal(ks[1], (bt, s, h)) - 1.0),
        -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5),
        (jax.random.normal(ks[3], (bt, s, g, n)) * 0.5).astype(dtype),
        (jax.random.normal(ks[4], (bt, s, g, n)) * 0.5).astype(dtype),
        jax.random.normal(ks[5], (h,)),
    ), jax.random.normal(ks[6], (bt, s, h, p))


# chunks 1, 2 and 5; 3: no power of two; 37 positions: no whole number
# of chunks; heads a group: 2, 1 and 4.
@pytest.mark.parametrize("s,chunk,h,g", [
    (8, 8, 4, 2), (16, 8, 4, 2), (40, 8, 4, 2), (24, 8, 2, 2),
    (37, 8, 4, 1), (32, 16, 8, 2),
], ids=["1_chunk", "2_chunks", "5_chunks", "3_chunks_1_head_a_group",
        "ragged_length", "4_heads_a_group"])
def test_forward_and_every_gradient_match_the_recurrence(s, chunk, h, g):
    args, weight = inputs(2, s, h, 4, g, 8, seed=s + h)
    got = ssd_scan(*args, chunk=chunk)
    want = ssd_reference(*args)
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=1e-5)
    every = tuple(range(6))
    got_g = jax.grad(lambda *a: jnp.sum(
        ssd_scan(*a, chunk=chunk) * weight), every)(*args)
    want_g = jax.grad(lambda *a: jnp.sum(
        ssd_reference(*a) * weight), every)(*args)
    for name, a, b in zip(NAMES, got_g, want_g):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))), rtol=1e-4,
            err_msg=name)


def test_a_state_is_carried_across_chunks():
    """One input at position 0, read out at the last position of the
    fourth chunk through a decay close to 1: a scan that kept to its
    chunks would give zero there."""
    s, chunk = 32, 8
    x = jnp.zeros((1, s, 2, 4)).at[0, 0].set(1.0)
    dt = jnp.full((1, s, 2), 0.5)
    a = jnp.asarray([-0.01, -0.02])
    b = jnp.ones((1, s, 1, 8))
    c = jnp.zeros((1, s, 1, 8)).at[0, s - 1].set(1.0)
    y = ssd_scan(x, dt, a, b, c, jnp.zeros((2,)), chunk=chunk)
    # h_0 = dt x B^T = 0.5 in every entry; 31 decays of exp(0.5 a); the
    # readout sums the state's 8 columns.
    want = 0.5 * 8 * np.exp(0.5 * np.asarray(a) * (s - 1))
    np.testing.assert_allclose(y[0, s - 1, :, 0], want, rtol=1e-5)
    assert float(jnp.abs(y[0, chunk:s - 1]).max()) == 0.0
    np.testing.assert_allclose(
        y, ssd_reference(x, dt, a, b, c, jnp.zeros((2,))), atol=1e-5)
    # And the gradient finds its way back through the three boundaries.
    dx = jax.grad(lambda x: ssd_scan(
        x, dt, a, b, c, jnp.zeros((2,)), chunk=chunk)[0, s - 1, 0, 0])(x)
    np.testing.assert_allclose(
        dx[0, 0, 0, 0], want[0], rtol=1e-5)


def test_bfloat16_inputs_stay_close_to_the_float32_recurrence():
    args, _ = inputs(1, 32, 4, 8, 2, 16, seed=3, dtype=jnp.bfloat16)
    got = ssd_scan(*args, chunk=8)
    assert got.dtype == jnp.bfloat16
    want = ssd_reference(*args)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.05 * (
        float(jnp.max(jnp.abs(want))))


def test_compiled_kernels_refuse_what_they_cannot_tile():
    args, _ = inputs(1, 16, 4, 4, 2, 8)
    assert not ssd.supports(args[0].shape, 2, 8, 8)
    assert ssd.supports((2, 8192, 64, 64), 8, 128, 128)
    with pytest.raises(ValueError, match="whole lane tiles"):
        ssd_scan(*args, chunk=8, interpret=False)
    with pytest.raises(ValueError, match="3 groups"):
        ssd_scan(args[0], args[1], args[2], args[3][:, :, :1].repeat(3, 2),
                 args[4][:, :, :1].repeat(3, 2), args[5], chunk=8)


def test_the_line_is_logged_once_a_shape():
    records = []
    handler = logging.Handler()
    handler.emit = lambda record: records.append(record.getMessage())
    ssd.logger.addHandler(handler)
    ssd.log_traced.cache_clear()
    try:
        for _ in range(2):
            ssd.log_traced((2, 8192, 64, 64), 8, 128, 128)
    finally:
        ssd.logger.removeHandler(handler)
        ssd.log_traced.cache_clear()
    assert records == [
        "ssd: traced pallas chunk kernel for x(2, 8192, 64, 64), 8 groups, "
        "state 128, chunk 128: 64 chunks, state carried in VMEM, backward "
        "kernel"]


def count_calls(jaxpr, primitive="pallas_call"):
    return sum(
        (eqn.primitive.name == primitive)
        + sum(count_calls(sub, primitive)
              for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns
    )


def test_one_kernel_forward_and_one_backward():
    args, weight = inputs(1, 16, 4, 4, 2, 8)
    scan = functools.partial(ssd_scan, chunk=8)
    assert count_calls(jax.make_jaxpr(scan)(*args).jaxpr) == 1
    grad = jax.grad(lambda *a: jnp.sum(scan(*a) * weight), (0, 1, 2, 3, 4, 5))
    assert count_calls(jax.make_jaxpr(grad)(*args).jaxpr) == 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_published_shapes_compile_for_a_described_v5e(one_chip):
    """The cell's shapes through the TPU's compiler, no chip attached
    (a compile that passes is not a chip run): both kernels are taken,
    and neither L nor a whole sequence's states beyond the (B, S/Q, H,
    P, N) residual appear among the program's buffers."""
    from jax.experimental.compilation_cache import compilation_cache

    bt, s, h, p, g, n = 2, 8192, 64, 64, 8, 128
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (shaped((bt, s, h, p), jnp.bfloat16), shaped((bt, s, h), jnp.float32),
            shaped((h,), jnp.float32), shaped((bt, s, g, n), jnp.bfloat16),
            shaped((bt, s, g, n), jnp.bfloat16), shaped((h,), jnp.float32))

    def loss(*a):
        return jnp.sum(ssd_scan(*a, interpret=False).astype(jnp.float32))

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)))).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 2
    # L for the whole sequence would be 537 MB in float32; the states
    # residual is 268 MB and x, y, dx, dy 67 MB each.
    assert compiled.memory_analysis().temp_size_in_bytes < 900e6


def test_block_diffusion_attention_compiles_for_a_described_v5e(one_chip):
    """``sdar_ep8_steady``'s attention through the TPU's compiler, no
    chip attached (a compile that passes is not a chip run): 32 query
    heads over 4 key/value heads of 128, the row twice over, 8,192
    positions, under the block-diffusion mask. Two kernels (forward;
    the backward is one since PR 35), and no
    (2L)^2 score tensor among the program's buffers. (In this file, not
    beside the kernels' other tests: one file may describe the topology,
    a second goes to another worker where the library's lock skips it.)"""
    from jax.experimental.compilation_cache import compilation_cache

    from elasticdl_tpu.ops.flash_attention import (
        BlockDiffusion,
        flash_attention,
    )

    rows, half, h, hkv, d = 2, 4096, 32, 4, 128
    shaped = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (rows, 2 * half, heads, d), jnp.bfloat16, sharding=one_chip)
    mask = BlockDiffusion(half, 4)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask).astype(
            jnp.float32))

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2))).lower(
                shaped(h), shaped(hkv), shaped(hkv)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 2
    # One head's scores over the doubled row would be 268 MB in float32,
    # a row's 8.6 GB; dk and dv leave the kernel a query head each (2 x
    # 268 MB, float32) beside dq and the operands' copies.
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("window", [4096, None], ids=["window", "global"])
def test_window_and_global_attention_compile_for_a_described_v5e(
        one_chip, window):
    """``smallthinker_ep8_steady``'s two kinds of attention layer
    through the TPU's compiler, no chip attached (a compile that passes
    is not a chip run): 28 query heads over 4 key/value heads of 128 (a
    group of 7), one row of 16,384 positions, under the band of 4,096
    (the plan kernels; the dq row of 8 MiB sits on the resident limit)
    and plain causal (the grid kernels). Two kernels a layer, and no S^2
    score tensor among the program's buffers."""
    from jax.experimental.compilation_cache import compilation_cache

    from elasticdl_tpu.ops.flash_attention import (
        SlidingWindow,
        flash_attention,
        supports,
    )

    s, h, hkv, d = 16384, 28, 4, 128
    shaped = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (1, s, heads, d), jnp.bfloat16, sharding=one_chip)
    mask = window and SlidingWindow(s, window)
    assert supports((1, s, h, d), mask=mask)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask).astype(
            jnp.float32))

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2))).lower(
                shaped(h), shaped(hkv), shaped(hkv)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 2
    # One head's scores would be 1.07 GB in float32; dk and dv leave the
    # kernel a query head each (2 x 235 MB, float32) beside dq.
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize(
    "bh,bkv,s,d,dv,half",
    [(128, 128, 1024, 64, 64, None), (128, 128, 4096, 192, 128, None),
     (64, 4, 8192, 128, 128, None), (64, 8, 8192, 128, 128, 4096),
     (128, 128, 4096, 64, 64, None), (128, 128, 2048, 64, 64, None),
     (16, 16, 16384, 128, 128, None)],
    ids=["gpt2m_steady", "joyai_ep16_steady", "nemotron3n_ep16_steady",
         "sdar_ep8_steady", "s4096_d64", "s2048_d64",
         "the_longest_resident_row"],
)
def test_the_one_backward_kernel_compiles_for_a_described_v5e(
        one_chip, bh, bkv, s, d, dv, half):
    """``flash_chunk_grads`` through the TPU's compiler, no chip
    attached (a compile that passes is not a chip run), at the four
    cells' attention shapes, at the shapes of the sweep in
    ``ops/flash_attention.py``'s header, and at the longest row whose dq
    stays resident: one kernel, under the scoped-VMEM limit the call
    sets from its blocks (Mosaic refuses S 4,096 at D 64 under its
    default: 18.2 MiB of 16)."""
    from jax.experimental.compilation_cache import compilation_cache

    from elasticdl_tpu.ops import flash_attention as flash

    shaped = lambda *shape, dtype=jnp.bfloat16: (  # noqa: E731
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))
    mask = half and flash.BlockDiffusion(half, 4)
    assert s == flash._resident_rows(d, 1024) or s < 16384

    def backward(*operands):
        return flash.flash_chunk_grads(
            *operands, 0, 0, causal=mask is None, mask=mask)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(backward).lower(
            shaped(bh, s, d), shaped(bkv, s, d), shaped(bkv, s, dv),
            shaped(bh, s, dv), shaped(bh, s, 1, dtype=jnp.float32),
            shaped(bh, s, 1, dtype=jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 1

