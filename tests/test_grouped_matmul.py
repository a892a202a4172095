"""The width the held experts' two grouped products run at
(``ops/grouped_matmul.py::product_width``), and that running them there
is the same work exactly: ``held_experts_part`` with its weights
zero-padded inside against a plain loop over the experts, the traced
program's shapes, the gradients in the parameters' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from elasticdl_tpu.models.mla_moe import held_experts_part, relu2
from elasticdl_tpu.ops.grouped_matmul import product_width, zero_padded


@pytest.mark.parametrize("f, wide", [
    (1856, 2048),                       # Nemotron-3-Nano's experts
    (2048, 2048), (2560, 2560),
    (768, 768), (1536, 1536),           # JoyAI's experts; gate beside up
    (1920, 2048), (2304, 2560),         # the sweep's other widths
    (1024, 1024), (3712, 4096),
    (4, 4), (16, 16), (24, 24), (48, 48),   # the tests' own
])
def test_product_width_by_the_table(f, wide):
    assert product_width(f) == wide
    assert product_width(wide) == wide


def test_zero_padded_pads_one_axis_and_leaves_a_wide_one_alone():
    w = jnp.arange(12.0).reshape(2, 3, 2)
    assert zero_padded(w, 2, 2) is w
    got = zero_padded(w, 1, 5)
    assert got.shape == (2, 5, 2)
    np.testing.assert_array_equal(got[:, :3], w)
    np.testing.assert_array_equal(got[:, 3:], 0)


def _inputs(gated, t=30, k=2, d=64, f=1856, n=2, width=4, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda scale, *shape: jnp.asarray(
        rng.normal(0, scale, shape), jnp.float32)
    chosen = jnp.asarray(
        np.stack([rng.permutation(width)[:k] for _ in range(t)]), jnp.int32)
    return dict(
        rows=mk(1.0, t, d), chosen=chosen,
        weights=jnp.asarray(rng.uniform(0.1, 1, (t, k)), jnp.float32),
        w_gate=mk(d ** -0.5, n, d, f) if gated else None,
        w_up=mk(d ** -0.5, n, d, f), w_down=mk(f ** -0.5, n, f, d))


def _by_a_loop(rows, chosen, weights, w_gate, w_up, w_down, first_held):
    """Expert after expert, every token's weight for it, float32."""
    dot = lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    out = jnp.zeros_like(rows)
    for e in range(w_up.shape[0]):
        weight = jnp.sum(
            jnp.where(chosen == first_held + e, weights, 0.0), axis=1)
        if w_gate is None:
            hidden = relu2(dot(rows, w_up[e]))
        else:
            hidden = nn.silu(dot(rows, w_gate[e])) * dot(rows, w_up[e])
        out = out + weight[:, None] * dot(hidden, w_down[e])
    return out


@pytest.mark.parametrize("gated", [False, True],
                         ids=["relu2", "silu_gated"])
def test_padded_products_are_the_plain_loops(gated):
    """d 64, experts 1,856 wide run at 2,048, experts 1 and 2 of a
    router 4 wide held: the part, its rows, and the gradient of every
    operand against a plain loop over the experts; the weights'
    gradients come back in the parameters' shapes."""
    given = _inputs(gated)
    assert product_width(given["w_up"].shape[2]) == 2048
    names = [name for name in ("rows", "weights", "w_gate", "w_up", "w_down")
             if given[name] is not None]
    mix = jnp.asarray(
        np.random.default_rng(1).normal(size=given["rows"].shape),
        jnp.float32)

    def total(part_of):
        def loss(*args):
            part = part_of(**dict(given, **dict(zip(names, args))),
                           first_held=1)
            return jnp.sum(part * mix), part
        return jax.jit(jax.value_and_grad(
            loss, tuple(range(len(names))), has_aux=True))(
                *(given[name] for name in names))

    with jax.default_matmul_precision("highest"):
        (_, got), grads = total(
            lambda **kw: held_experts_part(**kw, router_width=4)[0])
        _, sizes = held_experts_part(**given, first_held=1, router_width=4)
    (_, want), want_grads = total(_by_a_loop)
    held = (given["chosen"] >= 1) & (given["chosen"] < 3)
    assert int(sizes.sum()) == int(held.sum()) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip(names, grads, want_grads):
        assert a.shape == given[name].shape, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _traced(gated, t, k, d, f, n, grad):
    shapes = dict(
        rows=(t, d), weights=(t, k), w_gate=(n, d, f), w_up=(n, d, f),
        w_down=(n, f, d))
    names = [name for name in shapes if gated or name != "w_gate"]
    chosen = jax.ShapeDtypeStruct((t, k), jnp.int32)

    def loss(chosen, *args):
        given = dict(zip(names, args))
        given.setdefault("w_gate", None)
        given["rows"] = given["rows"].astype(jnp.bfloat16)
        part, _ = held_experts_part(
            chosen=chosen, first_held=0, router_width=n, **given)
        return jnp.sum(part.astype(jnp.float32))

    fn = jax.grad(loss, tuple(range(1, 1 + len(names)))) if grad else loss
    closed = jax.make_jaxpr(fn)(chosen, *(
        jax.ShapeDtypeStruct(shapes[name], jnp.float32) for name in names))
    return list(_equations(closed.jaxpr)), closed


def _shapes(eqn, of="invars"):
    return [tuple(v.aval.shape) for v in getattr(eqn, of)
            if hasattr(v.aval, "shape")]


def test_relu2_at_1856_runs_its_products_2048_wide():
    """The traced program of the cell's experts at a small bound: both
    products' operands are 2,048 wide, the weights are padded in the
    compute type, ``hidden`` is not cut between the products, and the
    gradient holds no (T*k, 1856) array at all; what it returns has the
    parameters' shapes."""
    t, k, d, f, n = 32, 6, 128, 1856, 8
    forward, _ = _traced(False, t, k, d, f, n, grad=False)
    products = [e for e in forward if e.primitive.name.startswith("ragged_dot")]
    assert [_shapes(e)[:2] for e in products] == [
        [(t * k, d), (n, d, 2048)], [(t * k, 2048), (n, 2048, d)]]
    pads = [e for e in forward if e.primitive.name == "pad"]
    assert sorted(_shapes(e, "outvars")[0] for e in pads) == [
        (n, d, 2048), (n, 2048, d)]
    assert all(e.invars[0].aval.dtype == jnp.bfloat16 for e in pads)
    assert not [e for e in forward
                if e.primitive.name in ("slice", "dynamic_slice")
                and _shapes(e)[0][0] == t * k]
    backward, closed = _traced(False, t, k, d, f, n, grad=True)
    assert [tuple(v.aval.shape) for v in closed.jaxpr.outvars] == [
        (t, d), (t, k), (n, d, f), (n, f, d)]
    assert not [e for e in backward
                if (t * k, f) in _shapes(e) + _shapes(e, "outvars")]
    # In its (T*k, .) arrays it is the program of experts 2,048 wide:
    # nothing that tall is added beside the wider hidden and what made it.
    tall = lambda eqns: sorted(
        (e.primitive.name, s) for e in eqns
        for s in _shapes(e, "outvars") if s[:1] == (t * k,))
    assert tall(backward) == tall(_traced(False, t, k, d, 2048, n, True)[0])


def test_silu_gated_at_768_is_traced_as_it_was():
    """JoyAI's experts: the rule finds nothing to gain, so nothing is
    padded, in the gradient either, and the only cuts of a (T*k, .)
    array are the two halves of the gate-and-up product."""
    t, k, d, f, n = 32, 8, 128, 768, 16
    backward, closed = _traced(True, t, k, d, f, n, grad=True)
    assert not [e for e in backward if e.primitive.name == "pad"
                and any(len(s) == 3 for s in _shapes(e))]
    forward, _ = _traced(True, t, k, d, f, n, grad=False)
    assert not [e for e in forward if e.primitive.name == "pad"]
    products = [e for e in forward if e.primitive.name.startswith("ragged_dot")]
    assert [_shapes(e)[:2] for e in products] == [
        [(t * k, d), (n, d, 2 * f)], [(t * k, f), (n, f, d)]]
    cuts = [e for e in forward if e.primitive.name == "slice"
            and _shapes(e)[0][0] == t * k]
    assert [(_shapes(e)[0], _shapes(e, "outvars")[0]) for e in cuts] == [
        ((t * k, 2 * f), (t * k, f))] * 2
