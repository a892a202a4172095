"""The expert layer of the three older families
(``models/mla_moe.py::ExpertLayer`` with default arguments) traces as it
did before it took a router input of its own and a third form (PR 38).
In a file of its own: a module's ``default_matmul_precision`` fixture
would write itself into the text."""

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.models import mla_moe
from elasticdl_tpu.models.mla_moe import ExpertLayer, MlaMoeConfig

FAMILIES = {
    "mla_moe": MlaMoeConfig(compute_dtype=jnp.float32),
    "nemotron_h": MlaMoeConfig(
        compute_dtype=jnp.float32, expert_form="relu2",
        shared_intermediate_size=48),
    "sdar_moe": MlaMoeConfig(
        compute_dtype=jnp.float32, scoring="softmax", selection_bias=False,
        shared_expert=False),
}


# sha256 (16 hex digits) of ``str(jax.make_jaxpr(...))`` of the layer's
# value and gradient at the PARENT of PR 38 (commit ce06a23, jax 0.9.0),
# before the layer took ``router_input`` and a third form. A jaxpr's
# text holds no file or line. A later change that means to move the
# older families' expert layer prints the new digests with this test's
# ``traced`` and says why.
PARENTS_JAXPR = {"mla_moe": "fcc0dabf7a744b83",
                 "nemotron_h": "012aae9287825c5c",
                 "sdar_moe": "0339b10d4c2e71d1"}

# What PR 40 added to that text, and all it added: the layer's fourth
# counter, ``moe_bound_rows``. Every expert is held here, so the ladder
# is the 2 x 8 x top-2 token-choices alone and the counter a constant;
# its equation binds no name, so the text without it is the parent's.
BOUND_ROWS_COUNTER = "    _:i32[] = stop_gradient 32:i32[]\n"


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_expert_layer_with_default_arguments_traces_as_it_did(family):
    """``router_input`` left out is the layer's own input and the form's
    activation the one each family had: the three older families' expert
    layers trace, value and gradient, to the parent's jaxpr."""
    import hashlib

    cfg = FAMILIES[family]
    layer = ExpertLayer(cfg)
    x = jnp.ones((2, 8, cfg.hidden_size), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))

    def traced(**kw):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, x: jnp.sum(layer.apply(p, x, **kw)[0]),
            argnums=(0, 1)))(params, x))

    text = traced()
    assert text.count(BOUND_ROWS_COUNTER) == 1
    text = text.replace(BOUND_ROWS_COUNTER, "")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        PARENTS_JAXPR[family]), (family, len(text))
    act = {"mla_moe": "logistic", "nemotron_h": "square",
           "sdar_moe": "logistic"}[family]
    assert act in text
    assert mla_moe.EXPERT_FORMS[cfg.expert_form][2] is {
        "silu_gated": jax.nn.silu, "relu2": mla_moe.relu2}[cfg.expert_form]
