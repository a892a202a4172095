"""The zoo contract for a configuration of the program's window-and-
global sparse-expert LM (``models/smallthinker.py`` through
``model_zoo/smallthinker/smallthinker_lm.py``): what ``lib/zoo.py`` is
for the GPT-2 family and ``lib/zoo_mla_moe.py``, ``lib/zoo_nemotron_h.py``
and ``lib/zoo_sdar_moe.py`` for the three after it, for the fifth.

``benchmark/models/<config>.py`` calls :func:`contract` with its own
file name; the sizes are ``../configs/<config>.json`` from there, under
the names the published ``config.json`` gives them (``router_width`` and
``first_held``, which say what share is held, under the configuration's
own). The two layouts stand as published; the layers held read their
first ``num_hidden_layers`` entries. The model code, the loss, the
optimizer and ``dataset_fn`` are the program's. Replaced is where the
initial weights come from (the configuration's reference makes them
from ``--seed``); ``dataset_fn`` is wrapped to note which rows the first
steps were fed (``feed.py``).

**The routing is held where gradients are compared**, as in the three
other expert families and for their reason (``lib/zoo_mla_moe.py``;
PERF.md, section 2): in the process of the comparison (``python -m
benchmark.lib.check``) ``apply`` hands the program's expert layers the
experts the reference chooses for the same weights and rows in float32.
Everywhere else (the worker, the rehearsal) the model is the program's,
untouched.
"""

import os

from benchmark.lib import paths
from benchmark.lib.zoo_mla_moe import in_the_comparison

# What the program's block is, where the published file has a switch.
SWITCHES = (
    ("moe_primary_router_apply_softmax", True), ("norm_topk_prob", True),
    ("rope_scaling", None), ("tie_word_embeddings", False),
)


def program_zoo():
    from elasticdl_tpu.core.model_spec import load_module

    return load_module(os.path.join(
        paths.ROOT, "model_zoo", "smallthinker", "smallthinker_lm.py"))


def model_config(cfg: dict):
    import jax.numpy as jnp

    from elasticdl_tpu.models.smallthinker import SmallThinkerConfig

    for key, value in SWITCHES:
        if cfg[key] != value:
            raise ValueError(
                f"{cfg['name']}: the program's block has {key} = {value!r}; "
                f"the configuration states {cfg[key]!r}")
    layers = cfg["num_hidden_layers"]
    return SmallThinkerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=layers, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        sliding_window=cfg["sliding_window_size"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"][:layers]),
        rope_layout=tuple(cfg["rope_layout"][:layers]),
        moe_intermediate_size=cfg["moe_ffn_hidden_size"],
        router_width=cfg["router_width"], first_held=cfg["first_held"],
        n_held=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        remat=bool(cfg["remat"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def seeded_lm(mcfg, cfg: dict, reference):
    """The program's ``SmallThinkerLM`` whose ``init`` returns the
    benchmark's weights for ``$BENCH_WEIGHT_SEED`` (see ``seeded.py``);
    the tree's structure and shapes are checked against the program's
    own init."""
    import jax

    from benchmark.lib import seeded
    from elasticdl_tpu.models.smallthinker import SmallThinkerLM

    holding = in_the_comparison()

    class SeededLM(SmallThinkerLM):
        def apply(self, variables, features, *args, routing=None, **kwargs):
            if routing is None and holding:
                routing = reference.choices(
                    reference.from_program_tree(variables["params"], cfg),
                    features, cfg)
            return SmallThinkerLM.apply(self, variables, features, *args,
                                        routing=routing, **kwargs)

        def init(self, rngs, *args, **kwargs):
            want = jax.eval_shape(
                lambda: SmallThinkerLM.init(self, rngs, *args, **kwargs))
            params = jax.jit(lambda key: reference.to_program_tree(
                reference.weights(cfg, key), cfg)
            )(seeded.seed_key(int(os.environ.get(seeded.SEED_ENV, 0))))
            got = jax.eval_shape(lambda: {"params": params})
            if (jax.tree.structure(want) != jax.tree.structure(got)
                    or jax.tree.leaves(want) != jax.tree.leaves(got)):
                raise ValueError(
                    "the program's parameter tree is no longer the one "
                    "the configuration's reference lays out: "
                    f"{jax.tree.structure(want)} vs "
                    f"{jax.tree.structure(got)}")
            jax.block_until_ready(params)
            return {"params": params}

    return SeededLM(mcfg)


def contract(module_file: str) -> dict:
    """The zoo-contract symbols for the configuration named like
    ``module_file``."""
    from benchmark.lib import feed, probe

    name = os.path.splitext(os.path.basename(module_file))[0]
    base = os.path.dirname(os.path.dirname(os.path.abspath(module_file)))
    cfg = paths.load_json(os.path.join(base, "configs", f"{name}.json"))
    zoo = program_zoo()
    hyper = cfg["optimizer"]
    if (hyper["name"], hyper["b1"], hyper["b2"], hyper["eps"]) != (
            "adam", 0.9, 0.999, 1e-8) or "bias_update_speed" in hyper:
        raise ValueError(
            f"{name}: the zoo's optimizer is optax.adam's defaults at a "
            f"learning rate, with no selection bias to move; the "
            f"configuration states {hyper}")

    def model():
        probe.install_from_env()
        reference = paths.load_module(
            paths.reference_path(base, cfg["reference"]))
        return seeded_lm(model_config(cfg), cfg, reference)

    return dict(
        model=model, CONFIG=cfg, loss=zoo.loss,
        optimizer=lambda: zoo.optimizer(
            hyper["learning_rate"], hyper["warmup_steps"]),
        dataset_fn=feed.wrap(zoo.dataset_fn),
        eval_metrics_fn=zoo.eval_metrics_fn,
    )
