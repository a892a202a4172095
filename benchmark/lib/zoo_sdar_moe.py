"""The zoo contract for a configuration of the program's block-diffusion
sparse-expert LM (``models/sdar_moe.py`` through
``model_zoo/sdar_moe/sdar_moe_lm.py``): what ``lib/zoo.py`` is for the
GPT-2 family, ``lib/zoo_mla_moe.py`` for the second and
``lib/zoo_nemotron_h.py`` for the third, for the fourth.

``benchmark/models/<config>.py`` calls :func:`contract` with its own
file name; the sizes are ``../configs/<config>.json`` from there, under
the names the published ``config.json`` gives them (the block length and
the noise, which it does not give, under the configuration's own:
``block_length``, ``noise_eps``, ``noise_seed``). The model code (its
noising of every row included), the loss, the optimizer and
``dataset_fn`` are the program's. Replaced is where the initial weights
come from (the configuration's reference makes them from ``--seed``);
``dataset_fn`` is wrapped to note which rows the first steps were fed
(``feed.py``).

**The routing is held where gradients are compared**, as in the two
other expert families and for their reason (``lib/zoo_mla_moe.py``;
PERF.md, section 2): in the process of the comparison (``python -m
benchmark.lib.check``) ``apply`` hands the program's expert layers the
experts the reference chooses for the same weights and rows in float32
(over the doubled row the reference noises itself). Everywhere else (the
worker, the rehearsal) the model is the program's, untouched.
"""

import os

from benchmark.lib import paths
from benchmark.lib.zoo_mla_moe import in_the_comparison

# What the program's block is, where the published file has a switch.
SWITCHES = (
    ("model_type", "sdar_moe"), ("hidden_act", "silu"),
    ("attention_bias", False), ("norm_topk_prob", True),
    ("decoder_sparse_step", 1), ("mlp_only_layers", []),
    ("rope_scaling", None), ("sliding_window", None),
    ("use_sliding_window", False), ("tie_word_embeddings", False),
)


def program_zoo():
    from elasticdl_tpu.core.model_spec import load_module

    return load_module(os.path.join(
        paths.ROOT, "model_zoo", "sdar_moe", "sdar_moe_lm.py"))


def model_config(cfg: dict):
    import jax.numpy as jnp

    from elasticdl_tpu.models.sdar_moe import SdarMoeConfig

    for key, value in SWITCHES:
        if cfg[key] != value:
            raise ValueError(
                f"{cfg['name']}: the program's block has {key} = {value!r}; "
                f"the configuration states {cfg[key]!r}")
    return SdarMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        block_length=cfg["block_length"], noise_eps=float(cfg["noise_eps"]),
        noise_seed=int(cfg["noise_seed"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_width=cfg["router_width"], first_held=cfg["first_held"],
        n_held=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        remat=bool(cfg["remat"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def seeded_lm(mcfg, cfg: dict, reference):
    """The program's ``SdarMoeLM`` whose ``init`` returns the
    benchmark's weights for ``$BENCH_WEIGHT_SEED`` (see ``seeded.py``);
    the tree's structure and shapes are checked against the program's
    own init."""
    import jax

    from benchmark.lib import seeded
    from elasticdl_tpu.models.sdar_moe import SdarMoeLM

    holding = in_the_comparison()

    class SeededLM(SdarMoeLM):
        def apply(self, variables, features, *args, routing=None, **kwargs):
            if routing is None and holding:
                routing = reference.choices(
                    reference.from_program_tree(variables["params"], cfg),
                    features, cfg)
            return SdarMoeLM.apply(self, variables, features, *args,
                                   routing=routing, **kwargs)

        def init(self, rngs, *args, **kwargs):
            want = jax.eval_shape(
                lambda: SdarMoeLM.init(self, rngs, *args, **kwargs))
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
