"""The zoo contract for a configuration of the program's latent-attention
sparse-expert LM (``models/mla_moe.py`` through
``model_zoo/mla_moe/mla_moe_lm.py``): what ``lib/zoo.py`` is for the
GPT-2 family, for the second family.

``benchmark/models/<config>.py`` calls :func:`contract` with its own
file name; the sizes are ``../configs/<config>.json`` from there, under
the names the published ``config.json`` gives them. The model code, the
loss (both terms), the optimizer and ``dataset_fn`` are the program's.
Replaced is where the initial weights come from (the configuration's
reference makes them from ``--seed``); ``dataset_fn`` is wrapped to note
which rows the first steps were fed (``feed.py``).

**The routing is held where gradients are compared.** In the process
of the comparison (``python -m benchmark.lib.check``, which takes the
program's gradient through this module like any other caller and has no
argument to say so: :func:`in_the_comparison`) ``apply`` hands the
program's layers the experts the reference chooses for the same weights
and tokens, in float32 (``reference.choices``, the program's routing
replay). A choice that turns on rounding moves a token's rows between a
held expert and an absent one and a leaf's norm by up to 7%, more than
the next lower precision moves it; without this the comparison could
not tell the two apart (PERF.md, section 2). Everywhere else (the
worker, the rehearsal) the model is the program's, untouched; its own
routing is held by the first task's loss and by its ``Task N routing``
counts beside the reference's (``tests/routed_rows.py``).
"""

import os
import sys

from benchmark.lib import paths


def in_the_comparison() -> bool:
    main = getattr(sys.modules.get("__main__"), "__spec__", None)
    return getattr(main, "name", None) == "benchmark.lib.check"


def _program_zoo():
    from elasticdl_tpu.core.model_spec import load_module

    return load_module(os.path.join(
        paths.ROOT, "model_zoo", "mla_moe", "mla_moe_lm.py"))


def model_config(cfg: dict):
    import jax.numpy as jnp

    from elasticdl_tpu.models.mla_moe import MlaMoeConfig

    for key, value in (("n_shared_experts", 1), ("n_group", 1),
                       ("topk_group", 1), ("scoring_func", "sigmoid"),
                       ("norm_topk_prob", True), ("hidden_act", "silu"),
                       ("rope_interleave", True), ("rope_scaling", None),
                       ("moe_layer_freq", 1), ("attention_bias", False)):
        if cfg[key] != value:
            raise ValueError(
                f"{cfg['name']}: the program's block has {key} = {value!r}; "
                f"the configuration states {cfg[key]!r}")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError(f"{cfg['name']}: qk_head_dim is not nope + rope")
    return MlaMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        router_width=cfg["router_width"], first_held=cfg["first_held"],
        n_held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        mtp_layers=cfg["num_nextn_predict_layers"],
        remat=bool(cfg["remat"]), fused_head=bool(cfg["fused_head"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def seeded_lm(mcfg, cfg: dict, reference, mesh=None):
    """The program's ``MlaMoeLM`` whose ``init`` returns the benchmark's
    weights for ``$BENCH_WEIGHT_SEED`` (see ``seeded.py``); the tree's
    structure and shapes are checked against the program's own init."""
    import jax

    from benchmark.lib import seeded
    from elasticdl_tpu.models.mla_moe import MlaMoeLM

    holding = in_the_comparison()

    class SeededLM(MlaMoeLM):
        def apply(self, variables, features, *args, routing=None, **kwargs):
            if routing is None and holding:
                routing = reference.choices(
                    reference.from_program_tree(variables["params"], cfg),
                    features, cfg)
            return MlaMoeLM.apply(self, variables, features, *args,
                                  routing=routing, **kwargs)

        def init(self, rngs, *args, **kwargs):
            want = jax.eval_shape(
                lambda: MlaMoeLM.init(self, rngs, *args, **kwargs))
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

    return SeededLM(mcfg, mesh=mesh)


def contract(module_file: str) -> dict:
    """The zoo-contract symbols for the configuration named like
    ``module_file``."""
    from benchmark.lib import feed, probe

    name = os.path.splitext(os.path.basename(module_file))[0]
    base = os.path.dirname(os.path.dirname(os.path.abspath(module_file)))
    cfg = paths.load_json(os.path.join(base, "configs", f"{name}.json"))
    zoo = _program_zoo()
    hyper = cfg["optimizer"]
    if (hyper["name"], hyper["b1"], hyper["b2"], hyper["eps"]) != (
            "adam", 0.9, 0.999, 1e-8):
        raise ValueError(
            f"{name}: the zoo's optimizer is optax.adam's defaults at a "
            f"learning rate (and plain descent of the selection bias at "
            f"a speed); the configuration states {hyper}")
    if cfg["mtp_loss_weight"] != zoo.MTP_LOSS_WEIGHT:
        raise ValueError(
            f"{name}: the zoo's loss weighs the MTP term by "
            f"{zoo.MTP_LOSS_WEIGHT}, the configuration by "
            f"{cfg['mtp_loss_weight']}")

    def model(mesh=None):
        probe.install_from_env()
        reference = paths.load_module(
            paths.reference_path(base, cfg["reference"]))
        return seeded_lm(model_config(cfg), cfg, reference, mesh)

    return dict(
        model=model, CONFIG=cfg, loss=zoo.loss,
        optimizer=lambda: zoo.optimizer(
            hyper["learning_rate"], hyper["bias_update_speed"],
            hyper["warmup_steps"]),
        dataset_fn=feed.wrap(zoo.dataset_fn),
        eval_metrics_fn=zoo.eval_metrics_fn,
        param_sharding_rules=zoo.param_sharding_rules,
        batch_sharding_rule=zoo.batch_sharding_rule,
    )
