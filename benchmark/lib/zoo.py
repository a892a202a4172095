"""The zoo contract for a configuration of the program's transformer.

``benchmark/models/<config>.py`` is what the worker's ``--model_def``
names; each is three lines that call :func:`contract` with its own file
name, and its sizes are ``../configs/<config>.json`` from there. The
model code is the program's (``models/transformer.py`` through
``model_zoo/transformer/transformer_lm.py``): this module only reads the
sizes and hands the program's own ``loss``, ``optimizer``,
``dataset_fn`` and sharding rules through. Replaced is where the initial
weights come from (the benchmark makes them from ``--seed``, see
``seeded.py``); ``dataset_fn`` is wrapped to note which rows the first
steps were fed (``feed.py``).
"""

import os

from benchmark.lib import paths


def _program_zoo():
    from elasticdl_tpu.core.model_spec import load_module

    return load_module(os.path.join(
        paths.ROOT, "model_zoo", "transformer", "transformer_lm.py"))


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from elasticdl_tpu.models.transformer import TransformerConfig

    assert cfg["n_embd"] % cfg["n_head"] == 0
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        d_ff=cfg["n_inner"], max_len=cfg["n_positions"],
        dropout_rate=cfg["dropout_as_run"], remat=bool(cfg["remat"]),
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def contract(module_file: str) -> dict:
    """The zoo-contract symbols for the configuration named like
    ``module_file``."""
    from benchmark.lib import feed, probe, seeded

    name = os.path.splitext(os.path.basename(module_file))[0]
    base = os.path.dirname(os.path.dirname(os.path.abspath(module_file)))
    cfg = paths.load_json(os.path.join(base, "configs", f"{name}.json"))
    zoo = _program_zoo()
    hyper = cfg["optimizer"]
    if (hyper["name"], hyper["b1"], hyper["b2"], hyper["eps"]) != (
            "adam", 0.9, 0.999, 1e-8):
        raise ValueError(
            f"{name}: the zoo's optimizer is optax.adam's defaults at a "
            f"learning rate; the configuration states {hyper}")

    def model(mesh=None):
        # Building the model, not importing the module, is what arms
        # the worker-side reading (and only where the harness asked).
        probe.install_from_env()
        reference = paths.load_module(
            paths.reference_path(base, cfg["reference"]))
        return seeded.seeded_lm(transformer_config(cfg), cfg, reference,
                                mesh)

    return dict(
        model=model, CONFIG=cfg, loss=zoo.loss,
        optimizer=lambda: zoo.optimizer(hyper["learning_rate"]),
        dataset_fn=feed.wrap(zoo.dataset_fn),
        eval_metrics_fn=zoo.eval_metrics_fn,
        param_sharding_rules=zoo.param_sharding_rules,
        batch_sharding_rule=zoo.batch_sharding_rule,
    )
