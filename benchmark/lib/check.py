"""The comparison with the plain reference, in a process of its own.

Run by the harness when the window has closed and the worker is gone,
so the chip is free and the reference's float32 copies count against
nobody's peak memory. The configuration file names everything it needs
(``reference``, ``optimizer``, ``model_def``, ``limits``). It

1. finds the rows the worker's reader fed to the job's first task among
   the records made from ``--seed`` (``feed.py``);
2. makes the weights the timed worker started from and replays that
   task in the reference: float32 at ``Precision.HIGHEST``, the
   configuration's optimizer in plain ``jax.numpy``, two rows at a
   time. The mean of its step losses is held against the mean loss the
   worker printed for the task its compiled program trained:
   ``task_loss_gap``;
3. builds the configuration through the worker's own ``--model_def``
   and zoo ``loss`` and takes the gradient on the first fed minibatch at
   those weights, in the configuration's own precision (bfloat16
   matmuls, the Pallas attention kernels on a TPU), against the
   reference's first gradient: ``grad_norm_gap`` (``compare.py``);
4. prints one JSON line with those numbers.

:func:`replay` also serves ``benchmark/tests`` for the control (the
reference one precision lower in the program's place) and for planted
faults; the benchmark's own runs never ask for those.
"""

import argparse
import json
import os
import sys
import time


def load_parts(config_file: str):
    """(cfg, reference module, optimizer module) as the file names them."""
    from benchmark.lib import paths

    cfg = paths.load_json(config_file)
    base = paths.base_of(config_file)
    return (cfg, paths.load_module(paths.reference_path(base, cfg["reference"])),
            paths.load_module(paths.reference_path(
                base, cfg["optimizer"]["reference"])))


def fed_batches(cfg, traffic, seed, feed_file, steps):
    """(tokens of the first ``steps`` fed minibatches as one array
    [steps, rows, seq_len + 1], stray rows)."""
    import numpy as np

    from benchmark.lib import feed, records

    spec = traffic["records"]
    rows = records.token_rows(spec["count"], cfg["seq_len"],
                              cfg["vocab_size"], spec, seed)
    batches, strays = feed.resolve(feed.read(feed_file)[:steps], rows)
    short = sum(cfg["minibatch"] - len(b) for b in batches)
    if len(batches) < steps or short:
        raise SystemExit(
            f"the feed log holds {len(batches)} of {steps} minibatches, "
            f"{short} rows unknown: nothing to replay")
    return np.stack([rows[b] for b in batches]), strays


def replay(reference, optimizer, cfg, weights, tokens, precision="f32",
           hyper=None):
    """The reference trained on ``tokens`` [steps, rows, seq + 1] from
    ``weights`` (consumed): ([loss of every step], the first step's
    gradients in the reference's layout)."""
    import jax
    import jax.numpy as jnp

    hyper = hyper or cfg["optimizer"]
    grads_of = jax.jit(lambda w, rows: reference.loss_and_grads(
        w, rows[:, :-1], rows[:, 1:], cfg, precision))
    apply = jax.jit(lambda w, g, s: optimizer.update(w, g, s, hyper),
                    donate_argnums=(0, 2))
    state, losses, first = optimizer.init(weights), [], None
    for step in range(tokens.shape[0]):
        loss, grads = grads_of(weights, jnp.asarray(tokens[step]))
        losses.append(float(loss))
        weights, state = apply(weights, grads, state)
        if first is None:
            first = grads
    return losses, first


def program_gradient(config_file, cfg, rows):
    """Loss and gradients of the configuration as the worker builds it
    (``--model_def``, the zoo's ``loss``), at the weights its ``init``
    returns for ``$BENCH_WEIGHT_SEED``."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import paths
    from elasticdl_tpu.core.model_spec import get_model_spec

    spec = get_model_spec(
        os.path.join(paths.base_of(config_file), "models"),
        cfg["model_def"])
    features, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    rng = jax.random.PRNGKey(0)
    params = spec.model.init({"params": rng, "dropout": rng}, features,
                             training=False)["params"]
    mask = jnp.ones((features.shape[0],), jnp.float32)

    # Rows and labels are arguments, not constants: a constant would
    # make a new program, and a new compilation, for every seed.
    @jax.jit
    def program(params, features, labels):
        def loss(p):
            out = spec.model.apply({"params": p}, features, training=True)
            return spec.loss(labels, out, mask)
        return jax.value_and_grad(loss)(params)

    loss, grads = program(params, features, labels)
    return float(loss), grads


def run(config_file, traffic_file, seed, feed_file, worker_losses) -> dict:
    os.environ["BENCH_WEIGHT_SEED"] = str(seed)
    import jax

    from benchmark.lib import compare, paths, seeded
    from elasticdl_tpu.common.jax_env import enable_compile_cache

    enable_compile_cache()
    started = time.monotonic()
    cfg, reference, optimizer = load_parts(config_file)
    traffic = paths.load_json(traffic_file)
    per_task = int(traffic["minibatches_per_task"])
    tokens, strays = fed_batches(cfg, traffic, seed, feed_file,
                                 per_task * len(worker_losses))
    program_loss, program_grads = program_gradient(
        config_file, cfg, tokens[0])
    weights = jax.jit(lambda key: reference.weights(cfg, key))(
        seeded.seed_key(seed))
    losses, first = replay(reference, optimizer, cfg, weights, tokens)
    # One program lays the gradients out as the program's tree
    # (hundreds of slices, far too slow one by one).
    first = jax.jit(lambda g: reference.to_program_tree(g, cfg))(first)
    gap, widest = compare.leaf_norm_gap(program_grads, first)
    task_means = [sum(losses[i:i + per_task]) / per_task
                  for i in range(0, len(losses), per_task)]
    device = jax.devices()[0]
    return {
        "config": cfg["name"], "seed": seed,
        "platform": device.platform, "device_kind": device.device_kind,
        "rows": int(tokens.shape[1]), "steps": int(tokens.shape[0]),
        "worker_task_losses": list(worker_losses),
        "reference_task_losses": task_means,
        "reference_step_losses": losses,
        "program_first_loss": program_loss,
        "numbers": {
            "task_loss_gap": max(
                abs(a - b) for a, b in zip(worker_losses, task_means)),
            "grad_norm_gap": gap,
            "stray_rows_fed": strays,
        },
        "widest_leaves": widest,
        "seconds": round(time.monotonic() - started, 2),
    }


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--config-file", required=True)
    parser.add_argument("--traffic-file", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--feed-file", required=True)
    parser.add_argument("--worker-losses", required=True,
                        help="JSON list: the mean loss the worker "
                             "printed for each of its first tasks")
    args = parser.parse_args(argv)
    print(json.dumps(run(args.config_file, args.traffic_file, args.seed,
                         args.feed_file, json.loads(args.worker_losses))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
