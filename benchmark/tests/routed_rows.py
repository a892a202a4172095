"""The drop-free witness, at a cell's own size: the rows the program's
grouped products ran over in the job's first task, step by step (the
worker's ``Task N routing: ... moe_rows=[...]`` line, its own routing
in its own precision), beside the token-choices of held experts the
reference counts when it trains the same steps on the same rows in
float32.

    python benchmark/tests/routed_rows.py <config file> <traffic file> \
        <seed> feed=<feed file of a run with that seed> \
        log=<that run's worker.log> [steps=<n>]

No choice of a held expert may be lost, so the program's count is never
under the reference's by more than the choices that turn on rounding
move (``flips``: how far apart the two are, as a share of the
reference's count). Run on the chip by the builder after a run of the
cell (``.bench_work/<cell>/``); the benchmark's own runs never run it.
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import check, paths, seeded  # noqa: E402

ROUTING_LINE = re.compile(r"Task \d+ routing: .*moe_rows=\[([^\]]*)\]")


def program_rows(worker_log: str) -> list:
    """``moe_rows`` of every step of the first task the worker trained."""
    with open(worker_log, errors="replace") as f:
        for line in f:
            found = ROUTING_LINE.search(line)
            if found:
                return [int(x) for x in found.group(1).replace(",", " ").split()]
    raise SystemExit(f"{worker_log}: no 'Task N routing' line")


def reference_rows(config_file, traffic_file, seed, feed_file, steps=None):
    """The reference's count at every step of its replay of that task."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.jax_env import enable_compile_cache

    enable_compile_cache()
    cfg, reference, optimizer = check.load_parts(config_file)
    traffic = paths.load_json(traffic_file)
    steps = int(steps or traffic["minibatches_per_task"])
    tokens, _ = check.fed_batches(cfg, traffic, seed, feed_file, steps)
    weights = jax.jit(lambda key: reference.weights(cfg, key))(
        seeded.seed_key(seed))
    count = jax.jit(lambda w, rows: reference.routed_rows(
        w, rows[:, :-1], cfg))
    grads_of = jax.jit(lambda w, rows: reference.loss_and_grads(
        w, rows[:, :-1], rows[:, 1:], cfg))
    apply = jax.jit(
        lambda w, g, s: optimizer.update(w, g, s, cfg["optimizer"]),
        donate_argnums=(0, 2))
    state, rows_of = optimizer.init(weights), []
    for step in range(steps):
        rows = jnp.asarray(tokens[step])
        rows_of.append(int(count(weights, rows)))
        if step + 1 < steps:
            weights, state = apply(weights, grads_of(weights, rows)[1], state)
    return rows_of


def main(argv):
    config_file, traffic_file, seed = argv[0], argv[1], int(argv[2])
    given = dict(a.split("=", 1) for a in argv[3:] if "=" in a)
    want = reference_rows(config_file, traffic_file, seed, given["feed"],
                          given.get("steps"))
    got = program_rows(given["log"])[:len(want)]
    print(json.dumps({
        "seed": seed, "program_moe_rows": got, "reference_routed_rows": want,
        "flips": [abs(a - b) / max(b, 1) for a, b in zip(got, want)],
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
