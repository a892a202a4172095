"""Readings for a configuration's ``limits``, at a cell's own size.

    python benchmark/tests/calibrate.py <config file> <traffic file> \
        <seed> [feed=<feed file of a run with that seed>]
        [task_loss=<the reference's task loss that run's check printed>]
        [control_steps=<n>] [faults=<name>,<name>...]

In one process, on the rows the job's first task was fed (the feed file
of the run just made; without one, the first records of the seed): the
reference's replay of that task (its first step alone where the run's
check has already printed the task's loss); the *control*, the same
replay computed one precision below the configuration's, in the
program's place; and the replay with each fault named planted
(``one_row_left_out`` or ``half_left_out`` of every minibatch;
``adam_b1_0.5``, Adam with another first-moment decay). Each is read
as the comparison reads the program: ``task_loss_gap`` against the
reference's task loss, ``grad_norm_gap`` against its first gradient.
The limits are set from these and from the
sound runs' own numbers (PERF.md). Run on the chip by the builder; the
benchmark's own runs never run the control.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import check, compare, paths, records  # noqa: E402
from benchmark.lib import seeded  # noqa: E402


def control_reading(config_file, traffic_file, seed, feed_file=None,
                    faults=(), task_loss=None, control_steps=None):
    import jax

    from elasticdl_tpu.common.jax_env import enable_compile_cache

    enable_compile_cache()
    cfg, reference, optimizer = check.load_parts(config_file)
    traffic = paths.load_json(traffic_file)
    steps = int(traffic["minibatches_per_task"])
    if feed_file:
        tokens, _ = check.fed_batches(cfg, traffic, seed, feed_file, steps)
    else:
        tokens = records.token_rows(
            steps * cfg["minibatch"], cfg["seq_len"], cfg["vocab_size"],
            traffic["records"], seed).reshape(steps, cfg["minibatch"], -1)

    def replayed(precision="f32", rows=tokens, hyper=None):
        weights = jax.jit(lambda key: reference.weights(cfg, key))(
            seeded.seed_key(seed))
        losses, first = check.replay(reference, optimizer, cfg, weights,
                                     rows, precision, hyper)
        return sum(losses) / len(losses), first

    sound_loss, sound_first = replayed(
        rows=tokens if task_loss is None else tokens[:1])
    if task_loss is not None:
        sound_loss = float(task_loss)
    lower = reference.CONTROL_OF[cfg["compute_dtype"]]
    control_steps = steps if control_steps is None else int(control_steps)
    control_loss, control_first = replayed(lower, tokens[:control_steps])
    out = {
        "config": cfg["name"], "seed": seed, "fed": bool(feed_file),
        "platform": jax.devices()[0].platform,
        "reference_task_loss": sound_loss,
        "control_precision": lower,
        "control": {"grad_norm_gap": compare.leaf_norm_gap(
            control_first, sound_first)[0]},
    }
    if control_steps == steps:
        out["control"]["task_loss_gap"] = abs(control_loss - sound_loss)
    del control_first, sound_first
    planted = {
        "one_row_left_out": dict(rows=tokens[:, :-1]),
        "half_left_out": dict(rows=tokens[:, :tokens.shape[1] // 2]),
        "adam_b1_0.5": dict(hyper=dict(cfg["optimizer"], b1=0.5)),
    }
    if faults:
        out["faults"] = {
            name: {"task_loss_gap": abs(
                replayed(**planted[name])[0] - sound_loss)}
            for name in faults}
    return out


def main(argv):
    config_file, traffic_file, seed = argv[0], argv[1], int(argv[2])
    given = dict(a.split("=", 1) for a in argv[3:] if "=" in a)
    print(json.dumps(control_reading(
        config_file, traffic_file, seed, given.get("feed"),
        [f for f in given.get("faults", "").split(",") if f],
        given.get("task_loss"), given.get("control_steps"))), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
