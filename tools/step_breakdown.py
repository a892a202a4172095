"""What a traced training step's device time belongs to.

    python tools/step_breakdown.py <profile_dir> [--steps 8] [--top 30]

``<profile_dir>`` is what a worker's ``--profile_dir`` window left:
the trace under ``plugins/`` and, beside it, ``programs/<module>.ops.json``,
the compiled training program's operations with their phase (forward,
recompute, backward, optimizer, mixed, other) and Flax module
(``elasticdl_tpu/utils/profiler.py``, ``utils/hlo_ops.py``). This joins
the two as the benchmark's ``step_*_ms`` readers do
(``benchmark/metrics/_scopes.py``) and prints, largest first: the
phases; phase x module; phase x operation kind (an operation's name
without its number: ``fusion``, ``multiply_add_fusion``, ``attn``);
``mixed`` by the phases found inside the fusions; and the largest
``mixed`` and ``other`` operations by name with their ``op_name``, for
whoever has to say which scope or un-fusing would name them. ``--steps`` is the minibatches a program call runs (a fused task's
``--num_minibatches_per_task``; 1 for ``jit_train_step``). Reads files
only: no jax, no chip.
"""

import argparse
import collections
import glob
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchmark.lib import trace as trace_lib  # noqa: E402
from benchmark.metrics import _scopes  # noqa: E402
from elasticdl_tpu.utils import hlo_ops  # noqa: E402

_NUMBER = re.compile(r"[.\d]+$")


def load(profile_dir: str, steps: int) -> dict:
    """The harness's record of a run, as far as the join reads it."""
    tables = sorted(glob.glob(
        os.path.join(profile_dir, "programs", "jit_*.ops.json")))
    path = trace_lib.find_trace(profile_dir)
    if not tables or path is None:
        raise SystemExit(
            f"{profile_dir}: needs programs/jit_*.ops.json and a trace "
            "under plugins/profile/")
    program = os.path.basename(tables[0])[len("jit_"):-len(".ops.json")]
    return {"trace_dir": profile_dir, "trace": trace_lib.Trace.load(path),
            "traffic": {"program": program}, "steps_per_task": steps}


def breakdown(run: dict) -> dict:
    table = _scopes.load_table(run)
    ops, programs = _scopes.program_ops(run)
    if not ops:
        raise SystemExit("no operation inside a traced training program")
    per_step = 1e3 / (programs * run["steps_per_task"])
    by_phase = collections.Counter()
    by_module = collections.Counter()
    by_kind = collections.Counter()
    by_mix = collections.Counter()
    by_name = collections.defaultdict(lambda: [0.0, 0, None])
    for dur, name, row in _scopes.joined(ops, table):
        ms = dur * per_step
        by_phase[row["phase"]] += ms
        by_module[(row["phase"], row["module"] or "-")] += ms
        by_kind[(row["phase"], _NUMBER.sub("", name))] += ms
        if "mixed" in row:
            by_mix["+".join(row["mixed"])] += ms
        entry = by_name[name]
        entry[0] += ms
        entry[1] += 1
        entry[2] = row
    return {"programs": programs, "phase": by_phase, "module": by_module,
            "kind": by_kind, "mix": by_mix, "name": by_name,
            "program_ms": per_step * sum(
                p[1] for p in _scopes.task_programs(run))}


def _print_table(title, counter, total, top):
    print(f"\n{title}")
    for key, ms in counter.most_common(top):
        label = key if isinstance(key, str) else " x ".join(key)
        print(f"  {ms:10.3f} ms  {100 * ms / total:6.2f}%  {label}")


def report(result: dict, steps: int, top: int):
    total = sum(result["phase"].values())
    scoped = sum(result["phase"][p] for p in hlo_ops.SCOPED)
    print(f"{result['programs']} program call(s) of {steps} step(s): "
          f"{result['program_ms']:.3f} ms a step on XLA Modules, "
          f"{total:.3f} ms a step in operations; "
          f"{100 * scoped / total:.2f}% of it in one phase")
    _print_table("phase, ms a step", result["phase"], total, top)
    _print_table("phase x module", result["module"], total, top)
    _print_table("phase x operation kind", result["kind"], total, top)
    _print_table("mixed, by the phases found inside", result["mix"], total,
                 top)
    for phase in (hlo_ops.MIXED, hlo_ops.OTHER):
        rows = sorted(
            ((ms, calls, name, row)
             for name, (ms, calls, row) in result["name"].items()
             if row["phase"] == phase), reverse=True)[:10]
        print(f"\nthe largest {phase} operations (ms a step, calls a "
              "program, name, phases, op_name)")
        for ms, calls, name, row in rows:
            print(f"  {ms:10.3f}  {calls // result['programs']:4d}  "
                  f"{name}  {','.join(row.get('mixed', []))}  "
                  f"{row['op_name'] or '(no metadata)'}")
    # Another cut of ``mixed``, a guess and labelled so: a fusion's own
    # op_name is its root instruction's.
    by_root = collections.Counter()
    for ms, _, row in result["name"].values():
        if row["phase"] == hlo_ops.MIXED:
            by_root[hlo_ops.phase_of(row["op_name"].split(";")[0])] += ms
    _print_table("mixed, by the phase of the fusion's own op_name (its "
                 "root's: a guess the table does not make)",
                 by_root, total, top)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("profile_dir")
    parser.add_argument("--steps", type=int, default=1,
                        help="minibatches a program call runs")
    parser.add_argument("--top", type=int, default=30)
    args = parser.parse_args(argv)
    report(breakdown(load(args.profile_dir, args.steps)), args.steps,
           args.top)


if __name__ == "__main__":
    main()
