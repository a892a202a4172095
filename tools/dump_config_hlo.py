"""Dump the compiled (optimized) HLO of a bench config's fused task
program and summarize named ops — companion to profile_config.py --raw:
the trace gives per-op device time, this maps the opaque fusion names
back to what they compute (root instruction + operand shapes), so hot
fusions can be attributed to model structure.

Usage:
    python tools/dump_config_hlo.py transformer --ops fusion.8986 attn.711
    python tools/dump_config_hlo.py transformer --out /tmp/t.hlo
"""

import argparse
import os
import re
import sys


HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--ops", nargs="*", default=[],
                    help="op names to locate and print (fusion.8986 ...)")
    ap.add_argument("--out", default="",
                    help="write the full optimized HLO text here")
    ap.add_argument("--context", type=int, default=25,
                    help="lines of fusion body to print per op")
    args = ap.parse_args()

    enable_compile_cache()
    import jax

    from benchlib import load_config_harness
    from elasticdl_tpu.core.step import runner_for_spec

    spec, task, batch, steps, _ = load_config_harness(args.config)
    runner = runner_for_spec(spec)
    state = runner.init_state(
        spec.model, spec.make_optimizer(),
        jax.tree.map(lambda x: x[0], task), seed=0,
    )
    lowered = runner.train_multi_step(spec.loss).lower(state, task)
    compiled = lowered.compile()
    text = compiled.as_text()
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {len(text)} bytes to {args.out}")

    for op in args.ops:
        # Fusion definition: '%fused_computation... {' bodies are listed
        # separately; the call site line carries calls=... — print both
        # the call site and the head of the called computation.
        pat = re.compile(
            rf"^\s*%?{re.escape(op)} = .*$", re.M
        )
        m = pat.search(text)
        if not m:
            print(f"== {op}: NOT FOUND")
            continue
        line = m.group(0)
        print(f"== {op}:")
        print(line.strip()[:600])
        cm = re.search(r"calls=%?([\w.\-]+)", line)
        if cm:
            body = re.search(
                rf"^%?{re.escape(cm.group(1))}[^\n]*\{{(.*?)^\}}",
                text, re.M | re.S,
            )
            if body:
                lines = [ln.strip()[:240]
                         for ln in body.group(1).strip().splitlines()]
                for ln in lines[: args.context]:
                    print("   ", ln)
                if len(lines) > args.context:
                    print(f"    ... ({len(lines) - args.context} more)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
