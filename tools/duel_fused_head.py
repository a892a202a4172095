"""Paired duel: transformer bench config with fused_head off vs on.

The materialized-logits path carries four (B,S,32768) f32 log-softmax
loop fusions (~2.5 ms/step at d512: the round-4 raw profile mapped back
by hand to the compiled program's fusions, a tool that went in PR 36;
``tools/step_breakdown.py <profile_dir>`` reads a ``--profile_dir``
window the same way now); fused_next_token_cross_entropy avoids forming
logits at all. An earlier-round duel measured the fused path ~4% slower;
runtime updates since (the flash custom-calls alone dropped ~21%) make
this worth re-measuring whenever the stack changes.

Usage: python tools/duel_fused_head.py [transformer|transformer_l]
Prints one JSON line per variant with device ms/step and MFU.
"""

import dataclasses
import json
import os
import sys


HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import enable_compile_cache, measure_multi_step  # noqa: E402


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "transformer"
    enable_compile_cache()
    from benchlib import load_config_harness

    spec, task, batch, steps, measure_tasks = load_config_harness(name)
    base_cfg = spec.model.cfg
    results = {}
    for fused in (False, True):
        cfg = dataclasses.replace(base_cfg, fused_head=fused)
        spec.model = spec.module.custom_model(config=cfg)
        m = measure_multi_step(
            spec, task, batch, steps, measure_tasks, compute_mfu=True
        )
        row = {
            "variant": f"fused_head={fused}",
            "device_ms_per_task": round(m["device_ms_per_task"], 2),
            "device_ms_per_step": round(
                m["device_ms_per_task"] / steps, 3
            ),
            "eps_device": round(m["eps_device"] or 0.0, 1),
            "mfu": round(m.get("mfu") or 0.0, 4),
        }
        results[fused] = row
        print(json.dumps(row))
    if results[False]["device_ms_per_task"]:
        speedup = (results[False]["device_ms_per_task"]
                   / max(results[True]["device_ms_per_task"], 1e-9))
        print(json.dumps({"fused_over_materialized_speedup":
                          round(speedup, 4)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
