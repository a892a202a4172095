"""One-line device-time measurement of a bench config (no floor I/O).

Usage: python tools/measure_config.py transformer [transformer_l ...]
"""

import json
import os
import sys


HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import (  # noqa: E402
    enable_compile_cache,
    load_config_harness,
    measure_multi_step,
)


def main():
    names = sys.argv[1:] or ["transformer"]
    enable_compile_cache()
    for name in names:
        spec, task, batch, steps, measure_tasks = load_config_harness(
            name
        )
        m = measure_multi_step(
            spec, task, batch, steps, measure_tasks, compute_mfu=True
        )
        print(json.dumps({
            "config": name,
            "device_ms_per_step": round(
                (m["device_ms_per_task"] or 0.0) / steps, 3
            ),
            "eps_device": round(m["eps_device"] or 0.0, 1),
            "eps_wall": round(m["eps"], 1),
            "mfu": round(m.get("mfu") or 0.0, 4),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
