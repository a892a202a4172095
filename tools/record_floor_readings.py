"""Derive bench floors from >= N isolated clean-run readings.

The documented floor procedure (BASELINE.md "Floor re-baseline") is a
band times the MEDIAN of isolated clean-run rates — this tool is that
procedure as code, so floors are never hand-set. Each reading is a
fresh subprocess (its own TPU client; the persistent compile cache —
common/jax_env.py — makes that cheap), run strictly
sequentially so readings never contend for the host or the chip.

Usage:
    python tools/record_floor_readings.py            # all configs, n=5
    python tools/record_floor_readings.py -n 7 cifar10 resnet50

Writes BENCH_SUITE_FLOOR.json entries:
    rate          = WALL_BAND   x median(wall eps readings)
    rate_device   = DEVICE_BAND x median(device eps readings)
plus the raw readings arrays (the audit trail the bands are judged
against) and the procedure string.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import bench_suite  # noqa: E402
from benchlib import load_json  # noqa: E402

SNIPPET = """
import json, sys
sys.path.insert(0, {here!r})
from benchlib import enable_compile_cache
enable_compile_cache()
import jax
platform = jax.devices()[0].platform
if platform == "cpu":
    # Floors gate TPU runs; a CPU reading silently replacing them would
    # neuter the regression gate (bench_suite.main has the same guard).
    print("READING_REFUSED cpu")
    raise SystemExit(3)
import bench_suite
m = bench_suite.run_config({name!r})
m["platform"] = platform
print("READING " + json.dumps(m))
"""


def one_reading(name, timeout=900):
    try:
        proc = subprocess.run(
            [sys.executable, "-c", SNIPPET.format(here=HERE, name=name)],
            capture_output=True, text=True, timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        # A hung reading is one failed attempt, not a crash of the
        # whole derivation run.
        sys.stderr.write(f"{name}: reading timed out after {timeout}s\n")
        return None
    if "READING_REFUSED cpu" in proc.stdout:
        raise SystemExit(
            "refusing to derive floors on a CPU backend — floors gate "
            "TPU runs"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("READING "):
            return json.loads(line[len("READING "):])
    sys.stderr.write(
        f"{name}: reading failed (rc={proc.returncode})\n"
        + proc.stderr[-2000:] + "\n"
    )
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="*", default=None)
    ap.add_argument("-n", type=int, default=5,
                    help="readings per config (>= 5 per procedure)")
    ap.add_argument("--max-tries", type=int, default=3,
                    help="extra attempts per failed reading")
    args = ap.parse_args()
    names = args.configs or list(bench_suite.CONFIGS)

    floors = load_json(bench_suite.FLOOR_FILE, {})
    date = time.strftime("%Y-%m-%d")
    for name in names:
        walls, devs, spreads = [], [], []
        tries_left = args.n * args.max_tries
        while len(walls) < args.n and tries_left > 0:
            tries_left -= 1
            m = one_reading(name)
            if m is None:
                continue
            walls.append(m["eps"])
            if m.get("eps_device"):
                devs.append(m["eps_device"])
            spreads.append(m.get("wall_spread", 0.0))
            print(json.dumps({
                "config": name, "reading": len(walls),
                "eps": round(m["eps"], 2),
                "eps_device": round(m.get("eps_device", 0.0), 2),
                "wall_spread": round(m.get("wall_spread", 0.0), 4),
            }), flush=True)
        if len(walls) < args.n:
            sys.stderr.write(
                f"{name}: only {len(walls)}/{args.n} readings; "
                f"floor NOT updated\n"
            )
            continue
        unit = ("tokens/sec/chip" if name.startswith("transformer")
                else "examples/sec/chip")
        entry = {
            "rate": round(
                float(np.median(walls)) * bench_suite.WALL_BAND, 2
            ),
            "unit": unit,
            "platform": "tpu",
            "batch": bench_suite.CONFIGS[name][1],
            "steps": bench_suite.CONFIGS[name][2],
            "rebaselined_from_rate": round(float(np.median(walls)), 2),
            "n_readings": len(walls),
            "readings_wall": [round(w, 2) for w in walls],
            "wall_spread_max": round(max(spreads), 4) if spreads else 0.0,
            "procedure": f"{bench_suite.WALL_BAND} x median of "
                         f"{len(walls)} isolated clean-run wall rates; "
                         f"{bench_suite.DEVICE_BAND} x median of "
                         f"{len(devs)} device-time rates "
                         f"(tools/record_floor_readings.py, {date})",
        }
        if devs:
            entry["rate_device"] = round(
                float(np.median(devs)) * bench_suite.DEVICE_BAND, 2
            )
            entry["readings_device"] = [round(d, 2) for d in devs]
            entry["device_spread"] = round(
                (max(devs) - min(devs)) / min(devs), 4
            )
        old = floors.get(name) or {}
        if "round1_floor" in old:
            entry["round1_floor"] = old["round1_floor"]
        floors[name] = entry
        with open(bench_suite.FLOOR_FILE, "w") as f:
            json.dump(floors, f, indent=1)
        print(json.dumps({
            "config": name, "floor_wall": entry["rate"],
            "floor_device": entry.get("rate_device"),
            "device_spread": entry.get("device_spread"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
