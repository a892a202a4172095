"""A reading of the worker's own counters, taken from outside.

The harness kills the worker when the window has closed (no drain, no
final save of gigabytes), so the worker's closing line, the only place
the program prints its peak device memory, never comes. Where
``$BENCH_PROBE_FILE`` is set, importing a benchmark model module (which
only the worker does with that variable set) installs a SIGUSR1 handler
that appends one JSON line to that file: device memory statistics as JAX
reports them. The handler runs on the main thread between two
bytecodes, a millisecond or so, and only when the harness asks: at the
window's two ends. Nothing runs in between.
"""

import json
import os
import signal
import time

PROBE_ENV = "BENCH_PROBE_FILE"


def _reading() -> dict:
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "monotonic": time.monotonic(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "bytes_limit": [s.get("bytes_limit") for s in stats],
    }


def _on_signal(signum, frame):
    path = os.environ.get(PROBE_ENV)
    try:
        line = json.dumps(_reading(), default=str)
    except Exception as exc:  # a reading must never kill the worker
        line = json.dumps({"error": f"{type(exc).__name__}: {exc}"})
    with open(path, "a") as f:
        f.write(line + "\n")


def install_from_env():
    if os.environ.get(PROBE_ENV):
        signal.signal(signal.SIGUSR1, _on_signal)
