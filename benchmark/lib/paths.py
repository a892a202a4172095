"""Where things are. The checkout root is two levels above this file;
nothing here imports jax."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """The module in the file ``path``, whatever its name."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def base_of(config_file: str) -> str:
    """``<base>/configs/<name>.json`` -> ``<base>``: the directory whose
    ``models/`` and ``traffic/`` belong with that configuration."""
    return os.path.dirname(os.path.dirname(os.path.abspath(config_file)))


def reference_path(base: str, name: str) -> str:
    """``reference/<name>.py`` beside the configuration, else the
    benchmark's own (a test size borrows the real reference)."""
    own = os.path.join(base, "reference", f"{name}.py")
    return own if os.path.exists(own) else os.path.join(
        BENCH, "reference", f"{name}.py")


def config_path(name: str) -> str:
    return os.path.join(BENCH, "configs", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", f"{name}.py")
