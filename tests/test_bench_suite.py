"""bench_suite configs stay runnable (CPU smoke, tiny shapes).

The suite itself measures on TPU; this guards against drift between the
batch synthesizers and the zoo model contracts (wrong feature shapes/dtypes
would otherwise only surface on a hardware run).
"""

import sys
import os

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench_suite  # noqa: E402
import benchlib  # noqa: E402


@pytest.fixture(autouse=True)
def tiny_configs(monkeypatch):
    tiny = {
        "mnist": ("mnist.mnist_functional.custom_model", 8, 2, 1),
        "cifar10": ("cifar10.cifar10_functional.custom_model", 8, 2, 1),
        "deepfm": ("deepfm.deepfm_functional.custom_model", 8, 2, 1),
        "census": ("census.census_wide_deep.custom_model", 8, 2, 1),
        "transformer": ("transformer.transformer_lm.custom_model", 2, 2, 1),
        "moe": ("transformer.transformer_lm.custom_model", 2, 2, 1),
    }
    monkeypatch.setattr(bench_suite, "CONFIGS", tiny)
    # The suite's cells name the zoo's width functions; the smoke runs
    # the zoo's toy custom_model (d128/L2, vocab 256) at sequence 16,
    # and "moe" swaps in a toy scatter-dispatch MoE of the same size.
    lm = bench_suite.lm_zoo()
    monkeypatch.setattr(lm, "SEQ_LEN", 16)
    monkeypatch.setattr(lm, "VOCAB", lm.CONFIG.vocab_size)
    real_config_spec = bench_suite.config_spec

    def tiny_config_spec(name):
        spec, *rest = real_config_spec(name)
        if name == "moe":
            import dataclasses

            spec.model = spec.module.custom_model(
                config=dataclasses.replace(
                    lm.CONFIG, moe_experts=4, moe_every=2,
                    moe_dispatch="scatter",
                )
            )
        return (spec, *rest)

    monkeypatch.setattr(bench_suite, "config_spec", tiny_config_spec)


def test_recsys_config_runs_tiny(monkeypatch):
    """The sparse recsys measure path (runner branch + dense control)
    stays runnable — tiny-vocab override so CPU smoke never allocates
    the 1M x 256 production table."""
    from elasticdl_tpu.testing.tiny_zoo import tiny_recsys_zoo

    monkeypatch.setitem(
        bench_suite.CONFIGS, "recsys",
        ("recsys.recsys_sparse.custom_model", 8, 2, 1),
    )
    with tiny_recsys_zoo(vocab=64, dim=8):
        result = bench_suite.run_config("recsys")
    assert np.isfinite(result["eps"]) and result["eps"] > 0
    # The paired dense-embedding control rode along.
    assert result["rate_dense"] > 0
    # The ratio exists iff BOTH runs produced a device rate (no device
    # lane on CPU; either trace parse can come up empty on TPU).
    assert "sparse_speedup_vs_dense" in result or \
        result["eps_device"] == 0 or result["rate_dense_device"] == 0


@pytest.mark.parametrize(
    "name", ["mnist", "cifar10", "deepfm", "census", "transformer",
             "moe"]
)
def test_config_runs(name):
    m = bench_suite.run_config(name)
    assert np.isfinite(m["eps"]) and m["eps"] > 0
    assert m["eps_median"] > 0 and m["wall_spread"] >= 0
    # A CPU trace carries no '/device:' lane, so there is no device
    # rate, and no utilization is computed from a wall clock.
    assert m["eps_device"] == 0
    assert "mfu" not in m and "hbm_frac" not in m


def test_module_device_times_parses_device_lane(tmp_path):
    """The device-time gate reads per-program durations off the 'XLA
    Modules' lane of the device process only — host lanes and other
    device threads (XLA Ops, transfers) must not contribute."""
    import gzip
    import json

    trace = {"traceEvents": [
        # metadata: device process 3 with Modules (tid 2) + Ops (tid 3),
        # host process 701.
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 701, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 701, "tid": 9, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        # events: two programs on the module lane (1.5ms + 2.5ms),
        # noise elsewhere.
        {"ph": "X", "pid": 3, "tid": 2, "dur": 1500,
         "name": "jit_multi_step(123)"},
        {"ph": "X", "pid": 3, "tid": 2, "dur": 2500,
         "name": "jit_multi_step(123)"},
        {"ph": "X", "pid": 3, "tid": 2, "dur": 9000,
         "name": "jit_other_program(9)"},
        {"ph": "X", "pid": 3, "tid": 3, "dur": 700, "name": "fusion"},
        {"ph": "X", "pid": 701, "tid": 9, "dur": 9999,
         "name": "host thing"},
    ]}
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump(trace, f)

    times = benchlib.module_device_times(str(tmp_path))
    assert times == [1.5, 2.5]
    # Unfiltered fallback when the name filter matches nothing.
    times = benchlib.module_device_times(str(tmp_path), "no_such_name")
    assert times == [1.5, 2.5, 9.0]
    # No trace at all -> empty (CPU backends without a device lane).
    assert benchlib.module_device_times(str(tmp_path / "empty")) == []


def test_merge_json_preserves_other_entries(tmp_path):
    path = str(tmp_path / "out.json")
    benchlib.merge_json(path, {"a": 1})
    data = benchlib.merge_json(path, {"b": 2})
    assert data == {"a": 1, "b": 2}


def test_bench_summary_built_from_this_runs_lines(monkeypatch, capsys):
    """bench.py's driver line must reflect THIS run's subprocess output,
    not the merged BENCH_SUITE.json (stale-data hazard)."""
    import json

    import bench

    class P:
        returncode = 0

        def __init__(self, out):
            self.stdout = out

    suite_out = "\n".join([
        "noise line",
        json.dumps({"metric": "mnist_train_examples_per_sec_per_chip"
                              "[tpu]", "value": 100.0,
                    "unit": "examples/sec/chip", "vs_baseline": 1.1,
                    "mfu": 0.09}),
        json.dumps({"metric": "transformer_train_tokens_per_sec_per_chip"
                              "[tpu]", "value": 200.0,
                    "unit": "tokens/sec/chip", "vs_baseline": 0.97,
                    "mfu": 0.23}),
    ])
    elastic_out = json.dumps({
        "metric": "elastic_recovery_seconds[tpu]", "value": 2.5,
        "unit": "seconds", "vs_baseline": 0.0,
    })
    outs = {"bench_suite.py": P(suite_out),
            "bench_elasticity.py": P(elastic_out)}
    monkeypatch.setattr(bench, "_run", lambda s, *a: outs[s])
    rc = bench.main()
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(last)
    assert rec["metric"] == "bench_suite_worst_vs_floor[tpu]"
    assert rec["value"] == 0.97  # the worst config gates
    assert rec["configs"]["mnist"]["mfu"] == 0.09
    assert rec["configs"]["transformer"]["rate"] == 200.0
    assert rec["elasticity"]["recovery_seconds"]["value"] == 2.5


def test_bench_timeout_still_prints_summary(monkeypatch, capsys):
    import json
    import subprocess

    import bench

    def boom(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw.get("timeout", 0))

    monkeypatch.setattr(subprocess, "run", boom)
    rc = bench.main()
    assert rc == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(last)  # the one-JSON-line contract holds
    assert rec["value"] == 0.0


def test_analytic_bytes_per_step_model():
    """The hbm_frac numerator is auditable: dense leaves cost
    5x params + 2x opt bytes; sparse tables cost (5 + 2*slots) rows of
    traffic per batch id and NOTHING for untouched rows."""
    import types

    import jax.numpy as jnp

    from benchlib import analytic_bytes_per_step
    from elasticdl_tpu.embedding.device_sparse import TableSpec

    params = {"w": np.zeros((10, 4), np.float32)}       # 160 B
    opt = {"m": np.zeros((10, 4), np.float32)}          # 160 B
    state = types.SimpleNamespace(params=params, opt_state=opt)
    dense = analytic_bytes_per_step(state, {"features": {}})
    assert dense == 5 * 160 + 2 * 160

    table = jnp.zeros((100, 8), jnp.float32)
    state = types.SimpleNamespace(
        params=params, opt_state=opt,
        tables={"t": table},
        slot_tables={"t": {"accumulator": table}},
    )
    spec = TableSpec(name="t", vocab=100, dim=8, feature_key="ids")
    batch = {"features": {"ids": np.zeros((4, 3), np.int32)}}
    got = analytic_bytes_per_step(state, batch, table_specs=(spec,))
    # 12 ids x 8 cols x 4 B = 384 B/row-pass; (5 + 2*1 slot) passes.
    assert got == dense + (5 + 2) * 12 * 8 * 4


def test_analytic_bytes_packed_layout():
    """A packed table (width > spec.dim, empty slot dict) switches to
    the 3*width + 2*dim per-id model."""
    import types

    import jax.numpy as jnp

    from benchlib import analytic_bytes_per_step
    from elasticdl_tpu.embedding.device_sparse import TableSpec

    params = {"w": np.zeros((10, 4), np.float32)}       # 160 B
    opt = {"m": np.zeros((10, 4), np.float32)}          # 160 B
    dense = 5 * 160 + 2 * 160
    state = types.SimpleNamespace(
        params=params, opt_state=opt,
        tables={"t": jnp.zeros((100, 16), jnp.float32)},  # packed 2x8
        slot_tables={"t": {}},
    )
    spec = TableSpec(name="t", vocab=100, dim=8, feature_key="ids")
    batch = {"features": {"ids": np.zeros((4, 3), np.int32)}}
    got = analytic_bytes_per_step(state, batch, table_specs=(spec,))
    assert got == dense + 12 * 4 * (3 * 16 + 2 * 8)
