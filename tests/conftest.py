"""Test env: an 8-device virtual CPU mesh, set before JAX initializes.

Mesh/sharding logic must be testable without TPU hardware (SURVEY.md §7
"hard parts" (a)); bench.py and real runs use the TPU backend instead.
"""

import os

import pytest

# grpc's C-core INFO logs (GOAWAY notices on every server teardown)
# splice into pytest's dot-progress lines and corrupt the plain-text
# test output the CI lane parses; only errors are worth the noise.
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")

# The TPU kernel-correctness lane (`make test-tpu`, tests marked `tpu`)
# must run on the REAL chip — compiled, non-interpret. It names the
# platform, so that without a chip JAX refuses to start and the lane
# fails; it never runs on, or skips to, the CPU.
_TPU_LANE = os.environ.get("ELASTICDL_TPU_TESTS", "") == "1"

if _TPU_LANE:
    os.environ["JAX_PLATFORMS"] = "tpu"
else:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-process integration tests"
    )
    config.addinivalue_line(
        "markers", "tpu: requires the real TPU chip (compiled, "
        "non-interpret kernel correctness lane; run via make test-tpu)"
    )
    config.addinivalue_line(
        "markers", "k8s: live-cluster integration lane, gated on "
        "ELASTICDL_K8S_TESTS=1 + a reachable cluster (make test-k8s)"
    )
    config.addinivalue_line(
        "markers", "perf: wall-clock overhead pins (sampler pass "
        "cost, null-span cost) that flake under CI box noise; "
        "excluded from the CI fast lane, still in make test-all"
    )


# Test tiering (VERDICT round 1 #10): `make test` runs the fast lane
# (<4 min); `make test-all` runs everything. Modules/tests listed here
# are auto-marked slow — measured >8s each on the CI box; the breadth
# they add (zoo e2e, multi-process jobs, bench smoke, heavy numerics)
# belongs in the full lane, not the edit-compile-test loop.
_SLOW_MODULES = {
    "test_example_zoo",
    "test_multihost_job",
    "test_multihost_2proc",
    "test_bench_suite",
    "test_elastic_mesh_resize",
    "test_pipeline_lm",
}
_SLOW_TESTS = {
    "test_fused_mesh_runner_matches_stepwise",
    "test_remat_matches_plain",
    "test_moe_top2_routing",
    "test_training_learns_on_dp_sp_tp",
    "test_mesh_training_matches_single_device",
    "test_moe_expert_parallel",
    "test_mesh_wiring_end_to_end",
    "test_sharded_roundtrip",
    "test_local_mnist_trains_and_loss_decreases",
    "test_remat_transformer_with_dropout",
    "test_incremental_decode_matches_full_forward",
    "test_trained_model_generates_learned_chain",
    "test_pallas_ring_matches_dense",
    "test_ring_gradients_match_dense",
    "test_single_worker_job_drains_and_learns",
    "test_two_workers_share_the_queue",
    "test_job_over_real_grpc",
    "test_graceful_sigterm_checkpoints_and_returns_task",
    "test_worker_death_checkpoint_resume",
    "test_mesh_matches_local_trajectory",
    "test_accum_steps_applies_every_n",
    "test_mesh_worker_in_cluster",
    "test_pipeline_gradients_match_sequential",
    "test_checkpoint_and_resume",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = getattr(item.module, "__name__", "")
        base = item.name.split("[")[0]
        if mod in _SLOW_MODULES or base in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        if not _TPU_LANE and item.get_closest_marker("tpu"):
            item.add_marker(pytest.mark.skip(
                reason="TPU lane: set ELASTICDL_TPU_TESTS=1 "
                       "(make test-tpu) to run on the real chip"
            ))


@pytest.fixture
def kernels_traced(monkeypatch):
    """Both model families take their TPU branch and the attention
    kernels are interpreted: for tracing here (jaxprs, saved residuals),
    where nothing of it is run."""
    import functools

    import jax

    from elasticdl_tpu.models import mla_moe, transformer
    from elasticdl_tpu.ops.flash_attention import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    interpreted = functools.partial(flash_attention, interpret=True)
    monkeypatch.setattr(mla_moe, "flash_attention", interpreted)
    monkeypatch.setattr(transformer, "flash_attention", interpreted)
