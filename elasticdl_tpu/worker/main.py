"""Worker process entry point (reference worker/main.py:8-59).

``python -m elasticdl_tpu.worker.main --worker_id N --master_addr H:P
<flags>``: connect the master channel with retries, build the Worker (with
a MeshRunner when --distribution_strategy=MeshStrategy), pull tasks until
the job drains. A relaunched worker (elastic recovery) lands here too —
it restores from the latest sharded checkpoint via
``--checkpoint_dir_for_init`` handed down by the master.
"""

import json
import sys

from elasticdl_tpu.common.args import parse_worker_args
from elasticdl_tpu.common.constants import DistributionStrategy
from elasticdl_tpu.common.jax_env import enable_compile_cache
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.core.step import runner_for_spec
from elasticdl_tpu.utils.profiler import from_args as profiler_from_args
from elasticdl_tpu.data.factory import (
    create_data_reader,
    parse_data_reader_params,
)
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker

logger = get_logger("worker_main")


def build_worker(args, master_client=None) -> Worker:
    """Assemble a Worker from parsed args (shared with tests)."""
    # Multi-host: wire jax.distributed BEFORE anything can touch the JAX
    # backend — including the user's model-zoo module imported below,
    # which may build arrays at import time. The process id must be
    # stable across elastic relaunches (--jax_process_id; membership
    # changes restart the whole multi-host job from checkpoint).
    num_procs = getattr(args, "num_jax_processes", 1)
    if num_procs > 1:
        from elasticdl_tpu.parallel import multihost

        process_id = getattr(args, "jax_process_id", -1)
        if process_id < 0:
            process_id = args.worker_id
        if process_id >= num_procs:
            raise ValueError(
                f"jax process id {process_id} out of range for "
                f"{num_procs} processes — elastic relaunches of a "
                "multi-host job must reuse the dead worker's process "
                "id (pass --jax_process_id)"
            )
        multihost.initialize_multihost(
            multihost.coordinator_from_args(args), num_procs, process_id
        )
    spec = get_model_spec(
        model_zoo=args.model_zoo,
        model_def=args.model_def,
        dataset_fn=args.dataset_fn,
        loss=args.loss,
        optimizer=args.optimizer,
        eval_metrics_fn=args.eval_metrics_fn,
        callbacks=args.callbacks,
        custom_data_reader=args.custom_data_reader,
    )
    reader_params = parse_data_reader_params(
        getattr(args, "data_reader_params", "")
    )
    data_origin = (
        getattr(args, "training_data", "")
        or getattr(args, "validation_data", "")
        or getattr(args, "prediction_data", "")
    )
    reader = create_data_reader(
        data_origin=data_origin,
        custom_reader=spec.custom_data_reader,
        **reader_params,
    ) if data_origin else None
    stream_dir = getattr(args, "stream_dir", "")
    if stream_dir:
        # Streaming job (docs/online_learning.md): stream-tagged tasks
        # read the live tail; any batch reader built above becomes the
        # fallback for watermark-triggered eval tasks.
        from elasticdl_tpu.data.stream import StreamDataReader

        reader = StreamDataReader(
            stream_dir=stream_dir, fallback=reader
        )
    elif reader is None:
        # Preserve the historical default: an origin-less worker gets a
        # record-file reader that fails at first read, not at boot.
        reader = create_data_reader(
            data_origin="",
            custom_reader=spec.custom_data_reader,
            **reader_params,
        )
    step_runner = None
    if args.distribution_strategy == DistributionStrategy.MESH:
        from elasticdl_tpu.parallel.mesh import make_mesh, parse_mesh_args
        from elasticdl_tpu.parallel.mesh_runner import make_runner_for_spec

        shape, axes = parse_mesh_args(args.mesh_shape, args.mesh_axes)
        mesh = make_mesh(shape, axes)
        if spec.make_sparse_runner is not None:
            # Device-tier sparse plane over the mesh: TableSpec tables
            # (+slots) row-shard over the first mesh axis, the batch
            # shards over it too, dense params replicate — the
            # multi-chip form of the reference's N-parameter-server
            # sparse plane (docs/designs/parameter_server.md).
            import inspect

            params = inspect.signature(
                spec.make_sparse_runner
            ).parameters
            accepts_mesh = "mesh" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()
            )
            if not accepts_mesh:
                raise ValueError(
                    f"{args.model_def}: make_sparse_runner must accept "
                    "mesh=... to run under MeshStrategy"
                )
            # The dense mesh path maps --grads_to_wait onto gradient
            # accumulation and async onto staleness LR modulation;
            # the sparse step has no accumulation mode — fail loudly
            # rather than silently change effective batch semantics.
            if getattr(args, "grads_to_wait", 1) > 1 or (
                getattr(args, "use_async", False)
                and getattr(args, "lr_staleness_modulation", False)
            ):
                raise ValueError(
                    "device-tier sparse models do not support "
                    "--grads_to_wait > 1 or async staleness LR "
                    "modulation under MeshStrategy; the sparse step "
                    "applies each batch's row grads directly"
                )
            step_runner = spec.make_sparse_runner(
                mesh=mesh, axis=axes[0]
            )
        else:
            # Mesh-aware models (e.g. the transformer flagship) rebuild
            # with the mesh so ring attention / sharding constraints
            # activate; the zoo module's sharding rules drive param &
            # batch layout.
            spec.model = spec.make_model(mesh)
            step_runner = make_runner_for_spec(
                spec,
                mesh,
                # grads_to_wait maps onto gradient accumulation before
                # the sync apply (SURVEY.md §7.4); async staleness LR
                # modulation becomes per-microbatch 1/staleness
                # weighting.
                accum_steps=getattr(args, "grads_to_wait", 1),
                staleness_modulation=(
                    getattr(args, "use_async", False)
                    and getattr(args, "lr_staleness_modulation", False)
                ),
            )
    host_runner_args = {}
    if spec.make_host_runner is not None:
        # Host-tier model (>HBM tables, embedding/host_engine.py): the
        # zoo module supplies the runner holding its row stores.
        if step_runner is not None:
            raise ValueError(
                "host-tier models (make_host_runner) do not combine "
                "with MeshStrategy; use the default strategy"
            )
        row_addr = getattr(args, "row_service_addr", "")
        if row_addr:
            # Multi-process sharing: rows live behind the row service
            # (embedding/row_service.py), the Pserver sparse role.
            # Check the signature up front — catching TypeError around
            # the call would also swallow TypeErrors raised INSIDE the
            # factory and misreport genuine zoo bugs.
            import inspect

            params = inspect.signature(spec.make_host_runner).parameters
            accepts_remote = "remote_addr" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()
            )
            if not accepts_remote:
                raise ValueError(
                    f"{args.model_def}: make_host_runner must accept "
                    "remote_addr=... to run against --row_service_addr"
                )
            host_runner_args["remote_addr"] = row_addr
        elif getattr(args, "num_workers", 1) > 1:
            # Per-process tables would silently fork: each pod would
            # train (and lose) its own rows.
            raise ValueError(
                "host-tier models with num_workers > 1 need a shared "
                "row service: start embedding.row_service and pass "
                "--row_service_addr"
            )
    if step_runner is None:
        # No mesh: the spec's host-tier runner, its device-tier sparse
        # runner (tables in HBM next to the model), or one device.
        step_runner = runner_for_spec(spec, **host_runner_args)
    if master_client is None:
        master_client = MasterClient(
            args.master_addr, worker_id=args.worker_id
        )
    # Workload attribution (observability/principal.py): every RPC
    # this process makes — task pulls, row pulls/pushes, reports —
    # meters fleet-wide under this identity. The job name comes from
    # the launcher's env (k8s pod spec); unset folds to "unknown".
    import os as _os

    from elasticdl_tpu.observability import principal as _principal

    _principal.set_process_principal(
        job=_os.environ.get("ELASTICDL_JOB_NAME", ""),
        component="worker", purpose="training",
    )
    recorder_spans = int(getattr(args, "flight_recorder", 0) or 0)
    if recorder_spans > 0:
        # Tracing on: step-phase spans into the process ring; they
        # piggyback to the master on the same snapshot RPCs as metrics.
        from elasticdl_tpu.observability import tracing

        tracing.set_process_role("worker", str(args.worker_id))
        tracing.install_recorder(
            tracing.FlightRecorder(recorder_spans)
        )
    # Continuous profiling: windows piggyback to the master inside the
    # same metrics snapshots as spans (observability/profiler.py).
    from elasticdl_tpu.observability import profiler as _profiler

    _profiler.maybe_start_from_args(
        args, "worker", str(args.worker_id)
    )
    import jax as _jax

    checkpoint_hook = None
    # Single-host: one writer (worker 0) suffices — state is shared.
    # Multi-host: EVERY process must hold a hook; orbax saves are
    # coordinated writes all processes participate in (the worker calls
    # maybe_save on the same globally-consistent versions everywhere).
    mesh_multihost = (
        args.distribution_strategy == DistributionStrategy.MESH
        and _jax.process_count() > 1
    )
    needs_hook = getattr(args, "checkpoint_dir", "") and (
        args.worker_id == 0 or mesh_multihost
    )
    if needs_hook:
        from elasticdl_tpu.checkpoint import CheckpointHook

        checkpoint_hook = CheckpointHook(
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_steps=getattr(args, "checkpoint_steps", 0),
            num_shards=getattr(args, "checkpoint_shards", 1) or 1,
            keep_max=getattr(args, "keep_checkpoint_max", 3),
            # Mesh multi-host only: global arrays aren't addressable
            # from one process; orbax writes shards coordinately, and
            # the barrier aligns save versions. Non-mesh strategies keep
            # the native per-process saver.
            backend="orbax" if mesh_multihost else "native",
            host_tables=step_runner.host_tables,
            delta_chain_max=(
                0 if mesh_multihost
                else getattr(args, "checkpoint_delta_chain", 0)
            ),
        )
    from elasticdl_tpu.callbacks import (
        ensure_saved_model_exporter,
        set_callback_parameters,
    )

    callbacks = ensure_saved_model_exporter(
        spec.callbacks_fn() if spec.callbacks_fn else [],
        getattr(args, "output", ""),
    )
    set_callback_parameters(
        callbacks,
        batch_size=args.minibatch_size,
        epochs=getattr(args, "num_epochs", 1),
    )
    return Worker(
        worker_id=args.worker_id,
        master_client=master_client,
        model_spec=spec,
        data_reader=reader,
        minibatch_size=args.minibatch_size,
        step_runner=step_runner,
        # SSP mapping: the master observes every N-th version only.
        version_report_steps=getattr(args, "get_model_steps", 1),
        prediction_outputs_processor=spec.prediction_outputs_processor,
        callbacks=callbacks,
        checkpoint_hook=checkpoint_hook,
        profiler=profiler_from_args(args),
        fuse_task_steps=getattr(args, "fuse_task_steps", False),
        prefetch_depth=getattr(args, "prefetch_depth", 2),
        host_prefetch_depth=getattr(args, "host_prefetch_depth", 2),
        metrics_report_secs=getattr(args, "metrics_report_secs", 15.0),
        master_reattach_grace=getattr(
            args, "master_reattach_grace", 60.0
        ),
        **resolve_init_checkpoint(args),
    )


def resolve_init_checkpoint(args) -> dict:
    """Pick the restore source for a booting worker.

    Priority: the job's rolling --checkpoint_dir when it already holds a
    valid version (elastic relaunch mid-job resumes the latest state),
    else the user's --checkpoint_dir_for_init (warm start / transfer —
    restore REQUIRED: a bad dir must fail loudly, not train from
    scratch), else fresh init.
    """
    rolling = getattr(args, "checkpoint_dir", "")
    user_init = getattr(args, "checkpoint_dir_for_init", "")
    if rolling:
        # Backend-agnostic probe: a multi-host gang restart must find
        # the orbax versions its previous generation wrote.
        from elasticdl_tpu.checkpoint.hooks import has_valid_checkpoint

        if has_valid_checkpoint(rolling):
            return {
                "checkpoint_dir_for_init": rolling,
                "checkpoint_init_required": True,
            }
    return {
        "checkpoint_dir_for_init": user_init,
        "checkpoint_init_required": bool(user_init),
    }


def device_report() -> dict:
    """What this process ran on, as JAX reports it — the closing line
    carries it so a launcher can check the device without touching JAX
    itself (a second process cannot share the chip)."""
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        # None where the backend keeps no memory statistics (the CPU).
        "peak_bytes_in_use": (
            max(peaks) if all(p is not None for p in peaks) else None
        ),
        # Per local device: a mesh job piled on its first device shows
        # here.
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
    }


def main(argv=None):
    args = parse_worker_args(argv)
    # Start-up is phases too (docs/observability.md): each is entered
    # once and kept as edl_tpu_worker_startup_seconds{phase}. The
    # worker's own seam takes over from here for state_init, restore
    # and first_program.
    from elasticdl_tpu.observability import default_registry, tracing

    phases = tracing.Phases(
        default_registry(), tracing.Tracer("worker", str(args.worker_id))
    )
    with phases.startup("build_worker"):
        logger.info("XLA compilation cache at %s", enable_compile_cache())
        worker = build_worker(args)
    # The first device query: a worker whose platform is missing dies
    # here, before it can pull (and fail) a single task.
    with phases.startup("backend_up"):
        runs_on = device_report()
    logger.info("Worker %d runs on %s", args.worker_id, runs_on)
    # k8s sends SIGTERM ahead of the KILL: stop at the next batch
    # boundary, checkpoint the freshest state, hand the task back.
    import signal

    signal.signal(
        signal.SIGTERM, lambda signum, frame: worker.request_stop()
    )
    result = worker.run()
    result.update(device_report())
    logger.info("Worker %d done: %s", args.worker_id, json.dumps(result))
    # A task that failed for any reason but preemption is a failed
    # worker, whether or not the master found it another home.
    return 1 if result["failed_tasks"] else 0


if __name__ == "__main__":
    sys.exit(main())
