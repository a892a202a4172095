"""Worker: the training engine.

Counterpart of the reference's ``worker/worker.py`` (1135 LoC) redesigned
TPU-first. The reference worker runs an eager GradientTape loop and ships
gradients to parameter servers over gRPC; this worker runs the whole
step — forward, backward, apply — as one jit-compiled XLA program on its
TPU slice, so there is no gradient RPC at all. What remains of the
reference's protocol:

- task pull loop against the master (get_task / report_task_result),
- version reporting (report_version) driving master-side eval triggers,
- eval tasks: forward pass + raw outputs/labels to the master,
- predict tasks: forward pass + user outputs processor,
- TRAIN_END_CALLBACK: run user callbacks,
- SSP ``get_model_steps`` (reference worker.py:297-305
  _update_local_model): under SPMD every step already applies to the
  one true state, so the knob maps onto ``version_report_steps`` —
  the master only observes (and eval-triggers on) every N-th version,
- no minibatch retry (the reference retried a rejected gradient,
  worker.py:49): the step donates its state, so a failed step cannot
  run again on it, and a compile or out-of-memory error fails the same
  way every time. A failed task is reported to the master (which
  re-queues it) and counted; a device error then ends the worker.

Under MeshStrategy the same code runs SPMD over the device mesh: batches
are globally sharded, the optimizer state is ZeRO-sharded (parallel/), and
collectives ride ICI inside the compiled step (see parallel/mesh_runner.py).
"""

import contextlib
import statistics
import time
import traceback
from collections import deque
from typing import Optional

import jax
import numpy as np

from elasticdl_tpu.common.constants import (
    Mode,
    TaskType,
)
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.core.step import StepRunner
from elasticdl_tpu.worker.task_data_service import TaskDataService

logger = get_logger("worker")

# The leaves of a training task's cycle, in order (docs/observability.md
# has where each is entered). Together they tile the cycle: what they do
# not cover is the slow-task line's ``other``.
CYCLE_LEAVES = (
    "get_task", "fetch", "stack", "dispatch", "device_wait",
    "report_version", "checkpoint", "task_log", "report_task",
)
# A training task is slow when its cycle takes over SLOW_TASK_FACTOR x
# the median of the last SLOW_TASK_WINDOW cycles.
SLOW_TASK_FACTOR = 1.5
SLOW_TASK_WINDOW = 32


class WorkerStopped(Exception):
    """Raised internally when a graceful stop (SIGTERM) was requested."""


class Worker:
    def __init__(
        self,
        worker_id: int,
        master_client,
        model_spec,
        data_reader,
        minibatch_size: int,
        step_runner=None,
        version_report_steps: int = 1,
        prediction_outputs_processor=None,
        callbacks=None,
        checkpoint_hook=None,
        checkpoint_dir_for_init: str = "",
        checkpoint_init_required: bool = True,
        profiler=None,
        fuse_task_steps: bool = False,
        prefetch_depth: int = 2,
        host_prefetch_depth: int = 2,
        metrics_registry=None,
        metrics_report_secs: float = 15.0,
        master_reattach_grace: float = 60.0,
    ):
        self._id = worker_id
        self._master = master_client
        self._spec = model_spec
        self._reader = data_reader
        self._minibatch_size = minibatch_size
        self._version_report_steps = version_report_steps
        self._processor = prediction_outputs_processor
        self._callbacks = callbacks or []
        # The runner builds the state and every program, and answers
        # what differs between one device, a mesh and the sparse tiers
        # (core/step.py::StepRunner: the one-device runner, and the seam).
        self._step_runner = step_runner or StepRunner()
        self.state = None
        self.last_batch = None
        self._train_step = None
        self._eval_step = None
        # Tracing (observability/tracing.py): spans into the process
        # flight recorder when one is installed; free otherwise
        # (Tracer.span is one module-global read). Recorded spans ride
        # the same piggybacked snapshots as metrics, incrementally via
        # the ring cursor.
        from elasticdl_tpu.observability import default_registry, tracing

        self._tracer = tracing.Tracer("worker", str(worker_id))
        self._metrics = metrics_registry or default_registry()
        # The phase seam: every region of the task cycle and of
        # start-up is entered once, through ``self._phases``, and lands
        # under one name in the phase histogram, in the flight recorder
        # and (while a --profile_dir window is open) on the device
        # trace. docs/observability.md has the table.
        self._phases = tracing.Phases(
            self._metrics, self._tracer,
            declare=CYCLE_LEAVES + ("task", "device_step"),
        )
        # Ring cursor of the last spans CONFIRMED delivered to the
        # master, plus the cursor offered on the in-flight snapshot —
        # committed only when the carrying RPC succeeds, so a failed
        # report re-offers its spans on the next one (the collector
        # dedups by span id, so an ambiguous failure resends harmlessly).
        self._trace_cursor = 0
        self._trace_cursor_offered = 0
        # Same offered/committed discipline for continuous-profiling
        # windows (observability/profiler.py): the store dedups by
        # (seq, t0), so an ambiguous failure resends harmlessly.
        self._profile_cursor = 0
        self._profile_cursor_offered = 0
        self._task_data = TaskDataService(
            master_client, data_reader, model_spec.dataset_fn,
            minibatch_size, prefetch_depth=prefetch_depth,
            on_wait=self._wait_tick,
            # Keep an idle worker alive in the master's cluster metrics
            # view: snapshots ride get_task too, not just the report
            # RPCs (rate-limited inside _metrics_snapshot).
            metrics_fn=self._metrics_snapshot,
            on_metrics_delivered=self._metrics_delivered,
            phases=self._phases,
            master_reattach_grace=master_reattach_grace,
        )
        self.last_metrics = None
        # Periodic sharded checkpoint (reference PS saves inside
        # push_gradients every checkpoint_steps versions,
        # ps/servicer.py:242-257); the job runner passes a hook only to
        # one worker (host 0) — state is replicated/sharded on the mesh,
        # so one writer suffices.
        from elasticdl_tpu.checkpoint import CheckpointHook

        self._checkpoint = checkpoint_hook or CheckpointHook()
        self._checkpoint_dir_for_init = checkpoint_dir_for_init
        # jax.profiler step-window trace (utils/profiler.py); None = off.
        self._profiler = profiler
        # Fused task execution: scan all of a task's minibatches in one
        # XLA program (core/step.build_multi_step) — removes the per-step
        # host dispatch, the dominant cost for small models. Version
        # reporting/checkpointing then happen at task granularity.
        self._fuse_task_steps = fuse_task_steps
        self._multi_step = None
        # Host-tier row pull-ahead depth (--host_prefetch_depth): how
        # far iter_prepared runs ahead of the device step. Validated
        # >= 1 (0 would disable the pull-ahead the runner's pull_ahead
        # property promised).
        self._host_prefetch_depth = max(1, int(host_prefetch_depth))
        # Multi-host SPMD + dynamic sharding need a step-alignment
        # barrier: every process runs the SAME compiled program the same
        # number of times (collectives span processes), but each pulls
        # its own tasks from the master. Protocol (_await_turn): per
        # tick every process announces a step code (train / forward /
        # drained); the max wins, lower-priority processes participate
        # with a zero-mask dummy and retry. Covers train, eval, and
        # predict tasks. Retries and task fusion are disabled under
        # sync (a failed collective step means restart-from-checkpoint,
        # and unequal fused lengths would desync the tick count).
        self._multihost_sync = False
        # Graceful preemption (k8s SIGTERM before the KILL): a stop
        # request checkpoints the freshest state and hands the current
        # task back before the pod dies (worker/main.py installs the
        # signal handler).
        self._stop_requested = False
        self._checkpoint_init_required = checkpoint_init_required
        # Telemetry (observability/): the step loop feeds the process
        # registry; snapshots piggyback on report_task_result /
        # report_version every metrics_report_secs (0 = every report,
        # for tests) so the master's cluster view stays fresh without a
        # dedicated RPC.
        # Reporting RPCs ride out master unavailability for the same
        # grace window the task stream uses (_master_call below): the
        # stub's own retry budget covers blips of a few seconds, but a
        # master restart (journal replay, pod reschedule) outlasts it,
        # and a crashed report would kill the worker exactly when its
        # lease is the thing the recovered master is waiting on.
        self._master_reattach_grace = max(
            float(master_reattach_grace), 0.1
        )
        self._metrics_report_secs = float(metrics_report_secs)
        self._last_metrics_report = 0.0
        self._m_step = self._metrics.histogram(
            "worker_step_seconds",
            "Device step: dispatch to the end of the first blocking "
            "readback (host-observed)", ["kind"],
        )
        # Saturation signal for the autoscaler (master/autoscaler.py):
        # device-step seconds / wall seconds over each report window.
        # A step's seconds are its ``device_step`` phase, which ends
        # when the device has answered, so ~1.0 = the device never
        # waits (scaling up helps); ~0 = the worker is starved or idle
        # (scaling down is safe).
        self._m_step_util = self._metrics.gauge(
            "worker_step_utilization",
            "Device-step seconds / wall seconds over the report window",
        )
        self._util_step_secs = 0.0
        self._util_window_t0 = time.monotonic()
        # Live-resize support (docs/elasticity.md): the master's resize
        # barrier piggybacks a directive on get_task; it is applied at
        # a TASK boundary (nothing half-consumed, no device buffers in
        # flight) and acked via report_resize. Idempotent by id — a
        # recovered master may re-offer the one we already applied.
        self._applied_resize_id = -1
        self._in_task = False
        self._resizing = False
        self._m_resize = self._metrics.histogram(
            "worker_resize_seconds",
            "Live reshard latency: gather + re-place + step rebuild",
        )
        self._m_examples = self._metrics.counter(
            "worker_examples_total",
            "Examples processed", ["task_type"],
        )
        self._m_h2d_bytes = self._metrics.counter(
            "worker_h2d_bytes_total",
            "Host batch bytes shipped to the device step",
        )
        self._m_compiles = self._metrics.counter(
            "worker_compiles_total",
            "Step-program builds (each first call triggers XLA compile)",
        )
        self._m_tasks = self._metrics.counter(
            "worker_tasks_total",
            "Tasks processed", ["type", "result"],
        )
        # Tasks that failed for a reason other than preemption: the
        # process exit code reports them (worker/main.py).
        self._failed_tasks = 0
        # Per-step losses of the training task in flight (device
        # scalars; read back once per task for the task log line).
        self._task_losses = []
        # What the model counted in those steps beside the loss
        # (core/step.py::_model_metrics), e.g. an expert layer's routed
        # rows: {name: device array} per program call, read back with
        # the losses.
        self._task_counters = []
        # The first call of the training program (load or compile, and
        # the first run) is a start-up phase around the first
        # ``device_step`` (_first_step).
        self._first_step_done = False
        # Slow-task line: the end of the last task cycle and the last
        # SLOW_TASK_WINDOW training cycles' seconds. A cycle runs from
        # the end of one task's report to the end of the next's.
        self._cycle_end = None
        self._cycle_secs = deque(maxlen=SLOW_TASK_WINDOW)

    # ---- state init ----------------------------------------------------

    def _maybe_init(self, batch):
        if self.state is not None:
            return
        with self._phases.startup("state_init"):
            self._init_state(batch)
            # The weights are made asynchronously: wait here, once, or
            # their seconds land in whatever blocks next.
            jax.block_until_ready(self.state)
        if self._checkpoint_dir_for_init:
            with self._phases.startup("restore"):
                self._restore_state()

    def _init_state(self, batch):
        self._m_compiles.inc()
        from elasticdl_tpu.callbacks import apply_callbacks_to_optimizer

        tx = apply_callbacks_to_optimizer(
            self._spec.make_optimizer(), self._callbacks
        )
        runner = self._step_runner
        self._multihost_sync = (
            jax.process_count() > 1 and runner.mesh is not None
        )
        if self._multihost_sync and self._fuse_task_steps:
            logger.warning(
                "fuse_task_steps disabled under multi-host sync "
                "(unequal task sizes would desync step counts)"
            )
            self._fuse_task_steps = False
        self.state = runner.init_state(self._spec.model, tx, batch)
        self._train_step = runner.train_step(self._spec.loss)
        self._eval_step = runner.eval_step()
        if self._fuse_task_steps and runner.accum_steps == 1:
            if runner.can_fuse:
                self._multi_step = runner.train_multi_step(self._spec.loss)
            else:
                # e.g. HostStepRunner: host-side work per batch can't
                # fuse into one XLA program; fall back to per-step.
                logger.warning(
                    "fuse_task_steps ignored: %s cannot fuse a task's "
                    "steps", type(runner).__name__,
                )

    def _restore_state(self):
        from elasticdl_tpu.checkpoint import restore_from_dir

        self.state = restore_from_dir(
            self.state, self._checkpoint_dir_for_init,
            required=self._checkpoint_init_required,
            host_tables=self._step_runner.host_tables,
        )
        # Restored leaves are host arrays; re-place them with the
        # runner's shardings or a mesh-sized table lands on one device.
        self.state = self._step_runner.place_state(self.state)
        # The restored version is the save baseline — without this,
        # interval-crossing counts pre-restore steps and writes a
        # spurious checkpoint on the first post-restore step.
        self._checkpoint.note_version(int(self.state.step))

    # ---- telemetry ------------------------------------------------------

    def _observe_step(self, kind: str, seconds: float):
        """Step-latency histogram + the utilization accumulator the
        report-window gauge derives from."""
        self._m_step.labels(kind).observe(seconds)
        self._util_step_secs += seconds

    def _metrics_snapshot(self) -> Optional[dict]:
        """Registry snapshot for piggybacking, rate-limited to one per
        metrics_report_secs; None between reports. When a flight
        recorder is installed, the spans recorded since the last
        CONFIRMED delivery ride along under a ``spans`` key (the
        master's MetricsPlane pops them into its TraceCollector); the
        cursor commits in _metrics_delivered, so spans offered on an
        RPC that failed are re-offered on the next report instead of
        being lost with the outage they describe."""
        from elasticdl_tpu.observability import tracing

        now = time.monotonic()
        if now - self._last_metrics_report < self._metrics_report_secs:
            return None
        self._last_metrics_report = now
        # Step utilization over the window just closing: device-step
        # seconds since the last snapshot divided by the wall time the
        # window spanned (clamped — host-observed step time can exceed
        # a tiny window by scheduling noise). Sub-50ms windows (back-
        # to-back RPCs, e.g. report then finished-poll) don't close:
        # a degenerate window would zero the gauge the autoscaler
        # reads; keep accumulating and let it hold its last value.
        window = now - self._util_window_t0
        if window >= 0.05:
            self._m_step_util.set(
                min(1.0, self._util_step_secs / window)
            )
            self._util_step_secs = 0.0
            self._util_window_t0 = now
        snapshot = self._metrics.snapshot()
        spans, self._trace_cursor_offered = tracing.spans_since(
            self._trace_cursor
        )
        if spans:
            snapshot["spans"] = spans
        from elasticdl_tpu.observability import profiler

        windows, self._profile_cursor_offered = profiler.windows_since(
            self._profile_cursor
        )
        if windows:
            snapshot["profiles"] = windows
        return snapshot

    def _metrics_delivered(self):
        """The RPC carrying the last snapshot succeeded — its spans
        and profile windows reached the master; advance the cursors
        past them."""
        self._trace_cursor = self._trace_cursor_offered
        self._profile_cursor = self._profile_cursor_offered

    def _master_call(self, fn, description: str):
        """Run a master RPC, riding out transient unavailability up to
        the reattach grace — the reporting-side mirror of the task
        stream's get_task ride-out (task_data_service.py). The stub's
        bounded retry absorbs blips; this absorbs a master restart. A
        non-retryable code or an exhausted grace re-raises (the task
        loop's error handling takes over)."""
        from elasticdl_tpu.comm.rpc import (
            RETRYABLE_CODES,
            RpcError,
            decorrelated_jitter,
        )

        deadline = time.monotonic() + self._master_reattach_grace
        retry_delay = 0.0
        while True:
            try:
                return fn()
            except RpcError as exc:
                if (exc.code not in RETRYABLE_CODES
                        or time.monotonic() >= deadline):
                    raise
                logger.warning(
                    "%s failed (%s); retrying while the master "
                    "recovers", description, exc,
                )
                # Decorrelated jitter (comm/rpc.py): a failover fails
                # every worker's report at once; fixed intervals would
                # stampede the promoted standby in lockstep.
                retry_delay = decorrelated_jitter(
                    retry_delay, base=0.2, cap=2.0
                )
                # Retry budget (comm/overload.py): the ride-out must
                # SURVIVE the grace window, so a denied spend
                # stretches the wait (rate-capping the storm on the
                # recovering master) instead of abandoning.
                from elasticdl_tpu.comm import overload

                if overload.controls_enabled():
                    if not overload.retry_budget_for(
                        "Master:rideout"
                    ).try_spend():
                        retry_delay = max(retry_delay, 1.0)
                # _wait_tick, not sleep: multi-host workers must keep
                # participating in barrier ticks during the ride-out
                # or they strand peers mid-collective. (If a stop was
                # requested, WorkerStopped propagates and _run's
                # handler exits the task loop — a stopping worker
                # gives up reporting through an outage.)
                self._wait_tick(retry_delay)
                # Fresh channel per retry: a channel refused for a few
                # seconds can wedge; reconnecting is what actually
                # re-attaches to the relaunched master.
                reconnect = getattr(self._master, "reconnect", None)
                if reconnect is not None:
                    reconnect()

    def _report_task(self, task_id: int, err_reason: str = ""):
        """report_task_result with the metrics/span piggyback and the
        span-cursor delivery commit."""
        with self._phases.phase("report_task"):
            snap = self._metrics_snapshot()
            accepted = self._master_call(
                lambda: self._master.report_task_result(
                    task_id, err_reason=err_reason, metrics=snap
                ),
                f"report_task_result({task_id})",
            )
            if snap is not None:
                self._metrics_delivered()
        return accepted

    def _report_version(self, version: int):
        with self._phases.phase("report_version"):
            snap = self._metrics_snapshot()
            self._master_call(
                lambda: self._master.report_version(
                    version, metrics=snap
                ),
                f"report_version({version})",
            )
            if snap is not None:
                self._metrics_delivered()

    def _traced_batches(self, batches):
        """Yield from ``batches`` with each blocking ``next()`` under a
        ``fetch`` phase — the input wait of the step timeline (decode /
        prefetch / row pull-ahead latency the device sits idle for)."""
        it = iter(batches)
        sentinel = object()
        while True:
            with self._phases.phase("fetch"):
                batch = next(it, sentinel)
            if batch is sentinel:
                return
            yield batch

    @staticmethod
    def _batch_nbytes(batch) -> int:
        return sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves(batch)
        )

    def _batch_examples(self, batch) -> int:
        mask = batch.get("mask") if isinstance(batch, dict) else None
        if mask is not None:
            return int(np.sum(np.asarray(mask) > 0))
        return self._minibatch_size

    # ---- live resize (docs/elasticity.md) ------------------------------

    def _maybe_apply_resize(self):
        """Apply a pending resize directive, if any. Called only at
        safe points — between tasks and while WAITing — so no task is
        half-consumed and no prefetch/prepared iterator holds device
        buffers on the dying mesh. A partial gradient-accumulation
        window does not survive (same loss as the checkpoint-restart
        path this replaces)."""
        directive = getattr(self._master, "pending_resize", None)
        ack = getattr(self._master, "report_resize", None)
        if not directive or ack is None or self._resizing:
            return
        # Reentrancy guard: the ack rides _master_call, whose ride-out
        # ticks _wait_tick — which checks for pending resizes.
        self._resizing = True
        try:
            self._apply_resize(directive, ack)
        finally:
            self._resizing = False

    def _apply_resize(self, directive, ack):
        resize_id = int(directive.get("resize_id", -1))

        def send_ack(status):
            self._master_call(
                lambda: ack(resize_id, status),
                f"report_resize({resize_id})",
            )

        if resize_id == self._applied_resize_id:
            # Re-offered (a recovered master's acks are volatile) —
            # the local apply already happened; just re-join the
            # barrier.
            send_ack("applied")
            return
        runner = self._step_runner
        if not runner.can_resize or self._multihost_sync:
            # Nothing mesh-resident to reshard: plain-jit and host-tier
            # runners keep dense state on one device and sparse rows in
            # the row service; multi-host jobs resize by gang restart.
            # Join the barrier as a no-op so it cannot hang on us.
            self._applied_resize_id = resize_id
            send_ack("noop")
            return
        from elasticdl_tpu.parallel import reshard as reshard_lib

        t0 = time.monotonic()
        try:
            with self._tracer.span("resize", resize_id=resize_id):
                new_mesh = reshard_lib.mesh_from_spec(directive["spec"])
                # Mesh-aware model defs re-bake against the new mesh
                # (sharding constraints name its axes); params are
                # untouched, only apply_fn follows the rebuilt module.
                # Re-bind BEFORE resharding: the shardings pytree the
                # runner derives carries the state's static metadata,
                # and the state fed to the rebuilt step must match it.
                make_model = getattr(self._spec, "make_model", None)
                if make_model is not None:
                    self._spec.model = make_model(new_mesh)
                    if self.state is not None and hasattr(
                        self.state, "apply_fn"
                    ):
                        self.state = self.state.replace(
                            apply_fn=self._spec.model.apply
                        )
                state = runner.resize(new_mesh, self.state)
                if state is not None:
                    self.state = state
                    # Every compiled step baked the old shardings.
                    self._m_compiles.inc()
                    self._train_step = runner.train_step(self._spec.loss)
                    self._eval_step = runner.eval_step()
                    if self._multi_step is not None:
                        self._multi_step = runner.train_multi_step(
                            self._spec.loss
                        )
        except Exception as exc:
            # A failed apply must not wedge the fleet's barrier: ack
            # with status "failed" (the autoscaler sees it in the ack
            # statuses) and keep training on the old mesh.
            # _applied_resize_id is deliberately NOT recorded: if a
            # recovered master re-offers this directive, the worker
            # retries the apply (the failure may have been transient)
            # instead of short-circuiting with a false "applied".
            logger.error(
                "resize %d failed; staying on the current mesh: %s\n%s",
                resize_id, exc, traceback.format_exc(),
            )
            send_ack("failed")
            return
        elapsed = time.monotonic() - t0
        self._m_resize.observe(elapsed)
        self._applied_resize_id = resize_id
        logger.info(
            "live reshard %d applied in %.3fs (mesh %s, state %s)",
            resize_id, elapsed, directive["spec"],
            "resharded" if self.state is not None else "pre-init",
        )
        send_ack("applied")

    # ---- task processing ----------------------------------------------

    def _wait_tick(self, wait_secs: float = 2.0):
        """While WAITing for tasks (queue empty, job unfinished): keep
        participating in barrier ticks as IDLE — a process that just
        sleeps would strand its peers mid-collective. The blocking
        exchange paces us to the peers' tick rate; we keep ticking for
        a polling interval before returning to get_task, so an idle
        worker doesn't hammer the master once per peer step."""
        import time as _time

        if self._stop_requested:
            # Idle worker: nothing to hand back; exit the task loop
            # (the post-loop path checkpoints whatever was trained).
            raise WorkerStopped()
        self._cycle_end = None  # a cycle that waited is not a slow task
        if not self._in_task and not self._resizing:
            # An idle worker must still join a resize barrier (WAIT
            # responses carry the directive); mid-task ticks (report
            # ride-out during processing) skip — resize only lands at
            # task boundaries.
            self._maybe_apply_resize()
        if (
            self._multihost_sync
            and self.state is not None
            and self.last_batch is not None
        ):
            from elasticdl_tpu.parallel import multihost

            deadline = _time.monotonic() + min(wait_secs, 0.5)
            while True:
                won = multihost.exchange_code(
                    self._step_runner.mesh, multihost.STEP_IDLE
                )
                if won > multihost.STEP_IDLE:
                    self._feed_dummy(won)
                    if _time.monotonic() < deadline:
                        continue  # keep ticking before re-polling
                    return
                _time.sleep(0.05)
                return
        _time.sleep(wait_secs)

    def _await_turn(self, code):
        """Barrier protocol: announce the program we want; while a
        higher-priority program wins the tick, participate in it with a
        zero-mask dummy, then retry. Returns when it's our turn."""
        from elasticdl_tpu.parallel import multihost

        mesh = self._step_runner.mesh
        while True:
            won = multihost.exchange_code(mesh, code)
            if won == code:
                return
            self._feed_dummy(won)

    def _feed_dummy(self, code):
        """Participate in another process's step with zero loss weight."""
        from elasticdl_tpu.parallel import multihost

        dummy = multihost.zero_mask_like(self.last_batch)
        if code == multihost.STEP_TRAIN:
            self.state, _ = self._train_step(self.state, dummy)
            # Checkpoint participation: orbax multi-host saves are
            # coordinated writes — every process must call save at the
            # same (globally consistent) versions, including ticks where
            # this process only fed a dummy.
            self._checkpoint.maybe_save(self.state)
        elif code == multihost.STEP_FORWARD:
            self._eval_step(self.state, dummy)

    def _process_train_batch(self, batch):
        if self._multihost_sync:
            # One barrier exchange per step; a failed collective step is
            # fatal (restart-from-checkpoint).
            from elasticdl_tpu.parallel import multihost

            self._await_turn(multihost.STEP_TRAIN)
        self.state, metrics = self._train_step(self.state, batch)
        self.last_metrics = metrics
        self._note_step_metrics(metrics)

    def request_stop(self):
        """Ask the worker to stop at the next TASK boundary, saving a
        checkpoint first (SIGTERM grace-period path). Task granularity
        keeps the exactly-once invariant: a handed-back task has
        consumed none of its records, so nothing trains twice — the
        checkpoint reflects completed tasks only. (A task outlasting
        the grace period falls back to the ordinary pod-death path.)"""
        self._stop_requested = True

    def _note_step_metrics(self, metrics):
        """Keep a program call's losses and counters on the device
        until the task's one readback (_log_trained_task)."""
        self._task_losses.append(metrics["loss"])
        counters = {k: v for k, v in metrics.items() if k != "loss"}
        if counters:
            self._task_counters.append(counters)

    def _process_train_task(self, task, batches) -> int:
        self._task_losses = []
        self._task_counters = []
        if self._fuse_task_steps:
            # The whole input wait of a fused task: the program needs
            # every minibatch before it can be dispatched.
            with self._phases.phase("fetch") as fetch:
                batch_list = list(batches)
                nbytes = sum(self._batch_nbytes(b) for b in batch_list)
                fetch.set(batches=len(batch_list), bytes=nbytes)
            if not batch_list:
                return 0
            self._maybe_init(batch_list[0])
            if self._multi_step is not None and len(batch_list) > 1:
                return self._process_train_task_fused(batch_list, nbytes)
            batches = iter(batch_list)
        # Host-tier runners: pull rows for upcoming minibatches on a
        # prefetch thread while the current one trains (the reference's
        # Go PS served pulls concurrently by design). Init needs a raw
        # first batch, so peek it before wrapping. Multi-host sync keeps
        # raw batches (dummy participation uses them directly).
        batches = iter(batches)
        prepared_iter = None
        if self._step_runner.pull_ahead and not self._multihost_sync:
            first = next(batches, None)
            if first is None:
                return 0
            self._maybe_init(first)
            import itertools

            from elasticdl_tpu.embedding.host_engine import PreparedBatch

            prepared_iter = self._step_runner.iter_prepared(
                itertools.chain([first], batches),
                depth=self._host_prefetch_depth,
            )
            batches = prepared_iter
        else:
            PreparedBatch = ()  # isinstance() no-match sentinel
        count = 0
        try:
            for batch in self._traced_batches(batches):
                raw = (
                    batch.raw if isinstance(batch, PreparedBatch)
                    else batch
                )
                self._maybe_init(raw)
                self.last_batch = raw
                if self._profiler is not None:
                    # Pre-step so the window [start, start+num) captures
                    # the steps it names.
                    self._profiler.observe_step(int(self.state.step))
                    self._profiler.note_program(
                        self._train_step, self.state, batch
                    )
                with self._first_step(), self._phases.phase(
                    "device_step", kind="train"
                ) as step:
                    with self._phases.phase("dispatch"):
                        self._process_train_batch(batch)
                    # The step's counters, while the device works.
                    self._m_examples.labels(task.type).inc(
                        self._batch_examples(raw)
                    )
                    self._m_h2d_bytes.inc(self._batch_nbytes(raw))
                    # The loop blocks here until the device has run the
                    # step; inside the phase, so ``device_step`` is the
                    # device's time and not the enqueue's.
                    with self._phases.phase("device_wait"):
                        version = int(self.state.step)
                self._observe_step("train", step.dur)
                count += 1
                if version % self._version_report_steps == 0:
                    self._report_version(version)
                with self._phases.phase("checkpoint"):
                    self._checkpoint.maybe_save(self.state)
        finally:
            if prepared_iter is not None:
                prepared_iter.close()
            # Drain the runner's async row applier at task granularity:
            # a row-service push failure must fail THIS task (and a
            # task-complete report must cover its last step's pushes —
            # nothing may ride a daemon thread past process exit).
            import sys as _sys

            # Snapshot whether an exception is already propagating
            # BEFORE calling flush — inside an except block exc_info()
            # would report the flush's own error and the re-raise would
            # be unreachable, silently downgrading a lost-push failure
            # to a warning.
            unwinding = _sys.exc_info()[0] is not None
            try:
                self._step_runner.flush()
            except Exception:
                if not unwinding:
                    raise
                # Don't mask the in-flight exception with the flush's
                # own.
                logger.warning(
                    "row applier flush failed during task "
                    "unwind:\n%s", traceback.format_exc(),
                )
        return count

    def _process_train_task_fused(self, batch_list, nbytes: int) -> int:
        """One compiled scan over the task's minibatches; version
        reporting and checkpointing at task granularity."""
        from elasticdl_tpu.core.step import stack_batches

        self.last_batch = batch_list[-1]
        if self._profiler is not None:
            self._profiler.observe_step(int(self.state.step))
        with self._phases.phase("stack"):
            stacked = stack_batches(batch_list)
        if self._profiler is not None:
            self._profiler.note_program(
                self._multi_step, self.state, stacked
            )
        with self._first_step(), self._phases.phase(
            "device_step", kind="train_fused", batches=len(batch_list)
        ) as step:
            with self._phases.phase("dispatch"):
                self.state, metrics = self._multi_step(
                    self.state, stacked
                )
            # The task's bookkeeping, while the device works: the slice
            # of the last loss is three small programs queued behind the
            # task's, 2 ms of host time on a v5e that would lie in the
            # gap between two task programs if it came after the wait
            # (PERF.md, PR 24).
            self.last_metrics = {"loss": metrics["loss"][-1]}
            self._note_step_metrics(metrics)
            self._m_examples.labels(TaskType.TRAINING).inc(
                sum(self._batch_examples(b) for b in batch_list)
            )
            self._m_h2d_bytes.inc(nbytes)
            # As on the per-step path: the wait for the device lies
            # inside ``device_step``.
            with self._phases.phase("device_wait"):
                version = int(self.state.step)
        self._observe_step("train_fused", step.dur)
        # Same SSP gating as the per-step path, at task granularity:
        # report iff a version_report_steps boundary was crossed.
        prev = version - len(batch_list)
        if (
            version // self._version_report_steps
            > prev // self._version_report_steps
        ):
            self._report_version(version)
        with self._phases.phase("checkpoint"):
            self._checkpoint.maybe_save(self.state)
        return len(batch_list)

    def _first_step(self):
        """The ``first_program`` start-up phase around the process's
        first training ``device_step``; nothing after it."""
        if self._first_step_done:
            return contextlib.nullcontext()
        self._first_step_done = True
        return self._phases.startup("first_program")

    def _log_trained_task(self, task, trained: int):
        """Two lines per training task: the job's loss trajectory at
        task granularity, then every step's loss (one host readback
        per task for both). A checker outside the process replays the
        steps against the second line; the first is what
        ``benchmark/lib/procs.py`` parses, letter for letter. A model
        that counts (an expert layer's routed rows) gets a third line,
        ``Task N routing:``, with every step's counters, from the same
        readback."""
        if not self._task_losses:
            return
        with self._phases.phase("task_log"):
            task_losses, task_counters = jax.device_get(
                (self._task_losses, self._task_counters)
            )
            losses = np.concatenate([np.ravel(x) for x in task_losses])
            logger.info(
                "Task %d trained: batches=%d version=%d mean_loss=%.6f",
                task.task_id, trained, int(self.state.step),
                float(losses.mean()),
            )
            logger.info(
                "Task %d losses: [%s]", task.task_id,
                ", ".join(f"{float(x):.6f}" for x in losses),
            )
            if task_counters:
                self._log_task_counters(task, task_counters)

    def _log_task_counters(self, task, task_counters):
        steps = {
            name: np.concatenate(
                [np.ravel(c[name]) for c in task_counters]
            ).astype(np.int64)
            for name in task_counters[0]
        }
        logger.info(
            "Task %d routing: %s", task.task_id,
            " ".join(
                f"{name}=[{', '.join(str(int(x)) for x in values)}]"
                for name, values in sorted(steps.items())
            ),
        )
        # Every counter a model brings has a series on the page, by its
        # name alone: ``<name>_max`` a gauge, the largest step of the
        # last trained task; any other a counter ``<name>_total``, the
        # sum over steps (docs/observability.md says what each counts).
        for name, values in steps.items():
            if name.endswith("_max"):
                self._metrics.gauge(
                    f"worker_{name}",
                    f"Model counter {name}: its largest step in the "
                    "last trained task",
                ).set(int(values.max()))
            else:
                self._metrics.counter(
                    f"worker_{name}_total",
                    f"Model counter {name}, summed over steps",
                ).inc(int(values.sum()))

    def _end_cycle(self, task, trained_ok: bool):
        """A task's cycle has ended (its report is in): say why, if it
        was a slow one. One WARNING line for a training task whose cycle
        took over SLOW_TASK_FACTOR x the running median, with every leaf
        of the cycle and ``other``, what the leaves do not cover (a
        resize, the profiler's start and stop). A cycle that waited for
        work (``_wait_tick``) is not judged."""
        now = time.monotonic()
        phases, self._phases.durations = self._phases.durations, {}
        last_end, self._cycle_end = self._cycle_end, now
        if (task.type != TaskType.TRAINING or not trained_ok
                or last_end is None):
            return
        cycle = now - last_end
        if len(self._cycle_secs) >= 4:
            median = statistics.median(self._cycle_secs)
            if cycle > SLOW_TASK_FACTOR * median:
                leaves = {
                    name: round(phases.get(name, 0.0), 4)
                    for name in CYCLE_LEAVES
                }
                leaves["other"] = round(
                    max(0.0, cycle - sum(leaves.values())), 4
                )
                logger.warning(
                    "Task %d slow: cycle=%.3fs median=%.3fs phases=%s",
                    task.task_id, cycle, median, leaves,
                )
        self._cycle_secs.append(cycle)

    def _drain_multihost(self):
        """Drain barrier: keep participating in other processes' steps
        (train or forward) until every process reports drained, so no
        one is left blocking in a cross-host collective."""
        if not self._multihost_sync or self.state is None:
            return
        if self.last_batch is None:
            return
        import time as _time

        from elasticdl_tpu.parallel import multihost

        while True:
            won = multihost.exchange_code(
                self._step_runner.mesh, multihost.STEP_DONE
            )
            if won == multihost.STEP_DONE:
                return
            if won == multihost.STEP_IDLE:
                # A peer is idle but its master link still lives — keep
                # ticking (it may yet pick up a requeued task).
                _time.sleep(0.05)
                continue
            self._feed_dummy(won)

    def _local_rows(self, preds):
        """This process's rows of the (possibly multi-host global)
        prediction array."""
        if self._multihost_sync:
            from elasticdl_tpu.parallel import multihost

            return multihost.host_local_slice(preds)
        return np.asarray(preds)

    def _forward_step(self, kind: str, batch):
        """One forward ``device_step``: this process's rows of the
        predictions, read back inside the phase (the readback is what
        waits for the device)."""
        with self._phases.phase("device_step", kind=kind) as step:
            with self._phases.phase("dispatch"):
                preds = self._eval_step(self.state, batch)
            with self._phases.phase("device_wait"):
                rows = self._local_rows(preds)
        self._observe_step(kind, step.dur)
        return rows

    def _process_eval_task(self, task, batches):
        outputs_acc, labels_acc = [], []
        for batch in batches:
            self._maybe_init(batch)
            self.last_batch = batch
            if self._multihost_sync:
                from elasticdl_tpu.parallel import multihost

                self._await_turn(multihost.STEP_FORWARD)
            rows = self._forward_step("eval", batch)
            real = int(np.sum(batch["mask"]))
            self._m_examples.labels(task.type).inc(real)
            self._m_h2d_bytes.inc(self._batch_nbytes(batch))
            outputs_acc.append(rows[:real])
            labels_acc.append(np.asarray(batch["labels"])[:real])
        if outputs_acc:
            outputs = np.concatenate(outputs_acc, axis=0)
            labels = np.concatenate(labels_acc, axis=0)
            self._master_call(
                # task_id keys the master-side dedup: the fold is an
                # accumulate, and this call retries through outages.
                lambda: self._master.report_evaluation_metrics(
                    outputs, labels, task_id=int(task.task_id)
                ),
                "report_evaluation_metrics",
            )

    def _process_predict_task(self, task, batches):
        for batch in batches:
            self._maybe_init(batch)
            self.last_batch = batch
            if self._multihost_sync:
                from elasticdl_tpu.parallel import multihost

                self._await_turn(multihost.STEP_FORWARD)
            rows = self._forward_step("predict", batch)
            real = int(np.sum(batch["mask"]))
            self._m_examples.labels(task.type).inc(real)
            self._m_h2d_bytes.inc(self._batch_nbytes(batch))
            if self._processor is not None:
                self._processor.process(rows[:real], self._id)

    def _run_train_end_callbacks(self):
        for cb in self._callbacks:
            on_end = getattr(cb, "on_train_end", None)
            if on_end is not None:
                on_end(self)

    # ---- main loop -----------------------------------------------------

    def run(self) -> dict:
        """The task pull loop (reference Worker.run → _train_and_evaluate)."""
        try:
            return self._run()
        finally:
            if self._profiler is not None:
                # Close a still-open trace even on preemption, or a later
                # start_trace in this process raises "already started".
                self._profiler.stop()
            try:
                # Land any in-flight async checkpoint write — a dying
                # worker's freshest checkpoint must hit disk before the
                # replacement looks for it.
                self._checkpoint.flush()
            except Exception as exc:
                logger.error("checkpoint flush on exit failed: %s", exc)

    def _run(self) -> dict:
        trained_batches = 0
        try:
            trained_batches = self._task_loop()
        except WorkerStopped:
            logger.info("stop requested while idle; exiting task loop")
        if not self._stop_requested:
            # A directive that arrived WITH the finished response would
            # otherwise never be acked (the task loop is over): apply
            # it now — the state sits at a boundary, and the final
            # checkpoint below then reflects the target mesh.
            self._maybe_apply_resize()
        # Multi-host: save_final is a coordinated write — EVERY process
        # must join whenever peers do (even one that trained 0 batches:
        # it stepped the shared state via dummy ticks). Only a stopping
        # worker skips (peers skip their drain-era saves symmetrically:
        # it's about to die and the gang restart resumes from the last
        # coordinated checkpoint).
        if (
            self.state is not None
            and (trained_batches or self._multihost_sync)
            and not (self._multihost_sync and self._stop_requested)
        ):
            self._checkpoint.save_final(self.state)
        return {
            "worker_id": self._id,
            "trained_batches": trained_batches,
            "failed_tasks": self._failed_tasks,
            "final_version": (
                int(self.state.step) if self.state is not None else 0
            ),
            "final_loss": (
                float(self.last_metrics["loss"])
                if self.last_metrics is not None else None
            ),
        }

    def _task_loop(self) -> int:
        trained_batches = 0
        for task, batches in self._task_data.task_stream():
            # Task boundary: the safe point to apply a pending resize
            # directive (the task just pulled has consumed nothing and
            # trains on the NEW mesh).
            self._maybe_apply_resize()
            if task.type == TaskType.TRAIN_END_CALLBACK:
                # Count the callback outcome once: a task whose report
                # RPC fails after the callback succeeded must not land
                # in both the ok and error series.
                callbacks_ok = False
                try:
                    self._run_train_end_callbacks()
                    callbacks_ok = True
                    self._m_tasks.labels(task.type, "ok").inc()
                    self._report_task(task.task_id)
                except Exception as exc:
                    if not callbacks_ok:
                        self._m_tasks.labels(task.type, "error").inc()
                        self._failed_tasks += 1
                    self._report_task(
                        task.task_id,
                        err_reason=f"callback: {type(exc).__name__}: {exc}",
                    )
                continue
            if self._stop_requested:
                # Graceful preemption, checked at the task boundary (the
                # pulled task has consumed nothing): checkpoint the
                # freshest state, hand the task back untouched (it
                # re-queues immediately, without burning its retry
                # budget), and exit.
                logger.info(
                    "stop requested: checkpointing at version %s and "
                    "returning task %d",
                    int(self.state.step) if self.state is not None
                    else "-", task.task_id,
                )
                try:
                    # Multi-host: a final save would block waiting for
                    # peers who aren't saving; the gang restart resumes
                    # from the last coordinated checkpoint instead.
                    if (
                        self.state is not None
                        and not self._multihost_sync
                    ):
                        self._checkpoint.save_final(self.state)
                except Exception as exc:
                    # A deferred write failure must not skip the task
                    # hand-back below (the master would wait on the
                    # pod-death timeout otherwise).
                    logger.error(
                        "final checkpoint on preemption failed: %s", exc
                    )
                self._m_tasks.labels(task.type, "preempted").inc()
                self._report_task(
                    task.task_id, err_reason="preempted (SIGTERM)"
                )
                break
            # Counts the processing outcome, not the report RPC's: a
            # task that trained fine but whose report raised stays an
            # "ok" task (the except below re-reports it, and without
            # the flag it would land in both series).
            processed_ok = False
            self._in_task = True
            try:
                if task.type == TaskType.TRAINING:
                    trained = self._process_train_task(task, batches)
                    trained_batches += trained
                    self._log_trained_task(task, trained)
                elif task.type == TaskType.EVALUATION:
                    self._process_eval_task(task, batches)
                elif task.type == TaskType.PREDICTION:
                    self._process_predict_task(task, batches)
                processed_ok = True
                self._in_task = False
                self._m_tasks.labels(task.type, "ok").inc()
                self._report_task(task.task_id)
            except Exception as exc:
                self._in_task = False
                if self._multihost_sync:
                    # A failed step after winning a barrier tick leaves
                    # peers inside a collective we never joined —
                    # report-and-continue would desync the tick count
                    # and hang the job. Die; recovery is a full restart
                    # from checkpoint (docs/designs/multihost.md).
                    logger.error(
                        "Fatal under multi-host sync — task %d: %s",
                        task.task_id, exc,
                    )
                    raise
                logger.error(
                    "Task %d failed: %s\n%s",
                    task.task_id, exc, traceback.format_exc(),
                )
                # type name prefix guarantees a non-empty reason (an empty
                # err_reason would read as success at the master).
                if not processed_ok:
                    self._m_tasks.labels(task.type, "error").inc()
                    self._failed_tasks += 1
                self._report_task(
                    task.task_id,
                    err_reason=f"{type(exc).__name__}: {exc}",
                )
                if isinstance(exc, jax.errors.JaxRuntimeError):
                    # A device or compiler error (a Mosaic refusal,
                    # RESOURCE_EXHAUSTED): the step donated the state
                    # it failed on, and the next task would fail the
                    # same way. The master has the task back; die.
                    raise
            self._end_cycle(task, processed_ok)
        if not self._stop_requested:
            # A stopping worker must not drain: the barrier drains only
            # when ALL processes are done, and peers aren't — its death
            # triggers the gang restart instead.
            self._drain_multihost()
        return trained_batches
