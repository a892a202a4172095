"""In-process distributed job harness.

Counterpart of the reference's ``tests/test_utils.py:271-426``
(``distributed_train_and_evaluate``): assemble a real TaskDispatcher +
EvaluationService + MasterServicer, then drive one or more Workers against
it — either with direct in-process calls or over a real localhost gRPC
server — and assert the job drains. This is how every elastic/distributed
path stays testable without a cluster (SURVEY.md §4 lesson).
"""

import threading
from typing import Dict, List, Optional

from elasticdl_tpu.comm.rpc import RpcServer
from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.core.step import runner_for_spec
from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.servicer import SERVICE_NAME, MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.testing.in_process_master import InProcessMaster
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker


class MiniCluster:
    """A master + N workers in one process."""

    def __init__(
        self,
        model_zoo: str,
        model_def: str,
        training_data: str = "",
        validation_data: str = "",
        prediction_data: str = "",
        num_workers: int = 1,
        minibatch_size: int = 16,
        num_minibatches_per_task: int = 2,
        num_epochs: int = 1,
        eval_steps: int = 0,
        use_rpc: bool = False,
        step_runner_factory=None,
        worker_callbacks: Optional[Dict[str, callable]] = None,
        shuffle: bool = False,
        checkpoint_dir: str = "",
        checkpoint_steps: int = 0,
        checkpoint_dir_for_init: str = "",
        mesh=None,
        fuse_task_steps: bool = False,
        metrics_port: Optional[int] = None,
        metrics_report_secs: float = 0.0,
        metrics_ttl_secs: float = 600.0,
        fault_injector=None,
        checkpoint_async: bool = True,
        checkpoint_delta_chain: int = 0,
        journal_dir: str = "",
        host_prefetch_depth: int = 2,
        version_report_steps: int = 1,
    ):
        # Chaos plane (chaos/interceptors.FaultInjector): over RPC the
        # injector's process-global hooks cover every call already; on
        # the direct-call path its per-RPC callbacks are merged into
        # worker_callbacks below so both transports inject the same
        # plan. checkpoint_async=False forces synchronous checkpoint
        # writes — chaos replay needs corrupt-at-save events ordered
        # deterministically against worker progress.
        self.fault_injector = fault_injector
        if fault_injector is not None and not use_rpc:
            chaos_cbs = fault_injector.in_process_callbacks()
            merged = dict(chaos_cbs)
            for name, cb in (worker_callbacks or {}).items():
                if name in merged:
                    chaos_cb = merged[name]

                    def both(request, _user=cb, _chaos=chaos_cb):
                        _chaos(request)
                        _user(request)

                    merged[name] = both
                else:
                    merged[name] = cb
            worker_callbacks = merged
        self.spec = get_model_spec(model_zoo, model_def)
        if mesh is not None:
            # Same wiring as worker/main.py MESH strategy: mesh-aware
            # model + spec-driven param/batch layout.
            from elasticdl_tpu.parallel.mesh_runner import (
                make_runner_for_spec,
            )

            self.spec.model = self.spec.make_model(mesh)
            if step_runner_factory is None:
                step_runner_factory = lambda: make_runner_for_spec(  # noqa: E731
                    self.spec, mesh
                )
        reader_of = lambda origin: create_data_reader(
            data_origin=origin, custom_reader=self.spec.custom_data_reader
        )
        self.train_reader = (
            reader_of(training_data) if training_data else None
        )
        self.eval_reader = (
            reader_of(validation_data) if validation_data else None
        )
        self.predict_reader = (
            reader_of(prediction_data) if prediction_data else None
        )
        # Kept for restart_master: a recovered dispatcher must be born
        # from the IDENTICAL config (shards, sizing, seed) before the
        # journal replays events into it.
        self._dispatcher_config = dict(
            training_shards=(
                self.train_reader.create_shards()
                if self.train_reader else {}
            ),
            evaluation_shards=(
                self.eval_reader.create_shards()
                if self.eval_reader else {}
            ),
            prediction_shards=(
                self.predict_reader.create_shards()
                if self.predict_reader else {}
            ),
            records_per_task=minibatch_size * num_minibatches_per_task,
            num_epochs=num_epochs,
            shuffle=shuffle,
        )
        self._eval_config = dict(
            eval_steps=eval_steps,
            eval_only=bool(validation_data and not training_data),
        )
        self.dispatcher = TaskDispatcher(**self._dispatcher_config)
        # Master write-ahead journal (master/journal.py): dispatch /
        # report events write through; restart_master() below replays
        # them into a recovered master (the chaos master-kill seam).
        self.journal_dir = journal_dir
        self._journal = None
        if journal_dir:
            from elasticdl_tpu.master.journal import MasterJournal

            self._journal = MasterJournal(journal_dir)
            self._journal.open_generation()
            self.dispatcher.attach_journal(self._journal)
        metrics_fns = (
            self.spec.eval_metrics_fn() if self.spec.eval_metrics_fn else {}
        )
        self.eval_service = EvaluationService(
            self.dispatcher, metrics_fns, **self._eval_config
        )
        if self._journal is not None:
            # Eval rounds are event-sourced onto the same journal
            # (open/fold/task_done/close records) so restart_master
            # recovers an open round intact.
            self.eval_service.attach_journal(self._journal)
        # Telemetry: in-process tests share ONE process registry across
        # master and workers (production is one worker per process);
        # per-worker keying comes from each client's worker_id at report
        # time. metrics_report_secs=0 → workers attach a snapshot to
        # every report so short jobs still populate the cluster view.
        from elasticdl_tpu.observability import MetricsPlane

        self.metrics_plane = MetricsPlane(ttl_secs=metrics_ttl_secs)
        self.servicer = MasterServicer(
            self.dispatcher, self.eval_service,
            metrics_plane=self.metrics_plane,
            journal=self._journal,
            generation=(
                self._journal.generation if self._journal else 0
            ),
        )
        self.metrics_http = (
            self.metrics_plane.serve(port=metrics_port)
            if metrics_port is not None else None
        )

        self._server = None
        self._use_rpc = use_rpc
        # Every InProcessMaster handed out (constructor workers AND
        # chaos replacement workers) registers here so restart_master
        # can rebind them all to a recovered servicer — a client bound
        # to the discarded one would keep mutating dead state.
        self._inprocess_clients: List[InProcessMaster] = []
        if use_rpc:
            self._server = RpcServer(
                "localhost:0", {SERVICE_NAME: self.servicer.handlers()}
            ).start()

        if step_runner_factory is None:
            # ONE runner shared by every worker: host-tier workers must
            # all train the same row stores (the PS-sharing shape; a
            # per-worker factory would silently fork the tables), and
            # the other runners hold no state of a worker's.
            shared_runner = runner_for_spec(self.spec)
            step_runner_factory = lambda: shared_runner  # noqa: E731
        task_reader = (
            self.train_reader or self.eval_reader or self.predict_reader
        )
        self.workers: List[Worker] = []
        hook = None
        for wid in range(num_workers):
            if use_rpc:
                client = MasterClient(
                    f"localhost:{self._server.port}", worker_id=wid,
                    connect_timeout=10, retries=1,
                )
            else:
                client = self.make_inprocess_client(
                    wid, callbacks=worker_callbacks
                )
            runner = step_runner_factory()
            if wid == 0 and checkpoint_dir:
                from elasticdl_tpu.checkpoint import CheckpointHook

                # Built once worker 0's runner exists so host-tier
                # tables (HostStepRunner) checkpoint alongside the state.
                hook = CheckpointHook(
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_steps=checkpoint_steps,
                    host_tables=runner.host_tables,
                    async_save=checkpoint_async,
                    delta_chain_max=checkpoint_delta_chain,
                )
            self.workers.append(
                Worker(
                    worker_id=wid,
                    master_client=client,
                    model_spec=self.spec,
                    data_reader=task_reader,
                    minibatch_size=minibatch_size,
                    step_runner=runner,
                    prediction_outputs_processor=(
                        self.spec.prediction_outputs_processor
                    ),
                    callbacks=(
                        self.spec.callbacks_fn()
                        if self.spec.callbacks_fn else []
                    ),
                    # One writer: worker 0 (state is shared/replicated).
                    checkpoint_hook=hook if wid == 0 else None,
                    checkpoint_dir_for_init=checkpoint_dir_for_init,
                    fuse_task_steps=fuse_task_steps,
                    metrics_report_secs=metrics_report_secs,
                    host_prefetch_depth=host_prefetch_depth,
                    # SSP mapping (--get_model_steps): the master
                    # observes every N-th version only.
                    version_report_steps=version_report_steps,
                )
            )

    def make_inprocess_client(self, worker_id: int,
                              callbacks=None) -> InProcessMaster:
        """An InProcessMaster bound to the CURRENT servicer and
        registered for restart_master rebinding. Replacement workers
        (chaos relaunch) must use this instead of constructing one
        directly, or a later master restart leaves them calling the
        discarded servicer."""
        client = InProcessMaster(
            self.servicer, worker_id=worker_id, callbacks=callbacks
        )
        self._inprocess_clients.append(client)
        return client

    def restart_master(self):
        """Simulated master crash + journal-replay recovery (the chaos
        ``master_kill`` seam; requires ``journal_dir``).

        The old dispatcher/servicer are DISCARDED exactly as a dead
        process would lose them — recovery may only use what the
        journal holds. A fresh dispatcher is built from the identical
        config, ``recover_master_state`` replays snapshot + tail into
        it (the same code path ``master/main.py`` runs on a real
        restart), and the transport re-points: the gRPC server rebinds
        the same port (the workers' channels reconnect, as they would
        to a relaunched master pod behind a stable Service), while
        in-process clients are rebound explicitly. Returns the replay
        stats dict."""
        from elasticdl_tpu.master.journal import recover_master_state

        if self._journal is None:
            raise RuntimeError(
                "restart_master needs MiniCluster(journal_dir=...)"
            )
        port = self._server.port if self._server is not None else None
        if self._server is not None:
            self._server.stop(0)
            self._server = None
        self._journal.close()
        dispatcher = TaskDispatcher(**self._dispatcher_config)
        metrics_fns = (
            self.spec.eval_metrics_fn()
            if self.spec.eval_metrics_fn else {}
        )
        eval_service = EvaluationService(
            dispatcher, metrics_fns, **self._eval_config
        )
        servicer = MasterServicer(
            dispatcher, eval_service,
            metrics_plane=self.metrics_plane,
            journal=self._journal,
        )
        stats = recover_master_state(
            self._journal, dispatcher, servicer=servicer,
            eval_service=eval_service,
        )
        self.dispatcher = dispatcher
        self.eval_service = eval_service
        self.servicer = servicer
        if self._use_rpc:
            self._server = RpcServer(
                f"localhost:{port}",
                {SERVICE_NAME: self.servicer.handlers()},
            ).start()
        else:
            for client in self._inprocess_clients:
                client.rebind(self.servicer)
        return stats

    def begin_resize(self, mesh, direction: str = "resize") -> int:
        """Open a live-resize barrier offering ``mesh`` to every
        worker (master/servicer.py; applied checkpointlessly via
        parallel/reshard.py at each worker's next task boundary)."""
        from elasticdl_tpu.parallel import reshard

        return self.servicer.begin_resize(
            reshard.mesh_spec(mesh), direction=direction
        )

    def run(self) -> List[dict]:
        """Run all workers (threads if >1) to completion."""
        results = [None] * len(self.workers)
        if len(self.workers) == 1:
            results[0] = self.workers[0].run()
        else:
            threads = []
            for i, worker in enumerate(self.workers):
                def _run(i=i, worker=worker):
                    results[i] = worker.run()
                t = threading.Thread(target=_run, daemon=True)
                threads.append(t)
                t.start()
            for t in threads:
                t.join(timeout=300)
        if self._server is not None:
            self._server.stop(0)
        return results

    def stop(self):
        """Release the metrics endpoint (its daemon thread and bound
        port outlive run() on purpose, so tests can scrape the final
        cluster state first)."""
        self.metrics_plane.stop()

    @property
    def finished(self) -> bool:
        return self.dispatcher.finished()
