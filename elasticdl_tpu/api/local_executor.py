"""LocalExecutor: in-process training without any RPC.

Counterpart of the reference's ``elasticdl/python/elasticdl/local_executor.py``
(:23-195) — `--distribution_strategy=Local` runs the whole job in one process:
read shards directly, run the jitted train step on the local device(s), and
evaluate periodically. Everything the distributed path uses (step fns, reader,
batcher, metrics) is exercised here first.
"""

import time
from typing import Optional

import jax
import numpy as np

from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.task import Task
from elasticdl_tpu.common.timing import Timing
from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.core.step import (
    concat_eval_accumulators,
    evaluate_metrics,
    runner_for_spec,
)
from elasticdl_tpu.data.batcher import batch_records
from elasticdl_tpu.checkpoint import CheckpointHook, restore_from_dir
from elasticdl_tpu.data.factory import (
    create_data_reader,
    parse_data_reader_params,
)


class LocalExecutor:
    def __init__(self, args):
        self._args = args
        self._logger = get_logger("local_executor", args.log_level)
        self._spec = get_model_spec(
            model_zoo=args.model_zoo,
            model_def=args.model_def,
            dataset_fn=args.dataset_fn,
            loss=args.loss,
            optimizer=args.optimizer,
            eval_metrics_fn=args.eval_metrics_fn,
            callbacks=args.callbacks,
            custom_data_reader=args.custom_data_reader,
        )
        reader_params = parse_data_reader_params(args.data_reader_params)
        self._train_reader = create_data_reader(
            data_origin=args.training_data,
            custom_reader=self._spec.custom_data_reader,
            **reader_params,
        )
        self._eval_reader = None
        if getattr(args, "validation_data", ""):
            self._eval_reader = create_data_reader(
                data_origin=args.validation_data,
                custom_reader=self._spec.custom_data_reader,
                **reader_params,
            )
        self._batch_size = args.minibatch_size
        self._epochs = args.num_epochs
        self._max_steps = getattr(args, "max_steps", 0)
        self._evaluation_steps = getattr(args, "evaluation_steps", 0)
        self._timing = Timing(args.log_level.upper() == "DEBUG", self._logger)
        self.state = None
        self.last_batch = None
        # The runner's steps are built at state init (a host-tier
        # runner's row-block template needs an example batch).
        self._step_runner = runner_for_spec(self._spec)
        self._train_step = None
        self._eval_step = None
        self.last_train_metrics = None
        # Checkpointing (reference save inside push_gradients every
        # checkpoint_steps versions, ps/servicer.py:242-257; restore-at-init
        # from --checkpoint_dir_for_init, ps/parameter_server.py:49-66).
        self._checkpoint = CheckpointHook(
            checkpoint_dir=getattr(args, "checkpoint_dir", ""),
            checkpoint_steps=getattr(args, "checkpoint_steps", 0),
            num_shards=getattr(args, "checkpoint_shards", 1) or 1,
            # 0 is a legal explicit value meaning "keep everything"
            # (CheckpointSaver.gc); only an absent flag falls back to 3.
            keep_max=getattr(args, "keep_checkpoint_max", 3),
            host_tables=self._step_runner.host_tables,
            delta_chain_max=getattr(args, "checkpoint_delta_chain", 0),
        )
        self._init_checkpoint_dir = getattr(
            args, "checkpoint_dir_for_init", ""
        )
        # Callbacks (reference callbacks.py + model_utils.py:44-63):
        # MaxStepsStopping becomes a dispatch bound, LearningRateScheduler
        # folds into the optax chain at state init, behavioral hooks run
        # at train end.
        from elasticdl_tpu.callbacks import (
            MaxStepsStopping,
            find_callback,
            set_callback_parameters,
        )

        from elasticdl_tpu.callbacks import ensure_saved_model_exporter

        self._callbacks = ensure_saved_model_exporter(
            self._spec.callbacks_fn() if self._spec.callbacks_fn else [],
            getattr(args, "output", ""),
        )
        set_callback_parameters(
            self._callbacks, batch_size=self._batch_size,
            epochs=self._epochs,
        )
        max_steps_cb = find_callback(self._callbacks, MaxStepsStopping)
        if max_steps_cb is not None and not self._max_steps:
            self._max_steps = max_steps_cb.max_steps
        self._tb_service = None
        if getattr(args, "tensorboard_log_dir", ""):
            from elasticdl_tpu.master.tensorboard_service import (
                TensorboardService,
            )

            self._tb_service = TensorboardService(args.tensorboard_log_dir)

    def _task_batches(self, reader, mode):
        gen = self._task_batches_raw(reader, mode)
        # Background decode of batch N+1 while the device runs step N
        # (same role as the worker path's data/prefetch.py wiring).
        depth = getattr(self._args, "prefetch_depth", 2)
        if depth > 0:
            from elasticdl_tpu.data.prefetch import prefetch

            with prefetch(gen, depth) as batches:
                yield from batches
        else:
            yield from gen

    def _task_batches_raw(self, reader, mode):
        shards = reader.create_shards()
        task_id = 0
        for shard_name, (start, count) in shards.items():
            task = Task(
                task_id=task_id, shard_name=shard_name,
                start=start, end=start + count, type=mode,
            )
            task_id += 1
            yield from batch_records(
                reader.read_records(task),
                self._batch_size,
                self._spec.dataset_fn,
                mode,
                reader.metadata,
            )

    def _maybe_init_state(self, batch):
        if self.state is None:
            from elasticdl_tpu.callbacks import apply_callbacks_to_optimizer

            tx = apply_callbacks_to_optimizer(
                self._spec.make_optimizer(), self._callbacks
            )
            self.state = self._step_runner.init_state(
                self._spec.model, tx, batch,
                seed=getattr(self._args, "random_seed", 0),
            )
            self._train_step = self._step_runner.train_step(self._spec.loss)
            self._eval_step = self._step_runner.eval_step()
            if self._init_checkpoint_dir:
                self.state = restore_from_dir(
                    self.state, self._init_checkpoint_dir,
                    host_tables=self._step_runner.host_tables,
                )

    def _maybe_checkpoint(self):
        with self._timing.record("checkpoint"):
            self._checkpoint.maybe_save(self.state)

    def train(self) -> dict:
        start_time = time.monotonic()
        steps = 0
        examples = 0
        stop = False
        for epoch in range(self._epochs):
            if stop:
                break
            for batch in self._task_batches(self._train_reader, Mode.TRAINING):
                self._maybe_init_state(batch)
                self.last_batch = batch
                with self._timing.record("batch_process"):
                    self.state, metrics = self._train_step(self.state, batch)
                self.last_train_metrics = metrics
                steps += 1
                examples += int(np.sum(batch["mask"]))
                self._maybe_checkpoint()
                if steps % 100 == 0:
                    self._logger.info(
                        "step=%d loss=%.5f", steps, float(metrics["loss"])
                    )
                    if self._tb_service is not None:
                        self._tb_service.write_dict_to_summary(
                            {"train/loss": float(metrics["loss"])}, steps
                        )
                if self._evaluation_steps and (
                    steps % self._evaluation_steps == 0
                ):
                    self.evaluate()
                if self._max_steps and steps >= self._max_steps:
                    stop = True
                    break
        if self.state is None:
            raise ValueError(
                f"Training data {self._args.training_data!r} produced no "
                "batches; nothing was trained"
            )
        jax.block_until_ready(self.state.params)
        self._checkpoint.save_final(self.state)
        elapsed = time.monotonic() - start_time
        eval_result = self.evaluate() if self._eval_reader else None
        if eval_result and self._tb_service is not None:
            self._tb_service.write_eval_metrics(steps, eval_result)
        for cb in self._callbacks:
            on_end = getattr(cb, "on_train_end", None)
            if on_end is not None:
                on_end(self)
        if self._tb_service is not None:
            self._tb_service.close()
        self._timing.report_timing()
        return {
            "steps": steps,
            "examples": examples,
            "elapsed_secs": elapsed,
            "examples_per_sec": examples / max(elapsed, 1e-9),
            "final_loss": (
                float(self.last_train_metrics["loss"])
                if self.last_train_metrics is not None else None
            ),
            "eval_metrics": eval_result,
        }

    def evaluate(self) -> Optional[dict]:
        if self._eval_reader is None or self._spec.eval_metrics_fn is None:
            return None
        if self.state is None:
            raise RuntimeError("evaluate() before any training step")
        all_outputs, all_labels = [], []
        for batch in self._task_batches(self._eval_reader, Mode.EVALUATION):
            preds = self._eval_step(self.state, batch)
            real = int(np.sum(batch["mask"]))
            all_outputs.append(np.asarray(preds)[:real])
            all_labels.append(
                jax.tree.map(lambda x: np.asarray(x)[:real], batch["labels"])
            )
        if not all_outputs:
            self._logger.warning(
                "Validation data produced no batches; skipping evaluation"
            )
            return None
        outputs, labels = concat_eval_accumulators(all_outputs, all_labels)
        metrics = evaluate_metrics(
            self._spec.eval_metrics_fn(), labels, outputs
        )
        self._logger.info("Eval metrics: %s", metrics)
        return metrics

    def run(self):
        return self.train()
