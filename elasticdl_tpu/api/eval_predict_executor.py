"""Evaluate-only / predict-only jobs booting from a checkpoint.

Counterpart of the reference's ``elasticdl evaluate|predict`` flows
(scripts/client_test.sh evaluate/predict blocks): no training tasks — the
model is restored from ``--checkpoint_dir_for_init`` and either scored
against validation data (metrics computed from raw outputs, reference
common/evaluation_utils.py:50-97) or run forward over prediction data with
outputs handed to the user's PredictionOutputsProcessor.
"""

from typing import Optional

import jax
import numpy as np

from elasticdl_tpu.checkpoint import restore_from_dir
from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.task import Task
from elasticdl_tpu.core.model_spec import get_model_spec
from elasticdl_tpu.core.step import (
    concat_eval_accumulators,
    evaluate_metrics,
    runner_for_spec,
)
from elasticdl_tpu.data.batcher import batch_records
from elasticdl_tpu.data.factory import (
    create_data_reader,
    parse_data_reader_params,
)

logger = get_logger("eval_predict")


class EvalPredictExecutor:
    def __init__(self, args, mode: str):
        if mode not in ("evaluate", "predict"):
            raise ValueError(f"mode must be evaluate|predict, got {mode}")
        self._mode = mode
        self._args = args
        self._spec = get_model_spec(
            model_zoo=args.model_zoo,
            model_def=args.model_def,
            dataset_fn=args.dataset_fn,
            loss=args.loss,
            optimizer=args.optimizer,
            eval_metrics_fn=args.eval_metrics_fn,
            custom_data_reader=args.custom_data_reader,
        )
        data_origin = (
            args.validation_data if mode == "evaluate"
            else args.prediction_data
        )
        if not data_origin:
            raise ValueError(f"{mode} requires data")
        self._reader = create_data_reader(
            data_origin=data_origin,
            custom_reader=self._spec.custom_data_reader,
            **parse_data_reader_params(
                getattr(args, "data_reader_params", "")
            ),
        )
        self._batch_size = args.minibatch_size
        self._ckpt_dir = args.checkpoint_dir_for_init
        self.state = None
        # Host-tier models: rows come back from the checkpoint into the
        # runner's tables; its eval step reads them per batch.
        self._step_runner = runner_for_spec(self._spec)
        self._eval_step = None

    def _batches(self):
        data_mode = (
            Mode.EVALUATION if self._mode == "evaluate"
            else Mode.PREDICTION
        )
        task_id = 0
        for shard_name, (start, count) in (
            self._reader.create_shards().items()
        ):
            task = Task(
                task_id=task_id, shard_name=shard_name,
                start=start, end=start + count, type=data_mode,
            )
            task_id += 1
            yield from batch_records(
                self._reader.read_records(task),
                self._batch_size,
                self._spec.dataset_fn,
                data_mode,
                self._reader.metadata,
            )

    def _restore(self, batch):
        # The optimizer tree must match the TRAINED one or the
        # checkpoint won't load: training folds LearningRateScheduler
        # callbacks into the optax chain (local_executor.py:162-165,
        # worker.py:135-138), so the restore-side skeleton must too —
        # eval/predict never applies updates, but the opt_state leaves
        # live in the checkpoint.
        from elasticdl_tpu.callbacks import apply_callbacks_to_optimizer

        tx = apply_callbacks_to_optimizer(
            self._spec.make_optimizer(),
            self._spec.callbacks_fn() if self._spec.callbacks_fn else [],
        )
        self.state = self._step_runner.init_state(
            self._spec.model, tx, batch
        )
        self._eval_step = self._step_runner.eval_step()
        self.state = restore_from_dir(
            self.state, self._ckpt_dir,
            host_tables=self._step_runner.host_tables,
        )
        logger.info(
            "Restored model version %d from %s",
            int(self.state.step), self._ckpt_dir,
        )

    def run(self) -> Optional[dict]:
        processor = self._spec.prediction_outputs_processor
        outputs_acc, labels_acc = [], []
        n_batches = 0
        for batch in self._batches():
            if self.state is None:
                self._restore(batch)
            preds = self._eval_step(self.state, batch)
            real = int(np.sum(batch["mask"]))
            n_batches += 1
            if self._mode == "evaluate":
                outputs_acc.append(np.asarray(preds)[:real])
                labels_acc.append(
                    jax.tree.map(
                        lambda x: np.asarray(x)[:real], batch["labels"]
                    )
                )
            elif processor is not None:
                processor.process(np.asarray(preds)[:real], 0)
        if self.state is None:
            raise ValueError("Data produced no batches")
        if self._mode == "predict":
            return {"batches": n_batches}
        if not self._spec.eval_metrics_fn:
            raise ValueError("evaluate requires eval_metrics_fn")
        outputs, labels = concat_eval_accumulators(outputs_acc, labels_acc)
        metrics = evaluate_metrics(
            self._spec.eval_metrics_fn(), labels, outputs
        )
        logger.info("Eval metrics: %s", metrics)
        return metrics
