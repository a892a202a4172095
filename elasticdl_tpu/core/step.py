"""Jit-compiled train / evaluate / predict step builders.

Counterpart of the reference worker's ``training_process`` /
``forward_process`` (``worker/worker.py:713-755``): where the reference runs a
TF2 ``GradientTape`` eagerly and ships gradients to a parameter server, here
the whole step — forward, backward, optimizer apply — is one XLA program.
Batches are padded to a static shape and carry a ``mask`` so partial final
batches don't break compilation caching (XLA static-shape semantics).
"""

import inspect
from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from elasticdl_tpu.core.train_state import init_train_state
from elasticdl_tpu.observability.tracing import LOSS_SCOPE, OPTIMIZER_SCOPE


def _call_loss(loss_fn, labels, predictions, mask):
    """Call the user loss; pass the padding mask iff it accepts 3 args."""
    try:
        nparams = len(inspect.signature(loss_fn).parameters)
    except (TypeError, ValueError):
        nparams = 2
    if nparams >= 3:
        return loss_fn(labels, predictions, mask)
    return loss_fn(labels, predictions)


def _apply_model(state, params, batch, training, rng):
    variables = {"params": params}
    has_batch_stats = bool(state.batch_stats)
    if has_batch_stats:
        variables["batch_stats"] = state.batch_stats
    mutable = ["batch_stats"] if (training and has_batch_stats) else False
    out = state.apply_fn(
        variables,
        batch["features"],
        training=training,
        rngs={"dropout": rng} if rng is not None else None,
        mutable=mutable,
    )
    if mutable:
        preds, updates = out
        return preds, updates.get("batch_stats", state.batch_stats)
    return out, state.batch_stats


def _model_metrics(preds) -> dict:
    """What a model's training output says should leave the step beside
    the loss: where the output is a mapping, its ``metrics`` entry (a
    flat dict of scalars the model counted: an expert layer's routed
    rows, the tokens a diffusion model's noising masked). Every other
    output says nothing."""
    return dict(preds.get("metrics", {})) if isinstance(preds, dict) else {}


def _train_step_body(loss_fn: Callable) -> Callable:
    """The dense step body ``(state, batch) -> (state, metrics)``: one
    forward+backward+apply. Every dense program, per batch or per task,
    on one device or on a mesh, is built from it. The loss and the
    optimizer run under a named scope each: metadata of the compiled
    program's instructions and nothing else, by which a profiler window's
    operation table (utils/hlo_ops.py) tells them from the scan's own
    operations; the model's are told by Flax's module paths."""

    def train_step(state, batch):
        state, rng = state.next_rng()

        def compute_loss(params):
            preds, new_batch_stats = _apply_model(
                state, params, batch, training=True, rng=rng
            )
            with jax.named_scope(LOSS_SCOPE):
                loss = _call_loss(
                    loss_fn, batch["labels"], preds, batch["mask"]
                )
            return loss, (preds, new_batch_stats)

        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)
        (loss, (preds, new_batch_stats)), grads = grad_fn(state.params)
        # Padded rows are masked out of the loss but BatchNorm would
        # still fold them into running stats — keep the old stats for
        # any batch that contains padding.
        if state.batch_stats:
            is_full = jnp.all(batch["mask"] > 0)
            new_batch_stats = jax.tree.map(
                lambda new, old: jnp.where(is_full, new, old),
                new_batch_stats, state.batch_stats,
            )
        with jax.named_scope(OPTIMIZER_SCOPE):
            new_state = state.apply_gradients(
                grads=grads, batch_stats=new_batch_stats
            )
        return new_state, {"loss": loss, **_model_metrics(preds)}

    return train_step


def jit_step(body: Callable, state_shardings=None, batch_shardings=None,
             donate: bool = True) -> Callable:
    """Compile a step body ``(state, batch) -> (state, metrics)``. With
    ``state_shardings`` the state goes in and comes out laid out so
    (``batch_shardings`` None: taken from the placed batch); without,
    the program is the one-device one. This and ``jit_task`` are the
    only places a training program is compiled."""
    shardings = {}
    if state_shardings is not None:
        shardings = dict(
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, None),
        )
    return jax.jit(
        body, donate_argnums=(0,) if donate else (), **shardings
    )


def jit_task(body: Callable, state_shardings=None,
             donate: bool = True) -> Callable:
    """Compile ``(state, batches) -> (state, metrics)`` where ``batches``
    leaves carry a leading task dim T: T steps of ``body`` fused into
    ONE XLA program via ``lax.scan``, jitted as ``jit_step`` would (the
    task's layout is taken from the placed batches).

    This is the task-granular execution mode: the reference's unit of
    work is already a task of ``num_minibatches_per_task`` minibatches
    (task_dispatcher.py records_per_task), and on TPU fusing those steps
    removes T-1 host dispatches per task — the dominant cost for small
    models. ``metrics`` leaves come back stacked (T,) so per-step
    losses stay observable.

    The scan is not unrolled: unrolled copies of a step overlap, and
    their temporaries with them (a 680M-parameter model's 8-step program
    for a v5e: 8.5 GB unrolled by 4 against 5.3 GB, refused beside 8.2
    GB of state), and a 406M-parameter GPT-2's task ran 5.5% slower
    unrolled by 4, 1,389.5 ms against 1,313.3 (PERF.md, PR 27).
    """

    # The benchmark finds the task programs on the trace by this name,
    # and the operation table a profiler window writes for them
    # (``<profile_dir>/programs/jit_multi_step.ops.json``).
    def multi_step(state, batches):
        return jax.lax.scan(body, state, batches)

    return jit_step(multi_step, state_shardings, donate=donate)


def build_train_step(loss_fn: Callable) -> Callable:
    """Build the one-device ``(state, batch) -> (state, metrics)``."""
    return jit_step(_train_step_body(loss_fn))


def build_multi_step(loss_fn: Callable) -> Callable:
    """Build the one-device task program (``jit_task``)."""
    return jit_task(_train_step_body(loss_fn))


def stack_batches(batches):
    """[{k: (B,...)}] -> {k: (T, B, ...)} for build_multi_step."""
    import numpy as np

    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


def build_grad_step(loss_fn: Callable) -> Callable:
    """Build ``(state, batch) -> (grads, metrics)`` without applying.

    Used by the accumulation path (reference sync-SGD ``grads_to_wait``
    semantics, ps/servicer.py:151-214) and by SSP local updates.
    """

    def grad_step(state, batch, rng):
        def compute_loss(params):
            preds, _ = _apply_model(
                state, params, batch, training=True, rng=rng
            )
            return _call_loss(loss_fn, batch["labels"], preds, batch["mask"])

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        return grads, {"loss": loss}

    return jax.jit(grad_step)


def build_eval_step() -> Callable:
    """Build ``(state, batch) -> predictions`` (reference forward_process)."""

    def eval_step(state, batch):
        preds, _ = _apply_model(
            state, state.params, batch, training=False, rng=None
        )
        return preds

    return jax.jit(eval_step)


def build_apply_gradients() -> Callable:
    @partial(jax.jit, donate_argnums=(0,))
    def apply_step(state, grads, lr_scale):
        scaled = jax.tree.map(lambda g: g * lr_scale, grads)
        return state.apply_gradients(grads=scaled)

    return apply_step


class StepRunner:
    """The one-device runner, and the seam every runner answers: what
    the worker and the executors ask in place of asking what a runner
    is. ``parallel/mesh_runner.py``, ``embedding/device_sparse.py`` and
    ``embedding/host_engine.py`` override what they do differently; the
    compiled functions come back as built, with nothing around them."""

    mesh = None  # the Mesh the state lives on; None = one device
    accum_steps = 1  # > 1: train_step carries a gradient window
    host_tables = None  # host-resident tables a checkpoint must carry
    pull_ahead = False  # True: the task loop feeds ``iter_prepared``
    can_fuse = True  # False: no ``train_multi_step``
    can_resize = False  # True: ``resize(new_mesh, state)`` reshards

    def init_state(self, model, tx, batch, seed: int = 0):
        return init_train_state(model, tx, batch, seed=seed)

    def train_step(self, loss_fn: Callable) -> Callable:
        return build_train_step(loss_fn)

    def train_multi_step(self, loss_fn: Callable) -> Callable:
        return build_multi_step(loss_fn)

    def eval_step(self) -> Callable:
        return build_eval_step()

    def place_state(self, state):
        """Re-place a host-restored state where the runner keeps it."""
        return state

    def flush(self):
        """Wait for work the runner does off the step's thread."""


def runner_for_spec(spec, **host_runner_args) -> StepRunner:
    """The runner a model spec trains under without a mesh: its
    host-tier runner, else its device-tier sparse runner, else the
    one-device runner."""
    if spec.make_host_runner is not None:
        return spec.make_host_runner(**host_runner_args)
    if spec.make_sparse_runner is not None:
        return spec.make_sparse_runner()
    return StepRunner()


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_scale(tree, scale):
    return jax.tree.map(lambda x: x * scale, tree)


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def concat_eval_accumulators(outputs_acc, labels_acc):
    """Concatenate per-batch (outputs, labels) accumulators; labels may be
    arrays or dicts of arrays (multi-output models). Shared by the local
    and eval/predict executors."""
    import numpy as np

    outputs = np.concatenate(outputs_acc, axis=0)
    labels = (
        np.concatenate(labels_acc, axis=0)
        if not isinstance(labels_acc[0], dict)
        else {
            k: np.concatenate([d[k] for d in labels_acc], axis=0)
            for k in labels_acc[0]
        }
    )
    return outputs, labels


def evaluate_metrics(
    metrics_fns: Dict[str, Callable], labels, predictions
) -> Dict[str, float]:
    """Apply stateless metric fns to accumulated raw outputs.

    Counterpart of the reference's master-side metric computation over
    worker-reported raw outputs (common/evaluation_utils.py:50-97).
    """
    out = {}
    for name, fn in metrics_fns.items():
        out[name] = float(fn(labels, predictions))
    return out
