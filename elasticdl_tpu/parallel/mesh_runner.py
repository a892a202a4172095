"""MeshRunner: SPMD train/eval steps over a device mesh.

This is the TPU-native replacement for the reference's entire parameter-
server data plane (``ps/servicer.py`` push/pull RPCs): the minibatch is
sharded over the ``dp`` axis, parameters stay replicated, optimizer state
is ZeRO-sharded over ``dp``, and XLA inserts the gradient all-reduce /
reduce-scatter / param all-gather collectives over ICI inside one compiled
step. The model "version" is the replicated step counter — there is no
central store to push to or pull from, hence nothing to lose when a
worker dies (recovery = sharded checkpoint + task re-queue, stage 5).

Sync semantics map (SURVEY.md §2.7):
- sync SGD ``grads_to_wait``  → ``accum_steps`` gradient accumulation,
- async staleness LR modulation → ``staleness_modulation=True``:
  microbatch j in a window of k is weighted 1/(k-j) — the delayed-apply
  analog of the PS scaling each grad's LR by 1/staleness (per-host
  accumulation + delayed sync is the principled mapping of async SGD
  onto SPMD; weighted rather than pretending RPC async),
- SSP ``get_model_steps``     → ``version_report_steps`` on the Worker:
  every step applies to the one true SPMD state, the master just
  observes (and eval-triggers on) every N-th version.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.core import step as step_lib
from elasticdl_tpu.core.train_state import TrainState, init_train_state
from elasticdl_tpu.embedding import partition as partition_lib
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.parallel import rules as rules_lib

logger = get_logger("mesh_runner")


class MeshRunner(step_lib.StepRunner):
    """The runner seam (core/step.py::StepRunner) over a Mesh."""

    can_resize = True

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        data_axis: str = "dp",
        accum_steps: int = 1,
        donate_state: bool = True,
        param_rule=None,
        batch_rule=None,
        staleness_modulation: bool = False,
        param_rule_factory=None,
    ):
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.data_axis = data_axis
        self.accum_steps = accum_steps
        self._donate_state = donate_state
        self._state_shardings = None
        # Optional (path, leaf) -> PartitionSpec for batch leaves; default
        # is leading-dim over the data axis. Multi-axis models (sequence
        # parallel) shard e.g. token ids (B, S) as P("dp", "sp").
        self.batch_rule = batch_rule
        # Async-SGD staleness LR modulation (reference
        # ps/learning_rate_modulator.py + ps/servicer.py:133-140: a grad
        # applied at staleness s gets lr/s): under delayed SPMD
        # application, microbatch j in a window of k has staleness k-j at
        # apply time, so its contribution is weighted 1/(k-j), normalized.
        self.staleness_modulation = staleness_modulation
        # Auto-partition pass (reference ModelHandler 2MB rewrite,
        # model_handler.py:85-89): big embedding tables row-shard over the
        # data axis, everything else replicates. Rules bake the mesh
        # (axis sizes decide what divides), so ``resize`` needs a
        # *factory* to re-derive them on the new mesh; a bare
        # ``param_rule`` is kept as-is across resizes (its fit checks
        # run against ``self.mesh`` at placement time).
        if param_rule_factory is None and param_rule is None:
            param_rule_factory = (
                lambda m: partition_lib.embedding_partition_rule(
                    axis=data_axis, axis_size=m.shape[data_axis]
                )
            )
        self._param_rule_factory = param_rule_factory
        self.param_rule = (
            param_rule_factory(self.mesh)
            if param_rule_factory is not None else param_rule
        )
        # Compiled-step memo keyed by (kind, loss-fn object, mesh): an
        # autoscaler oscillates between a few mesh rungs, and a
        # long-lived worker that has trained on a rung before must not
        # re-trace/re-compile on returning to it — the rung's step
        # programs stay warm for the process lifetime, making repeat
        # resizes pay only the state movement. (Sharding derivation is
        # deterministic per mesh, so a cached step's baked shardings
        # match the re-derived ones structurally.) The accum path is
        # NOT memoized: it carries a cross-call grad accumulator whose
        # placement dies with its mesh.
        self._step_memo = {}

    def _mesh_memo_key(self):
        return (
            tuple(d.id for d in self.mesh.devices.flat),
            tuple(self.mesh.axis_names),
            tuple(self.mesh.devices.shape),
        )

    def _memoized(self, kind, fn_key, builder):
        key = (kind, fn_key, self._mesh_memo_key())
        step = self._step_memo.get(key)
        if step is None:
            step = builder()
            self._step_memo[key] = step
        return step

    # ---- sharding rules ------------------------------------------------

    def _batch_sharding(self):
        return mesh_lib.batch_sharding(self.mesh, self.data_axis)

    def _shard_batch_tree(self, batch):
        if self.batch_rule is not None:
            mesh = self.mesh
            return jax.tree_util.tree_map_with_path(
                lambda path, leaf: NamedSharding(
                    mesh,
                    rules_lib.fit_spec(
                        self.batch_rule(path, leaf), leaf, mesh
                    ),
                ),
                batch,
            )
        sharding = self._batch_sharding()
        return jax.tree.map(
            lambda _: sharding, batch
        )

    def state_shardings(self, state: TrainState):
        """Params placed by the partition rule (big embedding tables
        row-sharded, rest replicated); batch_stats/rng/step replicated;
        optimizer state ZeRO-sharded over the data axis (slot tables get
        their first divisible dim — i.e. rows — so slots co-shard with
        their table, reference ps/parameters.py:156)."""
        replicated = mesh_lib.replicated(self.mesh)

        def opt_leaf(path, leaf):
            # Optax state paths embed the param path as a suffix, so the
            # param rule re-applies here and moments/slots co-shard with
            # their parameter (reference slot co-location,
            # ps/parameters.py:156). Unmatched leaves ZeRO-shard over dp.
            spec = self.param_rule(path, leaf)
            if (
                any(a is not None for a in tuple(spec))
                and rules_lib.spec_fits(spec, leaf, self.mesh)
            ):
                return NamedSharding(self.mesh, spec)
            return mesh_lib.shard_leaf_over_axis(
                self.mesh, leaf, self.data_axis
            )

        return state.replace(
            step=replicated,
            params=partition_lib.tree_shardings(
                state.params, self.mesh, self.param_rule
            ),
            batch_stats=jax.tree.map(lambda _: replicated,
                                     state.batch_stats),
            opt_state=jax.tree_util.tree_map_with_path(
                opt_leaf, state.opt_state
            ),
            rng=replicated,
        )

    # ---- runner interface ---------------------------------------------

    def init_state(self, model, tx, example_batch, seed: int = 0):
        """Initialize state already laid out on the mesh.

        Shardings are derived from an abstract eval_shape pass and the init
        runs under jit with those out_shardings, so a table sized for the
        whole mesh (plus its optimizer slots) never has to materialize
        unsharded on one device first."""

        def make_state(batch):
            return init_train_state(model, tx, batch, seed=seed)

        abstract = jax.eval_shape(make_state, example_batch)
        shardings = self.state_shardings(abstract)
        self._state_shardings = shardings
        state = jax.jit(make_state, out_shardings=shardings)(example_batch)
        for name, tree in (
            ("params", state.params),
            ("optimizer state", state.opt_state),
            ("first batch", self.place_batch(example_batch)),
        ):
            logger.info(
                "mesh %s: %s: %s", dict(self.mesh.shape), name,
                mesh_lib.placement_summary(tree),
            )
        return state

    def place_batch(self, batch):
        """Shard a host batch onto the mesh (leading dim over dp by
        default; per-leaf ``batch_rule`` when set, e.g. tokens over
        dp×sp for sequence-parallel models). Multi-host: this process's
        batch becomes its process-local shard of the global batch
        (parallel/multihost.py)."""
        from elasticdl_tpu.parallel import multihost

        return multihost.make_global_batch(
            batch, self.mesh, self._shard_batch_tree(batch)
        )

    def place_state(self, state):
        """Re-place a (host-restored) state onto the mesh shardings.

        Used after checkpoint restore: restored leaves are numpy arrays
        with no sharding; without re-placement a row-sharded table would
        be committed whole to one device."""
        return jax.device_put(state, self._require_shardings())

    def resize(self, new_mesh: Mesh, state=None):
        """Checkpointless live reshard onto ``new_mesh``
        (parallel/reshard.py): re-derive shardings with the partition
        rules re-bound to the new mesh and move the state's shards
        device-to-device — no disk round trip, no full host
        materialization (host bounce only as backend fallback).
        Returns the resharded state (or None when called pre-init,
        which just re-targets the runner so ``init_state`` lands on
        the new mesh).

        Every compiled step this runner handed out baked the OLD
        shardings and is dead after this call — the caller (Worker
        resize path) must rebuild ``train_step`` / ``eval_step`` /
        ``train_multi_step``. Call only at a step boundary; a partial
        gradient-accumulation window does not survive (same loss as
        checkpoint-restart, which it replaces)."""
        from elasticdl_tpu.parallel import reshard as reshard_lib

        self.mesh = new_mesh
        if self._param_rule_factory is not None:
            self.param_rule = self._param_rule_factory(new_mesh)
        self._state_shardings = None
        if state is None:
            return None

        def shardings_fn(abstract):
            self._state_shardings = self.state_shardings(abstract)
            return self._state_shardings

        return reshard_lib.live_reshard(state, shardings_fn)

    def train_step(self, loss_fn: Callable) -> Callable:
        if self.accum_steps > 1:
            return self._accum_train_step(loss_fn)
        # Keyed on the function OBJECT (the memo entry pins it alive):
        # an id() key could be recycled after gc and silently serve a
        # step compiled for a different loss.
        return self._memoized(
            "train", loss_fn,
            lambda: self._placed(
                step_lib.jit_step, loss_fn, self.place_batch
            ),
        )

    def _placed(self, jit, loss_fn: Callable, place) -> Callable:
        """The dense step compiled by ``jit`` (core/step.py) with this
        mesh's state shardings, fed host batches through ``place``."""
        jitted = jit(
            step_lib._train_step_body(loss_fn),
            self._require_shardings(), donate=self._donate_state,
        )

        def wrapped(state, batch):
            return jitted(state, place(batch))

        return wrapped

    def _accum_train_step(self, loss_fn: Callable):
        """Gradient accumulation: the mesh-native mapping of the reference
        sync-SGD ``grads_to_wait`` (ps/servicer.py:151-214). Each call
        accumulates one microbatch; the optimizer applies every
        ``accum_steps`` calls, scaled by 1/accum_steps."""
        shardings = self._require_shardings()
        accum_steps = self.accum_steps
        if self.staleness_modulation:
            # Microbatch j (count=j) has staleness k-j at the delayed
            # apply; weight 1/(k-j), normalize by the harmonic sum so the
            # effective LR is preserved (reference lr/staleness scaling).
            weight_of = lambda count: 1.0 / (accum_steps - count)
            norm = float(sum(1.0 / (accum_steps - j)
                             for j in range(accum_steps)))
        else:
            weight_of = lambda count: 1.0
            norm = float(accum_steps)

        def micro_step(carry, batch):
            state, grad_acc, count = carry
            state, rng = state.next_rng()

            def compute_loss(params):
                preds, new_bs = step_lib._apply_model(
                    state, params, batch, training=True, rng=rng
                )
                loss = step_lib._call_loss(
                    loss_fn, batch["labels"], preds, batch["mask"]
                )
                return loss, new_bs

            (loss, new_bs), grads = jax.value_and_grad(
                compute_loss, has_aux=True
            )(state.params)
            # BatchNorm stats update every microbatch (guarded against
            # padded rows), independent of the delayed optimizer apply.
            if state.batch_stats:
                is_full = jnp.all(batch["mask"] > 0)
                new_bs = jax.tree.map(
                    lambda new, old: jnp.where(is_full, new, old),
                    new_bs, state.batch_stats,
                )
                state = state.replace(batch_stats=new_bs)
            w = weight_of(count)
            grad_acc = jax.tree.map(
                lambda acc, g: acc + w * g, grad_acc, grads
            )
            count = count + 1

            def apply(args):
                state, grad_acc, count = args
                mean_grads = jax.tree.map(
                    lambda g: g / norm, grad_acc
                )
                new_state = state.apply_gradients(grads=mean_grads)
                zeros = jax.tree.map(jnp.zeros_like, grad_acc)
                return new_state, zeros, jnp.zeros_like(count)

            def keep(args):
                return args

            state, grad_acc, count = jax.lax.cond(
                count >= accum_steps, apply, keep, (state, grad_acc, count)
            )
            return (state, grad_acc, count), loss

        # Pin the carry's shardings so a host-restored state (numpy
        # leaves) re-places onto the mesh instead of committing to one
        # device; grad accumulator co-shards with params.
        carry_shardings = (
            shardings, shardings.params, mesh_lib.replicated(self.mesh)
        )
        jit_micro = jax.jit(
            micro_step,
            in_shardings=(carry_shardings, None),
            out_shardings=(carry_shardings, None),
            donate_argnums=(0,) if self._donate_state else (),
        )
        runner = self
        carry_box = {"grad_acc": None, "count": None}

        def wrapped(state, batch):
            batch = runner.place_batch(batch)
            if carry_box["grad_acc"] is None:
                # zeros_like preserves the params' sharding, so the grad
                # accumulator co-shards with (possibly row-sharded) params
                # instead of replicating a mesh-sized table per device.
                carry_box["grad_acc"] = jax.tree.map(
                    jnp.zeros_like, state.params
                )
                carry_box["count"] = jnp.zeros((), jnp.int32)
            (state, grad_acc, count), loss = jit_micro(
                (state, carry_box["grad_acc"], carry_box["count"]), batch
            )
            carry_box["grad_acc"] = grad_acc
            carry_box["count"] = count
            return state, {"loss": loss}

        return wrapped

    def train_multi_step(self, loss_fn: Callable) -> Callable:
        """Fused task-granular step: scan a whole task's minibatches
        (stacked with a leading T dim) through one compiled SPMD
        program (core/step.jit_task). Only the plain (accum_steps == 1)
        path fuses — accumulation already carries cross-call state."""
        return self._memoized(
            "multi", loss_fn,
            lambda: self._placed(
                step_lib.jit_task, loss_fn, self.place_task
            ),
        )

    def place_task(self, batches):
        """Place a stacked task ({k: (T, B, ...)}) on the mesh: per-leaf
        batch specs shift right one dim for the leading T."""
        mesh = self.mesh

        def sharding(path, leaf):
            if self.batch_rule is not None:
                # The rule sees the per-batch view (leading T stripped)
                # so its ndim/shape dispatch matches the unstacked case.
                spec = self.batch_rule(path, leaf[0])
                spec = rules_lib.fit_spec(
                    P(None, *tuple(spec)), leaf, mesh
                )
            else:
                spec = rules_lib.fit_spec(
                    P(None, self.data_axis), leaf, mesh
                )
            return NamedSharding(mesh, spec)

        return jax.device_put(
            batches,
            jax.tree_util.tree_map_with_path(sharding, batches),
        )

    def eval_step(self) -> Callable:
        return self._memoized("eval", None, self._build_eval_step)

    def _build_eval_step(self) -> Callable:
        shardings = self._require_shardings()
        runner = self

        def eval_step(state, batch):
            preds, _ = step_lib._apply_model(
                state, state.params, batch, training=False, rng=None
            )
            return preds

        jitted = jax.jit(eval_step, in_shardings=(shardings, None))

        def wrapped(state, batch):
            return jitted(state, runner.place_batch(batch))

        return wrapped

    def _require_shardings(self):
        if self._state_shardings is None:
            raise RuntimeError(
                "MeshRunner.init_state must run before building steps"
            )
        return self._state_shardings


def make_runner_for_spec(
    spec,
    mesh: Optional[Mesh] = None,
    data_axis: str = "dp",
    accum_steps: int = 1,
    **kwargs,
) -> MeshRunner:
    """Build a MeshRunner wired to a ModelSpec's parallel extras.

    The production path (worker/main.py, tests alike): the zoo module's
    ``param_sharding_rules()`` regexes place params on tp/ep/sp axes with
    the 2MB embedding auto-partition as fallback, and its
    ``batch_sharding_rule`` lays batches over dp×sp. Modules without the
    extras get the plain dp behavior.
    """
    mesh = mesh if mesh is not None else mesh_lib.make_mesh()
    param_rule_factory = None
    if getattr(spec, "param_sharding_rules", None) is not None:
        # A factory, not a one-shot rule: live resize (MeshRunner.resize)
        # re-derives the regex rules against the new mesh so a tp rule
        # that fit the old mesh degrades (or re-engages) per-dim.
        rules = spec.param_sharding_rules()

        def param_rule_factory(m, rules=rules):
            return rules_lib.regex_param_rule(
                rules, mesh=m,
                fallback=partition_lib.embedding_partition_rule(
                    axis=data_axis, axis_size=m.shape[data_axis]
                ),
            )

    return MeshRunner(
        mesh=mesh,
        data_axis=data_axis,
        accum_steps=accum_steps,
        param_rule_factory=param_rule_factory,
        batch_rule=getattr(spec, "batch_sharding_rule", None),
        **kwargs,
    )
