"""Device-tier sparse embedding training: the PS hot path, in HBM.

The reference trains big embedding tables on parameter-server pods —
pull rows, compute, push row grads, C++ kernels apply them
(``pkg/ps/server.go:162-192``, ``pkg/kernel/capi/kernel_api.cc:6-96``).
The TPU-native shape when the table FITS in HBM (the v5e has 16 GB —
a 4M x 256 f32 table is 4 GB): keep the table next to the model and
make the whole step one XLA program, with the sparse structure
preserved —

- **forward** reads the table through the measured Pallas row-streaming
  lookup (``ops/pallas_embedding.lookup_combine`` auto-dispatch: each
  touched row leaves HBM exactly once; the table never enters autodiff,
  so no dense (V, D) gradient ever exists),
- **backward** produces row gradients for only the batch's unique ids
  (linear-transpose of the combiner — exact, no hand math),
- **update** scatters through the in-place Pallas row kernels
  (``embedding/optimizer.sparse_apply``: one HBM read+write per touched
  row, slots included — the C++ kernel family this replaces).

``tables/slots`` ride a ``SparseTrainState`` (a ``TrainState`` with
extra pytree fields), so jit/donation/checkpoint treat them like any
other state leaf. Models read per-batch embeddings through the
``SparseEmbed`` module (collection ``sparse_emb``), mirroring the host
tier's ``HostEmbedding``/``host_rows`` contract.
"""

from dataclasses import dataclass
from typing import Any, Callable, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.core.step import (
    StepRunner,
    _call_loss,
    jit_step,
    jit_task,
)
from elasticdl_tpu.core.train_state import TrainState
from elasticdl_tpu.embedding.combiner import COMBINERS, RaggedIds, combine
from elasticdl_tpu.embedding.optimizer import (
    RowOptimizer,
    init_slot_tables,
    pack_table,
    sparse_apply,
    sparse_apply_packed,
)
from elasticdl_tpu.embedding.partition import (
    DEFAULT_PARTITION_THRESHOLD_BYTES,
)

SPARSE_EMB_COLLECTION = "sparse_emb"


@dataclass(frozen=True)
class TableSpec:
    """One device-resident sparse table: ``feature_key`` names the
    batch feature carrying its RaggedIds (or dense (B, L) ids)."""

    name: str
    vocab: int
    dim: int
    combiner: str = "sum"
    feature_key: str = "ids"

    def __post_init__(self):
        if self.combiner not in COMBINERS:
            raise ValueError(f"combiner must be one of {COMBINERS}")


class SparseEmbed(nn.Module):
    """Read the runner-computed (B, dim) combined embedding for one
    table (collection ``sparse_emb``). The model never touches the
    (V, D) table — the sparse step owns lookup and update."""

    table_name: str
    output_dim: int

    @nn.compact
    def __call__(self):
        return self.variable(
            SPARSE_EMB_COLLECTION,
            self.table_name,
            lambda: jnp.zeros((1, self.output_dim), jnp.float32),
        ).value


class SparseTrainState(TrainState):
    """TrainState + the sparse plane: {table: (V, D)} main tables,
    their slot tables, and per-table apply counters (Adam bias
    correction — reference kernel_api.cc:52-55 step semantics)."""

    tables: Dict[str, jnp.ndarray] = struct.field(default_factory=dict)
    slot_tables: Dict[str, Dict[str, jnp.ndarray]] = struct.field(
        default_factory=dict
    )
    table_steps: Dict[str, jnp.ndarray] = struct.field(
        default_factory=dict
    )


def _ragged(ids) -> RaggedIds:
    if isinstance(ids, RaggedIds):
        return ids
    ids = jnp.asarray(ids)
    return RaggedIds(
        ids=ids.astype(jnp.int32),
        weights=jnp.ones(ids.shape, jnp.float32),
    )


def _unique_pad_jit(ids_flat: jnp.ndarray, vocab: int):
    """In-jit static-shape dedup: (uids, inverse) with uids padded to
    ``ids_flat.size`` by the out-of-range sentinel ``vocab`` (the pad
    contract every Pallas row kernel skips on)."""
    uids, inverse = jnp.unique(
        ids_flat, return_inverse=True, size=ids_flat.size,
        fill_value=vocab,
    )
    return uids.astype(jnp.int32), inverse.astype(jnp.int32)


def _row_grads(d_emb, uids, inverse, ragged, combiner):
    """Exact row gradients via linear transpose of the combiner (it is
    linear in the rows): (B, dim) cotangent -> (U, dim) row grads,
    scatter-add over duplicate ids included. XLA's native strength —
    the lookup kernel's VJP design note (ops/pallas_embedding.py)."""
    n_unique = uids.shape[0]
    dim = d_emb.shape[-1]
    inv = inverse.reshape(ragged.ids.shape)

    def lookup(rows):
        return combine(
            jnp.take(rows, inv, axis=0), ragged.weights, combiner
        )

    transpose = jax.linear_transpose(
        lookup, jax.ShapeDtypeStruct((n_unique, dim), jnp.float32)
    )
    (rows_ct,) = transpose(d_emb.astype(jnp.float32))
    return rows_ct


def sparse_apply_sharded(opt: RowOptimizer, table, slot_tables, unique_ids,
                         row_grads, step, mesh, axis: str,
                         use_pallas: str = "auto",
                         interpret: bool = False):
    """``sparse_apply`` over a ROW-SHARDED ``(V, D)`` table: each device
    owns rows [idx*V/n, (idx+1)*V/n) and applies only the updates whose
    (globally unique) id lands in its range — the TPU-native analogue of
    the reference's id%N scatter to parameter-server pods
    (``worker/worker.py:570-580``, ``common/hash_utils.py:4-49``), with
    contiguous row ranges instead of modulo so each shard stays one
    dense slice (the placement ``checkpoint/saver.py`` repartitions).

    Ids out of the local range (including the global pad sentinel
    ``vocab``) map to the LOCAL pad sentinel ``shard_rows``, which
    ``sparse_apply`` drops (XLA path ``mode="drop"``; kernels skip) —
    so pads and remote ids cost nothing locally. ``unique_ids`` must be
    globally deduplicated (``_unique_pad_jit``): each real id then
    updates exactly one shard exactly once. Slot tables co-shard with
    their main table; ``step`` is the replicated apply counter."""
    num_shards = mesh.shape[axis]
    vocab = table.shape[0]
    if vocab % num_shards:
        raise ValueError(
            f"vocab {vocab} not divisible by mesh axis {axis!r} size "
            f"{num_shards}; pad the table"
        )
    shard_rows = vocab // num_shards

    def per_shard(tbl, slots, uids, grads, step_):
        lo = (jax.lax.axis_index(axis) * shard_rows).astype(jnp.int32)
        local = uids.astype(jnp.int32) - lo
        in_range = (local >= 0) & (local < shard_rows)
        local = jnp.where(in_range, local, shard_rows)
        return sparse_apply(
            opt, tbl, slots, local, grads, step_,
            use_pallas=use_pallas, interpret=interpret,
        )

    # check_vma=False for the same reason as lookup_combine_sharded:
    # the forced-kernel path's pallas_call outputs carry no varying-mesh
    # annotation; the out_specs make the row sharding explicit.
    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(None), P(None, None),
                  P()),
        out_specs=(P(axis, None), P(axis, None)), check_vma=False,
    )(table, slot_tables, jnp.asarray(unique_ids),
      jnp.asarray(row_grads), jnp.asarray(step))


def build_sparse_train_step(
    loss_fn: Callable,
    specs: Tuple[TableSpec, ...],
    row_opt: RowOptimizer,
    template,
    use_pallas: str = "auto",
    interpret: bool = False,
    mesh=None,
    axis: str = "dp",
    sharded_tables: FrozenSet[str] = frozenset(),
    packed_slots: bool = False,
) -> Callable:
    """Build ``(SparseTrainState, batch) -> (state, metrics)`` — one
    jittable program covering lookup, model fwd/bwd, dense apply, and
    the sparse row-kernel apply. ``template`` is the model's
    ``sparse_emb`` collection structure (``sparse_template``).
    ``core/step.py`` compiles it, per batch (``jit_step``) or scanned
    over a task (``jit_task``).

    With ``mesh``, tables named in ``sharded_tables`` are row-sharded
    over ``axis``: lookup goes through
    ``lookup_combine_sharded``'s shard_map path and the row update
    through ``sparse_apply_sharded`` — same math, partitioned by row
    range, so the dp-N trajectory equals dp-1 exactly (dryrun case 5).
    Everything else (dedup, model fwd/bwd, dense apply) stays in the
    global view and GSPMD partitions it over the batch sharding.

    ``packed_slots``: slot tables live INSIDE the main table rows
    ((V, D*(1+n_slots)), optimizer.pack_table) so the apply is one
    gather + one scatter instead of (1 + n_slots) of each — the
    measured scatter-latency win (optimizer.sparse_apply_packed).
    Single-mesh only; forward narrows gathered rows to the first D
    columns."""
    from elasticdl_tpu.embedding.host_engine import _nest_rows
    from elasticdl_tpu.ops.pallas_embedding import (
        lookup_combine,
        lookup_combine_sharded,
    )
    if sharded_tables and mesh is None:
        raise ValueError("sharded_tables requires a mesh")
    if packed_slots and (mesh is not None or sharded_tables):
        raise ValueError(
            "packed_slots is single-mesh only (the row-sharded path "
            "keeps split tables)"
        )
    if packed_slots and use_pallas in ("always", "fused"):
        raise ValueError(
            "packed_slots uses the XLA gather/scatter path; the Pallas "
            "row kernels (serial and fused) operate on split tables"
        )

    def train_step(state: SparseTrainState, batch):
        state, rng = state.next_rng()
        features = batch["features"]

        embs, lookups = {}, {}
        for spec in specs:
            ragged = _ragged(features[spec.feature_key])
            table = state.tables[spec.name]
            # Forward from the LIVE table (Pallas auto-dispatch); the
            # table is not differentiated — row grads come from the
            # combiner transpose below.
            if packed_slots:
                # Gather the packed rows, narrow to the live first-D
                # columns, combine — the slot columns ride the same
                # (coalesced, cheap) gather; see sparse_apply_packed.
                rows = jnp.take(
                    jax.lax.stop_gradient(table), ragged.ids, axis=0
                )[..., :spec.dim]
                embs[spec.name] = combine(
                    rows, ragged.weights, spec.combiner
                )
            elif spec.name in sharded_tables:
                embs[spec.name] = lookup_combine_sharded(
                    jax.lax.stop_gradient(table), ragged.ids,
                    ragged.weights, spec.combiner, mesh, axis,
                    interpret=interpret,
                    force_pallas=(use_pallas == "always"),
                    force_xla=(use_pallas == "never"),
                )
            else:
                embs[spec.name] = lookup_combine(
                    jax.lax.stop_gradient(table), ragged.ids,
                    ragged.weights, spec.combiner,
                    interpret=interpret,
                    force_pallas=(use_pallas == "always"),
                    force_xla=(use_pallas == "never"),
                )
            uids, inverse = _unique_pad_jit(
                jnp.ravel(ragged.ids), spec.vocab
            )
            lookups[spec.name] = (ragged, uids, inverse)

        def compute_loss(params, embs):
            variables = {
                "params": params,
                SPARSE_EMB_COLLECTION: _nest_rows(template, embs),
            }
            preds = state.apply_fn(
                variables, batch["features"], training=True,
                rngs={"dropout": rng} if rng is not None else None,
                mutable=False,
            )
            return _call_loss(
                loss_fn, batch["labels"], preds, batch["mask"]
            )

        grad_fn = jax.value_and_grad(compute_loss, argnums=(0, 1))
        loss, (param_grads, emb_grads) = grad_fn(state.params, embs)

        new_tables = dict(state.tables)
        new_slots = dict(state.slot_tables)
        new_steps = dict(state.table_steps)
        for spec in specs:
            ragged, uids, inverse = lookups[spec.name]
            rows_ct = _row_grads(
                emb_grads[spec.name], uids, inverse, ragged,
                spec.combiner,
            )
            step_count = state.table_steps[spec.name] + 1
            if packed_slots:
                table = sparse_apply_packed(
                    row_opt, state.tables[spec.name], uids, rows_ct,
                    step_count, spec.dim,
                )
                slots = state.slot_tables[spec.name]  # {} — in-row
            elif spec.name in sharded_tables:
                table, slots = sparse_apply_sharded(
                    row_opt, state.tables[spec.name],
                    state.slot_tables[spec.name], uids, rows_ct,
                    step_count, mesh, axis, use_pallas=use_pallas,
                    interpret=interpret,
                )
            else:
                table, slots = sparse_apply(
                    row_opt, state.tables[spec.name],
                    state.slot_tables[spec.name], uids, rows_ct,
                    step=step_count, use_pallas=use_pallas,
                    interpret=interpret,
                )
            new_tables[spec.name] = table
            new_slots[spec.name] = slots
            new_steps[spec.name] = step_count

        state = state.apply_gradients(
            grads=param_grads, tables=new_tables,
            slot_tables=new_slots, table_steps=new_steps,
        )
        return state, {"loss": loss}

    return train_step


def init_sparse_state(
    model, tx, example_batch, specs: Tuple[TableSpec, ...],
    row_opt: RowOptimizer, seed: int = 0,
    table_dtype=jnp.float32, packed_slots: bool = False,
) -> Tuple[SparseTrainState, Any]:
    """Trace the model (zero embeddings in the collection), attach
    deterministic tables + zero slots; returns ``(state, template)``
    where template is the model's sparse_emb collection structure
    (pass to ``build_sparse_train_step``). Table init is seeded
    uniform, so elastic relaunches reproduce. With ``packed_slots``
    each table leaf is the (V, D*(1+n_slots)) packed store (identical
    main-table values — slots concatenate onto the same seeded init)
    and ``slot_tables`` entries are empty."""
    from elasticdl_tpu.embedding.host_engine import _iter_leaves

    rng = jax.random.PRNGKey(seed)
    variables = model.init(
        {"params": rng, "dropout": rng}, example_batch["features"],
        training=False,
    )
    template = variables.get(SPARSE_EMB_COLLECTION, {})
    names = [k for k, _ in _iter_leaves(template)]
    missing = {s.name for s in specs} - set(names)
    if missing:
        raise ValueError(
            f"model declares no SparseEmbed for tables {missing}"
        )

    tables = {}
    slot_tables = {}
    table_steps = {}
    for i, spec in enumerate(specs):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        scale = 1.0 / np.sqrt(spec.dim)
        main = jax.random.uniform(
            key, (spec.vocab, spec.dim), table_dtype, -scale, scale
        )
        slots = init_slot_tables(
            row_opt, spec.vocab, spec.dim, table_dtype
        )
        if packed_slots:
            tables[spec.name] = pack_table(main, slots, row_opt)
            slot_tables[spec.name] = {}
        else:
            tables[spec.name] = main
            slot_tables[spec.name] = slots
        table_steps[spec.name] = jnp.zeros((), jnp.int32)

    state = SparseTrainState(
        step=jnp.zeros((), jnp.int32),
        apply_fn=model.apply,
        params=variables["params"],
        batch_stats={},
        tx=tx,
        opt_state=tx.init(variables["params"]),
        rng=jax.random.PRNGKey(seed),
        tables=tables,
        slot_tables=slot_tables,
        table_steps=table_steps,
    )
    return state, template


class DeviceSparseRunner(StepRunner):
    """The runner seam (core/step.py::StepRunner) for device-tier sparse
    models — the deployment adapter the host tier has in HostStepRunner.

    With ``mesh``, every TableSpec table over ``partition_threshold_bytes``
    whose vocab divides the ``axis`` size is ROW-SHARDED over the mesh
    (+its slot tables, co-sharded — reference slot co-location,
    ``ps/parameters.py:156``); the batch shards over the same ``axis``
    (data parallel), dense params replicate, and the step is jitted with
    explicit in/out shardings. This is the multi-chip form of the
    reference's N-parameter-server sparse plane
    (``docs/designs/parameter_server.md`` "Model Parameter Partition"):
    row ranges instead of id%N, XLA collectives over ICI instead of
    gRPC pull/push."""

    can_resize = True

    def __init__(self, specs: Tuple[TableSpec, ...],
                 row_opt: RowOptimizer, use_pallas: str = "auto",
                 interpret: Optional[bool] = None,
                 mesh=None, axis: str = "dp",
                 partition_threshold_bytes: int =
                 DEFAULT_PARTITION_THRESHOLD_BYTES,
                 packed_slots: bool = False):
        # packed_slots: slots live inside the table rows so the apply
        # is one gather + one scatter (optimizer.sparse_apply_packed —
        # the measured single-chip scatter-latency win). Single-mesh
        # only; checkpoints are layout-specific (a packed checkpoint
        # does not restore into a split-table runner or vice versa —
        # same class of opt-in as resnet50's s2d stem).
        if packed_slots and mesh is not None:
            raise ValueError(
                "packed_slots is single-mesh only (row-sharded tables "
                "keep the split layout)"
            )
        if packed_slots and use_pallas in ("always", "fused"):
            raise ValueError(
                "packed_slots uses the XLA gather/scatter path; "
                f"use_pallas={use_pallas!r} pins split-table kernels"
            )
        self.packed_slots = bool(packed_slots)
        self.specs = tuple(specs)
        self.row_opt = row_opt
        self.use_pallas = use_pallas
        # interpret=None: auto — real kernels on TPU, interpreter off
        # TPU (CPU tests) only when a kernel path is forced.
        if interpret is None:
            interpret = (
                use_pallas in ("always", "fused")
                and jax.default_backend() != "tpu"
            )
        self.interpret = interpret
        self.mesh = mesh
        self.axis = axis
        self.partition_threshold_bytes = int(partition_threshold_bytes)
        self.sharded_tables = self._sharded_tables_for(mesh)
        self._template = None
        self._state_shardings = None
        self._batch_shardings = None
        self._abstract_batch = None

    def _sharded_tables_for(self, mesh) -> frozenset:
        """Which tables row-shard on ``mesh``: vocab divides the axis
        and the table clears the size threshold. Re-derived on resize —
        a table that divided dp4 may not divide dp3."""
        if mesh is None:
            return frozenset()
        n = mesh.shape[self.axis]
        return frozenset(
            s.name for s in self.specs
            if s.vocab % n == 0
            and s.vocab * s.dim * 4 > self.partition_threshold_bytes
        )

    def _table_sharding(self, name):
        spec = P(self.axis, None) if name in self.sharded_tables else P()
        return NamedSharding(self.mesh, spec)

    def state_shardings(self, state):
        """Pytree of NamedShardings for a (possibly abstract)
        SparseTrainState: sharded tables/slots on P(axis, None),
        everything else replicated."""
        rep = NamedSharding(self.mesh, P())
        sh = jax.tree.map(lambda _: rep, state)
        return sh.replace(
            tables={
                k: self._table_sharding(k) for k in state.tables
            },
            slot_tables={
                k: jax.tree.map(
                    lambda _, s=self._table_sharding(k): s, v
                )
                for k, v in state.slot_tables.items()
            },
        )

    def init_state(self, model, tx, batch, seed: int = 0):
        if self.mesh is None:
            state, self._template = init_sparse_state(
                model, tx, batch, self.specs, self.row_opt, seed=seed,
                packed_slots=self.packed_slots,
            )
            return state

        # Build under jit with explicit out_shardings so a table sized
        # for the whole mesh never materializes on one device
        # (MeshRunner.init_state's pattern).
        def make_state():
            state, template = init_sparse_state(
                model, tx, batch, self.specs, self.row_opt, seed=seed
            )
            return state, template

        abstract_state, abstract_template = jax.eval_shape(make_state)
        shardings = self.state_shardings(abstract_state)
        self._state_shardings = shardings
        rep = NamedSharding(self.mesh, P())
        state, template = jax.jit(
            make_state,
            out_shardings=(
                shardings,
                jax.tree.map(lambda _: rep, abstract_template),
            ),
        )()
        self._template = template
        self._batch_shardings = self._batch_shardings_for(batch)
        # Shape-only copy of the example batch so resize() can rebuild
        # the batch shardings against the new mesh.
        self._abstract_batch = jax.eval_shape(lambda b: b, batch)
        return state

    def _batch_shardings_for(self, batch):
        return jax.tree.map(
            lambda leaf: NamedSharding(
                self.mesh,
                P(self.axis) if np.ndim(leaf) >= 1 else P(),
            ),
            batch,
        )

    def place_state(self, state):
        """Re-place restored host arrays with the runner's shardings
        (checkpoint restore would otherwise land a mesh-sized table on
        one device) — MeshRunner.place_state's contract."""
        if self.mesh is None:
            return state
        shardings = self._state_shardings or self.state_shardings(state)
        return jax.device_put(state, shardings)

    def resize(self, new_mesh, state=None):
        """Checkpointless live reshard onto ``new_mesh``
        (MeshRunner.resize's contract, sparse edition): every
        row-sharded table's per-device row range changes — dp4 → dp2
        doubles each shard — and the co-sharded slot tables move with
        it, with no disk round trip. Compiled steps baked the old
        shardings and must be rebuilt by the caller."""
        from elasticdl_tpu.parallel import reshard as reshard_lib

        self.mesh = new_mesh
        self.sharded_tables = self._sharded_tables_for(new_mesh)
        self._state_shardings = None
        if self._abstract_batch is not None:
            self._batch_shardings = self._batch_shardings_for(
                self._abstract_batch
            )
        if state is None:
            return None

        def shardings_fn(abstract):
            self._state_shardings = self.state_shardings(abstract)
            return self._state_shardings

        return reshard_lib.live_reshard(state, shardings_fn)

    def _step_body(self, loss_fn):
        return build_sparse_train_step(
            loss_fn, self.specs, self.row_opt, self._template,
            use_pallas=self.use_pallas, interpret=self.interpret,
            mesh=self.mesh, axis=self.axis,
            sharded_tables=self.sharded_tables,
            packed_slots=self.packed_slots,
        )

    def train_step(self, loss_fn):
        return jit_step(
            self._step_body(loss_fn), self._state_shardings,
            self._batch_shardings,
        )

    def train_multi_step(self, loss_fn):
        return jit_task(self._step_body(loss_fn), self._state_shardings)

    def eval_step(self):
        from elasticdl_tpu.embedding.host_engine import _nest_rows
        from elasticdl_tpu.ops.pallas_embedding import (
            lookup_combine,
            lookup_combine_sharded,
        )

        specs = self.specs
        template = self._template

        def step(state, batch):
            embs = {}
            for spec in specs:
                ragged = _ragged(batch["features"][spec.feature_key])
                if self.packed_slots:
                    rows = jnp.take(
                        state.tables[spec.name], ragged.ids, axis=0
                    )[..., :spec.dim]
                    embs[spec.name] = combine(
                        rows, ragged.weights, spec.combiner
                    )
                elif spec.name in self.sharded_tables:
                    embs[spec.name] = lookup_combine_sharded(
                        state.tables[spec.name], ragged.ids,
                        ragged.weights, spec.combiner, self.mesh,
                        self.axis, interpret=self.interpret,
                        force_pallas=(self.use_pallas == "always"),
                        force_xla=(self.use_pallas == "never"),
                    )
                else:
                    embs[spec.name] = lookup_combine(
                        state.tables[spec.name], ragged.ids,
                        ragged.weights, spec.combiner,
                        interpret=self.interpret,
                        force_pallas=(self.use_pallas == "always"),
                        force_xla=(self.use_pallas == "never"),
                    )
            variables = {
                "params": state.params,
                SPARSE_EMB_COLLECTION: _nest_rows(template, embs),
            }
            return state.apply_fn(
                variables, batch["features"], training=False,
                mutable=False,
            )

        return jax.jit(step)
