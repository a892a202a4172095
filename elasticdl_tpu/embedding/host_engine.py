"""Host-tier embedding training engine (>HBM tables, end to end).

SURVEY.md §7 stage 6 "hard part #2": dynamic-shape id batches vs XLA
static shapes. The reference trains huge tables by keeping rows on
parameter-server pods and shipping row batches over gRPC
(``worker/worker.py:362-391`` pull, ``:570-580`` scatter,
``ps/optimizer_wrapper.py:143`` lookup-apply-writeback). Here the same
capability is mesh-native:

- the table lives in host RAM (`EmbeddingTable` or the C++
  `NativeEmbeddingTable` via `make_host_table`),
- per batch, ids are deduplicated host-side and their rows pulled into a
  device array whose leading dim is **bucket-padded** (next power of two)
  so the jit step compiles once per bucket, not once per batch,
- the model reads those rows through the ``host_rows`` flax collection
  (`HostEmbedding` layer) and indexes them with the batch's inverse map,
- the step function differentiates w.r.t. the row block; the engine
  scatters the row gradients back through a row optimizer
  (`HostOptimizerWrapper` / native); slot tables and step counters ride
  the checkpoint via `HostStepRunner.host_tables`,
- `prepared_batches` double-buffers for engine-driven loops: rows for
  batch N+1 are pulled on a background thread while batch N trains.
  (`HostStepRunner` — the Worker adapter — prepares synchronously
  inside each step, since the worker hands it one batch at a time.)

Scope: one engine = one process's tables. In-process multi-worker jobs
share a single runner (engine lock serializes host access); multi-
PROCESS jobs share rows through `embedding/row_service.py` — the
Pserver sparse role over RPC (`--row_service_addr`).
"""

import queue
import threading
import time
import weakref
from typing import Callable, Dict, Iterable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from elasticdl_tpu.core.step import StepRunner, _call_loss
from elasticdl_tpu.embedding.combiner import RaggedIds, combine


class PreparedBatch(NamedTuple):
    """A batch whose host half is already done (rows pulled, ids
    inverse-mapped): what ``HostStepRunner.iter_prepared`` yields so
    pulls for batch N+1 can run while batch N's device step executes.

    ``device_rows``/``device_batch`` are filled by the pipeline's
    device-placement stage (``prepared_batches(place_rows=True)``):
    the row blocks and batch already ``jax.device_put`` while the
    previous batch steps, so the jit call consumes resident buffers
    instead of paying the host→device copy on the critical path. None
    (the default) means the step transfers them itself."""

    raw: dict       # the original batch (multihost dummies, init)
    batch: dict     # features with inverse maps substituted
    host_rows: dict
    uniques: dict
    device_rows: Optional[dict] = None
    device_batch: Optional[dict] = None

MIN_BUCKET = 8

# Collection name through which the engine hands the per-batch row block
# to the model.
HOST_ROWS_COLLECTION = "host_rows"


def bucket_size(n: int, min_bucket: int = MIN_BUCKET) -> int:
    """Next power of two >= n (>= min_bucket): bounds the number of
    distinct compiled shapes to O(log vocab) per table."""
    b = min_bucket
    while b < n:
        b *= 2
    return b


class HostEmbedding(nn.Module):
    """Embedding lookup over the engine-provided per-batch row block.

    Input is the batch's **inverse map** (positions -> slots in the row
    block), produced by ``HostEmbeddingEngine.prepare_batch`` — not raw
    ids. Supports the same dense / RaggedIds+combiner forms as the
    in-HBM `Embedding` layer.
    """

    table_name: str
    output_dim: int
    combiner: Optional[str] = None

    @nn.compact
    def __call__(self, inverse):
        rows = self.variable(
            HOST_ROWS_COLLECTION,
            self.table_name,
            lambda: jnp.zeros((MIN_BUCKET, self.output_dim), jnp.float32),
        ).value
        if isinstance(inverse, RaggedIds):
            if self.combiner is None:
                raise ValueError("RaggedIds input requires a combiner")
            emb = jnp.take(rows, inverse.ids, axis=0)
            return combine(emb, inverse.weights, self.combiner)
        return jnp.take(rows, jnp.asarray(inverse), axis=0)


def host_rows_template(model, example_batch, seed: int = 0):
    """The model's ``host_rows`` collection structure (nested by module
    path, as flax scopes it). The engine speaks flat {table: rows}; the
    step nests/flattens against this template. Table names must be
    unique across the model."""
    variables = model.init(
        {"params": jax.random.PRNGKey(seed)},
        example_batch["features"], training=False,
    )
    template = variables.get(HOST_ROWS_COLLECTION, {})
    names = [k for k, _ in _iter_leaves(template)]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise ValueError(
            f"host table names must be unique across the model: {dupes}"
        )
    return template


def _iter_leaves(node, out=None):
    out = [] if out is None else out
    for key, value in node.items():
        if isinstance(value, dict):
            _iter_leaves(value, out)
        else:
            out.append((key, value))
    return out


def _nest_rows(template, flat):
    """Flat {table: rows} -> the template's nested module-path shape."""
    return {
        key: (_nest_rows(value, flat) if isinstance(value, dict)
              else flat[key])
        for key, value in template.items()
    }


def build_host_train_step(loss_fn: Callable, rows_template) -> Callable:
    """Build ``(state, batch, host_rows) -> (state, row_grads, metrics)``.

    Same contract as core/step.build_train_step plus the host row block:
    ``host_rows`` (flat {table: (bucket, dim)}) enters as a
    differentiated argument; its gradients come back (flat) for the
    engine to scatter into the host store. ``rows_template`` comes from
    ``host_rows_template``. BatchNorm models are supported the same way
    as the core step (running stats frozen on padded batches).
    """
    def train_step(state, batch, host_rows):
        state, rng = state.next_rng()

        def compute_loss(params, host_rows):
            variables = {
                "params": params,
                HOST_ROWS_COLLECTION: _nest_rows(rows_template, host_rows),
            }
            has_batch_stats = bool(state.batch_stats)
            if has_batch_stats:
                variables["batch_stats"] = state.batch_stats
            mutable = ["batch_stats"] if has_batch_stats else False
            out = state.apply_fn(
                variables,
                batch["features"],
                training=True,
                rngs={"dropout": rng} if rng is not None else None,
                mutable=mutable,
            )
            if mutable:
                preds, updates = out
                new_stats = updates.get("batch_stats", state.batch_stats)
            else:
                preds, new_stats = out, state.batch_stats
            loss = _call_loss(loss_fn, batch["labels"], preds, batch["mask"])
            return loss, new_stats

        grad_fn = jax.value_and_grad(compute_loss, argnums=(0, 1),
                                     has_aux=True)
        (loss, new_stats), (param_grads, row_grads) = grad_fn(
            state.params, host_rows
        )
        if state.batch_stats:
            is_full = jnp.all(batch["mask"] > 0)
            new_stats = jax.tree.map(
                lambda new, old: jnp.where(is_full, new, old),
                new_stats, state.batch_stats,
            )
        state = state.apply_gradients(
            grads=param_grads, batch_stats=new_stats
        )
        return state, row_grads, {"loss": loss}

    return jax.jit(train_step, donate_argnums=(0,))


def build_host_eval_step(rows_template) -> Callable:
    """Build ``(state, batch, host_rows) -> predictions`` (host-tier
    counterpart of core/step.build_eval_step)."""

    def eval_step(state, batch, host_rows):
        variables = {
            "params": state.params,
            HOST_ROWS_COLLECTION: _nest_rows(rows_template, host_rows),
        }
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        return state.apply_fn(
            variables, batch["features"], training=False, mutable=False
        )

    return jax.jit(eval_step)


class HostEmbeddingEngine:
    """Pull/dedup/pad rows per batch; scatter row grads back after.

    ``tables``:   {name: EmbeddingTable-like} (host or native),
    ``optimizer``: a HostOptimizerWrapper-compatible object
                  (``apply_gradients(table, ids, grads)``),
    ``id_keys``:  {table_name: feature_key} — which feature carries the
                  raw ids for each table; prepare_batch replaces it with
                  the inverse map.
    """

    def __init__(self, tables: Dict, optimizer, id_keys: Dict[str, str],
                 metrics_registry=None, table_fanout: bool = True):
        # Serializes host-side table access: in-process multi-worker
        # jobs share ONE engine (threads), and neither the dict table
        # nor the C++ open-addressing row map (which rehashes on
        # growth) is safe under concurrent mutation. The device step
        # itself still runs outside the lock.
        #
        # Stores that are safe under concurrent IO — the RPC row
        # service, whose server serializes internally (the reference Go
        # PS served pulls concurrently with pushes by design,
        # ps/server.go) — declare ``concurrent_safe = True``; pulls and
        # pushes then skip the lock so a prefetching pull can be in
        # flight while the applier pushes the previous step's grads.
        self.lock = threading.RLock()
        self.concurrent_io = (
            all(getattr(t, "concurrent_safe", False)
                for t in tables.values())
            and getattr(optimizer, "concurrent_safe", False)
        )
        unknown = set(id_keys) - set(tables)
        if unknown:
            raise ValueError(f"id_keys reference unknown tables {unknown}")
        keys = list(id_keys.values())
        dupes = {k for k in keys if keys.count(k) > 1}
        if dupes:
            # Two tables sharing one feature would see the first table's
            # inverse map as the second's raw ids — silent corruption.
            raise ValueError(
                f"feature keys must be unique across tables: {dupes}"
            )
        self.tables = tables
        self.optimizer = optimizer
        self.id_keys = id_keys
        # table_fanout=False pins the serial per-table loop — the
        # pre-fan-out shape (benchmark baseline; also an escape hatch
        # if a store misdeclares concurrent_safe).
        self.table_fanout = bool(table_fanout)
        # Per-TABLE fan-out pool (lazy; only built for multi-table
        # engines over concurrent-safe stores): prepare_batch pulls and
        # apply_row_grads pushes fan out per table, so a DeepFM-style
        # batch pays max(table pull/push), not sum. Sized for one wave
        # of pulls AND one wave of pushes concurrently (the prefetch
        # thread prepares batch N+1 while the applier pushes batch N's
        # grads). This pool is DISTINCT from the sharded-client pool in
        # row_service.py on purpose — a table-level task there would
        # occupy a worker while waiting on its own shard sub-tasks
        # (nested submission deadlocks a shared bounded pool).
        self._table_pool = None
        self._table_pool_lock = threading.Lock()
        # Telemetry: lookup/update latency, row traffic, and the dedup
        # ("cache hit") ratio — total vs unique ids per batch. Rows
        # materialized is a pull-time gauge over the live tables.
        from elasticdl_tpu.observability import default_registry

        registry = metrics_registry or default_registry()
        self._m_lookup = registry.histogram(
            "embedding_lookup_seconds",
            "Host row pull + dedup + pad latency per batch",
        )
        # Phase split of the lookup monolith (matching dedup/row_pull/
        # pad child spans are emitted inside prepare_batch): the
        # critical-path report and dashboards can attribute INSIDE
        # prepare — "lookup is slow" becomes "the pull RPC is slow" or
        # "dedup is slow", which point at different fixes.
        self._m_dedup = registry.histogram(
            "embedding_dedup_seconds",
            "np.unique dedup latency per table per batch",
        )
        self._m_pull = registry.histogram(
            "embedding_row_pull_seconds",
            "Row fetch (store get / pull RPC) latency per table per "
            "batch",
        )
        self._m_pad = registry.histogram(
            "embedding_pad_seconds",
            "Bucket-pad + inverse-map assembly latency per table per "
            "batch",
        )
        self._m_device_put = registry.histogram(
            "embedding_device_put_seconds",
            "Device placement latency per prepared batch (the "
            "pipeline's jax.device_put stage)",
        )
        self._m_update = registry.histogram(
            "embedding_update_seconds",
            "Row-gradient scatter/apply latency per step",
        )
        self._m_ids = registry.counter(
            "embedding_lookup_ids_total",
            "Raw ids looked up (pre-dedup)",
        )
        self._m_unique = registry.counter(
            "embedding_lookup_unique_ids_total",
            "Unique rows actually pulled (1 - unique/raw = batch dedup "
            "hit rate)",
        )
        self._m_rows_updated = registry.counter(
            "embedding_rows_updated_total",
            "Rows receiving gradient updates",
        )
        # weakref: the registry is process-global and outlives engines;
        # a strong closure over self would pin the (larger-than-HBM)
        # host tables of every discarded engine for the process life.
        self_ref = weakref.ref(self)

        def _rows_materialized() -> float:
            engine = self_ref()
            if engine is None:
                return 0.0
            return sum(
                t.num_rows for t in engine.tables.values()
                if hasattr(t, "num_rows")
            )

        registry.gauge(
            "embedding_rows_materialized",
            "Rows resident across host tables (lazy-init high-water)",
        ).set_function(_rows_materialized)

    def prepare_batch(self, batch: dict) -> Tuple[dict, dict, dict]:
        """Host-side half of the step (runs off-thread under
        ``prepared_batches``): dedup ids, pull rows, bucket-pad.

        Returns (batch', host_rows, uniques):
        - batch' — ``batch`` with each id feature replaced by its int32
          inverse map into the row block,
        - host_rows — {table: (bucket, dim) float32}; rows[u:] are zero
          padding whose grads are dropped,
        - uniques — {table: (unique_ids, u)} for apply_row_grads.

        Tracing: each table emits ``dedup`` / ``row_pull`` / ``pad``
        phase spans. Called under an open span (the synchronous path,
        where prepare runs inside ``device_step``) they become its
        direct children, so the critical-path step breakdown names the
        pull; called from a pipeline thread (no ambient span) they nest
        under a fresh ``prepare_batch`` root — the span the overlap
        checker (tools/check_overlap.py) matches against concurrent
        device steps.
        """
        from elasticdl_tpu.observability import tracing

        t0 = time.monotonic()
        try:
            ctx = tracing.current_ctx()
            if ctx is not None:
                if self.concurrent_io:
                    return self._prepare_batch_locked(batch, ctx)
                with self.lock:
                    return self._prepare_batch_locked(batch, ctx)
            with tracing.span(
                "prepare_batch", tables=len(self.id_keys)
            ) as sp:
                ctx = sp.ctx()
                if self.concurrent_io:
                    return self._prepare_batch_locked(batch, ctx)
                with self.lock:
                    return self._prepare_batch_locked(batch, ctx)
        finally:
            self._m_lookup.observe(time.monotonic() - t0)

    def _get_table_pool(self):
        from concurrent.futures import ThreadPoolExecutor

        with self._table_pool_lock:
            if self._table_pool is None:
                self._table_pool = ThreadPoolExecutor(
                    max_workers=min(2 * len(self.id_keys), 16),
                    thread_name_prefix="table-fanout",
                )
                # Discarded engines (chaos relaunches build one per
                # replacement worker) must not leak their pool threads
                # for the process life; close() is explicit, the
                # finalizer covers engines that are simply dropped.
                weakref.finalize(
                    self, self._table_pool.shutdown, wait=False
                )
            return self._table_pool

    def close(self):
        """Shut down the per-table fan-out pool (idempotent). Engines
        are also finalizer-cleaned on GC; call this when discarding an
        engine deterministically (worker teardown, tests)."""
        with self._table_pool_lock:
            pool, self._table_pool = self._table_pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def _prepare_table(self, table_name, ids, ctx):
        """One table's prepare: dedup → pull → pad, phase-timed. Pure
        per-table work (no shared mutable state beyond the thread-safe
        metrics/tables), so the fan-out path runs it on pool threads."""
        from elasticdl_tpu.observability import tracing

        ragged = isinstance(ids, RaggedIds)
        raw = np.asarray(ids.ids if ragged else ids)
        t0 = time.monotonic()
        with tracing.child_span("dedup", ctx, table=table_name):
            uniq, inverse = np.unique(raw, return_inverse=True)
        t1 = time.monotonic()
        self._m_dedup.observe(t1 - t0)
        u = len(uniq)
        self._m_ids.inc(raw.size)
        self._m_unique.inc(u)
        table = self.tables[table_name]
        with tracing.child_span("row_pull", ctx, table=table_name,
                                rows=u):
            pulled = table.get(uniq)
        t2 = time.monotonic()
        self._m_pull.observe(t2 - t1)
        with tracing.child_span("pad", ctx, table=table_name):
            rows = np.zeros((bucket_size(u), table.dim), np.float32)
            rows[:u] = pulled
            inv = inverse.reshape(raw.shape).astype(np.int32)
        self._m_pad.observe(time.monotonic() - t2)
        feature = (
            RaggedIds(ids=inv, weights=ids.weights) if ragged else inv
        )
        return feature, rows, (uniq, u)

    def _prepare_batch_locked(self, batch, ctx=None):
        if not isinstance(batch["features"], dict):
            raise TypeError(
                "host-tier batches need dict features (id_keys names the "
                "feature carrying each table's ids); got "
                f"{type(batch['features']).__name__}"
            )
        features = dict(batch["features"])
        host_rows, uniques = {}, {}
        items = list(self.id_keys.items())
        if len(items) > 1 and self.concurrent_io and self.table_fanout:
            # Parallel per-table fan-out: a multi-table batch pays
            # max(table pull), not sum. Only over concurrent-safe
            # stores (the RPC row plane) — a locked local store would
            # serialize the futures on self.lock anyway, and this
            # method already holds it then.
            pool = self._get_table_pool()
            futures = [
                (name, key,
                 pool.submit(self._prepare_table, name, features[key],
                             ctx))
                for name, key in items
            ]
            for name, key, future in futures:
                feature, rows, uniq_u = future.result()
                features[key] = feature
                host_rows[name] = rows
                uniques[name] = uniq_u
        else:
            for name, key in items:
                feature, rows, uniq_u = self._prepare_table(
                    name, features[key], ctx
                )
                features[key] = feature
                host_rows[name] = rows
                uniques[name] = uniq_u
        out = dict(batch)
        out["features"] = features
        return out, host_rows, uniques

    def place_on_device(self, prepared: PreparedBatch) -> PreparedBatch:
        """The pipeline's device-placement stage: ``jax.device_put``
        the row blocks and the batch for an upcoming step while the
        current one executes, so the jit call consumes already-resident
        buffers (``device_rows``/``device_batch``)."""
        from elasticdl_tpu.observability import tracing

        t0 = time.monotonic()
        with tracing.span("device_put", tables=len(prepared.host_rows)):
            device_rows = jax.device_put(prepared.host_rows)
            device_batch = jax.device_put(prepared.batch)
        self._m_device_put.observe(time.monotonic() - t0)
        return prepared._replace(
            device_rows=device_rows, device_batch=device_batch
        )

    def apply_row_grads(self, row_grads: dict, uniques: dict) -> None:
        """Scatter the step's row gradients into the host tables
        (lookup-apply-writeback, reference optimizer_wrapper.py:143)."""
        t0 = time.monotonic()
        try:
            if self.concurrent_io:
                self._apply_row_grads_inner(row_grads, uniques)
                return
            with self.lock:
                self._apply_row_grads_inner(row_grads, uniques)
        finally:
            self._m_update.observe(time.monotonic() - t0)

    def _apply_row_grads_inner(self, row_grads, uniques):
        items = list(uniques.items())
        if len(items) > 1 and self.concurrent_io and self.table_fanout:
            # Same max-not-sum fan-out as prepare: tables are disjoint
            # row spaces, so cross-table applies commute; per-table
            # FIFO is preserved because the (single) applier joins one
            # batch's futures before starting the next batch's.
            pool = self._get_table_pool()
            futures = [
                pool.submit(self._apply_table, name, uniq, u,
                            row_grads[name])
                for name, (uniq, u) in items
            ]
            for f in futures:
                f.result()
        else:
            for name, (uniq, u) in items:
                self._apply_table(name, uniq, u, row_grads[name])

    def _apply_table(self, table_name, uniq, u, grads):
        grads = np.asarray(grads)[:u]
        self._m_rows_updated.inc(u)
        self.optimizer.apply_gradients(
            self.tables[table_name], uniq, grads
        )

    def prepared_batches(self, batches: Iterable[dict], depth: int = 2,
                         place_rows: bool = False):
        """Double-buffered iterator of ``PreparedBatch``: rows for
        upcoming batches are pulled while the current batch trains
        (data/prefetch.py plays the same role for record decode).
        ``place_rows`` adds the device-placement stage: a second
        pipeline thread ``jax.device_put``s each prepared batch's row
        blocks (+batch) so the step consumes resident buffers.

        STALENESS WINDOW: a prefetched batch can read rows up to
        ``depth + 1`` apply_row_grads behind on ids it shares with
        in-flight batches — the reference async PS pull's
        relaxed-consistency window (async_sgd.md), widened by the
        prefetch depth. The device stage widens it by up to 2 more
        batches (its queue slot plus the transfer in flight): with
        ``place_rows`` the bound is ``depth + 3``. Shape unchanged —
        only the count of in-flight batches a shared id's pull may
        trail by.

        Returns a PrefetchIterator; ``close()`` it (or use as a context
        manager) when abandoning mid-stream — closing the last stage
        tears down the whole chain. (``HostStepRunner.iter_prepared``
        is a thin delegate — ONE pull-ahead implementation.)"""
        from elasticdl_tpu.data.prefetch import prefetch, staged

        prepared = prefetch(
            (PreparedBatch(b, *self.prepare_batch(b)) for b in batches),
            depth=depth,
        )
        if not place_rows:
            return prepared
        return staged(prepared, self.place_on_device, depth=1)


class HostStepRunner(StepRunner):
    """Step-runner adapter: drive host-tier models through the standard
    Worker/MiniCluster loop (the runner seam, core/step.py::StepRunner).
    prepare/apply happen inside the wrapped step so the worker's
    (state, batch) contract is unchanged —
    the role the reference worker's PS stubs played inline
    (worker.py:869-908), collapsed into the runner.

    Overlap (VERDICT r2 #7 — the reference's Go PS served pulls
    concurrently with training by design):

    - **Async apply**: the step dispatches the device program and hands
      (row_grads, uniques) to a single background applier thread; the
      device->host grad transfer and the lookup-apply-writeback (an RPC
      round trip for row-service engines) leave the critical path.
      Writes stay FIFO (one thread); reads that must see them —
      checkpoints via ``host_tables``, eval, init — flush first. The
      relaxed window (a pull may be one unapplied step behind on shared
      ids) is the reference async-PS consistency model (async_sgd.md).
    - **Pull-ahead**: ``iter_prepared`` wraps a batch stream so rows
      for upcoming batches are pulled on a prefetch thread while the
      current batch trains; the Worker task loop uses it when present.
    - **Device double-buffering**: a second pipeline stage
      ``jax.device_put``s batch N+1's row blocks while batch N steps,
      so the jit call consumes resident buffers (the host→device copy
      leaves the critical path too). Staleness-window math on
      ``prepared_batches``.
    """

    # Host-side work per batch cannot fuse into one XLA program.
    can_fuse = False

    def __init__(self, engine: HostEmbeddingEngine,
                 async_apply: bool = True):
        self.engine = engine
        self._template = None
        self._model = None
        self._async_apply = async_apply
        self._apply_queue = None
        self._apply_thread = None
        self._apply_error = None

    # ---- async applier --------------------------------------------------

    def _applier_loop(self):
        while True:
            item = self._apply_queue.get()
            try:
                if item is None:
                    return
                row_grads, uniques = item
                try:
                    self.engine.apply_row_grads(
                        {k: np.asarray(v) for k, v in row_grads.items()},
                        uniques,
                    )
                except BaseException as exc:  # surfaced on next step/flush
                    self._apply_error = exc
            finally:
                self._apply_queue.task_done()

    def _enqueue_apply(self, row_grads, uniques):
        if self._apply_thread is None:
            # Bounded depth 2: the applier can fall at most one step
            # behind before the trainer blocks — keeps the staleness
            # window at the documented one step.
            self._apply_queue = queue.Queue(maxsize=2)
            self._apply_thread = threading.Thread(
                target=self._applier_loop, daemon=True,
                name="host-row-applier",
            )
            self._apply_thread.start()
        self._raise_pending()
        self._apply_queue.put((row_grads, uniques))

    def _raise_pending(self):
        if self._apply_error is not None:
            exc, self._apply_error = self._apply_error, None
            raise exc

    def flush(self):
        """Wait for every enqueued row apply to land (checkpoint/eval/
        init read barriers); re-raises applier failures."""
        if self._apply_queue is not None:
            self._apply_queue.join()
        self._raise_pending()

    @property
    def pull_ahead(self) -> bool:
        """Whether the Worker task loop should wrap batches in
        ``iter_prepared``: only under async apply — a synchronous
        runner (``async_apply=False``) promised exact semantics, and
        pull-ahead would reintroduce the stale-read window."""
        return self._async_apply

    def iter_prepared(self, batches: Iterable[dict], depth: int = 2,
                      place_rows: bool = True):
        """Pull-ahead iterator of ``PreparedBatch`` for the Worker task
        loop (delegates to the engine's prepared_batches — one
        implementation); ``close()`` it when abandoning mid-stream.
        ``depth`` is the pull-ahead queue (--host_prefetch_depth);
        ``place_rows`` (default on — this runner feeds a device step)
        adds the device double-buffering stage, widening the staleness
        window as documented on ``prepared_batches``."""
        return self.engine.prepared_batches(
            batches, depth=max(1, int(depth)), place_rows=place_rows
        )

    @property
    def host_tables(self) -> Dict:
        """Everything the checkpoint must carry: main tables PLUS the
        row optimizer's slot tables and per-table step counters (Adam
        bias correction must not restart at 1 after a relaunch). Pass
        to CheckpointHook(host_tables=...) / restore_from_dir. Views
        are lock-guarded so checkpoint snapshots don't race training
        threads sharing the engine. None for remote engines
        (embedding/row_service.py): the row SERVICE owns its rows'
        checkpointing, like the reference PS did."""
        if getattr(self.engine, "remote", False):
            return None
        return locked_checkpoint_tables(
            self.engine.tables, self.engine.optimizer, self.engine.lock,
            flush=self.flush,
        )

    def init_state(self, model, tx, batch, seed: int = 0):
        self.flush()
        prepared, _, _ = self.engine.prepare_batch(batch)
        self._template = host_rows_template(model, prepared, seed=seed)
        self._model = model
        return super().init_state(model, tx, prepared, seed=seed)

    def train_step(self, loss_fn: Callable) -> Callable:
        host_step = build_host_train_step(loss_fn, self._template)
        engine = self.engine

        def step(state, batch):
            if isinstance(batch, PreparedBatch):
                # Device-resident buffers when the pipeline's placement
                # stage ran: the jit call then pays no host→device copy.
                prepared = (
                    batch.device_batch if batch.device_batch is not None
                    else batch.batch
                )
                host_rows = (
                    batch.device_rows if batch.device_rows is not None
                    else batch.host_rows
                )
                uniques = batch.uniques
            else:
                prepared, host_rows, uniques = engine.prepare_batch(batch)
            state, row_grads, metrics = host_step(
                state, prepared, host_rows
            )
            if self._async_apply:
                # Device dispatch is async too: the applier thread
                # blocks on the grads transfer, not the caller.
                self._enqueue_apply(row_grads, uniques)
            else:
                engine.apply_row_grads(
                    {k: np.asarray(v) for k, v in row_grads.items()},
                    uniques,
                )
            return state, metrics

        return step

    def eval_step(self) -> Callable:
        host_eval = build_host_eval_step(self._template)
        engine = self.engine

        def step(state, batch):
            # Eval must see every trained row: drain pending applies.
            self.flush()
            if isinstance(batch, PreparedBatch):
                # A pull-ahead batch was prepared BEFORE the flush just
                # above — its row block may predate applies that were
                # still queued at pull time. Re-pull from the raw batch
                # so eval reads post-flush rows (eval bypasses
                # pull-ahead; exactness over overlap here).
                batch = batch.raw
            prepared, host_rows, _ = engine.prepare_batch(batch)
            return host_eval(state, prepared, host_rows)

        return step


def locked_checkpoint_tables(tables: Dict, optimizer, lock,
                             flush=None) -> Dict:
    """Everything a host-tier checkpoint must carry — main tables plus
    the optimizer's slot tables and step counters — each behind a
    lock-guarded view. Shared by HostStepRunner and HostRowService so
    the local and served checkpoint payloads cannot drift. ``flush``
    (the runner's async-apply drain) runs before any read so a snapshot
    never misses an in-flight row apply."""
    out = dict(tables)
    state_tables = getattr(optimizer, "state_tables", None)
    if state_tables is not None:
        out.update(state_tables(tables))
    return {
        name: _LockedTable(table, lock, flush)
        for name, table in out.items()
    }


class _LockedTable:
    """Lock-guarded view over a host table (or checkpoint adapter): the
    checkpoint hook snapshots and restore refills under the engine's
    lock, never racing training threads; reads drain the async applier
    first (``flush``). Dirty-row tracking (incremental checkpoints)
    passes through under the same lock when the wrapped table supports
    it."""

    def __init__(self, table, lock, flush=None):
        self._table = table
        self._lock = lock
        self._flush = flush

    def _drain(self):
        if self._flush is not None:
            self._flush()

    def to_arrays(self):
        self._drain()
        with self._lock:
            return self._table.to_arrays()

    @property
    def supports_dirty_rows(self) -> bool:
        return bool(getattr(self._table, "supports_dirty_rows", False))

    def dirty_arrays(self):
        self._drain()
        with self._lock:
            return self._table.dirty_arrays()

    def capture_arrays(self):
        """Full snapshot + dirty-drain under ONE lock acquisition
        (full-base capture): splitting them lets a write land between
        the two, excluded from the snapshot with its dirty mark
        wiped — the row would never ride any subsequent delta."""
        self._drain()
        with self._lock:
            ids, rows = self._table.to_arrays()
            if getattr(self._table, "supports_dirty_rows", False):
                self._table.clear_dirty()
            return ids, rows

    def mark_dirty(self, ids):
        with self._lock:
            self._table.mark_dirty(ids)

    def clear_dirty(self):
        with self._lock:
            self._table.clear_dirty()

    @property
    def dirty_count(self) -> int:
        with self._lock:
            return self._table.dirty_count

    def set(self, ids, values):
        self._drain()
        with self._lock:
            return self._table.set(ids, values)

    def get(self, ids):
        self._drain()
        with self._lock:
            return self._table.get(ids)

    @property
    def num_rows(self):
        self._drain()
        with self._lock:
            return self._table.num_rows

    def __getattr__(self, name):
        return getattr(self._table, name)
