"""The flag system: argparse groups per role + arg re-serialization.

Counterpart of the reference's ``elasticdl/python/common/args.py`` (721 LoC,
~70 flags). Same structure: shared arg groups composed into per-role parsers
(client train/evaluate/predict/clean, master, worker), plus
``build_arguments_from_parsed_result`` so the master can re-serialize its own
parsed args into the CLI of the pods it spawns, and ``parse_envs`` for k=v
env plumbing (reference args.py:61-87).

TPU-specific flags replace the PS flags: ``--num_workers`` describes TPU-VM
worker pods, ``--mesh_shape``/``--dp_axis`` describe the device mesh, and the
sync-SGD knobs (``grads_to_wait``, staleness) map onto gradient-accumulation +
LR modulation in the mesh step.
"""

import argparse
from itertools import chain


def pos_int(value):
    res = int(value)
    if res <= 0:
        raise ValueError(f"Positive integer required, got {value}")
    return res


def non_neg_int(value):
    res = int(value)
    if res < 0:
        raise ValueError(f"Non-negative integer required, got {value}")
    return res


def pos_float(value):
    res = float(value)
    if res <= 0:
        raise ValueError(f"Positive float required, got {value}")
    return res


def parse_envs(arg):
    """Parse ``key1=val1,key2=val2`` into a dict (reference args.py:61-87)."""
    envs = {}
    if not arg:
        return envs
    for kv in arg.split(","):
        kv = kv.strip()
        if not kv:
            continue
        if "=" not in kv:
            raise ValueError(f"Malformed env entry {kv!r}; expected k=v")
        key, _, value = kv.partition("=")
        envs[key.strip()] = value.strip()
    return envs


def str2bool(value):
    if isinstance(value, bool):
        return value
    if value.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if value.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"Boolean value expected, got {value!r}")


def add_bool_param(parser, name, default, help_msg):
    parser.add_argument(
        name, type=str2bool, nargs="?", const=True, default=default, help=help_msg
    )


def add_common_params(parser):
    """Flags shared by every role (reference args.py add_common_params)."""
    parser.add_argument(
        "--model_zoo", help="Directory containing user-defined model modules",
        required=True,
    )
    parser.add_argument(
        "--model_def",
        help="Model module path, e.g. mnist.custom_model",
        required=True,
    )
    parser.add_argument("--dataset_fn", default="dataset_fn")
    parser.add_argument("--loss", default="loss")
    parser.add_argument("--optimizer", default="optimizer")
    parser.add_argument("--eval_metrics_fn", default="eval_metrics_fn")
    parser.add_argument("--custom_data_reader", default="custom_data_reader")
    parser.add_argument(
        "--prediction_outputs_processor", default="PredictionOutputsProcessor"
    )
    parser.add_argument("--callbacks", default="callbacks")
    parser.add_argument(
        "--distribution_strategy",
        default="Local",
        choices=["Local", "MeshStrategy", "ParameterServerStrategy",
                 "AllreduceStrategy"],
    )
    parser.add_argument("--job_name", default="elasticdl-tpu-job")
    parser.add_argument("--envs", type=str, default="",
                        help="Runtime environment variables, k1=v1,k2=v2")
    parser.add_argument("--data_reader_params", type=str, default="")
    parser.add_argument("--log_level", default="INFO",
                        choices=["DEBUG", "INFO", "WARNING", "ERROR"])
    parser.add_argument("--image_name", default="",
                        help="Container image for spawned pods")
    parser.add_argument("--namespace", default="default")
    parser.add_argument("--num_workers", type=pos_int, default=1)
    parser.add_argument("--checkpoint_shards", type=pos_int, default=1,
                        help="Shard files per checkpoint version "
                             "(reference: one file per PS pod)")
    parser.add_argument("--worker_resource_request",
                        default="cpu=1,memory=4096Mi")
    parser.add_argument("--worker_resource_limit", default="")
    parser.add_argument("--master_resource_request",
                        default="cpu=0.1,memory=1024Mi")
    parser.add_argument("--master_resource_limit", default="")
    parser.add_argument("--volume", default="")
    parser.add_argument("--restart_policy", default="Never")
    parser.add_argument("--master_addr", default="localhost:50001")
    parser.add_argument("--docker_image_repository", default="")
    add_bool_param(parser, "--force_use_kube_config_file", False,
                   "Use kube config file instead of in-cluster config")
    parser.add_argument("--cluster_spec", default="")
    # Mesh flags (TPU-native replacement for the PS flags).
    parser.add_argument(
        "--mesh_shape", default="",
        help="Device mesh shape, e.g. '8' (dp) or '2,4' (dp,mp); empty = all "
             "devices on one dp axis",
    )
    parser.add_argument(
        "--mesh_axes", default="dp",
        help="Comma-separated mesh axis names matching --mesh_shape",
    )
    add_bool_param(parser, "--use_bf16", True,
                   "Run matmuls in bfloat16 on the MXU")
    add_bool_param(parser, "--wait", False,
                   "After submitting to k8s, poll the job to completion "
                   "(exit 0 on master Succeeded) — reference "
                   "k8s_job_monitor semantics")
    add_bool_param(parser, "--wait_unknown_ok", False,
                   "With --wait: treat a master pod that vanishes while "
                   "Running as completed (clusters that GC finished pods "
                   "between polls); default treats it as not-success")


def add_train_params(parser):
    parser.add_argument("--tensorboard_log_dir", default="")
    parser.add_argument("--num_epochs", type=pos_int, default=1)
    parser.add_argument("--grads_to_wait", type=pos_int, default=1,
                        help="Gradient accumulation count before a sync apply")
    parser.add_argument("--training_data", default="")
    parser.add_argument("--validation_data", default="")
    parser.add_argument("--evaluation_steps", type=non_neg_int, default=0)
    parser.add_argument("--evaluation_start_delay_secs", type=pos_int,
                        default=100)
    parser.add_argument("--evaluation_throttle_secs", type=non_neg_int,
                        default=0)
    parser.add_argument("--checkpoint_steps", type=non_neg_int, default=0)
    parser.add_argument("--checkpoint_dir", default="")
    parser.add_argument("--keep_checkpoint_max", type=non_neg_int, default=3)
    parser.add_argument("--checkpoint_delta_chain", type=non_neg_int,
                        default=0,
                        help="Max incremental delta checkpoints riding "
                             "one full base before a save compacts into "
                             "a fresh base (host-tier embedding rows "
                             "only; dense state always rides in full). "
                             "0 (default) = full snapshots only. "
                             "docs/fault_tolerance.md")
    parser.add_argument("--checkpoint_dir_for_init", default="")
    parser.add_argument("--output", default="",
                        help="Export directory for the trained model")
    parser.add_argument("--minibatch_size", type=pos_int, required=True)
    parser.add_argument("--num_minibatches_per_task", type=pos_int, default=2)
    add_bool_param(parser, "--use_async", False,
                   "Async apply (staleness-modulated LR) instead of sync")
    parser.add_argument("--lr_staleness_modulation", type=str2bool,
                        nargs="?", const=True, default=False)
    parser.add_argument("--sync_version_tolerance", type=non_neg_int, default=0)
    parser.add_argument("--get_model_steps", type=pos_int, default=1,
                        help=">1 enables SSP-style local updates between syncs")
    parser.add_argument("--random_seed", type=non_neg_int, default=0)
    parser.add_argument("--max_steps", type=non_neg_int, default=0)
    parser.add_argument("--num_jax_processes", type=pos_int, default=1,
                        help=">1 wires jax.distributed across worker "
                             "processes (multi-host mesh over DCN)")
    parser.add_argument("--coordinator_addr", default="",
                        help="jax.distributed coordinator host:port "
                             "(required when num_jax_processes > 1)")
    parser.add_argument("--jax_process_id", type=int, default=-1,
                        help="Stable process id for jax.distributed; "
                             "-1 = use worker_id. Elastic relaunches "
                             "must reuse the dead worker's id")
    parser.add_argument("--prefetch_depth", type=non_neg_int, default=2,
                        help="Background batch-decode queue depth "
                             "(0 disables prefetching)")
    parser.add_argument("--host_prefetch_depth", type=pos_int, default=2,
                        help="Host-tier row pull-ahead depth: how many "
                             "upcoming batches the sparse pipeline "
                             "prepares (dedup + row pull + pad) while "
                             "the current batch steps. Widens the "
                             "async-apply staleness window to "
                             "depth + 3 batches (docs/sparse_path.md); "
                             "must be >= 1")
    parser.add_argument("--row_service_addr", default="",
                        help="Address(es) of the shared host-tier row "
                             "service (embedding/row_service.py) — "
                             "required for host-tier models with "
                             "num_workers > 1. A comma list means N "
                             "shards: rows scatter client-side by "
                             "id %% N (the reference's N parameter "
                             "servers, worker.py:404-414)")
    parser.add_argument("--num_row_service_shards", type=pos_int,
                        default=1,
                        help="Row-service shard pods (reference "
                             "--num_ps_pods): rows live by id %% N, one "
                             "stable Service + pod per shard, each with "
                             "its own checkpoint subdir (max 16)")
    parser.add_argument("--row_service_resource_request",
                        default="cpu=1,memory=4096Mi",
                        help="Resources for the row-service pod (the "
                             "reference's --ps_resource_request role); "
                             "CPU-only, independent of worker sizing")
    parser.add_argument("--row_service_resource_limit", default="")
    parser.add_argument("--row_service_checkpoint_steps", type=non_neg_int,
                        default=0,
                        help="Checkpoint interval for the row service, in "
                             "gradient PUSHES (its version unit). 0 = "
                             "derive from --checkpoint_steps scaled by "
                             "num_workers (each worker step pushes once "
                             "per table-holding step), so the service "
                             "checkpoints at roughly the cadence the "
                             "user asked for in model versions")
    parser.add_argument("--row_service_push_log",
                        choices=["durable", "applied", "off"],
                        default="durable",
                        help="Write-ahead push log mode for launched "
                             "row-service pods (with --checkpoint_dir; "
                             "docs/fault_tolerance.md 'Zero-RPO row "
                             "plane'): durable (default, acked-push "
                             "RPO=0), applied (RPO = one group "
                             "window; for media with slow fsync), "
                             "off (pre-WAL checkpoint-bounded loss)")
    parser.add_argument("--row_service_push_log_group_ms", type=float,
                        default=2.0,
                        help="Group-commit window for the row-service "
                             "push log (one fsync covers every push "
                             "landing within it)")
    parser.add_argument("--row_service_admission_limit", type=int,
                        default=0,
                        help="Priority admission control on launched "
                             "row-service pods: bound on concurrently "
                             "admitted handlers; beyond it requests "
                             "shed lowest-priority-first by principal "
                             "purpose (docs/fault_tolerance.md "
                             "'Graceful degradation'). 0 (default) = "
                             "off")
    parser.add_argument("--row_service_push_durable_wait_secs",
                        type=float, default=60.0,
                        help="Ceiling on the row-service durable-ack "
                             "fsync wait; a propagated request "
                             "deadline shrinks it per-push")
    parser.add_argument("--master_admission_limit", type=int,
                        default=0,
                        help="Priority admission control on the "
                             "master RPC servicer (same ladder as the "
                             "row plane). 0 (default) = off")
    add_bool_param(parser, "--fuse_task_steps", False,
                   "Scan a whole task's minibatches in one XLA program "
                   "(removes per-step host dispatch)")
    parser.add_argument("--profile_dir", default="",
                        help="Write a jax.profiler trace (TensorBoard/"
                             "Perfetto) for a step window")
    parser.add_argument("--profile_start_step", type=non_neg_int,
                        default=5)
    parser.add_argument("--profile_steps", type=pos_int, default=5)
    # Continuous profiling plane (observability/profiler.py;
    # docs/observability.md "Continuous profiling & exemplars"): an
    # always-on sampling profiler whose flame-table windows ride the
    # metrics piggyback into the master's /profile endpoint.
    parser.add_argument("--profile_hz", type=float, default=0.0,
                        help="Always-on sampling-profiler rate (Hz) "
                             "for master and workers; flame-table "
                             "windows serve on the master's /profile "
                             "endpoint. ~67 is the intended default "
                             "rate; 0 (default) = off")
    parser.add_argument("--profile_window_secs", type=pos_float,
                        default=10.0,
                        help="Sampling-profiler window length: stacks "
                             "fold per window, windows ride the "
                             "metrics piggyback to the master")
    parser.add_argument("--task_timeout_secs", type=pos_float, default=300.0)
    parser.add_argument("--journal_dir", default="",
                        help="Master write-ahead job-state journal "
                             "directory (docs/fault_tolerance.md): "
                             "dispatch/report events + periodic "
                             "snapshots, replayed on master restart so "
                             "task accounting survives the crash. "
                             "Point at a volume that outlives the "
                             "master pod; empty (default) disables")
    add_bool_param(parser, "--standby", False,
                   help_msg="Run this master as a HOT STANDBY "
                             "(docs/fault_tolerance.md 'Hot standby "
                             "& failover'): tail --journal_dir into "
                             "a continuously-replayed warm state and "
                             "heartbeat --primary_addr; on missed "
                             "heartbeats fence the old incarnation "
                             "and take over serving. Requires "
                             "--journal_dir on storage shared with "
                             "the primary")
    parser.add_argument("--primary_addr", default="",
                        help="Standby role: the primary master "
                             "address to heartbeat (defaults to "
                             "--master_addr)")
    parser.add_argument("--standby_heartbeat_secs", type=pos_float,
                        default=1.0,
                        help="Standby role: primary heartbeat cadence")
    parser.add_argument("--standby_miss_threshold", type=int,
                        default=3,
                        help="Standby role: consecutive missed "
                             "heartbeats before takeover")
    parser.add_argument("--master_reattach_grace", type=pos_float,
                        default=60.0,
                        help="How long a worker rides out master "
                             "unavailability before treating the job "
                             "as finished. Size it to measured master "
                             "recovery time (master_recovery_seconds "
                             "on /metrics) when running with "
                             "--journal_dir; the default matches the "
                             "old hard-coded ~60s budget")
    parser.add_argument("--metrics_port", type=int, default=-1,
                        help="Master Prometheus endpoint (/metrics + "
                             "/healthz): port to serve on; 0 picks an "
                             "ephemeral port, -1 (default) disables")
    parser.add_argument("--flight_recorder", type=int, default=0,
                        help="Install a distributed-tracing flight "
                             "recorder of this many spans in the "
                             "master (collected worker spans + its own "
                             "are served on /traces next to /metrics; "
                             "see docs/observability.md). 0 (default) "
                             "= tracing off")
    parser.add_argument("--metrics_report_secs", type=pos_float,
                        default=15.0,
                        help="How often each worker piggybacks a metrics "
                             "registry snapshot on master RPCs")
    # SLO engine (observability/timeseries.py + slo.py;
    # docs/observability.md): the master samples its telemetry into a
    # bounded time-series store each run tick, evaluates declarative
    # SLO rules (burn rate / threshold / absence) on it, and serves
    # /timeseries + /alerts next to /metrics.
    parser.add_argument("--timeseries_secs", type=float, default=5.0,
                        help="Master time-series sampling cadence "
                             "(seconds); 0 disables the store, the SLO "
                             "engine, and the /timeseries + /alerts "
                             "endpoints")
    parser.add_argument("--slo_rules", default="",
                        help="JSON SLO rule file (docs/observability.md "
                             "'SLOs & alerting' for the format); empty "
                             "= the built-in default rules")
    parser.add_argument("--incident_dir", default="",
                        help="Write a black-box incident bundle here "
                             "(flight-recorder trace, time-series "
                             "window, critical-path attribution, "
                             "journal tail) whenever an SLO rule "
                             "starts firing; empty (default) disables "
                             "capture")
    parser.add_argument("--metrics_ttl_secs", type=pos_float, default=None,
                        help="Master drops a worker's metrics after this "
                             "long without a report (elastic resize "
                             "aging). Snapshots only ride existing RPCs, "
                             "so a healthy worker can go silent for a "
                             "whole task (fused steps, stragglers) — "
                             "keep this above the longest task, not just "
                             "a few report intervals; default is 2x "
                             "task_timeout_secs")
    # Closed-loop elastic autoscaling (master/autoscaler.py;
    # docs/elasticity.md): the master watches queue depth, worker step
    # utilization, and p99 straggler attribution, and grows/shrinks the
    # worker fleet between the bounds.
    add_bool_param(parser, "--autoscale", False,
                   "Enable the master's closed-loop autoscaler "
                   "(k8s mode: scales worker pods between "
                   "--autoscale_min_workers/--autoscale_max_workers)")
    parser.add_argument("--autoscale_min_workers", type=pos_int,
                        default=1)
    parser.add_argument("--autoscale_max_workers", type=non_neg_int,
                        default=0,
                        help="0 = use --num_workers as the ceiling")
    parser.add_argument("--autoscale_cooldown_secs", type=pos_float,
                        default=60.0,
                        help="Quiet period after any scale decision")
    parser.add_argument("--autoscale_hysteresis_ticks", type=pos_int,
                        default=3,
                        help="Consecutive agreeing poll ticks required "
                             "before a decision fires")
    parser.add_argument("--autoscale_up_backlog_factor", type=pos_float,
                        default=2.0,
                        help="Scale up when todo depth exceeds this "
                             "many tasks per live worker (and workers "
                             "are saturated)")
    parser.add_argument("--autoscale_up_utilization", type=pos_float,
                        default=0.7,
                        help="Minimum mean worker_step_utilization for "
                             "scale-up (a starved fleet's backlog is an "
                             "input problem, not a capacity problem)")
    parser.add_argument("--autoscale_down_utilization", type=pos_float,
                        default=0.3,
                        help="Scale down when the queue is empty and "
                             "mean utilization sits below this")
    add_bool_param(parser, "--autoscale_from_timeseries", False,
                   "Feed the autoscaler the mean worker utilization "
                   "over --autoscale_trend_window_secs from the "
                   "time-series store instead of the instantaneous "
                   "snapshot (requires --timeseries_secs > 0)")
    parser.add_argument("--autoscale_trend_window_secs", type=pos_float,
                        default=120.0,
                        help="Trailing window for the time-series-"
                             "backed utilization signal")
    # Row-plane elasticity (master/row_reshard.py; docs/sparse_path.md
    # "Live resharding & hot-row replication"): the master runs the
    # shard-map authority over the --row_service_addr fleet — load-
    # imbalance range moves plus hot-row replica designation.
    add_bool_param(parser, "--row_reshard", False,
                   "Run the row-service shard-map controller in the "
                   "master tick (needs --row_service_addr; live range "
                   "rebalancing + hot-row read replicas)")
    parser.add_argument("--row_reshard_state", default="",
                        help="Shard-map authority state file (default: "
                             "<journal_dir>/shard_map.json; required "
                             "when no --journal_dir is set)")
    parser.add_argument("--row_reshard_cooldown_secs", type=pos_float,
                        default=30.0,
                        help="Quiet period between reshard actions "
                             "(range moves / replica updates)")
    parser.add_argument("--row_replica_top_k", type=pos_int, default=64,
                        help="Hottest ids per table eligible for read "
                             "replication")
    parser.add_argument("--row_replica_count", type=non_neg_int,
                        default=2,
                        help="Read replicas per hot id (capped at "
                             "fleet size - 1; 0 disables replication)")
    add_bool_param(parser, "--row_pod_autoscale", False,
                   "Close the split/merge pod loop (master/"
                   "autoscaler.py RowServicePodScaler): grow spawns a "
                   "row-service pod before splitting onto it, and a "
                   "merged-away pod drains once the shard-map "
                   "controller retires its slot (needs --row_reshard "
                   "and k8s)")
    # Multi-tenant gang scheduling (master/scheduler.py;
    # docs/scheduler.md): many jobs on one elastic fleet, with
    # journal-event-sourced job table, priority preemption, and
    # usage-plane fair share.
    add_bool_param(parser, "--sched", False,
                   "Run the multi-job gang scheduler in the master "
                   "(submit_job RPC + /sched endpoint; job table "
                   "event-sources onto --journal_dir and survives "
                   "failover)")
    # Streaming ingestion (master/stream_ingest.py + data/stream.py;
    # docs/online_learning.md): online/continual learning from an
    # append-only record stream instead of a finite shard table.
    parser.add_argument("--stream_dir", default="",
                        help="Directory of *.edlstream append-only "
                             "partitions (data/stream.py). Non-empty "
                             "switches the dispatcher to streaming "
                             "mode: unbounded offset-ranged tasks, "
                             "journaled watermarks, watermark-"
                             "triggered eval, /stream endpoint")
    parser.add_argument("--stream_max_todo", type=pos_int, default=64,
                        help="Backpressure bound: stop generating "
                             "stream tasks while the todo queue holds "
                             "this many (stream_ingest_backpressure_"
                             "seconds meters the stall)")
    parser.add_argument("--stream_eval_every_records", type=non_neg_int,
                        default=0,
                        help="Open an eval round each time this many "
                             "stream records commit past the watermark "
                             "(replaces epoch-end eval in streaming "
                             "mode; 0 disables)")
    parser.add_argument("--stream_poll_secs", type=pos_float,
                        default=0.5,
                        help="Stream tail poll + pump cadence")
    parser.add_argument("--usage_max_jobs", type=non_neg_int, default=0,
                        help="Distinct job labels the usage plane "
                             "admits before folding new tenants into "
                             "__other__ (observability/usage.py); 0 "
                             "(default) keeps the built-in cap of 32. "
                             "Raise on legitimately multi-job fleets "
                             "(--sched) so every tenant keeps its own "
                             "usage series")
    # Synthetic probing (observability/prober.py;
    # docs/observability.md "Synthetic probing"): black-box canary
    # probes against the reserved top-of-int64 id range, the repo's
    # first outside-in SLIs. Served at /probes; /healthz becomes the
    # aggregated probe verdict (200/503).
    add_bool_param(parser, "--probes", False,
                   "Run the synthetic canary prober inside the master "
                   "(dispatch/row/stream probes auto-wire from the "
                   "matching flags; serving needs "
                   "--probe_serving_addr)")
    parser.add_argument("--probe_interval_secs", type=pos_float,
                        default=15.0,
                        help="Cadence for each registered probe")
    parser.add_argument("--probe_serving_addr", default="",
                        help="host:port of a serving router; non-empty "
                             "registers the serving_freshness probe "
                             "(canary push -> prediction change)")
    parser.add_argument("--probe_serving_feature_key", default="",
                        help="Sparse feature key the serving_freshness "
                             "probe queries with a canary id (empty = "
                             "'ids')")


def add_evaluate_params(parser):
    parser.add_argument("--validation_data", default="", required=False)
    parser.add_argument("--checkpoint_dir_for_init", required=True)
    parser.add_argument("--minibatch_size", type=pos_int, required=True)
    parser.add_argument("--num_minibatches_per_task", type=pos_int, default=2)


def add_predict_params(parser):
    parser.add_argument("--prediction_data", required=True)
    parser.add_argument("--checkpoint_dir_for_init", required=True)
    parser.add_argument("--minibatch_size", type=pos_int, required=True)
    parser.add_argument("--num_minibatches_per_task", type=pos_int, default=2)


def add_clean_params(parser):
    add_bool_param(parser, "--force", False, "Force-delete job resources")
    parser.add_argument("--job_name", default="")
    parser.add_argument("--namespace", default="default")
    add_bool_param(parser, "--force_use_kube_config_file", False,
                   "Use kube config file instead of in-cluster config")


def add_worker_params(parser):
    parser.add_argument("--worker_id", type=non_neg_int, required=True)


def build_parser(role: str) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog=f"elasticdl_tpu-{role}",
                                     allow_abbrev=False)
    if role == "clean":
        add_clean_params(parser)
        return parser
    add_common_params(parser)
    if role in ("train", "master"):
        add_train_params(parser)
    elif role == "evaluate":
        add_evaluate_params(parser)
    elif role == "predict":
        add_predict_params(parser)
    elif role == "worker":
        add_train_params(parser)
        add_worker_params(parser)
    else:
        raise ValueError(f"Unknown role {role}")
    return parser


def parse_master_args(args=None):
    return build_parser("master").parse_args(args=args)


def parse_worker_args(args=None):
    return build_parser("worker").parse_args(args=args)


def build_arguments_from_parsed_result(args, filter_args=None):
    """Reserialize parsed args back into a CLI list for spawning child pods
    (reference args.py build_arguments_from_parsed_result).

    None-valued optionals are SKIPPED, not stringified: an unset
    ``--metrics_ttl_secs`` (default None = "derive from
    task_timeout_secs") would otherwise re-serialize as the literal
    string "None", which the worker parser's ``pos_float`` rejects —
    omitting the flag reproduces the default-deriving behavior in the
    child process."""
    items = vars(args).items()
    if filter_args:
        items = filter(lambda kv: kv[0] not in filter_args, items)

    def _to_pair(key, value):
        if value is None:
            return []
        if isinstance(value, bool):
            return [f"--{key}", "true" if value else "false"]
        return [f"--{key}", str(value)]

    return list(chain.from_iterable(_to_pair(k, v) for k, v in items))


def wrap_python_args_with_string(args):
    """Quote arg values so they survive a shell command line."""
    out = []
    for item in args:
        if not item.startswith("--"):
            out.append(f"'{item}'")
        else:
            out.append(item)
    return out
