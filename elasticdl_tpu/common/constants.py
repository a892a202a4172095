"""Framework-wide constants.

Counterpart of the reference's ``elasticdl/python/common/constants.py`` — the
gRPC limits, pod type names and strategy names keep the same semantics so a
reference user finds the same knobs, but the values are TPU-deployment flavored.
"""


class GRPC:
    # Tiny control messages only (tasks, versions, metrics); tensors never ride
    # gRPC in this framework — they live sharded on the mesh. 256MB cap kept for
    # eval raw-output reporting parity (reference constants.py:3-5).
    MAX_SEND_MESSAGE_LENGTH = 256 * 1024 * 1024
    MAX_RECEIVE_MESSAGE_LENGTH = 256 * 1024 * 1024


class InstanceManagerStatus:
    PENDING = "Pending"
    RUNNING = "Running"
    FINISHED = "Finished"


class PodStatus:
    PENDING = "Pending"
    RUNNING = "Running"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"
    DELETED = "Deleted"


class PodType:
    MASTER = "master"
    WORKER = "worker"


class TaskType:
    """Task types dispatched by the master (reference elasticdl.proto:24-30)."""

    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"
    WAIT = "wait"
    TRAIN_END_CALLBACK = "train_end_callback"


class JobType:
    TRAINING_ONLY = "training_only"
    TRAINING_WITH_EVALUATION = "training_with_evaluation"
    EVALUATION_ONLY = "evaluation_only"
    PREDICTION_ONLY = "prediction_only"


class Mode:
    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"


class DistributionStrategy:
    LOCAL = "Local"
    # Mesh data-parallel with sharded optimizer state. Subsumes the reference's
    # ParameterServerStrategy: the ICI mesh *is* the parameter store.
    MESH = "MeshStrategy"
    # Kept as an alias for reference-API compatibility.
    PARAMETER_SERVER = "ParameterServerStrategy"
    ALLREDUCE = "AllreduceStrategy"


class ReaderType:
    CSV = "CSV"
    RECORD_FILE = "RecordFile"
    TEXT = "Text"
    TABLE = "Table"  # row-range table service (ODPS-equivalent)
    STREAM = "Stream"  # append-only record stream (data/stream.py)


class MetricsDictKey:
    MODEL_OUTPUT = "output"
    LABEL = "label"


class SaveModelConfig:
    SAVED_MODEL_PATH = "saved_model_path"


# Exit code k8s gives OOM-killed / preempted containers; the instance manager
# treats it as relaunchable (reference k8s_instance_manager.py:250-271).
EXIT_CODE_KILLED = 137

# Default ports for in-cluster services (reference k8s_client.py:19-22).
MASTER_SERVICE_PORT = 50001
WORKER_COORD_PORT = 50002

MAX_TASK_RETRIES = 3

# Embedding tables larger than this are auto-sharded across the mesh
# (reference model_handler.py:85-89).
EMBEDDING_AUTO_SHARD_BYTES = 2 * 1024 * 1024

DEFAULT_TASK_TIMEOUT_SECS = 300.0
