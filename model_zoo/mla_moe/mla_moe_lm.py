"""Latent-attention sparse-expert language model: the DeepSeek-V3 block
family (``models/mla_moe.py``) under the standard zoo contract.

``CONFIG`` is a tiny size for the CPU tests; a deployment's sizes come
through ``custom_model(config=...)`` (the benchmark's configuration
files do that). Records are those of ``transformer/transformer_lm.py``:
msgpack payloads {"tokens": [seq_len+1 ints]}, features tokens[:-1],
labels tokens[1:].

Loss = CE_main + MTP_LOSS_WEIGHT x CE_mtp. Position i of the multi-token
prediction module has seen features[i+1] and predicts labels[i+1], the
token after next; the last position has no such target and is masked.

The optimizer is Adam for every weight but the expert layers' selection
bias, which moves against its load's direction at
``BIAS_UPDATE_SPEED`` a step (auxiliary-loss-free balancing): what
``jax.grad`` hands back for it is that direction, not a gradient of the
loss (``models/mla_moe.py::_load_tap``).
"""

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import tensor_utils
from elasticdl_tpu.models.mla_moe import (
    MlaMoeConfig,
    MlaMoeLM,
    balanced_adam,
    mla_moe_sharding_rules,
)
from elasticdl_tpu.parallel import rules as rules_lib

CONFIG = MlaMoeConfig(compute_dtype=jnp.float32)

# DeepSeek-V3's weight of the multi-token prediction term for most of
# its pretraining (Liu et al. 2024, section 4.2).
MTP_LOSS_WEIGHT = 0.3


def custom_model(mesh=None, config: MlaMoeConfig = CONFIG):
    return MlaMoeLM(config, mesh=mesh)


def param_sharding_rules():
    return mla_moe_sharding_rules()


def batch_sharding_rule(path, leaf):
    """Rows over dp; the sequence stays whole (no ring here)."""
    return P("dp")


def _cross_entropy(labels, head_output, weights):
    from elasticdl_tpu.ops import (
        fused_next_token_cross_entropy,
        masked_next_token_cross_entropy,
    )

    if isinstance(head_output, tuple):
        return fused_next_token_cross_entropy(labels, head_output, weights)
    return masked_next_token_cross_entropy(labels, head_output, weights)


def loss_terms(labels, predictions, mask):
    """(CE of the main head, CE of the MTP head or None)."""
    if not isinstance(predictions, dict):
        return _cross_entropy(labels, predictions, mask), None
    main = _cross_entropy(labels, predictions["logits"], mask)
    if "mtp_logits" not in predictions:
        return main, None
    seq = labels.shape[1]
    has_target = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
    mtp = _cross_entropy(
        jnp.roll(labels, -1, axis=1), predictions["mtp_logits"],
        mask.astype(jnp.float32)[:, None] * has_target[None, :],
    )
    return main, mtp


def loss(labels, predictions, mask):
    main, mtp = loss_terms(labels, predictions, mask)
    return main if mtp is None else main + MTP_LOSS_WEIGHT * mtp


# DeepSeek-V3's bias update speed for most of its pretraining (Liu et
# al. 2024, section 4.2).
BIAS_UPDATE_SPEED = 1e-3


def optimizer(lr=1e-3, bias_update_speed=BIAS_UPDATE_SPEED, warmup_steps=0):
    """Adam at ``lr``, reached linearly from 0 over ``warmup_steps``
    steps where given (the first step then moves nothing); the selection
    biases by plain descent at ``bias_update_speed``."""
    return balanced_adam(lr, bias_update_speed, warmup_steps)


def dataset_fn(records, mode, metadata):
    seqs = []
    for payload in records:
        rec = tensor_utils.loads(payload)
        seqs.append(np.asarray(rec["tokens"], np.int32))
    tokens = np.stack(seqs)
    return tokens[:, :-1], tokens[:, 1:]


def eval_metrics_fn():
    def token_accuracy(labels, outputs):
        return float(np.mean(np.argmax(outputs, axis=-1) == labels))

    return {"token_accuracy": token_accuracy}
