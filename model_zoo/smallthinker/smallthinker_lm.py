"""Window-and-global attention, sparse ReGLU experts behind a router
placed before attention: the SmallThinker family
(``models/smallthinker.py``) under the standard zoo contract.

``CONFIG`` is a tiny size for the CPU tests; a deployment's sizes come
through ``custom_model(config=...)`` (the benchmark's configuration
files do that). Records are those of ``transformer/transformer_lm.py``:
msgpack payloads {"tokens": [seq_len+1 ints]}, features tokens[:-1],
labels tokens[1:]. Loss: next-token cross-entropy.

The optimizer is the expert families' (``models/mla_moe.py::
balanced_adam``): with no selection bias in the tree it is Adam, warmed
up linearly where asked.
"""

import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common import tensor_utils
from elasticdl_tpu.models.mla_moe import balanced_adam
from elasticdl_tpu.models.smallthinker import (
    SmallThinkerConfig,
    SmallThinkerLM,
)

CONFIG = SmallThinkerConfig(compute_dtype=jnp.float32)


def custom_model(config: SmallThinkerConfig = CONFIG):
    return SmallThinkerLM(config)


def loss(labels, predictions, mask):
    from elasticdl_tpu.ops import masked_next_token_cross_entropy

    logits = (predictions["logits"] if isinstance(predictions, dict)
              else predictions)
    return masked_next_token_cross_entropy(labels, logits, mask)


def optimizer(lr=1e-3, warmup_steps=0):
    return balanced_adam(lr, 0.0, warmup_steps)


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
