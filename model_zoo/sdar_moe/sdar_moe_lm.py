"""Block-diffusion language model over a Qwen3-MoE body: the SDAR family
(``models/sdar_moe.py``) under the standard zoo contract.

``CONFIG`` is a tiny size for the CPU tests; a deployment's sizes come
through ``custom_model(config=...)`` (the benchmark's configuration
files do that). Records are those of ``transformer/transformer_lm.py``:
msgpack payloads {"tokens": [seq_len+1 ints]}, features tokens[:-1],
labels tokens[1:].

**``labels`` is not read by the loss.** The model noises ``features``
itself (a pure function of the row and the configuration's
``noise_seed``) and its training output carries the loss's targets (the
clean row, in place: position i predicts token i, no shift) and weights
(1 / p where the token was masked, else 0); the loss is the weighted
cross-entropy over those. The next-token ``labels`` stay in the
contract because the reader, the batcher and the callers that mark a
minibatch by them are the other language models' too.

**A row that comes again gets the noise it got before.** The noise is a
function of the row and ``noise_seed`` alone: over several epochs a row
is masked at the same tokens with the same p every time it is read.
That is NOT the fresh noise of masked-diffusion training (SDAR, BD3-LM
draw a new time and a new mask whenever a row is seen); a job of one
pass over its data trains as they do, a job that repeats its rows sees
one noising of each. The reason is outside the program: the
benchmark's check calls ``model.apply`` with no random stream, so the
worker and the check can agree on the mask only if the data decide it.
Fresh noise is one key into ``models/sdar_moe.py::noise`` (folded into
``row_key``) once that caller hands a stream over; until then vary
``noise_seed`` between epochs by hand if the repeat matters.

The optimizer is the expert families' (``models/mla_moe.py::
balanced_adam``): with no selection bias in the tree it is Adam, warmed
up linearly where asked.
"""

import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common import tensor_utils
from elasticdl_tpu.models.mla_moe import balanced_adam
from elasticdl_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeLM

CONFIG = SdarMoeConfig(compute_dtype=jnp.float32)


def custom_model(config: SdarMoeConfig = CONFIG):
    return SdarMoeLM(config)


def loss(labels, predictions, mask):
    from elasticdl_tpu.ops import weighted_in_place_cross_entropy

    del labels  # next-token labels: see the module docstring
    return weighted_in_place_cross_entropy(
        predictions["targets"], predictions["logits"],
        predictions["weights"], mask)


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
        """Of the noised row's in-place predictions, masked or not:
        position i + 1 against ``labels[:, i]``, the clean row's token
        there (position 0's is not among the labels)."""
        return float(np.mean(
            np.argmax(outputs[:, 1:], axis=-1) == labels[:, :-1]))

    return {"token_accuracy": token_accuracy}
