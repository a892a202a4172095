"""Transformer language model — long-context / multi-axis-parallel zoo entry.

Net-new relative to the reference model zoo (its largest sequence dim is
DeepFM's input_length=10, model_zoo/deepfm_edl_embedding/
deepfm_edl_embedding.py:28): a decoder-only LM over byte tokens whose
attention runs as a ppermute ring when the mesh has an ``sp`` axis, with
tensor-parallel dense layers and optional expert-parallel MoE blocks.

Follows the standard zoo contract (custom_model/loss/optimizer/dataset_fn/
eval_metrics_fn) plus the parallel extras the MeshRunner consumes:
``param_sharding_rules()`` and ``batch_sharding_rule``.

Records are msgpack payloads {"tokens": [seq_len+1 ints]}; features are
tokens[:-1], labels tokens[1:] (next-token prediction).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import tensor_utils
from elasticdl_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    transformer_sharding_rules,
)
from elasticdl_tpu.parallel import rules as rules_lib

CONFIG = TransformerConfig(
    vocab_size=256,
    d_model=128,
    n_heads=8,
    n_layers=2,
    d_ff=256,
    max_len=128,
)


# The widths the benchmark cells and chip_smoke.py run, as model
# functions ``--model_def`` can name
# (``transformer.transformer_lm.transformer_l``), so suite, worker and
# smoke build one model from one definition. Sequence 1,024 engages the
# Pallas flash kernels. head_dim 128 is the MXU/lane width: D=64 heads
# measured half the attention-kernel throughput on v5e. remat=False:
# activations at these sizes fit HBM and rematerialization costs ~10%.
SEQ_LEN = 1024
VOCAB = 32768
WIDTHS = {
    "transformer": dict(d_model=512, n_heads=4, n_layers=8, d_ff=2048),
    "transformer_l": dict(d_model=1024, n_heads=8, n_layers=12,
                          d_ff=4096),
    "moe": dict(d_model=512, n_heads=4, n_layers=8, d_ff=2048,
                moe_experts=8, moe_every=2, moe_top_k=1,
                moe_dispatch="scatter"),
}


def width_config(name: str) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=VOCAB, max_len=SEQ_LEN, remat=False, **WIDTHS[name]
    )


def custom_model(mesh=None, config: TransformerConfig = CONFIG):
    return TransformerLM(config, mesh=mesh)


def transformer(mesh=None):
    return TransformerLM(width_config("transformer"), mesh=mesh)


def transformer_l(mesh=None):
    return TransformerLM(width_config("transformer_l"), mesh=mesh)


def moe(mesh=None):
    return TransformerLM(width_config("moe"), mesh=mesh)


def generate_text(params, prompt_tokens, max_new_tokens,
                  temperature=0.0, rng=None,
                  config: TransformerConfig = CONFIG):
    """KV-cache sampling with the trained params (greedy by default)."""
    from elasticdl_tpu.models.transformer import generate

    return generate(config, params, prompt_tokens, max_new_tokens,
                    temperature=temperature, rng=rng)


def param_sharding_rules():
    return transformer_sharding_rules()


def batch_sharding_rule(path, leaf):
    """Token ids/labels (B, S) shard over dp×sp; row mask (B,) over dp."""
    name = rules_lib.path_str(path)
    if name in ("features", "labels") and getattr(leaf, "ndim", 0) == 2:
        return P("dp", "sp")
    return P("dp")


def loss(labels, predictions, mask):
    """Per-token next-token cross entropy; ``mask`` is the (B,) padded-row
    mask from the batcher, broadcast over the token dim. Fused-head
    models (config.fused_head) emit (hidden, kernel, bias) during
    training and take the chunked no-logits-materialization path."""
    from elasticdl_tpu.ops import (
        fused_next_token_cross_entropy,
        masked_next_token_cross_entropy,
    )

    if isinstance(predictions, tuple):
        return fused_next_token_cross_entropy(labels, predictions, mask)
    return masked_next_token_cross_entropy(labels, predictions, mask)


def optimizer(lr=1e-3):
    import optax

    return optax.adam(lr)


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

    def perplexity(labels, outputs):
        logits = np.asarray(outputs, np.float64)
        logits -= logits.max(axis=-1, keepdims=True)
        logp = logits - np.log(np.exp(logits).sum(axis=-1, keepdims=True))
        ll = np.take_along_axis(
            logp, np.asarray(labels)[..., None].astype(np.int64), axis=-1
        )[..., 0]
        return float(np.exp(-ll.mean()))

    return {"token_accuracy": token_accuracy, "perplexity": perplexity}
