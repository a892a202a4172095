"""Numerically-robust masked losses for TPU.

Why this module exists: on TPU, XLA fuses the fully-reduced form of
``optax.softmax_cross_entropy_with_integer_labels`` inside a
``value_and_grad`` train step into a softmax-probability formulation whose
fast-math ``exp`` can give ``p[label]`` marginally above 1 — the scalar
loss then reads as ``-log(p) < 0`` (observed at up to -0.32 on a v5e).
The ``log_softmax``-first formulation below keeps the reduction in log
space and is rewrite-stable: loss ≥ 0 always.

These take ``(labels, predictions, mask)`` exactly like the model-zoo
loss contract, with ``mask`` weighting padded rows of the final partial
batch (XLA static shapes; see data/batcher.py).
"""

import jax
import jax.numpy as jnp

from elasticdl_tpu.data.batcher import masked_mean


def masked_softmax_cross_entropy(labels, logits, mask):
    """Integer-label softmax CE, masked mean over real rows."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = labels.astype(jnp.int32)
    per_example = -jnp.take_along_axis(
        logp, labels[..., None], axis=-1
    )[..., 0]
    return masked_mean(per_example, mask)


def masked_sigmoid_cross_entropy(labels, logits, mask):
    """Binary CE on logits, masked mean over real rows.

    log-space formulation: ``max(x,0) - x*z + log1p(exp(-|x|))``.
    """
    x = logits
    z = labels.astype(x.dtype)
    if x.ndim == z.ndim + 1 and x.shape[-1] == 1:
        x = x[..., 0]
    per_example = (
        jnp.maximum(x, 0.0) - x * z + jnp.log1p(jnp.exp(-jnp.abs(x)))
    )
    return masked_mean(per_example, mask)


def fused_next_token_cross_entropy(labels, outputs, mask,
                                   chunk_size: int = 128):
    """LM cross entropy WITHOUT materializing (B, S, V) logits.

    ``outputs`` is the fused-head model output ``(hidden, kernel, bias)``
    (models/transformer.py ``fused_head``): per sequence-chunk, logits
    are computed on the MXU with f32 accumulation, reduced to
    (logsumexp − label logit), and discarded — a ``jax.checkpoint``
    inside the ``lax.scan`` makes the backward recompute each chunk's
    logits instead of storing them. HBM traffic for the head drops from
    ~6 full (B,S,V)-f32 passes (store bf16 + cast f32 + log_softmax +
    gather + backward reads) to ~2 transient chunk passes fwd + bwd
    recompute; at d512/V32k this is the difference between the head
    being HBM-bound and MXU-bound.

    Numerics match masked_next_token_cross_entropy: f32 logits (MXU
    accumulation), log-space reduction, masked mean over real rows.
    """
    hidden, kernel, bias = outputs
    b, s, d = hidden.shape
    labels = labels.astype(jnp.int32)
    weights = mask.astype(jnp.float32)
    if weights.ndim == 1:
        weights = jnp.broadcast_to(weights[:, None], (b, s))
    chunk = min(chunk_size, s)
    if s % chunk:
        raise ValueError(f"seq len {s} must tile by chunk {chunk}")
    n = s // chunk
    # (n, B, chunk, ...) so scan walks sequence chunks.
    hs = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)
    ws = weights.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(h, lab, wt):
        logits = jax.lax.dot_general(
            h, kernel, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + bias.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab_logit = jnp.take_along_axis(
            logits, lab[..., None], axis=-1
        )[..., 0]
        return jnp.sum((lse - lab_logit) * wt)

    def body(acc, xs):
        h, lab, wt = xs
        return acc + chunk_loss(h, lab, wt), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (hs, ls, ws))
    return total / jnp.maximum(jnp.sum(weights), 1.0)


def masked_next_token_cross_entropy(labels, logits, mask):
    """Per-token LM cross entropy: labels (B, S) int, logits (B, S, V),
    ``mask`` the (B,) padded-row mask broadcast over tokens, or (B, S)
    weights of every position (the fused form takes either too).

    Formulated as ``logsumexp(x) - x[label]`` rather than gathering from
    ``log_softmax(x)``: identical math (logsumexp is max-stabilized),
    but only (B, S) tensors materialize — the log_softmax form wrote
    full (B, S, V) f32 log-probs, which at the d512 bench shape
    (8, 1024, 32768) was four ~1 GB loop fusions ≈ 2.5 ms/step of pure
    HBM traffic (round-4 raw profile, attributed from the compiled
    program's text by hand; ``tools/step_breakdown.py`` does that join
    for a ``--profile_dir`` window now).
    The backward is ``(softmax - onehot) * w`` either way; here XLA
    fuses it straight into the lm_head gradient matmul's input."""
    import jax
    import jax.numpy as jnp

    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)            # (B, S)
    lab_logit = jnp.take_along_axis(
        logits32, labels[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    ll = lab_logit - lse
    weights = mask if mask.ndim == 2 else jnp.broadcast_to(
        mask[:, None], ll.shape
    )
    return -jnp.sum(ll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def weighted_in_place_cross_entropy(targets, logits, weights, mask):
    """The loss of masked (absorbing-state) diffusion over a row:
    position i predicts ``targets[:, i]`` itself, no shift; ``weights``
    (B, S) are the model's own (zero where the token was not masked,
    1 / p of its block where it was: ``models/sdar_moe.py``); ``mask``
    is the (B,) padded-row mask. Targets (B, S) int, logits (B, S, V).
    The sum over rows and positions of weight x CE over the positions of
    the real rows: (1 / (B S)) sum m / p CE. The ``logsumexp(x) -
    x[target]`` form of :func:`masked_next_token_cross_entropy`, for its
    reasons."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)            # (B, S)
    target_logit = jnp.take_along_axis(
        logits32, targets[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    rows = mask.astype(jnp.float32)
    total = jnp.sum((lse - target_logit) * weights * rows[:, None])
    return total / jnp.maximum(jnp.sum(rows) * targets.shape[1], 1.0)
