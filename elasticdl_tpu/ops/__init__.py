from elasticdl_tpu.ops.losses import (  # noqa: F401
    fused_next_token_cross_entropy,
    masked_next_token_cross_entropy,
    masked_sigmoid_cross_entropy,
    masked_softmax_cross_entropy,
    weighted_in_place_cross_entropy,
)
