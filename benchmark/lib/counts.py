"""Operations and bytes from shapes: the model's and the attention
kernels'. Analytic, so recomputation (remat, the kernels' recomputed
score tiles) is not counted: these are the operations the mathematics
requires, which is what a utilisation is measured against.

A multiply-add is 2 operations. All counts are for training (forward
and backward: the backward of a matmul is two matmuls of the same size).
"""


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul once per token: the four
    attention projections and the two MLP matrices of every layer, and
    the output head. Embedding lookups, biases and LayerNorms do no
    matmul."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 4 * d * d + 2 * d * f
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def attention_flops_per_token_fwd(cfg: dict, seq_len: int,
                                  causal: bool = True) -> float:
    """QK^T and PV for one token against the positions it may see: on
    average (seq_len + 1) / 2 of them under the causal mask. Per layer
    2 matmuls x 2 ops x n_embd per visible position."""
    visible = (seq_len + 1) / 2.0 if causal else float(seq_len)
    return cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * visible


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward = 3 x forward: 6 per matmul weight, plus the
    attention products."""
    forward = 2.0 * matmul_params(cfg) + attention_flops_per_token_fwd(
        cfg, seq_len)
    return 3.0 * forward


def param_count(cfg: dict) -> int:
    """Every trained number of the program's model (untied head with a
    bias, q/k/v/out and MLP biases, two LayerNorms a layer and a last)."""
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    per_layer = (4 * d * d + 4 * d) + (2 * d * f + f + d) + 4 * d
    return (v * d + cfg["n_positions"] * d + cfg["n_layer"] * per_layer
            + 2 * d + d * v + v)


def attention_kernel_step(cfg: dict, rows: int, seq_len: int,
                          dtype_bytes: int = 2) -> dict:
    """One training step's attention kernels over all layers: the
    forward kernel and the two backward kernels (dq; dk and dv).

    Operations required: forward QK^T and PV (2 matmuls); backward dV,
    dP, dQ and dK (4 matmuls); each 2 x rows x heads x S x S_visible x
    head_dim, with the causal half. The score tiles the backward
    kernels recompute are not counted.

    Bytes that must cross HBM at least once, whatever the split into
    kernels: the forward reads q, k, v and writes o (and the float32
    logsumexp, one number a row and head); the backward reads q, k, v,
    o, do (and the logsumexp) and writes dq, dk, dv. Twelve tensors of
    rows x S x n_embd. (The program's backward is two kernels that each
    read q, k, v and do again: 16; the extra reads are the
    implementation's, not the algorithm's.)
    """
    d, n = cfg["n_embd"], cfg["n_layer"]
    heads = cfg["n_head"]
    tensor = rows * seq_len * d * dtype_bytes
    lse = rows * heads * seq_len * 4
    visible = (seq_len + 1) / 2.0
    one_matmul = 2.0 * rows * seq_len * visible * d
    return {
        "flops": n * 6 * one_matmul,
        "bytes": n * (12 * tensor + 2 * lse),
    }
