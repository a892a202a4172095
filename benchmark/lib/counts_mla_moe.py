"""Operations and bytes from shapes, for configurations of the
latent-attention sparse-expert family (``reference/mla_moe.py``): the
model's, the attention kernels' with their two head sizes, and the
grouped expert products'. What ``counts.py`` is for GPT-2, under the same
conventions: analytic, recomputation (remat, the kernels' recomputed
score tiles) not counted; a multiply-add is 2 operations; training is
forward + backward = 3 x forward. Everything is of what THIS chip holds:
its experts, its slice of the vocabulary.

How many rows the held experts see is data (the router decides): the
callers pass the rows the program counted.
"""


def attention_blocks(cfg: dict) -> int:
    """Blocks with an attention layer: the layers and the MTP modules."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def expert_layers(cfg: dict) -> int:
    """Expert layers: the layers after the leading dense ones, and one
    in every MTP module."""
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def attention_params(cfg: dict) -> int:
    """Weights of one attention layer, each in one matmul per token:
    W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def per_token_matmul_params(cfg: dict) -> int:
    """Weights every token meets in a matmul once a forward pass, the
    routed experts apart: attention projections, the dense MLPs, every
    expert layer's router and shared expert, the MTP module's
    projection, and the head once per head that is computed."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    experts = expert_layers(cfg) * (
        d * cfg["router_width"] + cfg["n_shared_experts"] * expert_params(cfg))
    mtp = cfg["num_nextn_predict_layers"]
    return (attention_blocks(cfg) * attention_params(cfg) + dense + experts
            + mtp * 2 * d * d + (1 + mtp) * d * cfg["vocab_size"])


def attention_flops_per_token_fwd(cfg: dict, seq_len: int) -> float:
    """QK^T over the q/k head size and PV over the v head size, for one
    token against the (seq_len + 1) / 2 positions it sees on average."""
    visible = (seq_len + 1) / 2.0
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (attention_blocks(cfg) * 2 * cfg["num_attention_heads"]
            * (qk + cfg["v_head_dim"]) * visible)


def train_flops_per_step(cfg: dict, rows: int, seq_len: int,
                         routed_rows: float) -> float:
    """One optimizer step over ``rows`` sequences; ``routed_rows``: the
    token-choices that fell on held experts, summed over the expert
    layers (the program's ``moe_rows`` counter)."""
    tokens = rows * seq_len
    forward = (tokens * (2.0 * per_token_matmul_params(cfg)
                         + attention_flops_per_token_fwd(cfg, seq_len))
               + routed_rows * 2.0 * expert_params(cfg))
    return 3.0 * forward


def param_count(cfg: dict) -> int:
    """Every trained number held here (the head's bias and the
    selection biases included)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    norms = 2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    block = attention_params(cfg) + norms
    dense = cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    expert_layer = (d * cfg["router_width"] + cfg["router_width"]
                    + (cfg["n_routed_experts"] + cfg["n_shared_experts"])
                    * expert_params(cfg))
    mtp = cfg["num_nextn_predict_layers"]
    return (2 * v * d + v + d + attention_blocks(cfg) * block + dense
            + expert_layers(cfg) * expert_layer + mtp * (2 * d * d + 3 * d))


def attention_kernel_step(cfg: dict, rows: int, seq_len: int,
                          dtype_bytes: int = 2) -> dict:
    """One training step's attention kernels over all blocks (forward,
    dq, dk/dv).

    Operations: six matmuls a (query, key) pair, three over the q/k
    head size (QK^T, dQ, dK) and three over the v head size (PV, dP,
    dV), causal half: 3 x 2 x (qk + v) per pair and head.

    Bytes that must cross HBM at least once: the forward reads q, k
    (width qk), v and writes o (width v); the backward reads q, k, v, o,
    do and writes dq, dk, dv: six tensors of each width, and the float32
    logsumexp twice."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = rows * h * seq_len * (seq_len + 1) / 2.0
    positions = rows * h * seq_len
    blocks = attention_blocks(cfg)
    return {
        "flops": blocks * 3 * 2.0 * (qk + dv) * pairs,
        "bytes": blocks * (6 * (qk + dv) * positions * dtype_bytes
                           + 2 * positions * 4),
    }


def expert_ffn_step(cfg: dict, routed_rows: float,
                    dtype_bytes: int = 2) -> dict:
    """One training step's grouped expert products over all expert
    layers, for ``routed_rows`` rows in all.

    Operations: a row meets its expert's three matrices, forward and
    twice in the backward.

    Bytes: the held experts' weights read once in the forward and once
    in the backward, their gradients written once (3 passes over the
    held weights in the compute type); a row read (hidden) and written
    (hidden) and its gate, up and product crossed once (3 x expert
    width), forward, and twice that in the backward."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
    return {
        "flops": 3 * 2.0 * routed_rows * expert_params(cfg),
        "bytes": (3 * held + 3 * routed_rows * (2 * d + 3 * f)) * dtype_bytes,
    }
