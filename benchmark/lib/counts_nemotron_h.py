"""Operations and bytes from shapes, for configurations of the hybrid
state-space / attention / sparse-expert family
(``reference/nemotron_h.py``): the model's, the scan kernels', the
attention kernels' with key/value heads shared by a group of query
heads, and the grouped products of relu^2 experts. Under ``counts.py``'s
conventions: analytic; a multiply-add is 2 operations; training is
forward + backward = 3 x forward; everything is of what THIS chip
holds: its experts, its slice of the vocabulary. The model's count
leaves recomputation out; a KERNEL's count is of the work its calls do
(said at each), so that a share of a roofline cannot pass 100%.

How many rows the held experts see is data (the router decides): the
callers pass the rows the program counted.
"""

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def layers(cfg: dict, kind: str) -> int:
    return cfg["hybrid_override_pattern"].count(kind)


def mamba_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_channels(cfg: dict) -> int:
    return mamba_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mamba_matmul_params(cfg: dict) -> int:
    """W_in and W_out of one Mamba-2 mixer."""
    d, inner = cfg["hidden_size"], mamba_inner(cfg)
    return d * (inner + conv_channels(cfg) + cfg["mamba_num_heads"]) + (
        inner * d)


def mamba_params(cfg: dict) -> int:
    """Every trained number of one Mamba-2 layer: the two projections,
    the convolution and its bias, dt_bias, A_log, D, the gated norm's
    scale and the layer's norm."""
    return (mamba_matmul_params(cfg)
            + (cfg["conv_kernel"] + 1) * conv_channels(cfg)
            + 3 * cfg["mamba_num_heads"] + mamba_inner(cfg)
            + cfg["hidden_size"])


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o (no bias) of one attention layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def per_token_matmul_params(cfg: dict) -> int:
    """Weights every token meets in a matmul once a forward pass, the
    routed experts apart."""
    d = cfg["hidden_size"]
    return (layers(cfg, MAMBA) * mamba_matmul_params(cfg)
            + layers(cfg, ATTENTION) * attention_params(cfg)
            + layers(cfg, EXPERTS) * (d * cfg["router_width"]
                                      + shared_expert_params(cfg))
            + d * cfg["vocab_size"])


def scan_flops_per_token_fwd(cfg: dict) -> float:
    """One Mamba-2 layer's recurrence as written, for one token: per
    head the state's decay (P N), the rank-one update (2 P N) and the
    readout (2 P N); the convolution's K multiply-adds a channel; the
    skip D x."""
    heads = cfg["mamba_num_heads"]
    state = cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    return (5.0 * heads * state
            + 2.0 * cfg["conv_kernel"] * conv_channels(cfg)
            + 2.0 * mamba_inner(cfg))


def attention_flops_per_token_fwd(cfg: dict, seq_len: int) -> float:
    """QK^T and PV for one token against the (seq_len + 1) / 2
    positions it sees on average, every query head."""
    visible = (seq_len + 1) / 2.0
    return (layers(cfg, ATTENTION) * 2 * cfg["num_attention_heads"]
            * 2 * cfg["head_dim"] * visible)


def train_flops_per_step(cfg: dict, rows: int, seq_len: int,
                         routed_rows: float) -> float:
    """One optimizer step over ``rows`` sequences, recomputation left
    out; ``routed_rows``: the token-choices that fell on held experts,
    summed over the expert layers (the program's ``moe_rows``)."""
    tokens = rows * seq_len
    forward = (tokens * (2.0 * per_token_matmul_params(cfg)
                         + layers(cfg, MAMBA) * scan_flops_per_token_fwd(cfg)
                         + attention_flops_per_token_fwd(cfg, seq_len))
               + routed_rows * 2.0 * expert_params(cfg))
    return 3.0 * forward


def param_count(cfg: dict) -> int:
    """Every trained number held here (the head's bias and the
    selection biases included)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    expert_layer = (d * cfg["router_width"] + cfg["router_width"]
                    + cfg["n_routed_experts"] * expert_params(cfg)
                    + shared_expert_params(cfg) + d)
    return (2 * v * d + v + d + layers(cfg, MAMBA) * mamba_params(cfg)
            + layers(cfg, ATTENTION) * (attention_params(cfg) + d)
            + layers(cfg, EXPERTS) * expert_layer)


def ssd_kernel_step(cfg: dict, rows: int, seq_len: int,
                    dtype_bytes: int = 2) -> dict:
    """One training step's ``ssd.N`` calls over all Mamba-2 layers:
    **what those calls do**, so with the configuration's ``remat`` on
    the forward kernel twice (the recomputed layer runs it again, and
    both runs write the chunk states) and the backward kernel once.

    Operations, the matrix products of the chunked form a token and
    head (Q the chunk, P the head, N the state, hg heads a group).
    Forward: (C B^T o L)(dt X) 2 Q P, the carried state read out 2 P N,
    the chunk's state 2 P N, and C B^T 2 Q N a group. Backward: M^T dY
    and dY (dt X)^T 4 Q P; B dh, C h, dY h, (dt X) dh and dY^T C 10 P N;
    C B^T again, dCB B and dCB^T C 6 Q N a group.

    Bytes that must cross HBM at least once. Forward: x read and y
    written (H P), B and C read (2 G N), s twice and dt once (float32,
    3 H), and the state every chunk started from written (float32, H P
    N / Q a token). Backward: x, dy read and dx written (3 H P), B, C
    read and dB, dC written (4 G N), s, dt in and ds, ddt out (float32,
    6 H), the states read."""
    q = cfg["chunk_size"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    tokens = rows * seq_len * layers(cfg, MAMBA)
    forwards = 2 if cfg.get("remat") else 1
    fwd_flops = heads * (2.0 * q * p + 4.0 * p * n) + groups * 2.0 * q * n
    bwd_flops = heads * (4.0 * q * p + 10.0 * p * n) + groups * 6.0 * q * n
    states = heads * p * n * 4.0 / q
    fwd_bytes = ((2 * heads * p + 2 * groups * n) * dtype_bytes
                 + 3 * heads * 4 + states)
    bwd_bytes = ((3 * heads * p + 4 * groups * n) * dtype_bytes
                 + 6 * heads * 4 + states)
    return {
        "flops": tokens * (forwards * fwd_flops + bwd_flops),
        "bytes": tokens * (forwards * fwd_bytes + bwd_bytes),
    }


def attention_kernel_step(cfg: dict, rows: int, seq_len: int,
                          dtype_bytes: int = 2) -> dict:
    """One training step's attention kernels over all attention layers
    (forward, dq, dk/dv; a recomputed layer keeps o and the logsumexp,
    so the forward kernel runs once).

    Operations: six matmuls a (query, key) pair and query head, each
    over the head size, causal half.

    Bytes that must cross HBM at least once, **k and v fetched once a
    group**: of the query heads' width q twice, dq, o twice, do (6);
    of the key/value heads' width k twice, dk, v twice, dv (6); the
    float32 logsumexp twice."""
    hd = cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = rows * h * seq_len * (seq_len + 1) / 2.0
    n_layers = layers(cfg, ATTENTION)
    return {
        "flops": n_layers * 3 * 2.0 * 2 * hd * pairs,
        "bytes": n_layers * rows * seq_len * (
            6 * hd * (h + kv) * dtype_bytes + 2 * h * 4),
    }


def expert_ffn_step(cfg: dict, routed_rows: float,
                    dtype_bytes: int = 2) -> dict:
    """One training step's grouped expert products over all expert
    layers, for ``routed_rows`` rows in all: two products an expert,
    **what the ``ragged-dot`` calls do**: forward once and the backward
    as two forwards, and with the configuration's ``remat`` on the
    forward once more (the recomputed layer runs both products again:
    8 calls a layer in the compiled step, not 6).

    Operations: a row meets its expert's two matrices once a pass.

    Bytes, a pass: the held experts' weights read, or their gradients
    written, once in the compute type; a row read (hidden) and written
    (hidden), its up-product written and its square read (2 x expert
    width)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = (layers(cfg, EXPERTS) * cfg["n_routed_experts"]
            * expert_params(cfg))
    passes = 4 if cfg.get("remat") else 3
    return {
        "flops": passes * 2.0 * routed_rows * expert_params(cfg),
        "bytes": passes * (held + routed_rows * (2 * d + 2 * f))
        * dtype_bytes,
    }
