"""Operations and bytes from shapes, for configurations of the
block-diffusion sparse-expert family (``reference/sdar_moe.py``): the
model's, the attention kernels' under the block-diffusion mask, and the
grouped products of SiLU-gated experts. Under ``counts.py``'s
conventions: analytic; a multiply-add is 2 operations; training is
forward + backward = 3 x forward; everything is of what THIS chip
holds: its experts, its slice of the vocabulary. The model's count
leaves recomputation out; a KERNEL's count is of the work its calls do
(said at each), so that a share of a roofline cannot pass 100%.

A step trains ``rows`` clean rows of ``seq_len`` data tokens; the model
runs over the row twice, 2 x ``seq_len`` positions a row (the noised
copy, then the clean row), and the head over the noised half alone.
**The attention's count is of the visible (query, key) pairs**, whatever
grid tiles a kernel visits. How many rows the held experts see is data
(the router decides): the callers pass the rows the program counted.
"""


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def positions(rows: int, seq_len: int) -> int:
    """Positions the layers run over: the row twice."""
    return rows * 2 * seq_len


def visible_pairs(cfg: dict, seq_len: int) -> int:
    """(query, key) pairs the mask leaves of one row and head, with L =
    ``seq_len`` and b the block length: clean on clean, block-causal,
    L (L + b) / 2; noised on the clean blocks before its own, L (L - b)
    / 2; noised on its own block, L b; clean on noised, none."""
    block = cfg["block_length"]
    return seq_len * (seq_len + block)


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o (no bias) of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def per_position_matmul_params(cfg: dict) -> int:
    """Weights every position of the doubled row meets in a matmul once
    a forward pass, the routed experts apart: the attention's
    projections and the router, every layer."""
    return layers(cfg) * (
        attention_params(cfg) + cfg["hidden_size"] * cfg["router_width"])


def attention_flops_fwd(cfg: dict, rows: int, seq_len: int) -> float:
    """QK^T and PV over the visible pairs, every query head, row and
    layer."""
    return (layers(cfg) * rows * cfg["num_attention_heads"]
            * 2 * 2.0 * cfg["head_dim"] * visible_pairs(cfg, seq_len))


def train_flops_per_step(cfg: dict, rows: int, seq_len: int,
                         routed_rows: float) -> float:
    """One optimizer step over ``rows`` clean rows, recomputation left
    out; ``routed_rows``: the token-choices that fell on held experts,
    summed over the layers (the program's ``moe_rows``). The head runs
    over the noised half: ``rows x seq_len`` positions."""
    forward = (positions(rows, seq_len) * 2.0 * per_position_matmul_params(cfg)
               + rows * seq_len * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
               + attention_flops_fwd(cfg, rows, seq_len)
               + routed_rows * 2.0 * expert_params(cfg))
    return 3.0 * forward


def param_count(cfg: dict) -> int:
    """Every trained number held here (MASK's embedding row and the
    head's bias included)."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    layer = (attention_params(cfg) + 2 * d + 2 * hd
             + d * cfg["router_width"]
             + cfg["num_experts"] * expert_params(cfg))
    return (v + 1) * d + d * v + v + d + layers(cfg) * layer


def attention_kernel_step(cfg: dict, rows: int, seq_len: int,
                          dtype_bytes: int = 2) -> dict:
    """One training step's attention kernels over all layers (forward,
    dq, dk/dv; a recomputed layer keeps o and the logsumexp, so the
    forward kernel runs once).

    Operations: six matmuls a VISIBLE (query, key) pair and query head,
    each over the head size; the pairs the mask hides are not counted,
    whatever tiles the kernels visit.

    Bytes that must cross HBM at least once over the 2 x seq_len
    positions, **k and v fetched once a group**: of the query heads'
    width q twice, dq, o twice, do (6); of the key/value heads' width k
    twice, dk, v twice, dv (6); the float32 logsumexp twice."""
    hd = cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = rows * h * visible_pairs(cfg, seq_len)
    return {
        "flops": layers(cfg) * 3 * 2.0 * 2 * hd * pairs,
        "bytes": layers(cfg) * positions(rows, seq_len) * (
            6 * hd * (h + kv) * dtype_bytes + 2 * h * 4),
    }


def expert_ffn_step(cfg: dict, routed_rows: float,
                    dtype_bytes: int = 2) -> dict:
    """One training step's grouped expert products over all layers, for
    ``routed_rows`` rows in all: three products an expert (gate and up
    as one call, down), **what the ``ragged-dot`` calls do**: forward
    once and the backward as two forwards, and with the configuration's
    ``remat`` on the forward once more (the recomputed layer runs both
    calls again).

    Operations: a row meets its expert's three matrices once a pass.

    Bytes, a pass: the held experts' weights read, or their gradients
    written, once in the compute type; a row read (hidden) and written
    (hidden), its gate and up products written and their product read
    (3 x expert width)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    passes = 4 if cfg.get("remat") else 3
    return {
        "flops": passes * 2.0 * routed_rows * expert_params(cfg),
        "bytes": passes * (held + routed_rows * (2 * d + 3 * f))
        * dtype_bytes,
    }
