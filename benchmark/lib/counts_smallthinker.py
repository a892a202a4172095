"""Operations and bytes from shapes, for configurations of the
window-and-global sparse-expert family (``reference/smallthinker.py``):
the model's, the attention kernels' under the band and under the plain
causal mask, and the grouped products of ReLU-gated experts. Under
``counts.py``'s conventions: analytic; a multiply-add is 2 operations;
training is forward + backward = 3 x forward; everything is of what
THIS chip holds: its experts, its slice of the vocabulary, its layers.
The model's count leaves recomputation out; a KERNEL's count is of the
work its calls do (said at each), so that a share of a roofline cannot
pass 100%.

**The attention's count is of the visible (query, key) pairs**, layer by
layer from the two layouts, whatever grid tiles a kernel visits. How
many rows the held experts see is data (the router decides): the
callers pass the rows the program counted.
"""


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def window_layers(cfg: dict) -> int:
    """The layers held whose attention is a band."""
    return sum(cfg["sliding_window_layout"][:layers(cfg)])


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs of one row and head under the causal mask."""
    return seq_len * (seq_len + 1) // 2


def window_pairs(cfg: dict, seq_len: int) -> int:
    """... under a band of ``sliding_window_size``: the first W queries
    see 1..W keys, every later one W."""
    w = min(cfg["sliding_window_size"], seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def visible_pairs(cfg: dict, seq_len: int) -> int:
    """... of one row and head, summed over the layers held: what the
    program's ``attn_visible_pairs`` counter reads a row."""
    windowed = window_layers(cfg)
    return (windowed * window_pairs(cfg, seq_len)
            + (layers(cfg) - windowed) * causal_pairs(seq_len))


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o (no bias) of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def per_position_matmul_params(cfg: dict) -> int:
    """Weights every position meets in a matmul once a forward pass, the
    routed experts and the head apart: the attention's projections and
    the router, every layer."""
    return layers(cfg) * (
        attention_params(cfg) + cfg["hidden_size"] * cfg["router_width"])


def attention_flops_fwd(cfg: dict, rows: int, seq_len: int) -> float:
    """QK^T and PV over the visible pairs, every query head, row and
    layer."""
    return (rows * cfg["num_attention_heads"] * 2 * 2.0 * cfg["head_dim"]
            * visible_pairs(cfg, seq_len))


def train_flops_per_step(cfg: dict, rows: int, seq_len: int,
                         routed_rows: float) -> float:
    """One optimizer step over ``rows`` rows, recomputation left out;
    ``routed_rows``: the token-choices that fell on held experts, summed
    over the layers (the program's ``moe_rows``)."""
    positions = rows * seq_len
    forward = (positions * 2.0 * per_position_matmul_params(cfg)
               + positions * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
               + attention_flops_fwd(cfg, rows, seq_len)
               + routed_rows * 2.0 * expert_params(cfg))
    return 3.0 * forward


def param_count(cfg: dict) -> int:
    """Every trained number held here (the head's bias included)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layer = (attention_params(cfg) + 2 * d + d * cfg["router_width"]
             + cfg["moe_num_primary_experts"] * expert_params(cfg))
    return v * d + d * v + v + d + layers(cfg) * layer


def _kernel_step(cfg, rows, seq_len, n_layers, pairs, dtype_bytes):
    hd = cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "flops": 3 * 2.0 * 2 * hd * rows * h * pairs,
        "bytes": n_layers * rows * seq_len * (
            6 * hd * (h + kv) * dtype_bytes + 2 * h * 4),
    }


def attention_kernel_step(cfg: dict, rows: int, seq_len: int,
                          dtype_bytes: int = 2) -> dict:
    """One training step's attention kernels over all layers, both
    kinds (forward and the one backward kernel; a recomputed layer
    keeps o and the logsumexp, so the forward kernel runs once).

    Operations: six matmuls a VISIBLE (query, key) pair and query head,
    each over the head size; the pairs a mask hides are not counted,
    whatever tiles the kernels visit.

    Bytes that must cross HBM at least once, **k and v fetched once a
    group**: of the query heads' width q twice, dq, o twice, do (6); of
    the key/value heads' width k twice, dk, v twice, dv (6); the float32
    logsumexp twice."""
    return _kernel_step(cfg, rows, seq_len, layers(cfg),
                        visible_pairs(cfg, seq_len), dtype_bytes)


def window_kernel_step(cfg: dict, rows: int, seq_len: int,
                       dtype_bytes: int = 2) -> dict:
    """:func:`attention_kernel_step` of the window layers' calls alone:
    the band's pairs, those layers' bytes."""
    n = window_layers(cfg)
    return _kernel_step(cfg, rows, seq_len, n,
                        n * window_pairs(cfg, seq_len), dtype_bytes)


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
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    held = (layers(cfg) * cfg["moe_num_primary_experts"]
            * expert_params(cfg))
    passes = 4 if cfg.get("remat") else 3
    return {
        "flops": passes * 2.0 * routed_rows * expert_params(cfg),
        "bytes": passes * (held + routed_rows * (2 * d + 3 * f))
        * dtype_bytes,
    }
