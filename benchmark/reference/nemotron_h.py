"""A hybrid state-space / attention / sparse-expert LM in plain
``jax.numpy``: forward pass, loss and gradients.

The plain reference for configurations of the Nemotron-H block family
(NVIDIA 2025, "Nemotron-H: A Family of Accurate and Efficient Hybrid
Mamba-Transformer Models"; the Mamba-2 mixer is Dao and Gu 2024,
"Transformers are SSMs", section 7 and the released ``Mamba2`` module;
the sizes come from the configuration file, under the names the
published ``config.json`` gives them). It imports nothing of the
program; from the benchmark's GPT-2 reference it borrows only how a
precision stores a value and multiplies two matrices (``store``,
``matmul``). float32 at ``Precision.HIGHEST``; **the scan is the
recurrence as written, one position after another** (``lax.scan`` over
t; blocks of positions are rematerialised so that the backward pass
keeps one state a block and not one a position: that changes what is
stored, not what is computed); dense causal attention, the scores of
one head's queries a block at a time; no kernel, no sort: every held expert is computed
for every token and weighed by the routing weights, which are zero
where the token did not choose it. Every layer walks the rows one at a
time and is rematerialised; a Mamba-2 mixer is computed a group of heads
at a time (nothing crosses groups before W_out sums them) and a row's
logits a block of positions at a time, so that the replay fits beside
the four trees of weights and gradients the check holds.

The equations, x a token's hidden state, d the hidden size. Every
layer is ``x <- x + f(RMSNorm(x))`` with ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * g``, f by the pattern's letter; after the last
layer RMSNorm and the head. No position is added anywhere.

- ``M``, Mamba-2 (H heads of P, G groups of state N, ``inner`` = H P):
  ``[z | xBC | dt] = x W_in`` (widths inner, inner + 2 G N, H);
  ``xBC = silu(conv(xBC))``, conv causal and depthwise over K taps with
  a bias; xBC splits into x (H, P), B (G, N), C (G, N); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; for every head, with
  its group's B and C: ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t``; ``y = GroupRMSNorm(y * silu(z)) * g`` over
  the G groups of inner / G; ``y W_out``.
- ``*``, attention: q over H heads, k and v over Hkv heads of the same
  size, query head h reading key/value head ``h // (H / Hkv)``;
  ``softmax(q k^T / sqrt(head size))`` v; W_o. No bias, no rotary.
- ``E``, experts: ``s = sigmoid(x W_r)``; chosen = top k of s + b;
  ``w = s[chosen] / (sum + 1e-20) * routed_scaling_factor``; ``y =
  Shared(x) + sum over chosen experts HELD HERE (first_held ..) of w_e
  E_e(x)``; an expert, routed or shared, is ``W_down(relu(W_up x)^2)``.

Departures, all the program's and all in the configuration's
``assumed``: the head has a bias (zero at the start); the selection
bias b is drawn from the seed; dt is not clamped.

``precision``: ``"f32"`` the reference proper; ``"bf16"`` what the
configuration states, as the program does it; ``"fp8"`` one lower, the
control. The router's product, the norms' statistics, the convolution
and the recurrence stay float32 in all three, as in the program; a
lower precision rounds what enters and leaves them.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import HIGHEST, matmul, store

CONTROL_OF = {"bfloat16": "fp8", "float32": "bf16"}
QUERY_BLOCKS = 4    # parts of a head's queries whose scores are alive at once
SCAN_BLOCK = 128    # positions whose states the backward pass keeps
LOGIT_BLOCKS = 8    # parts of a row whose logits are alive at once
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def sizes(cfg: dict) -> dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        d=cfg["hidden_size"], pattern=cfg["hybrid_override_pattern"],
        h=h, p=p, g=g, n=n, inner=h * p, channels=h * p + 2 * g * n,
        taps=cfg["conv_kernel"],
        ah=cfg["num_attention_heads"], akv=cfg["num_key_value_heads"],
        ad=cfg["head_dim"],
        f=cfg["moe_intermediate_size"],
        fs=cfg["moe_shared_expert_intermediate_size"],
        held=cfg["n_routed_experts"], width=cfg["router_width"],
        first=cfg.get("first_held", 0), k=cfg["num_experts_per_tok"],
        v=cfg["vocab_size"], eps=float(cfg["layer_norm_epsilon"]),
    )


def layer_shapes(z: dict, kind: str) -> dict:
    d = z["d"]
    if kind == MAMBA:
        return {
            "norm_g": ((d,), "ones"),
            "in_proj": ((d, z["inner"] + z["channels"] + z["h"]), "normal"),
            "conv_w": ((z["taps"], z["channels"]), "conv"),
            "conv_b": ((z["channels"],), "zeros"),
            "dt_bias": ((z["h"],), "dt_bias"), "A_log": ((z["h"],), "a_log"),
            "D": ((z["h"],), "ones"), "gnorm_g": ((z["inner"],), "ones"),
            "out_proj": ((z["inner"], d), "normal"),
        }
    if kind == ATTENTION:
        return {
            "norm_g": ((d,), "ones"),
            "wq": ((d, z["ah"] * z["ad"]), "normal"),
            "wk": ((d, z["akv"] * z["ad"]), "normal"),
            "wv": ((d, z["akv"] * z["ad"]), "normal"),
            "wo": ((z["ah"] * z["ad"], d), "normal"),
        }
    if kind == EXPERTS:
        n = z["held"]
        return {
            "norm_g": ((d,), "ones"),
            "router": ((d, z["width"]), "normal"),
            "router_b": ((z["width"],), "bias"),
            "e_up": ((n, d, z["f"]), "normal"),
            "e_down": ((n, z["f"], d), "normal"),
            "s_up": ((d, z["fs"]), "normal"),
            "s_down": ((z["fs"], d), "normal"),
        }
    raise ValueError(f"layer kind {kind!r}: not M, * or E")


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind of initial value), layers as ``layer_<i>/``."""
    z = sizes(cfg)
    d, v = z["d"], z["v"]
    out = {
        "wte": ((v, d), "normal"), "final_g": ((d,), "ones"),
        "head_w": ((d, v), "normal"), "head_b": ((v,), "zeros"),
    }
    for i, kind in enumerate(z["pattern"]):
        for name, spec in layer_shapes(z, kind).items():
            out[f"layer_{i}/{name}"] = spec
    return out


def weights(cfg: dict, key) -> dict:
    """Initial weights from a PRNG key (traceable; jit it). Matrices
    normal(0, initializer_range); norm scales and D one; biases zero;
    the selection bias normal(0, router_bias_std); the convolution
    uniform(+-K^-1/2) (a depthwise ``Conv1d``'s default); ``A_log`` =
    log uniform(1, 16) and ``dt_bias`` = softplus^-1 of a step drawn
    log-uniformly in [time_step_min, time_step_max], not under
    time_step_floor (Mamba-2's released initialiser)."""
    std = float(cfg["initializer_range"])
    bias_std = float(cfg["router_bias_std"])
    low, high = float(cfg["time_step_min"]), float(cfg["time_step_max"])
    floor = float(cfg["time_step_floor"])
    out = {}
    for index, (name, (shape, kind)) in enumerate(
            sorted(shapes(cfg).items())):
        k = jax.random.fold_in(key, index)
        if kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif kind == "conv":
            bound = shape[0] ** -0.5
            out[name] = jax.random.uniform(
                k, shape, jnp.float32, -bound, bound)
        elif kind == "a_log":
            out[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32) * (
                math.log(high) - math.log(low)) + math.log(low))
            dt = jnp.maximum(dt, floor)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            out[name] = (bias_std if kind == "bias" else std) * (
                jax.random.normal(k, shape, jnp.float32))
    return out


def _layer_layout(prefix: str, z: dict, kind: str) -> list:
    d = z["d"]
    rows = [("norm_g", ("norm", "scale"), None)]
    if kind == MAMBA:
        rows += [
            ("in_proj", ("mamba", "in_proj", "kernel"), None),
            ("conv_w", ("mamba", "conv_weight"), None),
            ("conv_b", ("mamba", "conv_bias"), None),
            ("dt_bias", ("mamba", "dt_bias"), None),
            ("A_log", ("mamba", "A_log"), None),
            ("D", ("mamba", "D"), None),
            ("gnorm_g", ("mamba", "norm_scale"), None),
            ("out_proj", ("mamba", "out_proj", "kernel"), None),
        ]
    elif kind == ATTENTION:
        rows += [
            ("wq", ("attn", "q", "kernel"), (d, z["ah"], z["ad"])),
            ("wk", ("attn", "k", "kernel"), (d, z["akv"], z["ad"])),
            ("wv", ("attn", "v", "kernel"), (d, z["akv"], z["ad"])),
            ("wo", ("attn", "out", "kernel"), (z["ah"], z["ad"], d)),
        ]
    else:
        rows += [
            ("router", ("moe", "router"), None),
            ("router_b", ("moe", "router_bias"), None),
            ("e_up", ("moe", "w_up"), None),
            ("e_down", ("moe", "w_down"), None),
            ("s_up", ("moe", "shared", "up", "kernel"), None),
            ("s_down", ("moe", "shared", "down", "kernel"), None),
        ]
    return [(f"{prefix}/{name}", (prefix,) + path, shape)
            for name, path, shape in rows]


def layout(cfg: dict) -> list:
    """[(name here, path in the parameter tree of the program's
    ``NemotronHLM``, the shape there where it is another view of the
    same numbers)]."""
    z = sizes(cfg)
    rows = [
        ("wte", ("token_embed", "embedding"), None),
        ("final_g", ("final_norm", "scale"), None),
        ("head_w", ("lm_head", "kernel"), None),
        ("head_b", ("lm_head", "bias"), None),
    ]
    for i, kind in enumerate(z["pattern"]):
        rows += _layer_layout(f"layer_{i}", z, kind)
    return rows


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The same numbers (weights, or gradients of them) laid out as the
    parameter tree of the program's ``NemotronHLM``."""
    tree = {}
    for name, path, shape in layout(cfg):
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = w[name] if shape is None else w[name].reshape(shape)
    return tree


def from_program_tree(tree: dict, cfg: dict) -> dict:
    """:func:`to_program_tree` backwards."""
    shapes_here = shapes(cfg)
    w = {}
    for name, path, _ in layout(cfg):
        leaf = tree
        for key in path:
            leaf = leaf[key]
        w[name] = leaf.reshape(shapes_here[name][0])
    return w


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def causal_conv(x, weight, bias):
    """x (S, C): y_t = bias + sum_k weight[k] x_{t - (K - 1) + k}."""
    taps, seq = weight.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return bias + sum(weight[k] * padded[k:k + seq] for k in range(taps))


def selective_scan(x, dt, a, b, c, d_skip):
    """The recurrence, one position after another. x (S, H, P), dt
    (S, H), a (H,), b and c (S, G, N), d_skip (H,) -> y (S, H, P)."""
    seq, h, p = x.shape
    g, n = b.shape[1:]
    per = h // g
    x = x.reshape(seq, g, per, p)
    dt = dt.reshape(seq, g, per)
    a, d_skip = a.reshape(g, per), d_skip.reshape(g, per)

    def step(state, inputs):                     # state (G, per, P, N)
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        y_t = jnp.sum(state * c_t[:, None, None, :], axis=-1)
        return state, y_t + d_skip[..., None] * x_t

    block = max(t for t in range(1, min(seq, SCAN_BLOCK) + 1)
                if seq % t == 0)

    @jax.checkpoint
    def some_positions(state, inputs):
        return jax.lax.scan(step, state, inputs)

    in_blocks = lambda z: z.reshape(  # noqa: E731
        (seq // block, block) + z.shape[1:])
    _, y = jax.lax.scan(
        some_positions, jnp.zeros((g, per, p, n), jnp.float32),
        (in_blocks(x), in_blocks(dt), in_blocks(b), in_blocks(c)))
    return y.reshape(seq, h, p)


def mamba_group(h, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gain,
                w_out, z, p):
    """One group's heads of the mixer, one row: h (S, d) -> this
    group's part of the result (S, d). ``w_in`` holds the group's
    columns of W_in in the order [z | x | B | C | dt], the other
    arguments its slices likewise."""
    keep = lambda y: store(y, p)  # noqa: E731
    seq = h.shape[0]
    per, n = z["h"] // z["g"], z["n"]
    inner = per * z["p"]
    zxbcdt = keep(matmul(h, w_in, p))
    gate = zxbcdt[:, :inner]
    xbc = keep(jax.nn.silu(causal_conv(
        zxbcdt[:, inner:2 * inner + 2 * n], conv_w, conv_b)))
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * n:] + dt_bias)
    y = keep(selective_scan(
        xbc[:, :inner].reshape(seq, per, z["p"]), dt, -jnp.exp(a_log),
        xbc[:, inner:inner + n].reshape(seq, 1, n),
        xbc[:, inner + n:].reshape(seq, 1, n), d_skip,
    ).reshape(seq, inner))
    y = keep(rms_norm(y * jax.nn.silu(gate), gain, z["eps"]))
    return matmul(y, w_out, p)


def mamba_mixer(h, w, z, p):
    """One row: h (S, d) -> (S, d). The heads of a group share B and C
    and a slice of the gated norm, and nothing crosses groups before
    W_out sums them, so the mixer is the sum of its groups' parts: they
    are computed one after another (each rematerialised), an eighth of
    the intermediates alive at a time."""
    inner, g, n = z["inner"], z["g"], z["n"]
    wide = inner // g                   # a group's heads x P
    per = z["h"] // g
    groups = jnp.arange(g)[:, None]
    columns = jnp.concatenate([
        groups * wide + jnp.arange(wide),                           # z
        inner + groups * wide + jnp.arange(wide),                   # x
        2 * inner + groups * n + jnp.arange(n),                     # B
        2 * inner + g * n + groups * n + jnp.arange(n),             # C
        2 * inner + 2 * g * n + groups * per + jnp.arange(per),     # dt
    ], axis=1)                          # (G, a group's columns of W_in)
    channels = columns[:, wide:2 * wide + 2 * n] - inner
    by_group = lambda v: v.reshape((g, v.shape[0] // g) + v.shape[1:])  # noqa: E731,E501

    part = jax.checkpoint(lambda *group: mamba_group(h, *group, z, p))

    def one(total, group):
        # The sum stays outside what is rematerialised: a group keeps
        # its slices, not the running total it is added to.
        return total + part(*group), None

    # The groups' slices are the scan's ``xs``, not values it carries.
    total, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.moveaxis(w["in_proj"][:, columns], 1, 0),
        jnp.moveaxis(w["conv_w"][:, channels], 1, 0), w["conv_b"][channels],
        by_group(w["dt_bias"]), by_group(w["A_log"]), by_group(w["D"]),
        by_group(w["gnorm_g"]), by_group(w["out_proj"])))
    return store(total, p)


def attention(h, w, z, p):
    """One row: h (S, d) -> (S, d)."""
    keep = lambda y: store(y, p)  # noqa: E731
    seq = h.shape[0]
    heads, kv, hd = z["ah"], z["akv"], z["ad"]
    group = heads // kv
    q = keep(matmul(h, w["wq"], p)).reshape(seq, heads, hd)
    k = keep(matmul(h, w["wk"], p)).reshape(seq, kv, hd)
    v = keep(matmul(h, w["wv"], p)).reshape(seq, kv, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    blocks = QUERY_BLOCKS if seq % QUERY_BLOCKS == 0 else 1
    size = seq // blocks
    positions = jnp.arange(seq)

    @jax.checkpoint
    def some_queries(args):
        """One head's queries ``first .. first + size`` against all its
        keys: the scores alive are (size, S)."""
        q, first, k, v = args
        scores = matmul(q, k.T, p) * scale
        visible = (first + jnp.arange(size))[:, None] >= positions[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v, p)

    def one_head(args):
        q, k, v = args                                     # (S, hd)
        out = jax.lax.map(
            lambda block: some_queries((block[0], block[1], k, v)),
            (q.reshape(blocks, size, hd), jnp.arange(blocks) * size))
        return out.reshape(seq, hd)

    # Query head i reads key/value head i // group.
    by_head = lambda x: x.transpose(1, 0, 2)  # noqa: E731
    att = jax.lax.map(one_head, (
        by_head(q), jnp.repeat(by_head(k), group, axis=0),
        jnp.repeat(by_head(v), group, axis=0)))
    att = keep(by_head(att).reshape(seq, heads * hd))
    return keep(matmul(att, w["wo"], p))


def relu2_mlp(h, up, down, p):
    keep = lambda y: store(y, p)  # noqa: E731
    hidden = keep(jnp.square(jax.nn.relu(keep(matmul(h, up, p)))))
    return keep(matmul(hidden, down, p))


def routing(h, w, z, cfg, held=None):
    """(chosen (S, k) expert ids over the whole width, weights (S, k));
    float32 whatever the precision, as in the program. ``held`` (S, k),
    where given, are the choices in place of this layer's own."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["router"], precision=HIGHEST))
    chosen = held
    if chosen is None:
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(w["router_b"]), z["k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * float(
        cfg["routed_scaling_factor"])
    return chosen, weights


def held_weights(chosen, weights, z):
    """(S, held): a token's weight for every expert held here, zero
    where it did not choose it."""
    local = chosen - z["first"]
    onehot = local[..., None] == jnp.arange(z["held"])     # (S, k, held)
    return jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)


def expert_layer(h, w, z, cfg, p, held=None):
    """(the layer's result, the experts every token chose (S, k))."""
    chosen, weights = routing(h, w, z, cfg, held)
    per_expert = held_weights(chosen, weights, z)

    @jax.checkpoint
    def one(up, down, weight):
        return weight[:, None] * relu2_mlp(h, up, down, p)

    # In Python, as ``_over_rows`` walks the rows: a scan would copy
    # the stacked experts into its state. The sum stays outside what is
    # rematerialised, so no expert keeps the running total.
    routed = jnp.zeros_like(h)
    for e in range(z["held"]):
        routed = routed + one(w["e_up"][e], w["e_down"][e], per_expert[:, e])
    shared = relu2_mlp(h, w["s_up"], w["s_down"], p)
    return store(shared + store(routed, p), p), chosen


def layer(x, w, z, cfg, p, kind: str, held=None):
    """(the layer's result, the expert layer's choices (S, k); none of
    another layer: (S, 0))."""
    keep = lambda y: store(y, p)  # noqa: E731
    h = keep(rms_norm(x, w["norm_g"], z["eps"]))
    chosen = jnp.zeros((x.shape[0], 0), jnp.int32)
    if kind == MAMBA:
        y = mamba_mixer(h, w, z, p)
    elif kind == ATTENTION:
        y = attention(h, w, z, p)
    else:
        y, chosen = expert_layer(h, w, z, cfg, p, held)
    return keep(x + y), chosen.astype(jnp.int32)


def _sub(w: dict, prefix: str) -> dict:
    cut = len(prefix) + 1
    return {name[cut:]: value for name, value in w.items()
            if name.startswith(prefix + "/")}


def _logits(x, w, p):
    return store(matmul(x, w["head_w"], p) + w["head_b"], p)


def _summed_cross_entropy(hidden, labels, w, p):
    logp = jax.nn.log_softmax(_logits(hidden, w, p), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _row_cross_entropy(hidden, labels, w, p):
    """The mean next-token cross-entropy of one row, its logits a block
    of positions at a time."""
    seq = hidden.shape[0]
    blocks = LOGIT_BLOCKS if seq % LOGIT_BLOCKS == 0 else 1
    size = seq // blocks
    one = jax.checkpoint(
        lambda block: _summed_cross_entropy(block[0], block[1], w, p))
    # A loop, not Python: the blocks' parts of the head's gradient are
    # summed in its state, where unrolled they would stand side by side.
    sums = jax.lax.map(one, (
        hidden.reshape(blocks, size, hidden.shape[1]),
        labels.reshape(blocks, size)))
    return jnp.sum(sums) / seq


def _over_rows(fn, weights, *per_row, apart=False):
    """``fn(weights, *row)`` of one row at a time, each row
    rematerialised: what is kept for the backward pass is the rows'
    inputs. The rows are walked in Python, not by ``lax.map``: a weight
    that a loop carries is copied into the loop's state by the TPU
    compiler, one more copy of every layer's weights beside the four
    trees the check already holds (2.3 GB at the published sizes:
    PERF.md, PR 32). ``apart`` (a lower precision): each row takes the
    weights through a barrier of its own, so that what one row derives
    from them (their rounded copies, 3.6 GB in float8) is not kept for
    the next; in float32 nothing is derived and the barrier would only
    cost (0.6 GB of copies)."""
    one = jax.checkpoint(fn)
    rows = []
    for r in range(per_row[0].shape[0]):
        own, args = weights, tuple(x[r] for x in per_row)
        if apart:
            own, args = jax.lax.optimization_barrier((own, args))
        if rows:
            # One row after another, forward and backward: unrolled,
            # the compiler would be free to run them side by side.
            args, rows[-1] = jax.lax.optimization_barrier((args, rows[-1]))
        rows.append(one(own, *args))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


def expert_layers(cfg: dict) -> list:
    """The layers with experts, in the order they run."""
    return [f"layer_{i}" for i, kind in enumerate(
        cfg["hybrid_override_pattern"]) if kind == EXPERTS]


def held_count(chosen, cfg: dict):
    """How many of the choices (..., k) fell on experts held here."""
    z = sizes(cfg)
    local = chosen - z["first"]
    return jnp.sum((local >= 0) & (local < z["held"]), dtype=jnp.int32)


def hidden_states(w, tokens, cfg, p="f32", held=None):
    """tokens (rows, S) int -> (the last hidden state after its norm
    (rows, S, d), {expert layer: the experts every token chose (rows,
    S, k)}). ``held``: such a mapping, to go by in place of the layers'
    own choices."""
    z = sizes(cfg)
    keep = lambda y: store(y, p)  # noqa: E731
    chosen = {}
    x = keep(w["wte"])[tokens]
    for i, kind in enumerate(z["pattern"]):
        name = f"layer_{i}"
        lw = _sub(w, name)
        if held is None or kind != EXPERTS:
            x, picks = _over_rows(
                lambda lw, row: layer(row, lw, z, cfg, p, kind), lw, x,
                apart=p != "f32")
        else:
            x, picks = _over_rows(
                lambda lw, row, go: layer(row, lw, z, cfg, p, kind, go),
                lw, x, held[name], apart=p != "f32")
        if kind == EXPERTS:
            chosen[name] = picks
    return keep(rms_norm(x, w["final_g"], z["eps"])), chosen


def row_logits(w, tokens, cfg, p="f32"):
    """One row, tokens (S,) int -> (logits (S, V), token-choices of
    held experts over every expert layer)."""
    hidden, chosen = hidden_states(w, tokens[None], cfg, p)
    return (_logits(hidden[0], w, p),
            sum(held_count(c, cfg) for c in chosen.values()))


def loss_terms(w, tokens, labels, cfg, p="f32", held=None):
    """tokens, labels (rows, S) int -> {"loss": the mean next-token
    cross-entropy, "chosen": as :func:`hidden_states` gives them}; the
    logits of one row at a time."""
    hidden, chosen = hidden_states(w, tokens, cfg, p, held)
    head = {name: w[name] for name in ("head_w", "head_b")}
    loss = jnp.mean(_over_rows(
        lambda head, h, l: _row_cross_entropy(h, l, head, p),
        head, hidden, labels, apart=p != "f32"))
    return {"loss": loss, "chosen": chosen}


def choices(w, tokens, cfg: dict) -> list:
    """The experts every token chooses, (rows, S, k) for every expert
    layer in :func:`expert_layers`' order, in float32: what a comparison
    of gradients holds the routing to (a choice that turns on rounding
    moves a token's rows between a held expert and an absent one: a
    legitimate difference between two precisions, and larger than what a
    lower precision does to the products)."""
    chosen = hidden_states(w, tokens, cfg)[1]
    return [chosen[name] for name in expert_layers(cfg)]


def load_direction(chosen, cfg: dict):
    """sign(an expert's token-choices - the mean over the router's
    width): what the selection bias moves against, at
    ``bias_update_speed`` a step (auxiliary-loss-free balancing)."""
    width = sizes(cfg)["width"]
    load = jnp.sum(chosen[..., None] == jnp.arange(width),
                   axis=tuple(range(chosen.ndim)), dtype=jnp.float32)
    return jnp.sign(load - jnp.mean(load))


def loss_and_grads(w, tokens, labels, cfg: dict, precision="f32"):
    """Loss and gradients over all rows. In a lower precision (the
    control) the routing is held to the float32 choices, as the
    comparison holds the program's. The selection bias's "gradient" is
    its load's direction, as in the program
    (``models/mla_moe.py::_load_tap``)."""
    held = None
    if precision != "f32":
        held = dict(zip(expert_layers(cfg), choices(w, tokens, cfg)))

    def total(w):
        terms = loss_terms(w, tokens, labels, cfg, precision, held)
        return terms["loss"], terms["chosen"]

    (loss, chosen), grads = jax.value_and_grad(total, has_aux=True)(w)
    for name, picks in chosen.items():
        grads[f"{name}/router_b"] = load_direction(picks, cfg)
    return loss, grads


def routed_rows(w, tokens, cfg: dict):
    """Token-choices of held experts over all rows, summed over the
    expert layers: what the program's ``moe_rows`` counter has to read
    for the same step (float32 routing; a choice that flips on rounding
    moves it by one)."""
    return sum(held_count(c, cfg) for c in choices(w, tokens, cfg))
