"""A latent-attention sparse-expert LM with multi-token prediction, in
plain ``jax.numpy``: forward pass, loss and gradients.

The plain reference for configurations of the DeepSeek-V3 block family
(DeepSeek-AI 2024, "DeepSeek-V3 Technical Report", sections 2.1, 2.2;
the sizes and switches come from the configuration file, under the
names the published ``config.json`` gives them). It imports nothing of
the program; from the benchmark's GPT-2 reference it borrows only how a
precision stores a value and multiplies two matrices (``store``,
``matmul``). float32 at ``Precision.HIGHEST``, dense causal attention
(scores of a few heads at a time), no kernel, no sort: every held
expert is computed for every token and weighed by the routing weights,
which are zero where the token did not choose it. Every layer walks the
rows one at a time and is rematerialised, so that the float32 state of
the replay and one row's temporaries fit a free chip.

The equations, x a token's hidden state:

- RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g; pre-norm blocks.
- Attention: c_q = RMSNorm(x W_qa); [q_nope; q_rope] = c_q W_qb per
  head; [c_kv; k_r] = x W_kva; c_kv = RMSNorm(c_kv); [k_nope; v] = c_kv
  W_kvb per head; RoPE turns the pairs (2i, 2i+1) of q_rope and of k_r
  (one head, shared); softmax(q k^T / sqrt(nope + rope)) v; W_o.
- MLP and every expert: W_down(silu(W_gate x) * (W_up x)).
- Expert layer: s = sigmoid(x W_g); chosen = top k of s + b;
  w = s[chosen] / (sum + 1e-20) * routed_scaling_factor; y = Shared(x) +
  sum over chosen experts HELD HERE (``first_held`` ..) of w_e E_e(x).
- MTP: h' = W_eh [RMSNorm(h); RMSNorm(Emb(t_next))], one expert block,
  RMSNorm, the shared head; loss = CE_main + mtp_loss_weight * CE_mtp.

Departures, all the program's and all in the configuration's
``assumed``: the head has a bias (zero at the start); the selection
bias b is drawn from the seed and held fixed.

``precision``: ``"f32"`` the reference proper; ``"bf16"`` what the
configuration states, as the program does it; ``"fp8"`` one lower, the
control. The router's product and the norms' statistics stay float32 in
all three, as in the program.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import HIGHEST, matmul, store

CONTROL_OF = {"bfloat16": "fp8", "float32": "bf16"}
HEAD_GROUP = 2     # heads whose scores are alive at once


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return dict(
        d=d, h=h, nope=nope, rope=rope, dv=cfg["v_head_dim"],
        ql=cfg["q_lora_rank"], kvl=cfg["kv_lora_rank"],
        f_dense=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        held=cfg["n_routed_experts"], width=cfg["router_width"],
        first=cfg.get("first_held", 0), k=cfg["num_experts_per_tok"],
        v=cfg["vocab_size"], dense=cfg["first_k_dense_replace"],
        layers=cfg["num_hidden_layers"],
        mtp=cfg["num_nextn_predict_layers"],
    )


def block_shapes(z: dict, experts: bool) -> dict:
    d, h = z["d"], z["h"]
    shapes = {
        "attn_g": ((d,), "ones"),
        "wqa": ((d, z["ql"]), "normal"), "qn_g": ((z["ql"],), "ones"),
        "wqb": ((z["ql"], h * (z["nope"] + z["rope"])), "normal"),
        "wkva": ((d, z["kvl"] + z["rope"]), "normal"),
        "kvn_g": ((z["kvl"],), "ones"),
        "wkvb": ((z["kvl"], h * (z["nope"] + z["dv"])), "normal"),
        "wo": ((h * z["dv"], d), "normal"),
        "ffn_g": ((d,), "ones"),
    }
    if not experts:
        f = z["f_dense"]
        shapes.update({"gate": ((d, f), "normal"), "up": ((d, f), "normal"),
                       "down": ((f, d), "normal")})
        return shapes
    f, n = z["f"], z["held"]
    shapes.update({
        "router": ((d, z["width"]), "normal"),
        "router_b": ((z["width"],), "bias"),
        "e_gate": ((n, d, f), "normal"), "e_up": ((n, d, f), "normal"),
        "e_down": ((n, f, d), "normal"),
        "s_gate": ((d, f), "normal"), "s_up": ((d, f), "normal"),
        "s_down": ((f, d), "normal"),
    })
    return shapes


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind), blocks as ``block_<i>`` / ``mtp_block``
    sub-dicts flattened with a '/'."""
    z = sizes(cfg)
    d, v = z["d"], z["v"]
    out = {
        "wte": ((v, d), "normal"), "final_g": ((d,), "ones"),
        "head_w": ((d, v), "normal"), "head_b": ((v,), "zeros"),
    }
    for i in range(z["layers"]):
        for name, spec in block_shapes(z, i >= z["dense"]).items():
            out[f"block_{i}/{name}"] = spec
    if z["mtp"]:
        out.update({
            "mtp_hnorm_g": ((d,), "ones"), "mtp_enorm_g": ((d,), "ones"),
            "mtp_eh": ((2 * d, d), "normal"),
            "mtp_final_g": ((d,), "ones"),
        })
        for name, spec in block_shapes(z, True).items():
            out[f"mtp_block/{name}"] = spec
    return out


def weights(cfg: dict, key) -> dict:
    """Initial weights from a PRNG key (traceable; jit it):
    normal(0, initializer_range), norm scales one, the head's bias zero,
    the router's selection bias normal(0, router_bias_std)."""
    std = float(cfg["initializer_range"])
    bias_std = float(cfg["router_bias_std"])
    out = {}
    for index, (name, (shape, kind)) in enumerate(
            sorted(shapes(cfg).items())):
        if kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = (bias_std if kind == "bias" else std) * (
                jax.random.normal(jax.random.fold_in(key, index), shape,
                                  jnp.float32))
    return out


def _block_layout(prefix: str, z: dict, experts: bool) -> list:
    h, d = z["h"], z["d"]
    rows = [
        ("attn_g", ("attn_norm", "scale"), None),
        ("wqa", ("attn", "q_a", "kernel"), None),
        ("qn_g", ("attn", "q_norm", "scale"), None),
        ("wqb", ("attn", "q_b", "kernel"),
         (z["ql"], h, z["nope"] + z["rope"])),
        ("wkva", ("attn", "kv_a", "kernel"), None),
        ("kvn_g", ("attn", "kv_norm", "scale"), None),
        ("wkvb", ("attn", "kv_b", "kernel"),
         (z["kvl"], h, z["nope"] + z["dv"])),
        ("wo", ("attn", "out", "kernel"), (h, z["dv"], d)),
        ("ffn_g", ("ffn_norm", "scale"), None),
    ]
    if not experts:
        rows += [(name, ("mlp", name, "kernel"), None)
                 for name in ("gate", "up", "down")]
    else:
        rows += [("router", ("moe", "router"), None),
                 ("router_b", ("moe", "router_bias"), None),
                 ("e_gate", ("moe", "w_gate"), None),
                 ("e_up", ("moe", "w_up"), None),
                 ("e_down", ("moe", "w_down"), None)]
        rows += [(f"s_{name}", ("moe", "shared", name, "kernel"), None)
                 for name in ("gate", "up", "down")]
    return [(f"{prefix}/{name}", (prefix,) + path, shape)
            for name, path, shape in rows]


def layout(cfg: dict) -> list:
    """[(name here, path in the parameter tree of the program's
    ``MlaMoeLM``, the shape there where it is another view of the same
    numbers)]."""
    z = sizes(cfg)
    rows = [
        ("wte", ("token_embed", "embedding"), None),
        ("final_g", ("final_norm", "scale"), None),
        ("head_w", ("lm_head", "kernel"), None),
        ("head_b", ("lm_head", "bias"), None),
    ]
    for i in range(z["layers"]):
        rows += _block_layout(f"block_{i}", z, i >= z["dense"])
    if z["mtp"]:
        rows += [
            ("mtp_hnorm_g", ("mtp_hnorm", "scale"), None),
            ("mtp_enorm_g", ("mtp_enorm", "scale"), None),
            ("mtp_eh", ("mtp_eh_proj", "kernel"), None),
            ("mtp_final_g", ("mtp_final_norm", "scale"), None),
        ] + _block_layout("mtp_block", z, True)
    return rows


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The same numbers (weights, or gradients of them) laid out as the
    parameter tree of the program's ``MlaMoeLM``."""
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


def rope(x, theta):
    """x (S, ..., R): the pair (2i, 2i+1) turned by position *
    theta^(-2i/R)."""
    seq, r = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    angle = angle.reshape((seq,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(h, w, z, cfg, p):
    """One row: h (S, d) -> (S, d)."""
    keep = lambda y: store(y, p)  # noqa: E731
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    seq = h.shape[0]
    heads, nope, rp, dv = z["h"], z["nope"], z["rope"], z["dv"]
    c_q = keep(rms_norm(keep(matmul(h, w["wqa"], p)), w["qn_g"], eps))
    q = keep(matmul(c_q, w["wqb"], p)).reshape(seq, heads, nope + rp)
    kv = keep(matmul(h, w["wkva"], p))
    c_kv = keep(rms_norm(kv[:, :z["kvl"]], w["kvn_g"], eps))
    k_rope = keep(rope(kv[:, z["kvl"]:], theta))           # (S, rope)
    k_v = keep(matmul(c_kv, w["wkvb"], p)).reshape(seq, heads, nope + dv)
    q = jnp.concatenate(
        [q[..., :nope], keep(rope(q[..., nope:], theta))], axis=-1)
    k = jnp.concatenate(
        [k_v[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (seq, heads, rp))], axis=-1)
    v = k_v[..., nope:]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rp))

    @jax.checkpoint
    def some_heads(qkv):
        q, k, v = qkv                                      # (g, S, .)
        scores = matmul(q, jnp.swapaxes(k, -1, -2), p) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v, p)

    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else 1

    def grouped(x):
        return x.transpose(1, 0, 2).reshape(
            heads // group, group, seq, x.shape[-1])

    att = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v)))
    att = keep(att.reshape(heads, seq, dv).transpose(1, 0, 2).reshape(
        seq, heads * dv))
    return keep(matmul(att, w["wo"], p))


def gated_mlp(h, gate, up, down, p):
    keep = lambda y: store(y, p)  # noqa: E731
    hidden = keep(jax.nn.silu(keep(matmul(h, gate, p)))
                  * keep(matmul(h, up, p)))
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
    def one(total, expert):
        gate, up, down, weight = expert
        return total + weight[:, None] * gated_mlp(h, gate, up, down, p), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["e_gate"], w["e_up"], w["e_down"], per_expert.T))
    shared = gated_mlp(h, w["s_gate"], w["s_up"], w["s_down"], p)
    return store(shared + store(routed, p), p), chosen


def block(x, w, z, cfg, p, experts: bool, held=None):
    """(the block's result, the expert layer's choices (S, k); none of
    a dense block: (S, 0))."""
    keep = lambda y: store(y, p)  # noqa: E731
    eps = float(cfg["rms_norm_eps"])
    x = keep(x + attention(keep(rms_norm(x, w["attn_g"], eps)), w, z, cfg, p))
    h = keep(rms_norm(x, w["ffn_g"], eps))
    if experts:
        y, chosen = expert_layer(h, w, z, cfg, p, held)
    else:
        y = gated_mlp(h, w["gate"], w["up"], w["down"], p)
        chosen = jnp.zeros((x.shape[0], 0), jnp.int32)
    return keep(x + y), chosen.astype(jnp.int32)


def _sub(w: dict, prefix: str) -> dict:
    cut = len(prefix) + 1
    return {name[cut:]: value for name, value in w.items()
            if name.startswith(prefix + "/")}


def _logits(x, w, p):
    return store(matmul(x, w["head_w"], p) + w["head_b"], p)


def _cross_entropy(logits, labels, weights):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked * weights) / jnp.sum(weights)


def _over_rows(fn, *per_row):
    """``fn`` of one row at a time (``lax.map``), each row
    rematerialised: what is kept for the backward pass is the rows'
    inputs, and a weight's gradient is summed over the rows where it is
    made, one layer's weights at a time."""
    return jax.lax.map(jax.checkpoint(lambda args: fn(*args)), per_row)


def expert_blocks(cfg: dict) -> list:
    """The blocks with an expert layer, in the order they run."""
    z = sizes(cfg)
    return [f"block_{i}" for i in range(z["dense"], z["layers"])] + (
        ["mtp_block"] if z["mtp"] else [])


def held_count(chosen, cfg: dict):
    """How many of the choices (..., k) fell on experts held here."""
    z = sizes(cfg)
    local = chosen - z["first"]
    return jnp.sum((local >= 0) & (local < z["held"]), dtype=jnp.int32)


def hidden_states(w, tokens, cfg, p="f32", held=None):
    """tokens (rows, S) int -> (the main model's last hidden state
    after its last norm (rows, S, d), the MTP module's or None,
    {expert block: the experts every token chose (rows, S, k)}).
    ``held``: such a mapping, to go by in place of the layers' own
    choices."""
    z = sizes(cfg)
    keep = lambda y: store(y, p)  # noqa: E731
    eps = float(cfg["rms_norm_eps"])
    embedding = keep(w["wte"])
    chosen = {}

    def run(name, x, experts):
        lw = _sub(w, name)
        if held is None or not experts:
            x, picks = _over_rows(
                lambda row: block(row, lw, z, cfg, p, experts), x)
        else:
            x, picks = _over_rows(
                lambda row, go: block(row, lw, z, cfg, p, True, go),
                x, held[name])
        if experts:
            chosen[name] = picks
        return x

    x = embedding[tokens]
    for i in range(z["layers"]):
        x = run(f"block_{i}", x, i >= z["dense"])
    main = keep(rms_norm(x, w["final_g"], eps))
    if not z["mtp"]:
        return main, None, chosen
    joined = jnp.concatenate([
        keep(rms_norm(x, w["mtp_hnorm_g"], eps)),
        keep(rms_norm(embedding[jnp.roll(tokens, -1, axis=1)],
                      w["mtp_enorm_g"], eps)),
    ], axis=-1)
    h = run("mtp_block", keep(matmul(joined, w["mtp_eh"], p)), True)
    return main, keep(rms_norm(h, w["mtp_final_g"], eps)), chosen


def row_logits(w, tokens, cfg, p="f32"):
    """One row, tokens (S,) int -> (logits of the main head (S, V),
    logits of the MTP head or None, token-choices of held experts over
    every expert layer)."""
    main, mtp, chosen = hidden_states(w, tokens[None], cfg, p)
    return (_logits(main[0], w, p),
            None if mtp is None else _logits(mtp[0], w, p),
            sum(held_count(c, cfg) for c in chosen.values()))


def loss_terms(w, tokens, labels, cfg, p="f32", held=None):
    """tokens, labels (rows, S) int -> {"main": CE of the main head,
    "mtp": CE of the MTP head, "chosen": as :func:`hidden_states` gives
    them, "routed_rows": how many of them fell on held experts}; the
    logits of one row at a time."""
    main, mtp, chosen = hidden_states(w, tokens, cfg, p, held)
    seq = tokens.shape[1]

    def mean_ce(hidden, labels, weights):
        return jnp.mean(_over_rows(
            lambda h, l: _cross_entropy(_logits(h, w, p), l, weights),
            hidden, labels))

    out = {"main": mean_ce(main, labels, jnp.ones((seq,), jnp.float32)),
           "mtp": jnp.float32(0.0), "chosen": chosen,
           "routed_rows": sum(held_count(c, cfg) for c in chosen.values())}
    if mtp is not None:
        # Position i has seen token i+1 and predicts labels[i+1]; the
        # last has no target.
        has_target = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
        out["mtp"] = mean_ce(mtp, jnp.roll(labels, -1, axis=1), has_target)
    return out


def row_loss(w, tokens, labels, cfg, p="f32"):
    """(loss, its terms) of one row: tokens, labels (S,) int."""
    terms = loss_terms(w, tokens[None], labels[None], cfg, p)
    return terms["main"] + float(cfg["mtp_loss_weight"]) * terms["mtp"], terms


def choices(w, tokens, cfg: dict) -> list:
    """The experts every token chooses, (rows, S, k) for every expert
    layer in :func:`expert_blocks`' order, in float32: what a
    comparison of gradients holds the routing to. A choice that turns
    on rounding (the 8th and the 9th of 256 scores lie 0.007 apart, a
    bfloat16 activation is 0.004 off) moves a token's rows between a
    held expert and an absent one: a legitimate difference between two
    precisions, and larger than what a lower precision does to the
    products."""
    chosen = hidden_states(w, tokens, cfg)[2]
    return [chosen[name] for name in expert_blocks(cfg)]


def load_direction(chosen, cfg: dict):
    """sign(an expert's token-choices - the mean over the router's
    width): what the selection bias moves against, at
    ``bias_update_speed`` a step (auxiliary-loss-free balancing)."""
    width = sizes(cfg)["width"]
    load = jnp.sum(chosen[..., None] == jnp.arange(width),
                   axis=tuple(range(chosen.ndim)), dtype=jnp.float32)
    return jnp.sign(load - jnp.mean(load))


def loss_and_grads(w, tokens, labels, cfg: dict, precision="f32"):
    """Loss and gradients over all rows. Every layer walks the rows one
    at a time (:func:`_over_rows`), so one row's temporaries are alive
    at once and no second tree of gradients ever is: beside the
    replay's weights, Adam moments and first gradients, a free chip has
    room for little more.

    In a lower precision (the control) the routing is held to the
    float32 choices, as the comparison holds the program's. The
    selection bias's "gradient" is its load's direction, as in the
    program (``models/mla_moe.py::_load_tap``)."""
    held = None
    if precision != "f32":
        held = dict(zip(expert_blocks(cfg), choices(w, tokens, cfg)))

    def total(w):
        terms = loss_terms(w, tokens, labels, cfg, precision, held)
        return (terms["main"] + float(cfg["mtp_loss_weight"]) * terms["mtp"],
                terms["chosen"])

    (loss, chosen), grads = jax.value_and_grad(total, has_aux=True)(w)
    for name, picks in chosen.items():
        grads[f"{name}/router_b"] = load_direction(picks, cfg)
    return loss, grads


def routed_rows(w, tokens, cfg: dict):
    """Token-choices of held experts over all rows, summed over the
    expert layers and the MTP block: what the program's ``moe_rows``
    counter has to read for the same step (float32 routing; a choice
    that flips on rounding moves it by one)."""
    return sum(held_count(c, cfg) for c in choices(w, tokens, cfg))
