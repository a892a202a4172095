"""A block-diffusion LM over a Qwen3-MoE body in plain ``jax.numpy``:
noising, forward pass, loss and gradients.

The plain reference for configurations of the SDAR family (SDAR,
"Synergistic Diffusion-AutoRegression", arXiv:2510.06303, ``model_type``
``sdar_moe``; the training objective and the attention mask are block
diffusion's, BD3-LM, arXiv:2503.09573; the body is Qwen3-MoE's; the
sizes come from the configuration file, under the names the published
``config.json`` gives them, the block length and the noise from its
``assumed`` keys). It imports nothing of the program; from the
benchmark's GPT-2 reference it borrows only how a precision stores a
value and multiplies two matrices (``store``, ``matmul``). float32 at
``Precision.HIGHEST``; **the mask is built densely from its four rules**
(:func:`diffusion_mask`) and the attention is dense under it, the scores
of one head's queries a block at a time over all 2L keys; no kernel, no
sort: every held expert is computed for every token (a few experts
side by side) and weighed by the routing weights, which are zero
where the token did not choose it. Every layer walks the rows one at a
time in Python and is rematerialised, and a row's logits are computed a
block of positions at a time, so that the replay fits beside the four
trees of weights and gradients the check holds.

The equations, d the hidden size, L the row's length, b the block
length, n(i) = i // b.

- *Noising a clean row x_0* (:func:`noising`): for each block n a time
  t_n ~ U(0, 1), p_n = (1 - eps) t_n + eps; for each position u_i ~
  U(0, 1), m_i = [u_i < p_n(i)]; x_t,i = MASK where m_i, else x_0,i.
  t, p and u are whole millionths (t and u uniform integers below
  10^6, p rounded down), so m is a comparison of integers and the same
  bits on every backend. The draws come from a threefry key that is a
  function of the row and ``noise_seed`` alone: the key of
  ``noise_seed`` (its data are the words (0, noise_seed)), folded with
  sum_i x_0,i (2 i + 1) mod 2^32; its first split draws t, its second
  u. MASK is row ``vocab_size`` of the embedding.
- *The sequence*: z = [x_t ; x_0], 2L positions, at positions [0..L-1,
  0..L-1]; h = Embed(z).
- *Every layer*: h <- h + Attn(RMSNorm(h)); h <- h + MoE(RMSNorm(h)),
  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
- *Attn*: q = W_q h over H heads, k = W_k h, v = W_v h over Hkv heads,
  no bias; q and k RMS-normalised over each head with a learned scale
  the heads share; rotary by halves over the whole head, (x1 cos - x2
  sin, x2 cos + x1 sin), angle_j = pos theta^(-2j/D); softmax attention
  at D^-1/2, query head h reading key/value head h // (H / Hkv); W_o.
  The mask, query i, key j, primes for a clean position's index in its
  half: noised i, noised j: n(i) = n(j); noised i, clean j: n(j') <
  n(i); clean i, clean j: n(j') <= n(i'); clean i, noised j: never.
- *MoE*: P = softmax(W_r h) over the router's whole width; the k
  largest; w = the chosen P over their sum, times
  ``routed_scaling_factor`` (1); y = sum over chosen experts HELD HERE
  (first_held ..) of w_e W_down(silu(W_gate h) * W_up h). No shared
  expert, no selection bias.
- *Head*: logits_i = W_head RMSNorm(h_i) + bias for the noised half, i
  < L. *Loss*: (1 / (rows L)) sum m_i / p_n(i) CE(logits_i, x_0,i): in
  place, no shift.

Departure, the program's and in the configuration's ``assumed``: the
head has a bias (zero at the start).

``precision``: ``"f32"`` the reference proper; ``"bf16"`` what the
configuration states, as the program does it; ``"fp8"`` one lower, the
control. The router's product and softmax, the norms' statistics, the
rotary angles and the noise stay float32 in all three, as in the
program; a lower precision rounds what enters and leaves them.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import HIGHEST, matmul, store

CONTROL_OF = {"bfloat16": "fp8", "float32": "bf16"}
QUERY_BLOCKS = 4    # parts of a head's queries whose scores are alive at once
LOGIT_BLOCKS = 4    # parts of a row whose logits are alive at once
EXPERT_GROUP = 4    # held experts computed side by side


def sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], theta=float(cfg["rope_theta"]),
        f=cfg["moe_intermediate_size"], held=cfg["num_experts"],
        width=cfg["router_width"], first=cfg.get("first_held", 0),
        k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]), block=cfg["block_length"],
    )


def block_shapes(z: dict) -> dict:
    d, n, f = z["d"], z["held"], z["f"]
    return {
        "attn_g": ((d,), "ones"), "ffn_g": ((d,), "ones"),
        "wq": ((d, z["h"] * z["hd"]), "normal"),
        "wk": ((d, z["kv"] * z["hd"]), "normal"),
        "wv": ((d, z["kv"] * z["hd"]), "normal"),
        "wo": ((z["h"] * z["hd"], d), "normal"),
        "q_g": ((z["hd"],), "ones"), "k_g": ((z["hd"],), "ones"),
        "router": ((d, z["width"]), "router"),
        "e_gate": ((n, d, f), "normal"), "e_up": ((n, d, f), "normal"),
        "e_down": ((n, f, d), "normal"),
    }


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind of initial value), layers as ``block_<i>/``.
    The embedding has one row more than the vocabulary held: MASK's."""
    z = sizes(cfg)
    d, v = z["d"], z["v"]
    out = {
        "wte": ((v + 1, d), "normal"), "final_g": ((d,), "ones"),
        "head_w": ((d, v), "normal"), "head_b": ((v,), "zeros"),
    }
    for i in range(z["layers"]):
        for name, spec in block_shapes(z).items():
            out[f"block_{i}/{name}"] = spec
    return out


ROUTER_INITS = ("independent", "members_alike")


def router_members(cfg: dict) -> int:
    """How many times the router's drawn columns stand side by side: 1
    where every expert's column is a draw of its own (``router_init``
    ``independent``, the default), and the members of the
    expert-parallel group, ``router_width / num_experts``, where they
    start alike (``members_alike``)."""
    kind = cfg.get("router_init", ROUTER_INITS[0])
    if kind not in ROUTER_INITS:
        raise ValueError(f"router_init {kind!r}: one of {ROUTER_INITS}")
    if kind == ROUTER_INITS[0]:
        return 1
    z = sizes(cfg)
    if z["first"] % z["held"] or z["width"] != z["k"] * z["held"]:
        raise ValueError(
            f"members_alike: {z['held']} held experts from {z['first']} "
            f"of a router {z['width']} wide, top {z['k']}: the held "
            "experts are one whole member and a token chooses as many "
            "experts as there are members")
    return z["width"] // z["held"]


def weights(cfg: dict, key) -> dict:
    """Initial weights from a PRNG key (traceable; jit it): matrices
    normal(0, initializer_range), norm scales one, the head's bias
    zero.

    ``router_init: members_alike``: **every member of the
    expert-parallel group starts with this member's router columns**
    (the columns of the experts held here are drawn, and stand once for
    every member: with 16 held, column 16 c + j is column j). A token's ``k`` largest
    probabilities are then those of one expert on each of ``k`` members
    (``k`` equals the members), so this member is sent exactly one
    choice a position and layer whatever the seed: the share of a
    deployment whose router is balanced, which weights drawn
    independently give only in expectation (with tokens without
    context a layer's positions choose nearly alike, and whether their
    8 of 128 are held ones is one draw a layer: PERF.md, PR 34). The
    columns are parameters like any other afterwards, and training
    moves the held copy of a column apart from the absent ones."""
    std = float(cfg["initializer_range"])
    members = router_members(cfg)
    out = {}
    for index, (name, (shape, kind)) in enumerate(
            sorted(shapes(cfg).items())):
        if kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            side_by_side = members if kind == "router" else 1
            drawn = std * jax.random.normal(
                jax.random.fold_in(key, index),
                shape[:-1] + (shape[-1] // side_by_side,), jnp.float32)
            out[name] = jnp.tile(drawn, (1,) * (len(shape) - 1)
                                 + (side_by_side,))
    return out


def layout(cfg: dict) -> list:
    """[(name here, path in the parameter tree of the program's
    ``SdarMoeLM``, the shape there where it is another view of the same
    numbers)]."""
    z = sizes(cfg)
    d, h, kv, hd = z["d"], z["h"], z["kv"], z["hd"]
    rows = [
        ("wte", ("token_embed", "embedding"), None),
        ("final_g", ("final_norm", "scale"), None),
        ("head_w", ("lm_head", "kernel"), None),
        ("head_b", ("lm_head", "bias"), None),
    ]
    for i in range(z["layers"]):
        b = f"block_{i}"
        rows += [(f"{b}/{name}", (b,) + path, shape) for name, path, shape in (
            ("attn_g", ("attn_norm", "scale"), None),
            ("ffn_g", ("ffn_norm", "scale"), None),
            ("wq", ("attn", "q", "kernel"), (d, h, hd)),
            ("wk", ("attn", "k", "kernel"), (d, kv, hd)),
            ("wv", ("attn", "v", "kernel"), (d, kv, hd)),
            ("wo", ("attn", "out", "kernel"), (h, hd, d)),
            ("q_g", ("attn", "q_norm", "scale"), None),
            ("k_g", ("attn", "k_norm", "scale"), None),
            ("router", ("moe", "router"), None),
            ("e_gate", ("moe", "w_gate"), None),
            ("e_up", ("moe", "w_up"), None),
            ("e_down", ("moe", "w_down"), None),
        )]
    return rows


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The same numbers (weights, or gradients of them) laid out as the
    parameter tree of the program's ``SdarMoeLM``."""
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


# ----------------------------------------------------------------- the noise

def noising(row, cfg: dict):
    """One clean row (L,) int -> (m (L,) bool: which tokens are masked,
    p (L,) float32: the masking probability of every position's
    block)."""
    length, block = row.shape[0], cfg["block_length"]
    million = 10 ** 6
    eps_millionths = round(float(cfg["noise_eps"]) * million)
    assert eps_millionths % 1000 == 0, "eps is whole thousandths"
    seed_key = jax.random.wrap_key_data(
        jnp.asarray([0, int(cfg["noise_seed"])], jnp.uint32),
        impl="threefry2x32")
    odd = (2 * jnp.arange(length) + 1).astype(jnp.uint32)
    mark = jnp.sum(row.astype(jnp.uint32) * odd, dtype=jnp.uint32)
    for_t, for_u = jax.random.split(jax.random.fold_in(seed_key, mark))
    t = jax.random.randint(for_t, (length // block,), 0, million)
    # p = (1 - eps) t + eps in millionths, rounded down: (1 - eps) is
    # (1000 - eps in thousandths) thousandths.
    p = eps_millionths + ((1000 - eps_millionths // 1000) * t) // 1000
    p = jnp.repeat(p, block)
    u = jax.random.randint(for_u, (length,), 0, million)
    return u < p, p.astype(jnp.float32) / million


def diffusion_mask(length: int, block: int):
    """(2L, 2L) bool: whether query i (a row) sees key j (a column), the
    four rules written out."""
    i = jnp.arange(2 * length)[:, None]
    j = jnp.arange(2 * length)[None, :]
    i_noised, j_noised = i < length, j < length
    n_i = jnp.where(i_noised, i, i - length) // block
    n_j = jnp.where(j_noised, j, j - length) // block
    noised_on_noised = i_noised & j_noised & (n_i == n_j)
    noised_on_clean = i_noised & ~j_noised & (n_j < n_i)
    clean_on_clean = ~i_noised & ~j_noised & (n_j <= n_i)
    # clean on noised: never.
    return noised_on_noised | noised_on_clean | clean_on_clean


# ---------------------------------------------------------------- the layers

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, positions, theta):
    """x (S, heads, D) turned by halves: (x1 cos - x2 sin, x2 cos + x1
    sin), angle_j = position * theta^(-2j/D)."""
    d = x.shape[-1]
    j = jnp.arange(d // 2, dtype=jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * theta ** (-2.0 * j / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, z, p):
    """One doubled row: h (2L, d) -> (2L, d)."""
    keep = lambda y: store(y, p)  # noqa: E731
    seq = h.shape[0]
    length = seq // 2
    heads, kv, hd = z["h"], z["kv"], z["hd"]
    group = heads // kv
    positions = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
    q = keep(matmul(h, w["wq"], p)).reshape(seq, heads, hd)
    k = keep(matmul(h, w["wk"], p)).reshape(seq, kv, hd)
    v = keep(matmul(h, w["wv"], p)).reshape(seq, kv, hd)
    q = keep(rotary(keep(rms_norm(q, w["q_g"], z["eps"])), positions,
                    z["theta"]))
    k = keep(rotary(keep(rms_norm(k, w["k_g"], z["eps"])), positions,
                    z["theta"]))
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    blocks = QUERY_BLOCKS if seq % QUERY_BLOCKS == 0 else 1
    size = seq // blocks
    mask = diffusion_mask(length, z["block"])

    @jax.checkpoint
    def some_queries(args):
        """One head's queries ``first .. first + size`` against all its
        keys: the scores alive are (size, 2L)."""
        q, first, k, v = args
        scores = matmul(q, k.T, p) * scale
        visible = jax.lax.dynamic_slice_in_dim(mask, first, size, axis=0)
        scores = jnp.where(visible, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v, p)

    def one_head(args):
        q, k, v = args                                     # (2L, hd)
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


def gated_mlp(h, gate, up, down, p):
    keep = lambda y: store(y, p)  # noqa: E731
    hidden = keep(jax.nn.silu(keep(matmul(h, gate, p)))
                  * keep(matmul(h, up, p)))
    return keep(matmul(hidden, down, p))


def routing(h, w, z, cfg, held=None):
    """(chosen (S, k) expert ids over the whole width, weights (S, k));
    float32 whatever the precision, as in the program. ``held`` (S, k),
    where given, are the choices in place of this layer's own."""
    probs = jax.nn.softmax(
        jnp.matmul(h, w["router"], precision=HIGHEST), axis=-1)
    chosen = held
    if chosen is None:
        _, chosen = jax.lax.top_k(probs, z["k"])
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * float(
        cfg.get("routed_scaling_factor", 1.0))
    return chosen, weights


def held_weights(chosen, weights, z):
    """(S, held): a token's weight for every expert held here, zero
    where it did not choose it."""
    local = chosen - z["first"]
    onehot = local[..., None] == jnp.arange(z["held"])     # (S, k, held)
    return jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)


def expert_layer(h, w, z, cfg, p, held=None):
    """(the held experts' part of the layer's result, the experts every
    token chose (S, k))."""
    chosen, weights = routing(h, w, z, cfg, held)
    per_expert = held_weights(chosen, weights, z)

    size = EXPERT_GROUP if z["held"] % EXPERT_GROUP == 0 else 1

    @jax.checkpoint
    def some(gate, up, down, weight):
        """``size`` experts side by side: their weighted results,
        summed."""
        return jnp.sum(jax.vmap(
            lambda gate, up, down, weight: weight[:, None] * gated_mlp(
                h, gate, up, down, p))(gate, up, down, weight.T), axis=0)

    # In Python, as ``_over_rows`` walks the rows: a scan copies the
    # stacked experts into its state (12.8 GB of temporaries where this
    # form has 4, CPU rehearsal for a described v5e, PR 34). A few
    # experts a step, because one a step, 16 x 6 layers x 2 rows of
    # rematerialised bodies, made a program whose compilation took 22.9
    # GB of the host here and, with the check's other programs, over the
    # chip machine's 40 GiB (my chip run, PR 34, call 1). The sum stays
    # outside what is rematerialised, so no group keeps the running
    # total.
    routed = jnp.zeros_like(h)
    for e in range(0, z["held"], size):
        routed = routed + some(
            w["e_gate"][e:e + size], w["e_up"][e:e + size],
            w["e_down"][e:e + size], per_expert[:, e:e + size])
    return store(routed, p), chosen


def block(x, w, z, cfg, p, held=None):
    """One layer over one doubled row: (x (2L, d), its choices)."""
    keep = lambda y: store(y, p)  # noqa: E731
    x = keep(x + attention(keep(rms_norm(x, w["attn_g"], z["eps"])), w, z, p))
    y, chosen = expert_layer(
        keep(rms_norm(x, w["ffn_g"], z["eps"])), w, z, cfg, p, held)
    return keep(x + y), chosen.astype(jnp.int32)


def _sub(w: dict, prefix: str) -> dict:
    cut = len(prefix) + 1
    return {name[cut:]: value for name, value in w.items()
            if name.startswith(prefix + "/")}


def _logits(x, w, p):
    return store(matmul(x, w["head_w"], p) + w["head_b"], p)


def _weighted_cross_entropy(hidden, targets, weights, w, p):
    logp = jax.nn.log_softmax(_logits(hidden, w, p), axis=-1)
    return -jnp.sum(
        weights * jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0])


def _row_loss(hidden, targets, weights, w, p):
    """(1 / L) sum_i weights_i CE(logits_i, targets_i) of one row, its
    logits a block of positions at a time."""
    seq = hidden.shape[0]
    blocks = LOGIT_BLOCKS if seq % LOGIT_BLOCKS == 0 else 1
    size = seq // blocks
    one = jax.checkpoint(lambda part: _weighted_cross_entropy(
        part[0], part[1], part[2], w, p))
    # A loop, not Python: the blocks' parts of the head's gradient are
    # summed in its state, where unrolled they would stand side by side.
    sums = jax.lax.map(one, (
        hidden.reshape(blocks, size, hidden.shape[1]),
        targets.reshape(blocks, size), weights.reshape(blocks, size)))
    return jnp.sum(sums) / seq


def _over_rows(fn, weights, *per_row, apart=False):
    """``fn(weights, *row)`` of one row at a time, each row
    rematerialised: what is kept for the backward pass is the rows'
    inputs. The rows are walked in Python, not by ``lax.map``: a weight
    that a loop carries is copied into the loop's state by the TPU
    compiler, one more copy of every layer's weights beside the four
    trees the check already holds (PERF.md, PR 32). ``apart`` (a lower
    precision): each row takes the weights through a barrier of its
    own, so that what one row derives from them (their rounded copies)
    is not kept for the next."""
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
    """The layers with experts, in the order they run: all of them."""
    return [f"block_{i}" for i in range(cfg["num_hidden_layers"])]


def held_count(chosen, cfg: dict):
    """How many of the choices (..., k) fell on experts held here."""
    z = sizes(cfg)
    local = chosen - z["first"]
    return jnp.sum((local >= 0) & (local < z["held"]), dtype=jnp.int32)


def noised_rows(tokens, cfg: dict):
    """tokens (rows, L) -> (the doubled rows [x_t ; x_0] (rows, 2L), m
    (rows, L), p (rows, L))."""
    masked, p = jax.vmap(lambda row: noising(row, cfg))(tokens)
    noised = jnp.where(masked, cfg["vocab_size"], tokens)
    return jnp.concatenate([noised, tokens], axis=1), masked, p


def hidden_states(w, tokens, cfg, p="f32", held=None):
    """tokens (rows, L) int, the clean rows -> (the noised half's last
    hidden state after its norm (rows, L, d), {layer: the experts every
    position of the doubled row chose (rows, 2L, k)}, m, p). ``held``:
    such a mapping, to go by in place of the layers' own choices."""
    z = sizes(cfg)
    keep = lambda y: store(y, p)  # noqa: E731
    doubled, masked, prob = noised_rows(tokens, cfg)
    chosen = {}
    x = keep(w["wte"])[doubled]
    for name in expert_layers(cfg):
        lw = _sub(w, name)
        if held is None:
            x, picks = _over_rows(
                lambda lw, row: block(row, lw, z, cfg, p), lw, x,
                apart=p != "f32")
        else:
            x, picks = _over_rows(
                lambda lw, row, go: block(row, lw, z, cfg, p, go),
                lw, x, held[name], apart=p != "f32")
        chosen[name] = picks
    length = tokens.shape[1]
    return (keep(rms_norm(x[:, :length], w["final_g"], z["eps"])), chosen,
            masked, prob)


def row_logits(w, tokens, cfg, p="f32"):
    """One clean row, tokens (L,) int -> (the noised half's logits (L,
    V), token-choices of held experts over every layer)."""
    hidden, chosen, _, _ = hidden_states(w, tokens[None], cfg, p)
    return (_logits(hidden[0], w, p),
            sum(held_count(c, cfg) for c in chosen.values()))


def loss_terms(w, tokens, cfg, p="f32", held=None):
    """tokens (rows, L) int -> {"loss": (1 / (rows L)) sum m / p CE in
    place, "chosen": as :func:`hidden_states` gives them, "masked": how
    many tokens the noise masked}."""
    hidden, chosen, masked, prob = hidden_states(w, tokens, cfg, p, held)
    head = {name: w[name] for name in ("head_w", "head_b")}
    loss = jnp.mean(_over_rows(
        lambda head, h, t, wt: _row_loss(h, t, wt, head, p),
        head, hidden, tokens, jnp.where(masked, 1.0 / prob, 0.0),
        apart=p != "f32"))
    return {"loss": loss, "chosen": chosen,
            "masked": jnp.sum(masked, dtype=jnp.int32)}


def choices(w, tokens, cfg: dict) -> list:
    """The experts every position of the doubled rows chooses, (rows,
    2L, k) for every layer in order, in float32: what a comparison of
    gradients holds the routing to (a choice that turns on rounding
    moves a token's rows between a held expert and an absent one: a
    legitimate difference between two precisions, and larger than what a
    lower precision does to the products)."""
    chosen = hidden_states(w, tokens, cfg)[1]
    return [chosen[name] for name in expert_layers(cfg)]


def loss_and_grads(w, tokens, labels, cfg: dict, precision="f32"):
    """Loss and gradients over all rows; ``labels`` (the next tokens)
    are not read: the targets are the clean row's own tokens, in place.
    In a lower precision (the control) the routing is held to the
    float32 choices, as the comparison holds the program's."""
    del labels
    held = None
    if precision != "f32":
        held = dict(zip(expert_layers(cfg), choices(w, tokens, cfg)))
    return jax.value_and_grad(
        lambda w: loss_terms(w, tokens, cfg, precision, held)["loss"])(w)


def routed_rows(w, tokens, cfg: dict):
    """Token-choices of held experts over all doubled rows, summed over
    the layers: what the program's ``moe_rows`` counter has to read for
    the same step (float32 routing; a choice that flips on rounding
    moves it by one)."""
    return sum(held_count(c, cfg) for c in choices(w, tokens, cfg))
