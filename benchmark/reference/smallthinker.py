"""A decoder LM with window and full attention layers mixed, a router
that reads the layer's input, and ReLU-gated sparse experts, in plain
``jax.numpy``: forward pass, loss and gradients.

The plain reference for configurations of the SmallThinker family
(PowerInfer, ``SmallThinker-21BA3B-Instruct``; the sizes come from the
configuration file, under the names the published ``config.json`` gives
them). It imports nothing of the program; from the benchmark's GPT-2
reference it borrows only how a precision stores a value and multiplies
two matrices (``store``, ``matmul``). float32 at ``Precision.HIGHEST``;
**the mask is built densely from its rule** (:func:`layer_mask`) and the
attention is dense under it, the scores of one head's queries a block
at a time over all S keys; no kernel, no sort: every held expert is
computed for every token (a few experts side by side) and weighed by
the routing weights, which are zero where the token did not choose it.
The layers walk the rows one at a time in Python and are
rematerialised two at a time (and each layer, and each half of a layer,
inside that), and a row's logits are computed a block of positions at a
time, so that the replay fits beside the four trees of weights and
gradients the check holds.

The equations of layer l, x the residual stream (S, d):

    r      = x                      # what the router reads: the layer's INPUT
    h      = RMSNorm_in(x)
    q,k,v  = h W_q (H x D), h W_k (Hkv x D), h W_v (Hkv x D)   # no bias, no q/k norm
    if rope_layout[l]:  q, k = rope(q), rope(k)   # by halves, positions 0..S-1
    a      = softmax(q k^T / sqrt(D) + M_l) v     # query head i reads k/v head i // (H / Hkv)
             M_l: j visible to i iff j <= i and (sliding_window_layout[l] == 0
                  or i - j < sliding_window_size)
    x      = x + a W_o
    g      = RMSNorm_post(x)
    P      = softmax(float32(r) W_r) over the router's whole width
    chosen = the k largest of P; w = the chosen P over their sum
    x      = x + sum over chosen experts HELD HERE of
                 w_e W_down_e( relu(W_gate_e g) * (W_up_e g) )

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * gain``; logits = W_head
RMSNorm_f(x) + bias; loss = mean next-token cross-entropy.

Departures from the published description, each in the configuration's
``assumed`` too:

- the router reads the layer's RAW input x, not ``RMSNorm_in(x)``
  ("router placed before attention" says no more);
- rotary by halves over the whole head, (x1 cos - x2 sin, x2 cos + x1
  sin), angle_j = pos theta^(-2j/D): the family's convention on the hub;
- a window of W: query i sees keys i - W + 1 .. i, itself among them;
- no secondary experts, no balancing loss;
- the head has a bias (zero at the start).

``precision``: ``"f32"`` the reference proper; ``"bf16"`` what the
configuration states, as the program does it; ``"fp8"`` one lower, the
control. The router's product and softmax, the norms' statistics and
the rotary angles stay float32 in all three, as in the program; a lower
precision rounds what enters and leaves them.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import HIGHEST, matmul, store

CONTROL_OF = {"bfloat16": "fp8", "float32": "bf16"}
QUERY_ROWS = 1024   # a head's queries whose scores are alive at once
LOGIT_BLOCKS = 4    # parts of a row whose logits are alive at once
EXPERT_GROUP = 2    # held experts computed side by side
LAYER_GROUP = 2     # layers whose input alone is kept for the backward pass


def sizes(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    return dict(
        d=cfg["hidden_size"], layers=layers,
        h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], theta=float(cfg["rope_theta"]),
        f=cfg["moe_ffn_hidden_size"], held=cfg["moe_num_primary_experts"],
        width=cfg["router_width"], first=cfg.get("first_held", 0),
        k=cfg["moe_num_active_primary_experts"], v=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]), window=cfg["sliding_window_size"],
        # The layers held are the first of the published pattern.
        windowed=tuple(cfg["sliding_window_layout"][:layers]),
        rotary=tuple(cfg["rope_layout"][:layers]),
    )


def block_shapes(z: dict) -> dict:
    d, n, f = z["d"], z["held"], z["f"]
    return {
        "attn_g": ((d,), "ones"), "ffn_g": ((d,), "ones"),
        "wq": ((d, z["h"] * z["hd"]), "normal"),
        "wk": ((d, z["kv"] * z["hd"]), "normal"),
        "wv": ((d, z["kv"] * z["hd"]), "normal"),
        "wo": ((z["h"] * z["hd"], d), "normal"),
        "router": ((d, z["width"]), "router"),
        "e_gate": ((n, d, f), "normal"), "e_up": ((n, d, f), "normal"),
        "e_down": ((n, f, d), "normal"),
    }


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind of initial value), layers as ``block_<i>/``."""
    z = sizes(cfg)
    d, v = z["d"], z["v"]
    out = {
        "wte": ((v, d), "normal"), "final_g": ((d,), "ones"),
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
    ``independent``, the default); under ``members_alike`` the ``k``
    members of the expert-parallel group (of ``router_width / held``)
    that a token's ``k`` choices reach."""
    kind = cfg.get("router_init", ROUTER_INITS[0])
    if kind not in ROUTER_INITS:
        raise ValueError(f"router_init {kind!r}: one of {ROUTER_INITS}")
    if kind == ROUTER_INITS[0]:
        return 1
    z = sizes(cfg)
    if (z["first"] % z["held"] or z["width"] % z["held"]
            or z["k"] > z["width"] // z["held"]):
        raise ValueError(
            f"members_alike: {z['held']} held experts from {z['first']} "
            f"of a router {z['width']} wide, top {z['k']}: the held "
            "experts are one whole member and a token chooses no more "
            "experts than there are members")
    return z["k"]


def weights(cfg: dict, key) -> dict:
    """Initial weights from a PRNG key (traceable; jit it): matrices
    normal(0, initializer_range), norm scales one, the head's bias
    zero.

    ``router_init: members_alike``: **the first k members of the
    expert-parallel group start with this member's router columns, the
    others at zero** (the columns of the experts held here are drawn,
    and stand once for each of members 0 .. k-1: with 8 held and k = 6,
    column 8 c + j is column j for c < 6, and columns 48-63 are zero). A
    token's largest logit then stands k times, equal to the bit, and
    its k choices are the k copies of its best local expert, whichever
    way ties are broken (where all 8 drawn logits are negative, one
    token in 256, the zero columns win and the held member is sent
    nothing). Member 0, held here, is sent one choice a position and
    layer whatever the seed; which of its 8 experts gets it still
    follows the seed. **Why k copies and not one a member**: with 8
    copies and 6 choices, which six of eight equal values are chosen is
    the tie rule's at the start (lower index first: the held member is
    among them) and the drift's after the first update (the held copy's
    gradient differs from the absent copies'), and on the chip the held
    member's rows fell from 131,072 a step to 33-50 thousand by seed
    and step from the third step on (my chip run, PR 38, call 1). With
    as many copies as choices every copy is chosen, as in
    ``reference/sdar_moe.py``, and a zero column is never chosen and so
    never trained. The columns are parameters like any other
    afterwards."""
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
            alike = kind == "router" and members > 1
            wide = cfg["moe_num_primary_experts"] if alike else shape[-1]
            drawn = std * jax.random.normal(
                jax.random.fold_in(key, index), shape[:-1] + (wide,),
                jnp.float32)
            if alike:
                copies = jnp.tile(drawn, (1, members))
                drawn = jnp.pad(
                    copies, ((0, 0), (0, shape[-1] - copies.shape[-1])))
            out[name] = drawn
    return out


def attention_name(z: dict, layer: int) -> str:
    """The name of layer ``layer``'s attention in the program's tree."""
    return "window_attn" if z["windowed"][layer] else "global_attn"


def layout(cfg: dict) -> list:
    """[(name here, path in the parameter tree of the program's
    ``SmallThinkerLM``, the shape there where it is another view of the
    same numbers)]."""
    z = sizes(cfg)
    d, h, kv, hd = z["d"], z["h"], z["kv"], z["hd"]
    rows = [
        ("wte", ("token_embed", "embedding"), None),
        ("final_g", ("final_norm", "scale"), None),
        ("head_w", ("lm_head", "kernel"), None),
        ("head_b", ("lm_head", "bias"), None),
    ]
    for i in range(z["layers"]):
        b, attn = f"block_{i}", attention_name(z, i)
        rows += [(f"{b}/{name}", (b,) + path, shape) for name, path, shape in (
            ("attn_g", ("attn_norm", "scale"), None),
            ("ffn_g", ("ffn_norm", "scale"), None),
            ("wq", (attn, "q", "kernel"), (d, h, hd)),
            ("wk", (attn, "k", "kernel"), (d, kv, hd)),
            ("wv", (attn, "v", "kernel"), (d, kv, hd)),
            ("wo", (attn, "out", "kernel"), (h, hd, d)),
            ("router", ("moe", "router"), None),
            ("e_gate", ("moe", "w_gate"), None),
            ("e_up", ("moe", "w_up"), None),
            ("e_down", ("moe", "w_down"), None),
        )]
    return rows


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The same numbers (weights, or gradients of them) laid out as the
    parameter tree of the program's ``SmallThinkerLM``."""
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


def layer_mask(first, rows: int, seq: int, window: int):
    """(rows, S) bool: whether query ``first + i`` (a row) sees key j (a
    column): j <= i, and i - j < window where the layer has one
    (``window`` 0: full causal)."""
    i = first + jnp.arange(rows)[:, None]
    j = jnp.arange(seq)[None, :]
    visible = j <= i
    return visible & (i - j < window) if window else visible


def attention(h, w, z, p, layer: int):
    """One row: h (S, d) -> (S, d)."""
    keep = lambda y: store(y, p)  # noqa: E731
    seq = h.shape[0]
    heads, kv, hd = z["h"], z["kv"], z["hd"]
    group = heads // kv
    q = keep(matmul(h, w["wq"], p)).reshape(seq, heads, hd)
    k = keep(matmul(h, w["wk"], p)).reshape(seq, kv, hd)
    v = keep(matmul(h, w["wv"], p)).reshape(seq, kv, hd)
    if z["rotary"][layer]:
        positions = jnp.arange(seq)
        q = keep(rotary(q, positions, z["theta"]))
        k = keep(rotary(k, positions, z["theta"]))
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    size = QUERY_ROWS if seq % QUERY_ROWS == 0 else seq
    blocks = seq // size
    window = z["window"] if z["windowed"][layer] else 0

    @jax.checkpoint
    def some_queries(args):
        """One head's queries ``first .. first + size`` against all its
        keys: the scores alive are (size, S)."""
        q, first, k, v = args
        scores = matmul(q, k.T, p) * scale
        scores = jnp.where(
            layer_mask(first, size, seq, window), scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v, p)

    def one_head(args):
        q, k, v = args                                     # (S, hd)
        out = jax.lax.map(
            lambda block: some_queries((block[0], block[1], k, v)),
            (q.reshape(blocks, size, hd), jnp.arange(blocks) * size))
        return out.reshape(seq, hd)

    def one_group(args):
        """The ``group`` query heads that read one key/value head."""
        q, k, v = args                         # (group, S, hd), (S, hd) x 2
        return jax.lax.map(lambda q: one_head((q, k, v)), q)

    # Query head i reads key/value head i // group: the key/value heads
    # are walked, never repeated.
    by_head = lambda x: x.transpose(1, 0, 2)  # noqa: E731
    att = jax.lax.map(one_group, (
        by_head(q).reshape(kv, group, seq, hd), by_head(k), by_head(v)))
    att = keep(by_head(att.reshape(heads, seq, hd)).reshape(seq, heads * hd))
    return keep(matmul(att, w["wo"], p))


def relu_gated_mlp(h, gate, up, down, p):
    keep = lambda y: store(y, p)  # noqa: E731
    hidden = keep(jax.nn.relu(keep(matmul(h, gate, p)))
                  * keep(matmul(h, up, p)))
    return keep(matmul(hidden, down, p))


def routing(read, w, z, held=None):
    """(chosen (S, k) expert ids over the whole width, weights (S, k))
    from ``read``, the layer's input; float32 whatever the precision, as
    in the program. ``held`` (S, k), where given, are the choices in
    place of this layer's own."""
    probs = jax.nn.softmax(
        jnp.matmul(read.astype(jnp.float32), w["router"],
                   precision=HIGHEST), axis=-1)
    chosen = held
    if chosen is None:
        _, chosen = jax.lax.top_k(probs, z["k"])
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    return chosen, picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)


def held_weights(chosen, weights, z):
    """(S, held): a token's weight for every expert held here, zero
    where it did not choose it."""
    local = chosen - z["first"]
    onehot = local[..., None] == jnp.arange(z["held"])     # (S, k, held)
    return jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)


def expert_layer(read, g, w, z, p, held=None):
    """(the held experts' part of the layer's result for the rows ``g``,
    the experts every token chose (S, k) from ``read``)."""
    chosen, weights = routing(read, w, z, held)
    per_expert = held_weights(chosen, weights, z)
    size = EXPERT_GROUP if z["held"] % EXPERT_GROUP == 0 else 1

    @jax.checkpoint
    def some(g, gate, up, down, weight):
        """``size`` experts side by side: their weighted results,
        summed."""
        return jnp.sum(jax.vmap(
            lambda gate, up, down, weight: weight[:, None] * relu_gated_mlp(
                g, gate, up, down, p))(gate, up, down, weight.T), axis=0)

    # In Python, a few experts a step: a scan would copy the stacked
    # experts into its state (``reference/sdar_moe.py`` has the
    # readings). One group after another, forward and backward: a
    # group's (experts, S, d) results are the layer's largest values,
    # and unrolled the compiler would be free to hold several groups'
    # at once.
    routed = jnp.zeros_like(g)
    for e in range(0, z["held"], size):
        g, routed = jax.lax.optimization_barrier((g, routed))
        routed = routed + some(
            g, w["e_gate"][e:e + size], w["e_up"][e:e + size],
            w["e_down"][e:e + size], per_expert[:, e:e + size])
    return store(routed, p), chosen


def block(x, w, z, p, layer: int, held=None):
    """One layer over one row: (x (S, d), its choices)."""
    keep = lambda y: store(y, p)  # noqa: E731
    read = x
    # Each half of the layer is rematerialised on its own inside the
    # layer's rematerialisation, so the backward pass holds the
    # attention's float32 intermediates or the experts', never both.
    x = keep(x + jax.checkpoint(
        lambda h, w: attention(h, w, z, p, layer))(
            keep(rms_norm(x, w["attn_g"], z["eps"])), w))
    y, chosen = jax.checkpoint(
        lambda read, g, w: expert_layer(read, g, w, z, p, held))(
            read, keep(rms_norm(x, w["ffn_g"], z["eps"])), w)
    return keep(x + y), chosen.astype(jnp.int32)


def _sub(w: dict, prefix: str) -> dict:
    cut = len(prefix) + 1
    return {name[cut:]: value for name, value in w.items()
            if name.startswith(prefix + "/")}


def _logits(x, w, p):
    return store(matmul(x, w["head_w"], p) + w["head_b"], p)


def _cross_entropy(hidden, labels, w, p):
    logp = jax.nn.log_softmax(_logits(hidden, w, p), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _row_loss(hidden, labels, w, p):
    """Mean next-token cross-entropy of one row, its logits a block of
    positions at a time."""
    seq = hidden.shape[0]
    blocks = LOGIT_BLOCKS if seq % LOGIT_BLOCKS == 0 else 1
    size = seq // blocks
    one = jax.checkpoint(lambda part: _cross_entropy(
        part[0], part[1], w, p))
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
    compiler. ``apart`` (a lower precision): each row takes the weights
    through a barrier of its own, so that what one row derives from
    them (their rounded copies) is not kept for the next."""
    one = jax.checkpoint(fn)
    rows = []
    for r in range(per_row[0].shape[0]):
        own, args = weights, tuple(x[r] for x in per_row)
        if apart:
            own, args = jax.lax.optimization_barrier((own, args))
        if rows:
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


def hidden_states(w, tokens, cfg, p="f32", held=None):
    """tokens (rows, S) int -> (the last hidden state after its norm
    (rows, S, d), {layer: the experts every position chose (rows, S,
    k)}). ``held``: such a mapping, to go by in place of the layers' own
    choices."""
    z = sizes(cfg)
    keep = lambda y: store(y, p)  # noqa: E731
    chosen = {}
    x = keep(w["wte"])[tokens]
    names = expert_layers(cfg)

    def some_layers(first, lws, row, *go):
        """``LAYER_GROUP`` layers in a row, each rematerialised inside
        the group's rematerialisation: what the backward pass keeps of
        the stack is one (S, d) float32 row a GROUP of layers."""
        picks = []
        for i, lw in enumerate(lws):
            row, picked = jax.checkpoint(
                lambda row, lw, *go, layer=first + i: block(
                    row, lw, z, p, layer, *go))(row, lw, *go[i:i + 1])
            picks.append(picked)
        return row, tuple(picks)

    for first in range(0, len(names), LAYER_GROUP):
        group = names[first:first + LAYER_GROUP]
        given = () if held is None else tuple(held[name] for name in group)
        x, picks = _over_rows(
            lambda lws, row, *go, first=first: some_layers(
                first, lws, row, *go),
            tuple(_sub(w, name) for name in group), x, *given,
            apart=p != "f32")
        chosen.update(zip(group, picks))
    return keep(rms_norm(x, w["final_g"], z["eps"])), chosen


def row_logits(w, tokens, cfg, p="f32"):
    """One row, tokens (S,) int -> (logits (S, V), token-choices of held
    experts over every layer)."""
    hidden, chosen = hidden_states(w, tokens[None], cfg, p)
    return (_logits(hidden[0], w, p),
            sum(held_count(c, cfg) for c in chosen.values()))


def loss_terms(w, tokens, labels, cfg, p="f32", held=None):
    """{"loss": mean next-token cross-entropy over all rows, "chosen":
    as :func:`hidden_states` gives them}."""
    hidden, chosen = hidden_states(w, tokens, cfg, p, held)
    head = {name: w[name] for name in ("head_w", "head_b")}
    loss = jnp.mean(_over_rows(
        lambda head, h, t: _row_loss(h, t, head, p),
        head, hidden, labels, apart=p != "f32"))
    return {"loss": loss, "chosen": chosen}


def choices(w, tokens, cfg: dict) -> list:
    """The experts every position chooses, (rows, S, k) for every layer
    in order, in float32: what a comparison of gradients holds the
    routing to (a choice that turns on rounding moves a token's rows
    between a held expert and an absent one: a legitimate difference
    between two precisions, and larger than what a lower precision does
    to the products)."""
    chosen = hidden_states(w, tokens, cfg)[1]
    return [chosen[name] for name in expert_layers(cfg)]


def loss_and_grads(w, tokens, labels, cfg: dict, precision="f32"):
    """Loss and gradients over all rows. In a lower precision (the
    control) the routing is held to the float32 choices, as the
    comparison holds the program's."""
    held = None
    if precision != "f32":
        held = dict(zip(expert_layers(cfg), choices(w, tokens, cfg)))
    return jax.value_and_grad(
        lambda w: loss_terms(w, tokens, labels, cfg, precision, held)["loss"]
    )(w)


def routed_rows(w, tokens, cfg: dict):
    """Token-choices of held experts over all rows, summed over the
    layers: what the program's ``moe_rows`` counter has to read for the
    same step (float32 routing; a choice that flips on rounding moves
    it by one)."""
    return sum(held_count(c, cfg) for c in choices(w, tokens, cfg))


def visible_pairs(cfg: dict, seq: int) -> int:
    """(query, key) pairs a head's attention sees of one row, summed
    over the layers, counted from :func:`layer_mask`'s rule: what the
    program's ``attn_visible_pairs`` counter has to read a row."""
    z = sizes(cfg)
    total = 0
    for windowed in z["windowed"]:
        w = min(z["window"], seq) if windowed else seq
        total += w * (w + 1) // 2 + (seq - w) * w
    return total
