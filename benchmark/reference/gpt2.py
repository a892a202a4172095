"""GPT-2 in plain ``jax.numpy``: forward pass, loss and gradients.

The plain reference the benchmark compares the program with. It imports
nothing of the program and takes nothing the program has made: its
weights come from the seed (:func:`weights`) and its sizes from the
configuration file. float32 throughout, matrix multiplications
at ``Precision.HIGHEST`` (on a TPU a float32 matmul is otherwise done in
bfloat16 passes), no kernels, no cache; one layer at a time under
``lax.scan`` with the layer rematerialised, so that a whole minibatch of
the published width fits beside its gradients.

Follows Radford et al. 2019 as released (pre-LayerNorm blocks, learned
positions, causal multi-head attention, GELU in its tanh form). Two
departures, both the program's, both in the configuration's ``assumed``:
the output head is a separate matrix with a bias, not the transposed
token embedding; LayerNorm's epsilon is the configuration's
``ln_eps_as_run`` (the program uses flax's 1e-6, GPT-2 published 1e-5).

What a configuration's ``reference`` has to offer ``lib/check.py`` (a
reference for another family is another file with the same four):
``weights(cfg, key)``, ``to_program_tree(tree, cfg)``,
``loss_and_grads(w, tokens, labels, cfg, precision)`` and
``CONTROL_OF``.

``precision`` selects what the model computes in:

- ``"f32"``: the reference proper;
- ``"bf16"``: what the configurations state, as the program does it:
  matmul operands rounded to bfloat16 with float32 accumulation, and
  every activation the program keeps in bfloat16 (the residual stream,
  LayerNorm outputs, q/k/v, the MLP's hidden layer, the logits) rounded
  to bfloat16 where it is stored. For seeing what the stated precision
  alone costs;
- ``"fp8"``: the same points one precision lower: operands and stored
  activations scaled per tensor and rounded to float8 (e4m3; e5m2 for
  the cotangents of the matmuls). The *control*, which the comparison
  has to refuse.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# The nearest precision below the one a configuration computes in: what
# the control is computed in.
CONTROL_OF = {"bfloat16": "fp8", "float32": "bf16"}


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind); kind is 'normal', 'resid', 'zeros' or
    'ones'. Layers are stacked on the first axis."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    n, v, p = cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    return {
        "wte": ((v, d), "normal"), "wpe": ((p, d), "normal"),
        "ln1_g": ((n, d), "ones"), "ln1_b": ((n, d), "zeros"),
        "wq": ((n, d, d), "normal"), "bq": ((n, d), "zeros"),
        "wk": ((n, d, d), "normal"), "bk": ((n, d), "zeros"),
        "wv": ((n, d, d), "normal"), "bv": ((n, d), "zeros"),
        "wo": ((n, d, d), "resid"), "bo": ((n, d), "zeros"),
        "ln2_g": ((n, d), "ones"), "ln2_b": ((n, d), "zeros"),
        "wfc": ((n, d, f), "normal"), "bfc": ((n, f), "zeros"),
        "wproj": ((n, f, d), "resid"), "bproj": ((n, d), "zeros"),
        "lnf_g": ((d,), "ones"), "lnf_b": ((d,), "zeros"),
        "head_w": ((d, v), "normal"), "head_b": ((v,), "zeros"),
    }


def weights(cfg: dict, key) -> dict:
    """Initial weights from a PRNG key, one array per kind of weight
    (traceable; jit it). Init follows GPT-2: normal(0,
    initializer_range), the projections that write to the residual
    stream scaled by 1/sqrt(2 n_layer), biases zero, LayerNorm scales
    one; the untied output head is drawn like the rest."""
    std = float(cfg["initializer_range"])
    resid_std = std / (2.0 * cfg["n_layer"]) ** 0.5
    out = {}
    for index, (name, (shape, kind)) in enumerate(
            sorted(shapes(cfg).items())):
        if kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            scale = resid_std if kind == "resid" else std
            out[name] = scale * jax.random.normal(
                jax.random.fold_in(key, index), shape, jnp.float32)
    return out


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The same numbers (weights, or gradients of them) laid out as the
    parameter tree of the program's ``TransformerLM``."""
    d, h = cfg["n_embd"], cfg["n_head"]
    hd = d // h
    tree = {
        "token_embed": {"embedding": w["wte"]},
        "pos_embed": w["wpe"],
        "ln_f": {"scale": w["lnf_g"], "bias": w["lnf_b"]},
        "lm_head": {"kernel": w["head_w"], "bias": w["head_b"]},
    }
    for i in range(cfg["n_layer"]):
        def proj(wn, bn):
            return {"kernel": w[wn][i].reshape(d, h, hd),
                    "bias": w[bn][i].reshape(h, hd)}
        tree[f"block_{i}"] = {
            "ln1": {"scale": w["ln1_g"][i], "bias": w["ln1_b"][i]},
            "attn": {
                "query": proj("wq", "bq"), "key": proj("wk", "bk"),
                "value": proj("wv", "bv"),
                "out": {"kernel": w["wo"][i].reshape(h, hd, d),
                        "bias": w["bo"][i]},
            },
            "ln2": {"scale": w["ln2_g"][i], "bias": w["ln2_b"][i]},
            "mlp": {
                "wi": {"kernel": w["wfc"][i], "bias": w["bfc"][i]},
                "wo": {"kernel": w["wproj"][i], "bias": w["bproj"][i]},
            },
        }
    return tree


def _round_to(x, dtype):
    """x scaled per tensor into ``dtype``'s range, rounded, scaled back;
    the result is exactly representable in bfloat16 times the scale."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = float(jnp.finfo(dtype).max) / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_matmul(a, b):
    return _fp8_fwd(a, b)[0]


def _fp8_fwd(a, b):
    qa = _round_to(a, jnp.float8_e4m3fn)
    qb = _round_to(b, jnp.float8_e4m3fn)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(res, g):
    qa, qb = res
    qg = _round_to(g, jnp.float8_e5m2)
    da = jnp.matmul(qg, jnp.swapaxes(qb, -1, -2), precision=HIGHEST)
    db = jnp.matmul(jnp.swapaxes(qa, -1, -2), qg, precision=HIGHEST)
    # b is a plain matrix everywhere it is used here; a may carry
    # leading batch axes, which db sums over.
    while db.ndim > qb.ndim:
        db = db.sum(axis=0)
    return da, db


_fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def store(x, precision: str):
    """x as the given precision would keep it in memory."""
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        return x + jax.lax.stop_gradient(
            _round_to(x, jnp.float8_e4m3fn) - x)
    raise ValueError(f"unknown precision {precision!r}")


def matmul(a, b, precision: str):
    if precision == "f32":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        return _fp8_matmul(a, b)
    raise ValueError(f"unknown precision {precision!r}")


def layer_norm(x, gain, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, w, n_head: int, ln_eps: float, precision: str):
    """One pre-LayerNorm block; ``w`` holds this layer's weights."""
    rows, seq, d = x.shape
    hd = d // n_head
    keep = lambda y: store(y, precision)  # noqa: E731
    h = keep(layer_norm(x, w["ln1_g"], w["ln1_b"], ln_eps))

    def heads(wn, bn):
        y = keep(matmul(h, w[wn], precision) + w[bn])
        return y.reshape(rows, seq, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("wq", "bq"), heads("wk", "bk"), heads("wv", "bv")
    # The program's kernel multiplies bfloat16 tiles and keeps the
    # softmax's running state in float32; so do the lower modes here.
    inner = precision
    scores = matmul(q, jnp.swapaxes(k, -1, -2), inner) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = matmul(jax.nn.softmax(scores, axis=-1), v, inner)
    att = keep(att.transpose(0, 2, 1, 3).reshape(rows, seq, d))
    x = keep(x + keep(matmul(att, w["wo"], precision) + w["bo"]))
    h = keep(layer_norm(x, w["ln2_g"], w["ln2_b"], ln_eps))
    h = keep(gelu_new(keep(matmul(h, w["wfc"], precision) + w["bfc"])))
    return keep(x + keep(matmul(h, w["wproj"], precision) + w["bproj"]))


_PER_LAYER = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "bo", "ln2_g", "ln2_b", "wfc", "bfc", "wproj", "bproj")


def logits_fn(w, tokens, n_head: int, ln_eps: float, precision="f32"):
    """tokens (rows, seq) int -> logits (rows, seq, vocab) float32."""
    seq = tokens.shape[1]
    x = store(store(w["wte"], precision)[tokens]
              + store(w["wpe"][:seq], precision), precision)
    layers = {name: w[name] for name in _PER_LAYER}

    @jax.checkpoint
    def step(x, layer):
        return block(x, layer, n_head, ln_eps, precision), None

    x, _ = jax.lax.scan(step, x, layers)
    x = store(layer_norm(x, w["lnf_g"], w["lnf_b"], ln_eps), precision)
    return store(matmul(x, w["head_w"], precision) + w["head_b"], precision)


def loss_fn(w, tokens, labels, n_head: int, ln_eps: float,
            precision="f32"):
    """Mean over every position of -log softmax(logits)[label]."""
    logits = logits_fn(w, tokens, n_head, ln_eps, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()


def loss_and_grads(w, tokens, labels, cfg: dict, precision="f32",
                   block_rows: int = 2):
    """Loss and gradients over all rows, ``block_rows`` at a time (the
    mean of equal blocks' means is the mean)."""
    n_head, ln_eps = cfg["n_head"], float(cfg["ln_eps_as_run"])
    rows = tokens.shape[0]
    if rows % block_rows:
        block_rows = 1
    blocks = rows // block_rows
    grad = jax.value_and_grad(loss_fn)

    def one(carry, batch):
        loss, grads = grad(w, batch[0], batch[1], n_head, ln_eps,
                           precision)
        total_loss, total = carry
        return (total_loss + loss / blocks,
                jax.tree.map(lambda t, g: t + g / blocks, total, grads)
                ), None

    shaped = (tokens.reshape(blocks, block_rows, -1),
              labels.reshape(blocks, block_rows, -1))
    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, w))
    (loss, grads), _ = jax.lax.scan(one, zero, shaped)
    return loss, grads
