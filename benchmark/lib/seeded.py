"""Initial weights made by the benchmark from ``--seed``.

The configuration's reference (``benchmark/reference/<name>.py``) makes
every weight on the device in one jitted call, in float32, the type the
program trains them in, and lays the same numbers out as the program's
flax parameter tree. The worker gets them through :func:`seeded_lm`,
whose ``init`` returns them in place of flax's own initialisers, so the
weights the timed path starts from are weights the reference can make
again, alone, from the seed.
"""

import os

SEED_ENV = "BENCH_WEIGHT_SEED"


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low 31 bits
    make the key and the rest is folded in, because a seed above 2**31
    does not fit the int32 that PRNGKey takes without x64."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def seeded_lm(tcfg, cfg: dict, reference, mesh=None):
    """The program's ``TransformerLM`` whose ``init`` returns the
    benchmark's weights for ``$BENCH_WEIGHT_SEED``. The forward pass,
    and with it everything timed, is the program's; the tree's
    structure and shapes are checked against the program's own init."""
    import jax

    from elasticdl_tpu.models.transformer import TransformerLM

    class SeededLM(TransformerLM):
        def init(self, rngs, *args, **kwargs):
            want = jax.eval_shape(
                lambda: TransformerLM.init(self, rngs, *args, **kwargs))
            params = jax.jit(lambda key: reference.to_program_tree(
                reference.weights(cfg, key), cfg)
            )(seed_key(int(os.environ.get(SEED_ENV, 0))))
            got = jax.eval_shape(lambda: {"params": params})
            if (jax.tree.structure(want) != jax.tree.structure(got)
                    or jax.tree.leaves(want) != jax.tree.leaves(got)):
                raise ValueError(
                    "the program's parameter tree is no longer the one "
                    "the configuration's reference lays out: "
                    f"{jax.tree.structure(want)} vs "
                    f"{jax.tree.structure(got)}")
            jax.block_until_ready(params)
            return {"params": params}

    return SeededLM(tcfg, mesh=mesh)
