"""The comparison that decides ``correct``: numbers and their limits.

``grad_norm_gap``: over the leaves of the program's parameter tree, the
largest gap between the program's gradient norm and the reference's,
measured against the reference's norm of that leaf or of the median
leaf, whichever is larger (some gradients are all but zero: the key
biases' are zero in exact arithmetic, softmax does not see them).

The limits are data, in each configuration file's ``limits.compared``,
with the readings they were set from in PERF.md; the harness holds every
number named there to its limit and knows none of them by name.
"""


def leaf_norm_gap(program_grads, reference_grads):
    """(the worst leaf's gap, the five widest leaves as [(name, gap)]);
    both arguments are trees of the program's structure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def norms(tree):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        names = [jax.tree_util.keystr(path) for path, _ in flat]
        values = jax.jit(lambda leaves: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves])([leaf for _, leaf in flat])
        return names, np.asarray(jax.device_get(values), np.float64)

    names, got = norms(program_grads)
    _, want = norms(reference_grads)
    median = float(np.median(want))
    gaps = np.abs(got - want) / np.maximum(want, median)
    widest = sorted(zip(names, (float(g) for g in gaps)),
                    key=lambda kv: -kv[1])[:5]
    return float(gaps.max()), widest


def verdicts(numbers: dict, limits: dict):
    """[(name, value, limit, ok)] for every compared number; a number
    without a limit, or a limit without a number, is not ok."""
    rows = []
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        ok = (value is not None and limit is not None
              and value == value and value <= limit)
        rows.append((name, value, limit, bool(ok)))
    return rows
