"""Reduction exactness, free of training dynamics (copied from chip_smoke.py's
`reducer_vs_pmean`, PR 21, so that no later PR can change the yardstick).

The Trainer's production reducer against one plain `lax.pmean` on the live
mesh: a gradient-shaped tree whose values differ on every device, reduced both
ways inside one shard_map program. PR 21 measured 4.9e-8 on four v5e chips with
a float32 wire; a bfloat16 wire reads about 2e-3.
"""

from __future__ import annotations

import math


def reducer_vs_pmean(trainer, seed: int) -> float:
    """Relative L2 difference between `trainer.reducer` and `lax.pmean`.
    Raises if the devices did not hold different values (a trivial pass)."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    reducer = trainer.reducer
    leaves, treedef = jax.tree_util.tree_flatten(trainer._params_template)

    def sumsq(tree):
        return sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(tree))

    def minus(a, b):
        return jax.tree_util.tree_map(jnp.subtract, a, b)

    def body(key):
        key = jax.random.fold_in(key, lax.axis_index(reducer.axis_name))
        grads = treedef.unflatten([
            jax.random.normal(k, leaf.shape, leaf.dtype)
            for k, leaf in zip(jax.random.split(key, len(leaves)), leaves)
        ])
        plain = lax.pmean(grads, reducer.axis_name)
        return jnp.stack([
            sumsq(minus(reducer(grads), plain)), sumsq(plain),
            sumsq(minus(grads, plain)),
        ])

    diff, norm, spread = jax.jit(shard_map(
        body, mesh=trainer.mesh, in_specs=P(), out_specs=P(),
        check_vma=False,
    ))(jax.random.PRNGKey(seed % (2**31)))
    if not float(spread) > 0.5 * float(norm):
        raise RuntimeError(
            "reduce check: the devices held the same values, so agreement "
            "with pmean would prove nothing"
        )
    return math.sqrt(float(diff) / float(norm))
