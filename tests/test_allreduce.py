"""Merged psum correctness on a virtual 8-device mesh: the collective result
must be identical to a plain all-reduce regardless of the merge schedule."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu.parallel.allreduce import (
    arrival_order,
    make_merged_allreduce,
)
from mgwfbp_tpu.parallel.costmodel import AlphaBeta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh


def _grad_tree(rng):
    return {
        "dense1": {"kernel": jnp.asarray(rng.randn(8, 16), jnp.float32),
                   "bias": jnp.asarray(rng.randn(16), jnp.float32)},
        "dense2": {"kernel": jnp.asarray(rng.randn(16, 4), jnp.float32)},
    }


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=8, seq=1))


@pytest.mark.parametrize("policy,kw", [
    ("wfbp", {}),
    ("single", {}),
    ("threshold", {"threshold": 100}),
    ("mgwfbp", {"cost_model": AlphaBeta(1e-4, 1e-9)}),
])
def test_merged_psum_matches_plain_pmean(mesh, policy, kw):
    rng = np.random.RandomState(0)
    tree = _grad_tree(rng)
    mar = make_merged_allreduce(tree, axis_name=DATA_AXIS, policy=policy, **kw)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS),), out_specs=P(),
    )
    def merged(shards):
        local = jax.tree.map(lambda x: x[0], shards)
        return mar(local)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS),), out_specs=P(),
    )
    def plain(shards):
        local = jax.tree.map(lambda x: x[0], shards)
        return jax.tree.map(lambda g: jax.lax.pmean(g, DATA_AXIS), local)

    # 8 different per-device grad shards
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(8)]), tree
    )
    got = jax.jit(merged)(stacked)
    want = jax.jit(plain)(stacked)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6
        ),
        got, want,
    )


def test_sum_mode_and_comm_dtype(mesh):
    tree = {"w": jnp.ones((4, 4), jnp.float32)}
    mar = make_merged_allreduce(
        tree, axis_name=DATA_AXIS, policy="single", mean=False,
        comm_dtype=jnp.bfloat16,
    )

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P())
    def f(shards):
        return mar(jax.tree.map(lambda x: x[0], shards))

    stacked = jax.tree.map(lambda x: jnp.stack([x] * 8), tree)
    out = jax.jit(f)(stacked)
    assert out["w"].dtype == jnp.float32  # cast back after wire
    np.testing.assert_allclose(np.asarray(out["w"]), 8.0)


def test_arrival_order_default_and_custom():
    assert arrival_order(4) == [3, 2, 1, 0]
    assert arrival_order(3, [1, 2, 0]) == [1, 2, 0]
    with pytest.raises(ValueError):
        arrival_order(3, [0, 0, 1])


def test_schedule_metadata_exposed(mesh):
    tree = _grad_tree(np.random.RandomState(1))
    mar = make_merged_allreduce(
        tree, axis_name=DATA_AXIS, policy="mgwfbp",
        cost_model=AlphaBeta(1e-3, 1e-8),
    )
    # big alpha vs tiny tensors -> everything merges into few groups
    assert mar.schedule.num_groups <= 3
    assert mar.layout.num_groups >= mar.schedule.num_groups
    assert np.isfinite(mar.schedule.predicted_total_time)


def test_merged_psum_multi_axis():
    mesh = make_mesh(MeshSpec(data=4, seq=2))
    tree = {"w": jnp.ones((2, 2), jnp.float32)}
    mar = make_merged_allreduce(
        tree, axis_name=(DATA_AXIS, "seq"), policy="single", mean=False
    )

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(DATA_AXIS, "seq"),), out_specs=P()
    )
    def f(shards):
        return mar(jax.tree.map(lambda x: x[0, 0], shards))

    stacked = jax.tree.map(lambda x: jnp.ones((4, 2) + x.shape), tree)
    out = jax.jit(f)(stacked)
    np.testing.assert_allclose(np.asarray(out["w"]), 8.0)


def test_forward_order_natural_sort():
    # Lexicographic pytree key order scrambles Block_10 before Block_2;
    # forward_order must restore numeric order.
    from mgwfbp_tpu.parallel.allreduce import arrival_order, forward_order

    names = [f"Block_{i}" for i in (0, 1, 10, 11, 2, 3)]  # lexicographic
    fwd = forward_order(names)
    assert [names[i] for i in fwd] == [
        "Block_0", "Block_1", "Block_2", "Block_3", "Block_10", "Block_11"
    ]
    arr = arrival_order(len(names), names=names)
    assert [names[i] for i in arr] == [
        "Block_11", "Block_10", "Block_3", "Block_2", "Block_1", "Block_0"
    ]


def test_make_merged_allreduce_uses_natural_order():
    import jax
    import jax.numpy as jnp
    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce

    # 12 sibling keys force the Block_10/Block_2 lexicographic trap.
    tree = {f"Block_{i}": jax.ShapeDtypeStruct((2,), jnp.float32) for i in range(12)}
    mar = make_merged_allreduce(tree, axis_name="data", policy="wfbp")
    names = mar.schedule.layer_names
    assert "Block_11" in names[0] and "Block_0" in names[-1]


def test_dtype_split_updates_schedule_predictions():
    import jax
    import jax.numpy as jnp
    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
    from mgwfbp_tpu.parallel.costmodel import AlphaBeta

    # One solver group crossing a dtype boundary must be split, and the
    # schedule's groups/predictions must describe the post-split collectives.
    tree = {
        "a": jax.ShapeDtypeStruct((1000,), jnp.float32),
        "b": jax.ShapeDtypeStruct((1000,), jnp.bfloat16),
    }
    cm = AlphaBeta(alpha=1.0, beta=0.0)  # pure-startup cost: count collectives
    mar = make_merged_allreduce(
        tree, axis_name="data", policy="single", tb=[1e-6, 1e-6], cost_model=cm
    )
    assert mar.schedule.num_groups == mar.layout.num_groups == 2
    assert mar.schedule.predicted_comm_time == 2.0  # one alpha per real group


def test_hierarchical_allreduce_matches_plain_pmean():
    """comm_op='hier' (reduce-scatter on the inner/ICI axis, all-reduce the
    shard on the outer/DCN axis, all-gather back — the lowering
    TwoLevelAlphaBeta prices) must be numerically identical to a flat pmean
    over both axes, including non-divisible buckets (pad path)."""
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh2 = Mesh(devs, ("ici", "dcn"))
    rng = np.random.RandomState(1)
    # bias sizes indivisible by the 4-wide inner axis exercise the padding
    tree = {
        "w": jnp.asarray(rng.randn(6, 5), jnp.float32),
        "b": jnp.asarray(rng.randn(7), jnp.float32),
    }
    mar = make_merged_allreduce(
        tree, axis_name=("ici", "dcn"), policy="wfbp", comm_op="hier",
    )

    @functools.partial(
        shard_map, mesh=mesh2,
        in_specs=(P(("ici", "dcn")),), out_specs=P(), check_vma=False,
    )
    def merged(shards):
        return mar(jax.tree_util.tree_map(lambda s: s.mean(0), shards))

    @functools.partial(
        shard_map, mesh=mesh2,
        in_specs=(P(("ici", "dcn")),), out_specs=P(), check_vma=False,
    )
    def plain(shards):
        return jax.lax.pmean(
            jax.tree_util.tree_map(lambda s: s.mean(0), shards),
            ("ici", "dcn"),
        )

    batched = jax.tree_util.tree_map(
        lambda a: jnp.stack([a + i for i in range(8)]), tree
    )
    got = merged(batched)
    want = plain(batched)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-6, atol=1e-6
        )


def test_hier_requires_two_axes():
    tree = {"w": jnp.ones((4,))}
    with pytest.raises(ValueError, match="hier"):
        make_merged_allreduce(
            tree, axis_name=DATA_AXIS, policy="wfbp", comm_op="hier"
        )
