"""Ring attention tests: sequence-sharded attention over the seq mesh axis
must match single-device full attention exactly (the long-context extension;
mesh.py axis docs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu.parallel.mesh import MeshSpec, SEQ_AXIS, make_mesh
from mgwfbp_tpu.parallel.ringattn import local_attention, ring_attention


@pytest.fixture(scope="module")
def mesh_seq():
    # 2-way data x 4-way sequence
    return make_mesh(MeshSpec(data=2, seq=4))


def _qkv(b=2, t=32, h=2, d=8, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, t, h, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_local(mesh_seq, causal):
    q, k, v = _qkv()
    want = local_attention(q, k, v, causal=causal)

    def f(q, k, v):
        return ring_attention(q, k, v, axis_name=SEQ_AXIS, causal=causal)

    spec = P(None, SEQ_AXIS)  # shard time dim; batch replicated over data
    got = jax.jit(
        shard_map(
            f, mesh=mesh_seq, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_local_attention_causal_masks_future():
    q, k, v = _qkv(b=1, t=8, h=1, d=4, seed=1)
    out = local_attention(q, k, v, causal=True)
    # position 0 attends only to itself: output = v[0]
    np.testing.assert_allclose(
        np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]), rtol=1e-5
    )


def test_ring_attention_softmax_normalized(mesh_seq):
    # uniform q/k -> output is the mean of visible v rows; last position in
    # causal mode sees everything
    b, t, h, d = 1, 16, 1, 4
    q = jnp.zeros((b, t, h, d))
    k = jnp.zeros((b, t, h, d))
    rs = np.random.RandomState(2)
    v = jnp.asarray(rs.randn(b, t, h, d), jnp.float32)

    def f(q, k, v):
        return ring_attention(q, k, v, axis_name=SEQ_AXIS, causal=True)

    spec = P(None, SEQ_AXIS)
    out = jax.jit(
        shard_map(
            f, mesh=mesh_seq, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out[0, -1, 0]),
        np.asarray(v[0].mean(axis=0)[0]),
        rtol=1e-5, atol=1e-5,
    )
