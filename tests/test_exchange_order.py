"""What merged_psum's ordering token hangs on (PR 29), stated on the program.

The token has three duties: the collectives issue in arrival order one
after another, XLA's all-reduce combiner does not re-merge the buckets, and
a NaN or infinity in one bucket reaches no other bucket's gradients. It
must do them WITHOUT gating the backward pass: the value a bucket's
producer computes may not depend on the previous group's reduced bucket
(only the collective's operand may), or an asynchronous all-reduce makes
every weight-gradient kernel wait for the one before it. The whole-bucket
token other lowerings still use (`_chain_token`) is the control: it keeps
the three duties and fails the fourth.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu.analysis.jaxpr_check import iter_eqns
from mgwfbp_tpu.parallel import allreduce
from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh

WORLD = 4
TOKENS = {
    "one_element": allreduce._order_after,
    "whole_bucket": allreduce._chain_token,
}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=WORLD, seq=1), devices=jax.devices()[:WORLD])


@pytest.fixture(params=sorted(TOKENS))
def token(request, monkeypatch):
    monkeypatch.setattr(allreduce, "_order_after", TOKENS[request.param])
    return request.param


def _weights(rng):
    """Six leaves; `threshold` merges some, `wfbp` keeps one a group."""
    return {
        f"layer{i}": {
            "kernel": jnp.asarray(rng.randn(12, 12), jnp.float32),
            "bias": jnp.asarray(rng.randn(12), jnp.float32),
        }
        for i in range(3)
    }


def _reduced_grads_fn(mesh, mar):
    """x, w -> merged all-reduce of d loss / d w: every bucket has a
    PRODUCER (the backward's products) inside the program."""

    def loss(w, x):
        h = x
        for name in sorted(w):
            h = jnp.tanh(h @ w[name]["kernel"] + w[name]["bias"])
        return (h * h).sum()

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P(DATA_AXIS)), out_specs=P(),
        check_vma=False,
    )
    def f(w, x):
        return mar(jax.grad(loss)(w, x))

    return f


def _body(closed_jaxpr):
    """The shard_map's own jaxpr: the level the token lives on."""
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name == "shard_map":
            return eqn.params["jaxpr"]
    raise AssertionError("no shard_map in the traced program")


def _cone(var, producer, memo):
    """Every eqn `var` depends on, transitively, inside one jaxpr. A nested
    call (jnp.where's pjit) counts as one eqn whose outputs depend on all
    of its inputs."""
    eqn = producer.get(var)
    if eqn is None:
        return frozenset()
    key = id(eqn)
    if key not in memo:
        memo[key] = frozenset()  # a jaxpr is acyclic; guards re-entry only
        deps = {key}
        for v in eqn.invars:
            if hasattr(v, "count"):  # a Var, not a Literal
                deps |= _cone(v, producer, memo)
        memo[key] = frozenset(deps)
    return memo[key]


def _psums_and_cones(mesh, policy, kw):
    rng = np.random.RandomState(0)
    w = _weights(rng)
    mar = make_merged_allreduce(w, axis_name=DATA_AXIS, policy=policy, **kw)
    x = jnp.asarray(rng.randn(WORLD * 2, 12), jnp.float32)
    body = _body(jax.make_jaxpr(_reduced_grads_fn(mesh, mar))(w, x))
    producer = {out: eqn for eqn in body.eqns for out in eqn.outvars}
    psums = [e for e in body.eqns if e.primitive.name == "psum"]
    assert len(psums) == mar.layout.num_groups >= 2
    return mar, psums, producer


POLICIES = [("wfbp", {}), ("threshold", {"threshold": 200})]


@pytest.mark.parametrize("policy,kw", POLICIES)
def test_collectives_issue_in_arrival_order_one_after_another(
    mesh, token, policy, kw
):
    """Group k's psum is traced under scope k, in order, and its operand
    depends on group k-1's psum: the chain XLA may not reorder or combine."""
    mar, psums, producer = _psums_and_cones(mesh, policy, kw)
    scopes = [
        int(re.search(r"mgwfbp_group(\d+)", str(e.source_info.name_stack))[1])
        for e in psums
    ]
    assert scopes == list(range(mar.layout.num_groups))
    memo = {}
    for prev, cur in zip(psums, psums[1:]):
        assert id(prev) in _cone(cur.invars[0], producer, memo)


@pytest.mark.parametrize("policy,kw", POLICIES)
def test_bucket_producer_does_not_wait_for_the_previous_group(
    mesh, policy, kw
):
    """The property PR 29 is about. Group k's collective operand is the
    bucket with ONE element rewritten: the bucket itself (operand 0 of the
    update) depends on no collective, the rewritten element (operand 1)
    carries group k-1's reduced value."""
    _, psums, producer = _psums_and_cones(mesh, policy, kw)
    psum_ids = {id(e) for e in psums}
    memo = {}
    for prev, cur in zip(psums, psums[1:]):
        update = producer[cur.invars[0]]
        assert update.primitive.name == "dynamic_update_slice"
        bucket, head = update.invars[0], update.invars[1]
        assert head.aval.shape == (1,)
        assert not (_cone(bucket, producer, memo) & psum_ids)
        assert id(prev) in _cone(head, producer, memo)


def test_whole_bucket_token_gates_the_producer(mesh, monkeypatch):
    """The control: under `_chain_token` the value fed to group k's psum is
    an add over the whole bucket, so nothing upstream of the collective is
    free of group k-1's reduced value once XLA fuses that add into the
    kernel that computes the gradient (PERF.md, PR 29: `%add_bitcast_fusion
    = fusion(..., %mul.470, ...)` under `Dense_0/dot_general`)."""
    monkeypatch.setattr(allreduce, "_order_after", allreduce._chain_token)
    _, psums, producer = _psums_and_cones(mesh, "wfbp", {})
    for cur in psums[1:]:
        feed = producer[cur.invars[0]]
        assert feed.primitive.name == "add"
        assert feed.outvars[0].aval.shape == cur.invars[0].aval.shape


@pytest.mark.parametrize("policy,kw", POLICIES)
def test_combiner_does_not_remerge_the_buckets(mesh, token, policy, kw):
    """One all-reduce per merge group survives XLA's optimizer."""
    rng = np.random.RandomState(0)
    w = _weights(rng)
    mar = make_merged_allreduce(w, axis_name=DATA_AXIS, policy=policy, **kw)
    x = jnp.asarray(rng.randn(WORLD * 2, 12), jnp.float32)
    text = jax.jit(_reduced_grads_fn(mesh, mar)).lower(w, x).compile().as_text()
    n = len(re.findall(r"[\s)]all-reduce(?:-start)?\(", text))
    assert n == mar.layout.num_groups


def test_without_the_token_the_combiner_remerges(mesh):
    """The positive control of the test above: `sequential=False` leaves the
    buckets independent and XLA folds them into fewer all-reduces."""
    rng = np.random.RandomState(0)
    w = _weights(rng)
    mar = dataclasses.replace(
        make_merged_allreduce(w, axis_name=DATA_AXIS, policy="wfbp"),
        sequential=False,
    )
    x = jnp.asarray(rng.randn(WORLD * 2, 12), jnp.float32)
    text = jax.jit(_reduced_grads_fn(mesh, mar)).lower(w, x).compile().as_text()
    n = len(re.findall(r"[\s)]all-reduce(?:-start)?\(", text))
    assert n < mar.layout.num_groups


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
@pytest.mark.parametrize("policy,kw", POLICIES)
def test_nonfinite_in_one_bucket_reaches_no_other(mesh, token, policy, kw, bad):
    """Every leaf's reduced value equals the plain pmean, poisoned leaf
    included; a NaN or infinity in the FIRST group's first element (the
    one the next token is read from) makes no later bucket non-finite."""
    rng = np.random.RandomState(1)
    tree = _weights(rng)
    mar = make_merged_allreduce(tree, axis_name=DATA_AXIS, policy=policy, **kw)
    first_leaf = mar.perm[mar.layout.groups[0][0]]
    flat, treedef = jax.tree_util.tree_flatten(tree)
    poisoned = flat[first_leaf].ravel().at[0].set(bad).reshape(
        flat[first_leaf].shape)
    flat[first_leaf] = poisoned
    tree = jax.tree_util.tree_unflatten(treedef, flat)
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(WORLD)]), tree)

    def run(reduce):
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P(),
            check_vma=False,
        )
        def f(shards):
            return reduce(jax.tree.map(lambda x: x[0], shards))
        return jax.tree_util.tree_leaves(jax.jit(f)(stacked))

    got = run(mar)
    want = run(lambda t: jax.tree.map(lambda g: jax.lax.pmean(g, DATA_AXIS), t))
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        if i != first_leaf:
            assert np.isfinite(np.asarray(a)).all()
    assert not np.isfinite(np.asarray(got[first_leaf])).all()


def test_one_element_token_leaves_the_bucket_bitwise_alone(mesh):
    """Adding `0 * finite` to one element changes no bit of the bucket
    (but the sign of a negative zero in that one element)."""
    buf = jnp.asarray([1.5, -2.25, 3.0, 0.0], jnp.float32)
    for t in (jnp.float32(7.0), jnp.float32(np.nan), jnp.float32(np.inf)):
        out = jax.jit(allreduce._order_after)(buf, t)
        assert np.asarray(out).tobytes() == np.asarray(buf).tobytes()
    assert allreduce._order_after(buf, None) is buf
    ints = jnp.arange(4)
    assert allreduce._order_after(ints, jnp.float32(1.0)) is ints


# ---- the compile options: chosen from what make_train_step holds ----------


class _MeshDescription:
    """What `async_collective_options` reads of a mesh: the axis extents
    and the devices' platform. A four-chip TPU host is not in the sandbox
    that runs tier-1; its description is."""

    def __init__(self, platform, **axes):
        import types

        self.shape = dict(axes)
        self.axis_names = tuple(axes)
        n = int(np.prod(list(axes.values())))
        self.devices = np.asarray(
            [types.SimpleNamespace(platform=platform, id=i) for i in range(n)],
            dtype=object,
        ).reshape(tuple(axes.values()))


@pytest.mark.parametrize(
    "platform,axes,reduce_axes,expect_set",
    [
        ("tpu", {"data": 4}, ("data",), True),
        ("tpu", {"data": 2, "seq": 2}, ("data", "seq"), True),
        ("tpu", {"data": 1, "seq": 4}, ("data", "seq"), True),
        # one device: no gradient collective in the program
        ("tpu", {"data": 1}, ("data",), False),
        ("tpu", {"data": 1, "seq": 1}, ("data", "seq"), False),
        # the reduction axes span one device, whatever else the mesh has
        ("tpu", {"data": 1, "model": 4}, ("data",), False),
        # the CPU backend refuses an `xla_tpu_*` option outright
        ("cpu", {"data": 4}, ("data",), False),
        ("gpu", {"data": 4}, ("data",), False),
    ],
)
def test_async_options_follow_the_mesh(platform, axes, reduce_axes, expect_set):
    from mgwfbp_tpu.train.step import (
        ASYNC_COLLECTIVE_OPTIONS, async_collective_options,
    )

    got = async_collective_options(
        _MeshDescription(platform, **axes), reduce_axes)
    assert got == (dict(ASYNC_COLLECTIVE_OPTIONS) if expect_set else {})
    assert got is not ASYNC_COLLECTIVE_OPTIONS  # a copy: callers may edit it
    if expect_set:
        assert got["xla_enable_async_all_reduce"] == "true"


def test_cpu_mesh_step_is_compiled_without_options(mesh):
    """Tier-1's programs compile as they always did: the real four-device
    CPU mesh gets no option, so `jax.jit` is handed `compiler_options=None`."""
    from mgwfbp_tpu.train.step import async_collective_options

    assert async_collective_options(mesh, (DATA_AXIS,)) == {}


# ---- the counter: how many of a compiled program's collectives are async --

_HLO_SAMPLE = """\
HloModule jit_step, is_scheduled=true

%fused_computation.7 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %all-reduce.9 = f32[8]{0} all-reduce(f32[8]{0} %p0), channel_id=7
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p: f32[8], q: f32[4]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %q = f32[4]{0} parameter(1)
  %async-collective-start = (f32[8]{0}, f32[8]{0}, u32[]) fusion(f32[8]{0} %p), kind=kCustom, calls=%fused_computation.7
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.8
  %async-collective-done = f32[8]{0} fusion((f32[8]{0}, f32[8]{0}, u32[]) %async-collective-start), kind=kCustom, calls=%fused_computation.9
  %all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %q), channel_id=2, to_apply=%region_add
  %all-reduce-done.1 = f32[4]{0} all-reduce-done(f32[4]{0} %all-reduce-start.1)
  %psum.4 = f32[4]{0} all-reduce(f32[4]{0} %all-reduce-done.1), channel_id=3, to_apply=%region_add
  %all-gather.2 = f32[16]{0} all-gather(f32[4]{0} %psum.4), channel_id=4, dimensions={0}
  ROOT %add.5 = f32[8]{0} add(f32[8]{0} %async-collective-done, f32[8]{0} %fusion.3)
}
"""


def test_collective_counts_of_a_compiled_text():
    """An async collective fusion and an `all-reduce-start` are
    asynchronous; a bare `all-reduce` (a `psum` by name) and an
    `all-gather` are synchronous; the `-done` halves and the all-reduce
    inside the fusion's called computation are counted with their start."""
    from mgwfbp_tpu.profiling import hlo_collective_counts

    assert hlo_collective_counts(_HLO_SAMPLE) == {
        "collectives": 4, "async_collectives": 2,
    }
    assert hlo_collective_counts("") == {
        "collectives": 0, "async_collectives": 0,
    }


@pytest.mark.parametrize("policy,kw", POLICIES)
def test_collective_counts_of_a_cpu_program(mesh, policy, kw):
    """On the CPU mesh every merge group is one synchronous all-reduce."""
    from mgwfbp_tpu.profiling import hlo_collective_counts

    rng = np.random.RandomState(0)
    w = _weights(rng)
    mar = make_merged_allreduce(w, axis_name=DATA_AXIS, policy=policy, **kw)
    x = jnp.asarray(rng.randn(WORLD * 2, 12), jnp.float32)
    text = jax.jit(_reduced_grads_fn(mesh, mar)).lower(w, x).compile().as_text()
    assert hlo_collective_counts(text) == {
        "collectives": mar.layout.num_groups, "async_collectives": 0,
    }
