"""comm_op='rs_opt_ag' — the sharded-optimizer merged collectives.

The contract under test: reduce-scatter each merge-group grad bucket, run
the optimizer on the 1/world shard, all-gather updated PARAMS — and end up
numerically indistinguishable from the replicated all_reduce path (pmean +
optax on every device), across optimizers x clipping x accumulation x
bf16 compute, while holding ~1/world the optimizer state per device and
checkpointing through the replicated interchange form.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu import models as zoo
from mgwfbp_tpu.optim import OptimSpec
from mgwfbp_tpu.parallel.allreduce import (
    group_scope_name,
    make_merged_allreduce,
)
from mgwfbp_tpu.parallel.costmodel import AlphaBeta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.train import create_train_state, make_train_step


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=8, seq=1))


def _tree(rng):
    return {
        "dense1": {"kernel": jnp.asarray(rng.randn(8, 16), jnp.float32),
                   "bias": jnp.asarray(rng.randn(16), jnp.float32)},
        "dense2": {"kernel": jnp.asarray(rng.randn(16, 4), jnp.float32)},
    }


def _assert_trees_close(a, b, rtol=1e-6, atol=1e-7):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        )


def _run_paths(mesh, axes, world, spec, nsteps=3, policy="wfbp", stack=None):
    """Drive the sharded lowering and the replicated optax chain on the
    same per-device grad shards; return (sharded params, replicated
    params, reducer, final sharded state, final replicated state)."""
    rng = np.random.RandomState(0)
    params = _tree(rng)
    tx = spec.make_tx()
    mar = make_merged_allreduce(
        params, axis_name=axes, policy=policy, comm_op="rs_opt_ag",
        optim_spec=spec, world_size=world,
    )
    if stack is None:
        def stack(x):
            return jnp.stack([x * (i + 1) * 0.01 for i in range(world)])
    grads_stack = jax.tree_util.tree_map(stack, params)
    g_mean = jax.tree_util.tree_map(lambda x: x.mean(0), grads_stack)

    @functools.partial(
        shard_map, mesh=mesh,
        # P(axes) shards the stacked dim 0 over the whole data dimension
        # (one joint dim for tuple axes), so each device sees (1, ...)
        in_specs=(P(axes), P(), mar.optim.partition_spec()),
        out_specs=(P(), mar.optim.partition_spec()), check_vma=False,
    )
    def sharded_step(gs, p, os_):
        local = jax.tree_util.tree_map(lambda x: x[0], gs)
        return mar.reduce_and_update(local, p, os_)

    f = jax.jit(sharded_step)
    ps, oss = params, mar.optim.init()
    pr, osr = params, tx.init(params)
    for _ in range(nsteps):
        ps, oss = f(grads_stack, ps, oss)
        u, osr = tx.update(g_mean, osr, pr)
        pr = optax.apply_updates(pr, u)
    return ps, pr, mar, oss, osr


SPECS = {
    "sgd": OptimSpec(lr=0.1, kind="sgd"),
    "sgd-momentum-wd": OptimSpec(
        lr=0.1, kind="sgd", momentum=0.9, weight_decay=1e-4
    ),
    "sgd-nesterov": OptimSpec(lr=0.1, kind="sgd", momentum=0.9, nesterov=True),
    "sgd-clip-sched": OptimSpec(
        lr=lambda c: 0.1 * 0.9 ** jnp.asarray(c, jnp.float32),
        kind="sgd", momentum=0.9, weight_decay=1e-4, norm_clip=0.25,
    ),
    "adam": OptimSpec(lr=0.01, kind="adam"),
    "adamw-clip": OptimSpec(
        lr=0.01, kind="adam", weight_decay=1e-2, decoupled_wd=True,
        norm_clip=0.25,
    ),
}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_sharded_update_matches_optax(mesh, name):
    """Every supported optimizer chain, 3 steps, vs the optax twin."""
    ps, pr, _, _, _ = _run_paths(mesh, DATA_AXIS, 8, SPECS[name])
    _assert_trees_close(ps, pr)


@pytest.mark.parametrize("name", ["sgd-clip-sched", "adamw-clip"])
def test_10_step_equivalence_at_1e6(mesh, name):
    """Acceptance: on IDENTICAL per-device grads — the surface rs_opt_ag
    actually changes (reduction + sharded update vs pmean + optax) — 10
    steps of SGD-momentum / AdamW with global-norm clipping stay within
    1e-6 relative L2 of the replicated path, per leaf. (The full-train-step
    tests below include the model backward, whose compilation
    nondeterminism adds its own f32 ulp noise on top.)"""
    ps, pr, _, _, _ = _run_paths(mesh, DATA_AXIS, 8, SPECS[name], nsteps=10)
    for a, b in zip(
        jax.tree_util.tree_leaves(ps), jax.tree_util.tree_leaves(pr)
    ):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        rel = np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30)
        assert rel <= 1e-6, rel


def test_sharded_state_matches_and_roundtrips(mesh):
    """gather() == the replicated state optax itself would hold after the
    same history (incl. the step count), and scatter(gather(s)) == s."""
    spec = SPECS["sgd-clip-sched"]
    ps, pr, mar, oss, osr = _run_paths(mesh, DATA_AXIS, 8, spec)
    gathered = mar.optim.gather(oss, spec.make_tx(), ps)
    _assert_trees_close(gathered, osr)
    _assert_trees_close(mar.optim.scatter(gathered, ps), oss)


def test_adam_state_roundtrip_carries_count(mesh):
    spec = SPECS["adamw-clip"]
    ps, pr, mar, oss, osr = _run_paths(mesh, DATA_AXIS, 8, spec, nsteps=2)
    gathered = mar.optim.gather(oss, spec.make_tx(), ps)
    _assert_trees_close(gathered, osr)
    back = mar.optim.scatter(gathered, ps)
    assert int(np.asarray(back.count)) == 2
    _assert_trees_close(back, oss)


def test_sharded_update_multi_axis_mesh():
    """The shard the param slice picks must line up with psum_scatter's
    shard assignment on a TWO-axis data dimension (first axis slowest)."""
    mesh2 = make_mesh(MeshSpec(data=4, seq=2))
    ps, pr, _, _, _ = _run_paths(
        mesh2, (DATA_AXIS, SEQ_AXIS), 8, SPECS["sgd-momentum-wd"]
    )
    _assert_trees_close(ps, pr)


def test_opt_state_memory_is_one_over_world(mesh):
    """Acceptance: per-device opt-state bytes ~= replicated / world."""
    model, meta = zoo.create_model("lenet")
    spec = OptimSpec(lr=0.01, kind="adam")
    tx = spec.make_tx()
    state = create_train_state(
        jax.random.PRNGKey(0), model, jnp.zeros((1,) + meta.input_shape), tx
    )
    mar = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="mgwfbp",
        cost_model=AlphaBeta(1e-4, 1e-9), comm_op="rs_opt_ag",
        optim_spec=spec, world_size=8,
    )
    per_dev = mar.optim.state_bytes_per_device()
    repl = mar.optim.replicated_state_bytes()
    # replicated baseline == the actual optax state's params-shaped leaves
    mirror_bytes = 2 * sum(  # adam: mu + nu
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(state.params)
    )
    assert repl == mirror_bytes
    # per-device = 1/world of replicated, up to padding + the int32 count
    pad_slack = 2 * mar.layout.num_groups * 8 * 4 + 4
    assert repl / 8 <= per_dev <= repl / 8 + pad_slack
    # and the real buffers agree with the accounting
    st = mar.optim.init()
    got = sum(
        int(np.prod(b.shape[1:])) * jnp.dtype(b.dtype).itemsize
        for slot in st.slots for b in slot
    ) + 4
    assert got == per_dev


# Cross-program tolerance of the 10-step comparison below. SGD keeps
# test_step.py's 2e-5. AdamW gets its own: the two programs' gradients
# differ by f32 ulps (summation order in the backward fusion), and Adam
# divides by sqrt(v) + eps, which is largest exactly where the gradient
# is smallest, so after ten steps a single element of 48,000 was seen
# 4.7e-5 relative (2.7e-6 absolute) apart. 1e-4 is twice that.
_TEN_STEP_RTOL = {"sgd-clip-sched": 2e-5, "adamw-clip": 1e-4}


@pytest.mark.parametrize("name,nsteps_update", [
    ("sgd-clip-sched", 2),
    ("adamw-clip", 2),
])
def test_train_step_10_steps_matches_all_reduce(mesh, name, nsteps_update):
    """A full lenet train step on the sharded path tracks the replicated
    all_reduce path over 10 optimizer steps, with global-norm clipping AND
    gradient accumulation on — at the cross-program tolerance of
    _TEN_STEP_RTOL (atol=1e-6): the two jitted programs
    compile the SAME backward under different downstream consumers, so the
    grads themselves already differ by f32 ulps before either optimizer
    runs (verified: pmean and psum_scatter are bitwise identical here; the
    noise enters in backward fusion, and Adam's preconditioner amplifies
    it). The 1e-6 acceptance bound is asserted on identical-grads inputs
    in test_10_step_equivalence_at_1e6 above."""
    spec = SPECS[name]
    model, meta = zoo.create_model("lenet")
    tx = spec.make_tx()
    state = create_train_state(
        jax.random.PRNGKey(0), model, jnp.zeros((1,) + meta.input_shape), tx
    )
    rs = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(
            rs.randn(nsteps_update, 16, *meta.input_shape), jnp.float32
        ),
        "y": jnp.asarray(
            rs.randint(0, 10, (nsteps_update, 16)), jnp.int32
        ),
    }
    red = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="mgwfbp",
        cost_model=AlphaBeta(1e-4, 1e-9), comm_op="rs_opt_ag",
        optim_spec=spec, world_size=8,
    )
    step_sh = make_train_step(
        model, meta, tx, mesh, red, nsteps_update=nsteps_update, donate=False
    )
    step_ref = make_train_step(
        model, meta, tx, mesh, nsteps_update=nsteps_update, donate=False
    )
    s_sh = state.replace(opt_state=red.optim.init())
    s_ref = state
    for _ in range(10):
        s_sh, m_sh = step_sh(s_sh, batch)
        s_ref, m_ref = step_ref(s_ref, batch)
    _assert_trees_close(
        s_sh.params, s_ref.params, rtol=_TEN_STEP_RTOL[name], atol=1e-6
    )
    assert float(m_sh["loss"]) == pytest.approx(float(m_ref["loss"]), rel=1e-5)


def test_train_step_bf16_compute_matches_all_reduce(mesh):
    """bf16 forward/backward (master params f32): both paths see the same
    bf16-quantized grads, so they must still track each other tightly."""
    spec = SPECS["sgd-momentum-wd"]
    model, meta = zoo.create_model("lenet")
    tx = spec.make_tx()
    state = create_train_state(
        jax.random.PRNGKey(0), model, jnp.zeros((1,) + meta.input_shape), tx
    )
    rs = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(rs.randn(1, 16, *meta.input_shape), jnp.float32),
        "y": jnp.asarray(rs.randint(0, 10, (1, 16)), jnp.int32),
    }
    red = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="wfbp",
        comm_op="rs_opt_ag", optim_spec=spec, world_size=8,
    )
    kw = dict(compute_dtype=jnp.bfloat16, donate=False)
    step_sh = make_train_step(model, meta, tx, mesh, red, **kw)
    step_ref = make_train_step(model, meta, tx, mesh, **kw)
    s_sh = state.replace(opt_state=red.optim.init())
    s_ref = state
    for _ in range(3):
        s_sh, _ = step_sh(s_sh, batch)
        s_ref, _ = step_ref(s_ref, batch)
    _assert_trees_close(s_sh.params, s_ref.params, rtol=1e-5, atol=1e-6)


def test_trainer_checkpoint_interchange(tmp_path):
    """A checkpoint written by an rs_opt_ag run resumes into an all_reduce
    run (and the momentum it carries matches the gathered shards): the
    interchange form is the replicated optax structure, whoever wrote it."""
    from mgwfbp_tpu.config import make_config
    from mgwfbp_tpu.train.trainer import Trainer

    common = dict(
        dataset="mnist", batch_size=4, max_epochs=2, num_batches_per_epoch=2,
        policy="mgwfbp", logdir=str(tmp_path / "logs"),
        checkpoint_dir=str(tmp_path / "ck"),
    )
    cfg_sh = make_config("lenet", comm_op="rs_opt_ag", **common)
    tr = Trainer(cfg_sh, profile_backward=False, synthetic_data=True)
    assert tr._sharded_opt
    tr.fit(1)
    tr.save(0)
    tr.checkpointer.wait()
    want_params = jax.tree_util.tree_leaves(tr.state.params)
    want_opt = tr.reducer.optim.gather(
        tr.state.opt_state, tr.tx, tr.state.params
    )
    tr.close()

    cfg_ar = make_config("lenet", comm_op="all_reduce", **common)
    tr2 = Trainer(cfg_ar, profile_backward=False, synthetic_data=True)
    assert not tr2._sharded_opt
    assert tr2.start_epoch == 1  # resumed from the rs_opt_ag checkpoint
    _assert_trees_close(tr2.state.params, want_params, rtol=0, atol=0)
    _assert_trees_close(tr2.state.opt_state, want_opt, rtol=0, atol=0)
    # momentum is non-trivial after an epoch of updates
    assert max(
        float(jnp.abs(l).max())
        for l in jax.tree_util.tree_leaves(tr2.state.opt_state)
    ) > 0
    tr2.close()


# --------------------------------------------------------------------------
# guards + solver cost term + static verification
# --------------------------------------------------------------------------


def test_rs_opt_ag_requires_spec_and_world():
    tree = {"a": jnp.ones((8,), jnp.float32)}
    with pytest.raises(ValueError, match="optim_spec"):
        make_merged_allreduce(tree, axis_name=DATA_AXIS, policy="single",
                              comm_op="rs_opt_ag")
    mar = make_merged_allreduce(
        tree, axis_name=DATA_AXIS, policy="single", comm_op="rs_opt_ag",
        optim_spec=SPECS["sgd"], world_size=8,
    )
    with pytest.raises(ValueError, match="reduce_and_update"):
        mar(tree)  # grads-only call is the wrong entry point


def test_update_beta_prices_the_middle():
    from mgwfbp_tpu.parallel.solver import (
        LayerSpec, build_schedule, effective_cost_fn,
    )

    cm = AlphaBeta(alpha=1e-5, beta=1e-9, update_beta=2e-9)
    assert effective_cost_fn(cm, "all_reduce")(1000.0) == cm.predict(1000.0)
    assert effective_cost_fn(cm, "rs_opt_ag")(1000.0) == pytest.approx(
        cm.predict(1000.0) + 2e-9 * 1000.0
    )
    layers = [LayerSpec(f"l{i}", 1000) for i in range(4)]
    tb = [1e-5] * 4
    plain = build_schedule(layers, tb, policy="single", cost_model=cm)
    mid = build_schedule(
        layers, tb, policy="single", cost_model=cm, comm_op="rs_opt_ag"
    )
    assert mid.predicted_comm_time > plain.predicted_comm_time
    assert mid.predicted_comm_time == pytest.approx(
        plain.predicted_comm_time + 2e-9 * 16000.0
    )


def test_verifier_clean_on_rs_opt_ag_head():
    from mgwfbp_tpu.analysis import verify_train_step

    assert verify_train_step(
        "lenet", "mgwfbp", comm_op="rs_opt_ag", norm_clip=1.0
    ) == []


def test_verifier_rejects_stray_allreduce_in_rs_opt_ag_group(mesh):
    """Mutation: a step whose group scope issues an EXTRA all-reduce next
    to the RS/AG pair must be rejected (that is the degeneration the
    sharded path exists to prevent — a replicated reduction sneaking back
    in)."""
    from mgwfbp_tpu.analysis import verify_jaxpr_against_reducer

    tree = {"a": jnp.ones((64,), jnp.float32), "b": jnp.ones((32,), jnp.float32)}
    spec = SPECS["sgd-momentum-wd"]
    mar = make_merged_allreduce(
        tree, axis_name=DATA_AXIS, policy="single", comm_op="rs_opt_ag",
        optim_spec=spec, world_size=8,
    )

    def per_device(grads, params, os_):
        new_p, new_os = mar.reduce_and_update(grads, params, os_)
        with jax.named_scope(group_scope_name(0)):
            # seeded violation: a stray replicated all-reduce in the scope
            extra = jax.lax.psum(new_p["a"], DATA_AXIS)
        return {**new_p, "a": extra / 8.0}, new_os

    fn = jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(), mar.optim.partition_spec()),
        out_specs=(P(), mar.optim.partition_spec()),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree, tree, mar.optim.init())
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    assert any(f.rule_id == "SCH001" for f in findings)


def test_verifier_rejects_clip_scope_abuse_on_sharded_path(mesh):
    """Even ON the rs_opt_ag path the clip scope is a contract, not a
    blanket whitelist: a spec WITHOUT clipping must carry zero psums
    there, and a second collective hiding in the scope is flagged."""
    from mgwfbp_tpu.analysis import verify_jaxpr_against_reducer

    tree = {"a": jnp.ones((64,), jnp.float32)}
    spec = SPECS["sgd-momentum-wd"]  # no norm_clip
    mar = make_merged_allreduce(
        tree, axis_name=DATA_AXIS, policy="single", comm_op="rs_opt_ag",
        optim_spec=spec, world_size=8,
    )

    def per_device(grads, params, os_):
        new_p, new_os = mar.reduce_and_update(grads, params, os_)
        with jax.named_scope("sharded_clip_norm"):
            s = jax.lax.psum(jnp.sum(new_p["a"] ** 2), DATA_AXIS)
        return {"a": new_p["a"] + 0.0 * s}, new_os

    fn = jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(), mar.optim.partition_spec()),
        out_specs=(P(), mar.optim.partition_spec()),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree, tree, mar.optim.init())
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    assert any(
        f.rule_id == "SCH004" and "sharded_clip_norm" in f.message
        for f in findings
    )


def test_verifier_rejects_clip_scope_abuse_on_plain_path(mesh):
    """The sharded_clip_norm scope only whitelists collectives for
    rs_opt_ag; a plain-path psum hiding under it is still a stray."""
    from mgwfbp_tpu.analysis import verify_jaxpr_against_reducer

    tree = {"a": jnp.ones((8,), jnp.float32)}
    mar = make_merged_allreduce(tree, axis_name=DATA_AXIS, policy="single")

    def per_device(grads):
        grads = mar(grads)
        with jax.named_scope("sharded_clip_norm"):
            s = jax.lax.psum(jnp.sum(grads["a"] ** 2), DATA_AXIS)
        return {"a": grads["a"] + 0.0 * s}

    fn = jax.jit(shard_map(
        per_device, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree)
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    assert any(f.rule_id == "SCH004" for f in findings)
