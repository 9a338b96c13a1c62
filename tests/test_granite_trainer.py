"""Granite 4.0-H (models/granite.py) on the Trainer's path at the tiny size,
data parallel over four virtual devices, through `train_cli`'s flags: it
trains under `--policy mgwfbp` and `wfbp`, the scan's counters ride on the
`step` records, and the TIED embedding (one leaf used by the lookup and by
the head, so its gradient is complete only when the backward pass reaches the
lookup) is reduced like any other leaf: last in arrival order, and equal to
`lax.pmean`'s of the same per-device gradients. The equations are held
against the plain reference in tests/benchmark/test_granite4h_reference.py."""

import jax
import jax.numpy as jnp
import numpy as np
import program_records
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu import train_cli
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.telemetry.events import events_of
from mgwfbp_tpu.train.step import make_loss_fn
from mgwfbp_tpu.train.trainer import Trainer

WORLD = 4
FLAGS = [
    "--dnn", "granite4h_tiny", "--dataset", "tokens", "--vocab-size", "256",
    "--num-steps", "64", "--batch-size", "2", "--lr", "0.01",
    "--lr-schedule", "const", "--synthetic", "--telemetry",
    "--no-profile-backward", "--num-batches-per-epoch", "6",
    "--max-epochs", "2", "--seed", "5",
]


def build(tmp_path, name, *extra):
    args = train_cli.build_parser().parse_args(
        [*FLAGS, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    mesh = make_mesh(MeshSpec(data=WORLD, seq=1), devices=jax.devices()[:WORLD])
    return cfg, Trainer(
        cfg, mesh=mesh, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


def test_preset_and_flags_reach_the_factory_and_the_optimizer(
        tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "64")
    cfg, trainer = build(tmp_path, "a", "--layers-held", "3")
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        assert trainer.model.layers_held == 3
        assert trainer.model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        params = trainer.state.params
        assert set(params) == {"embed", "layer_0", "layer_1", "layer_2", "out"}
        assert set(params["out"]) == {"norm"}  # no head leaf: tied
        assert params["layer_0"]["in_proj"].shape == (32, 64 + 80 + 4)
        assert params["layer_0"]["conv_w"].shape == (4, 80)
        assert set(params["layer_2"]) >= {"wq", "wk", "wv", "wo"}
        a = -np.exp(np.asarray(params["layer_1"]["a_log"]))
        assert ((a <= -1.0) & (a >= -16.0)).all()
        dt = np.log1p(np.exp(np.asarray(params["layer_1"]["dt_bias"])))
        assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    finally:
        trainer.close()
    from mgwfbp_tpu.models import create_model

    with pytest.raises(ValueError, match="dense"):
        create_model("granite4h_tiny", experts_held=(0, 2))


def trained(tmp_path, patch, policy):
    """Two epochs under `policy`, after the tied leaf was seen to reduce like
    `lax.pmean`'s: what `program_records.read_run` reads of them."""
    patch.setenv("MGWFBP_SYNTH_TRAIN_N", str(6 * 2 * WORLD))
    patch.setenv("MGWFBP_SYNTH_VAL_N", "8")
    cfg, trainer = build(tmp_path, policy, "--policy", policy)
    try:
        reducer = trainer.reducer
        assert reducer is not None and trainer.data_size == WORLD
        leaves, treedef = jax.tree_util.tree_flatten(trainer.state.params)
        names = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]]
        tied = names.index("['embed']['embedding']")
        # arrival order: the lookup is the backward pass's last consumer
        assert reducer.perm[-1] == tied
        assert sorted(i for g in reducer.layout.groups for i in g) \
            == list(range(len(leaves)))
        if policy == "wfbp":
            assert reducer.schedule.num_groups == len(leaves)

        # the real per-device gradients, reduced both ways in one program
        loss_fn = make_loss_fn(trainer.model, trainer.meta)
        x, y = trainer.bundle.train.inner.load_batch(0, 0)
        assert x.shape == (2 * WORLD, 64)

        def body(params, xb, yb):
            grads = jax.grad(
                lambda p: loss_fn(
                    p, {}, {"x": xb, "y": yb}, jax.random.PRNGKey(0), None,
                )[0])(params)
            return reducer(grads), lax.pmean(grads, DATA_AXIS), \
                grads["embed"]["embedding"][None]

        reduced, plain, local = jax.jit(shard_map(
            body, mesh=trainer.mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P(), P(DATA_AXIS)), check_vma=False,
        ))(trainer.state.params, jnp.asarray(x), jnp.asarray(y))
        local = np.asarray(local)  # (WORLD, vocabulary, hidden)
        assert np.abs(local[0] - local[1]).max() > 0  # ranks differ
        for got, want, name in zip(
                jax.tree_util.tree_leaves(reduced),
                jax.tree_util.tree_leaves(plain), names):
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-9, err_msg=name)
        np.testing.assert_allclose(
            reduced["embed"]["embedding"], local.mean(axis=0),
            rtol=1e-5, atol=1e-8)

        trainer.fit(2)
        assert trainer.iteration == 12
    finally:
        trainer.close()
    return program_records.read_run(str(tmp_path / policy), cfg, trainer)


@pytest.fixture(scope="module")
def wfbp_run(tmp_path_factory):
    """The file's one training under `wfbp` with the telemetry on, for every
    test that reads what it left."""
    with pytest.MonkeyPatch.context() as patch:
        return trained(tmp_path_factory.mktemp("wfbp"), patch, "wfbp")


@pytest.mark.parametrize("policy", ["mgwfbp", "wfbp"])
def test_trains_with_counters_and_the_tied_leaf_reduces_like_pmean(
        tmp_path, monkeypatch, request, policy):
    noted, records, _ = (
        request.getfixturevalue("wfbp_run") if policy == "wfbp"
        else trained(tmp_path, monkeypatch, policy))
    steps = events_of(records, "step")
    health = {h["step"]: h for h in events_of(records, "health")}
    assert [s["step"] for s in steps] == list(range(1, 13))
    assert set(health) == set(range(1, 13))
    # the first and the twelfth loss are the parent commit's to the last
    # digit, under either policy (read there on the same seeds, PR 42: the
    # convolution's plain form moved to ops/shortconv.py, it did not change)
    assert (health[1]["loss"], health[12]["loss"]) == (5.5441083908081055, 5.335992813110352)
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    # the three Mamba-2 layers' convolutions (ops/shortconv.py), counted
    # while the step was traced and through cached traces: the plain form
    assert noted["conv"] == {"kernel": 0, "plain": 3, "programs": 0}
    with_counters = [s for s in steps if "ssm_state_rms" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["ssm_state_rms"] > 0.0 and s["ssm_log_decay_min"] < 0.0
        assert "stats_ready" in s
    assert not [k for s in steps for k in s if k.startswith("health/")]


@pytest.mark.parametrize("op,want", [
    # (mamba, mamba, attention, mamba): the attention layer's core, and a
    # convolution and a scan a Mamba-2 layer (ops/ssd.py's plain form: the
    # tiny preset's heads of 16 over a state of 8 are no lane tiles); the
    # layers share cached traces
    ("attention", {"kernel": 0, "blocks": 1}),
    ("experts", {"kernel": 0, "ragged": 0, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 0, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 0}),
    ("scan", {"kernel": 0, "plain": 0, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 0, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 3, "programs": 0}),
    ("streams", {"kernel": 0, "plain": 0, "programs": 0}),
    ("ssd", {"kernel": 0, "plain": 3, "programs": 0}),
], ids=program_records.OPS)
def test_the_step_program_leaves_its_records(wfbp_run, op, want):
    program_records.holds(wfbp_run, op, want)


def test_the_step_verifies_and_the_counters_add_no_collective():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_health_stats_footprint,
        verify_train_step,
    )

    assert verify_train_step("granite4h_tiny", "wfbp", batch_size=8) == []
    assert verify_train_step(
        "granite4h_tiny", "mgwfbp", batch_size=8, norm_clip=1.0) == []
    assert verify_health_stats_footprint("granite4h_tiny", "wfbp") == []
