"""Mellum 2 (models/mellum.py) on the Trainer's path at the tiny size:
through `train_cli`'s flags, on the CPU mesh. Loss falls, every step has its
`health` record, the routing counters ride on the `step` records, and a save
with an exact-step resume of the new tree (stacked expert leaves, AdamW
moments) continues bitwise. The equations themselves are held against the
plain reference in tests/benchmark/test_mellum2_reference.py."""

import jax
import numpy as np
import program_records
import pytest

from mgwfbp_tpu import train_cli
from mgwfbp_tpu.telemetry.events import events_of
from mgwfbp_tpu.train.trainer import Trainer
from mgwfbp_tpu.utils.faults import Preempted

FLAGS = [
    "--dnn", "mellum2_tiny", "--dataset", "tokens", "--experts-held", "2:2",
    "--layers-held", "4", "--vocab-size", "256", "--num-steps", "64",
    "--batch-size", "2", "--lr", "0.01", "--lr-schedule", "const",
    "--synthetic", "--telemetry", "--no-profile-backward",
    "--num-batches-per-epoch", "6", "--max-epochs", "2", "--seed", "5",
]


def build(tmp_path, name, *extra):
    args = train_cli.build_parser().parse_args(
        [*FLAGS, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    return cfg, Trainer(
        cfg, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


def test_flags_reach_the_factory_and_the_optimizer(tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "32")
    cfg, trainer = build(tmp_path, "a")
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        assert trainer.model.experts_held == (2, 2)
        assert trainer.model.layers_held == 4
        assert trainer.model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        params = trainer.state.params
        assert params["layer_3"]["w_gate"].shape == (2, 64, 32)
        assert params["layer_0"]["router"].shape == (64, 8)  # all 8 experts
        assert params["embed"]["embedding"].shape == (256, 64)
        # AdamW: two moments a parameter
        moments = [
            leaf for leaf in jax.tree_util.tree_leaves(trainer.state.opt_state)
            if getattr(leaf, "shape", ()) == (2, 64, 32)]
        assert len(moments) == 2 * 2 * 4  # mu and nu x gate and up x layers
        assert trainer.optim_spec.kind == "adam"
        assert trainer.optim_spec.decoupled_wd
        x, y = trainer.bundle.train.inner.load_batch(0, 0)
        assert x.shape == y.shape == (2 * trainer.data_size, 64)
        assert x.dtype == np.int32 and int(x.max()) < 256
        np.testing.assert_array_equal(x[:, 1:], y[:, :-1])  # y is x shifted
    finally:
        trainer.close()


@pytest.fixture(scope="module")
def fit_run(tmp_path_factory):
    """The file's one training of two epochs with the telemetry on, for every
    test that reads what it left (`program_records.read_run`)."""
    tmp_path = tmp_path_factory.mktemp("fit")
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("MGWFBP_SYNTH_TRAIN_N", "96")
        patch.setenv("MGWFBP_SYNTH_VAL_N", "8")
        cfg, trainer = build(tmp_path, "fit")
        try:
            trainer.fit(2)
            assert trainer.iteration == 12
        finally:
            trainer.close()
    return program_records.read_run(str(tmp_path / "fit"), cfg, trainer)


def test_fit_loss_falls_health_and_counters_every_step(fit_run):
    _, records, _ = fit_run
    steps = events_of(records, "step")
    health = {h["step"]: h for h in events_of(records, "health")}
    assert [s["step"] for s in steps] == list(range(1, 13))
    assert set(health) == set(range(1, 13))
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    # counters: on every record whose aftermath drained a step's statistics
    with_counters = [s for s in steps if "moe_here" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["moe_dropped"] == 0.0 and "stats_ready" in s
        assert 0.0 < s["moe_here"] < 1.0
        assert s["moe_load_max"] >= s["moe_load_mean"] > 0.0
        # two of eight experts held, top 2 of 8 per token
        assert s["moe_load_mean"] * 2 <= 2 * 64 * 2
    # the model's statistics never reach the log-facing metrics
    assert not [k for s in steps for k in s if k.startswith("health/")]


@pytest.mark.parametrize("op,want", [
    # four layers: a core each (none under a `jax.checkpoint`: each is
    # traced), 3 grouped products and 2 permutations each (the held experts'
    # part shares ONE cached trace)
    ("attention", {"kernel": 0, "blocks": 4}),
    ("experts", {"kernel": 0, "ragged": 12, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 8, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 4}),
    ("scan", {"kernel": 0, "plain": 0, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 0, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 0, "programs": 0}),
    ("streams", {"kernel": 0, "plain": 0, "programs": 0}),
    ("ssd", {"kernel": 0, "plain": 0, "programs": 0}),
], ids=program_records.OPS)
def test_the_step_program_leaves_its_records(fit_run, op, want):
    program_records.holds(fit_run, op, want)


def test_exact_step_resume_of_the_new_tree_is_bitwise(tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "96")
    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    _, whole = build(tmp_path, "whole")
    try:
        whole.fit(1)
        want = jax.tree_util.tree_map(np.asarray, (
            whole.state.params, whole.state.opt_state))
        assert whole.iteration == 6
    finally:
        whole.close()
    ckpt = ["--checkpoint-dir", str(tmp_path / "ckpt"),
            "--ckpt-every-steps", "2"]
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "preempt@step=3")
    _, cut = build(tmp_path, "cut", *ckpt)
    try:
        with pytest.raises(Preempted) as exc:
            cut.fit(1)
        assert exc.value.iteration == 3
    finally:
        cut.close()
    monkeypatch.delenv("MGWFBP_FAULT_PLAN")
    _, resumed = build(tmp_path, "cut", *ckpt)
    try:
        assert resumed.iteration == 3 and resumed.start_epoch == 0
        resumed.fit(1)
        assert resumed.iteration == 6
        got = jax.tree_util.tree_map(np.asarray, (
            resumed.state.params, resumed.state.opt_state))
    finally:
        resumed.close()
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(a, b)


def test_expert_leaves_reduce_like_any_other_leaf_and_the_step_verifies():
    """On the CPU's virtual devices the model is data parallel: every rank
    holds the SAME experts, so their gradients are reduced over the data axis
    like every other leaf's (right for replicated experts; an expert axis of
    their own is the four-chip cell's). The jaxpr verifier finds the merged
    groups covering all leaves, no host callback, no stray collective, and
    the health statistics (routing counts among them) add no collective."""
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_health_stats_footprint,
        verify_train_step,
    )

    assert verify_train_step("mellum2_tiny", "wfbp", batch_size=8) == []
    assert verify_train_step(
        "mellum2_tiny", "mgwfbp", batch_size=8, norm_clip=1.0) == []
    assert verify_health_stats_footprint("mellum2_tiny", "wfbp") == []
    _, reducer, leaves = trace_train_step("mellum2_tiny", "wfbp", batch_size=8)
    stacked = [leaf for leaf in leaves if leaf.ndim == 3]
    assert len(stacked) == 3 * 4 and len(leaves) == 43
    assert sorted(i for g in reducer.layout.groups for i in g) == list(range(43))


def test_only_a_model_that_can_be_held_in_part_takes_a_share():
    from mgwfbp_tpu.models import create_model

    with pytest.raises(ValueError, match="cannot be held in part"):
        create_model("lenet", experts_held=(0, 2))
    model, _ = create_model("mellum2_tiny", experts_held=(6, 4))
    with pytest.raises(ValueError, match="not among the model's 8"):
        model.init({"params": jax.random.PRNGKey(0)},
                   np.zeros((1, 64), np.int32), train=False)
