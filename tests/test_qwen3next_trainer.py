"""Qwen3-Next (models/qwen3next.py) on the Trainer's path at the tiny size,
data parallel over four virtual devices, through `train_cli`'s flags: the
first period and a quarter of the experts (`--layers-held 4 --experts-held
4:4`, as the benchmark's cell holds layers 0 to 3 and 32 of 512), it trains
under `--policy mgwfbp` and `wfbp`, the routing counters (Mellum 2's names)
and the three new ones ride on the `step` records, every leaf (the 4-element
`a_log` and `dt_bias` beside the (count, hidden, width) expert stacks) is
reduced like `lax.pmean`'s, it resumes from a checkpoint bitwise, the
`delta_program` record says which way the delta rules went, and the jaxpr
verifier finds the step clean. The equations are held against the plain
reference in tests/benchmark/test_qwen3next_reference.py."""

import jax
import jax.numpy as jnp
import numpy as np
import program_records
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu import train_cli
from mgwfbp_tpu.models import create_model
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.telemetry.events import events_of
from mgwfbp_tpu.train.step import make_loss_fn
from mgwfbp_tpu.train.trainer import Trainer
from mgwfbp_tpu.utils.faults import Preempted

WORLD = 4
FLAGS = [
    "--dnn", "qwen3next_tiny", "--dataset", "tokens", "--layers-held", "4",
    "--experts-held", "4:4", "--vocab-size", "256", "--num-steps", "64",
    "--batch-size", "2", "--lr", "0.01", "--lr-schedule", "const",
    "--synthetic", "--telemetry", "--no-profile-backward",
    "--num-batches-per-epoch", "6", "--max-epochs", "2", "--seed", "5",
]
# a Gated DeltaNet layer's 8 mixer leaves, the full layer's 7, and the 9 of
# the sparse block that every layer has; the embedding, the final norm, the head
LEAVES = 3 * (8 + 9) + (7 + 9) + 3


def build(tmp_path, name, *extra, world=WORLD, flags=FLAGS):
    args = train_cli.build_parser().parse_args(
        [*flags, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    mesh = make_mesh(MeshSpec(data=world, seq=1), devices=jax.devices()[:world])
    return cfg, Trainer(
        cfg, mesh=mesh, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


def test_preset_and_flags_reach_the_factory_and_the_optimizer(
        tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "64")
    cfg, trainer = build(tmp_path, "a", world=1)
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        assert trainer.model.layers_held == 4
        assert trainer.model.experts_held == (4, 4)
        assert trainer.model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        assert trainer.model.layer_kinds() == (
            "linear_attention",) * 3 + ("full_attention",)
        params = trainer.state.params
        assert set(params) == {
            "embed", "out", *(f"layer_{i}" for i in range(4))}
        assert set(params["out"]) == {"norm", "head"}  # untied
        sparse = {"moe_norm", "router", "shared_gate", "shared_up",
                  "shared_down", "shared_gate_w", "w_gate", "w_up", "w_down"}
        for i in range(3):
            assert set(params[f"layer_{i}"]) == sparse | {
                "attn_norm", "w_qkvz", "w_ba", "conv_w", "dt_bias", "a_log",
                "gate_norm", "w_out"}
        assert set(params["layer_3"]) == sparse | {
            "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
        assert params["layer_0"]["w_qkvz"].shape == (32, 96)
        assert params["layer_3"]["wq"].shape == (32, 128)  # queries and gates
        assert params["layer_1"]["router"].shape == (32, 16)  # all 16 scored
        assert params["layer_2"]["w_gate"].shape == (4, 32, 16)
        # the zero-centred norms start at zero, the gated norm at one
        for name in ("attn_norm", "moe_norm"):
            assert not np.asarray(params["layer_0"][name]).any()
        assert not np.asarray(params["layer_3"]["q_norm"]).any()
        assert not np.asarray(params["out"]["norm"]).any()
        assert (np.asarray(params["layer_0"]["gate_norm"]) == 1).all()
        a = np.exp(np.asarray(params["layer_1"]["a_log"]))
        assert ((a > 0) & (a < 16)).all()
        dt = np.log1p(np.exp(np.asarray(params["layer_1"]["dt_bias"])))
        assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
        conv = np.asarray(params["layer_2"]["conv_w"])
        assert conv.shape == (4, 64) and np.abs(conv).max() <= 0.5
        assert np.abs(conv).max() > 0.3
    finally:
        trainer.close()


def trained(tmp_path, patch, policy):
    """Two epochs under `policy`, after every leaf was seen to reduce like
    `lax.pmean`'s: what `program_records.read_run` reads of them."""
    patch.setenv("MGWFBP_SYNTH_TRAIN_N", str(6 * 2 * WORLD))
    patch.setenv("MGWFBP_SYNTH_VAL_N", "8")
    cfg, trainer = build(tmp_path, policy, "--policy", policy)
    try:
        reducer = trainer.reducer
        assert reducer is not None and trainer.data_size == WORLD
        with_paths = jax.tree_util.tree_flatten_with_path(
            trainer.state.params)[0]
        names = [jax.tree_util.keystr(kp) for kp, _ in with_paths]
        shapes = {leaf.shape for _, leaf in with_paths}
        # what the solver and the buckets are handed: leaves of 4 elements
        # beside stacked expert leaves
        assert {(4,), (8,), (16,), (32,), (4, 64), (32, 96), (32, 8),
                (4, 32, 16), (4, 16, 32), (32, 16)} <= shapes
        assert len(names) == LEAVES
        assert sorted(i for g in reducer.layout.groups for i in g) \
            == list(range(len(names)))
        if policy == "wfbp":
            assert reducer.schedule.num_groups == len(names)
        else:
            assert 1 <= reducer.schedule.num_groups <= len(names)

        # the real per-device gradients, reduced both ways in one program
        loss_fn = make_loss_fn(trainer.model, trainer.meta)
        x, y = trainer.bundle.train.inner.load_batch(0, 0)
        assert x.shape == (2 * WORLD, 64)

        def body(params, xb, yb):
            grads = jax.grad(
                lambda p: loss_fn(
                    p, {}, {"x": xb, "y": yb}, jax.random.PRNGKey(0), None,
                )[0])(params)
            return reducer(grads), lax.pmean(grads, DATA_AXIS)

        reduced, plain = jax.jit(shard_map(
            body, mesh=trainer.mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P()), check_vma=False,
        ))(trainer.state.params, jnp.asarray(x), jnp.asarray(y))
        for got, want, name in zip(
                jax.tree_util.tree_leaves(reduced),
                jax.tree_util.tree_leaves(plain), names):
            assert float(jnp.linalg.norm(want)) > 0, name
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-9, err_msg=name)

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
def test_trains_with_counters_and_every_leaf_reduces_like_pmean(
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
    assert (health[1]["loss"], health[12]["loss"]) == (5.552236080169678, 3.8970396518707275)
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    with_counters = [s for s in steps if "delta_state_rms" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["moe_dropped"] == 0.0 and "stats_ready" in s
        assert 0.0 < s["moe_here"] < 1.0
        assert s["moe_load_max"] >= s["moe_load_mean"] > 0.0
        # four of sixteen experts held, 2 x 64 tokens a device choosing 3
        assert s["moe_load_mean"] * 4 <= 2 * 64 * 3
        assert s["delta_state_rms"] > 0.0
        assert 0.3 < s["delta_beta_mean"] < 0.7
        assert 0.3 < s["shared_gate_mean"] < 0.7
        # no other family's counter on this model's records
        assert not {"attn_gate_mean", "moe_score_sum", "ssm_state_rms",
                    "sel_scan_state_rms"} & set(s)
    # the seeded start: both gates' means at a half
    assert with_counters[0]["delta_beta_mean"] == pytest.approx(0.5, abs=0.05)
    assert with_counters[0]["shared_gate_mean"] == pytest.approx(0.5, abs=0.02)
    assert not [k for s in steps for k in s if k.startswith("health/")]
    # the share's three delta rules, counted while the step was traced
    # (three equal layers share ONE cached trace under jax.checkpoint, and
    # the second policy's step finds it in jax's cache still): the plain
    # chunked form, the only one there is; 3 grouped products and 2
    # permutations a layer, through every layer's cached or fresh trace
    # (the records of the `wfbp` run: the test below)
    assert noted["delta"] == {"kernel": 0, "plain": 3, "programs": 0}
    assert (noted["experts"]["ragged"], noted["rows"]["rows_all"]) == (12, 8)
    import telemetry_report

    report = telemetry_report.format_report(records)
    assert "expert routing" in report and "linear attention (" in report
    assert "3 through the plain chunked form" in report


@pytest.mark.parametrize("op,want", [
    # the first period: three Gated DeltaNet layers (ONE cached trace of the
    # mixer's half, a delta rule and a convolution each) and a full layer's
    # core; 3 grouped products and 2 permutations a layer's sparse half
    ("attention", {"kernel": 0, "blocks": 1}),
    ("experts", {"kernel": 0, "ragged": 12, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 8, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 4}),
    ("scan", {"kernel": 0, "plain": 0, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 3, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 3, "programs": 0}),
    ("streams", {"kernel": 0, "plain": 0, "programs": 0}),
    ("ssd", {"kernel": 0, "plain": 0, "programs": 0}),
], ids=program_records.OPS)
def test_the_step_program_leaves_its_records(wfbp_run, op, want):
    program_records.holds(wfbp_run, op, want)


def test_exact_step_resume_is_bitwise(tmp_path, monkeypatch):
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
        assert resumed.model.layers_held == 4
        resumed.fit(1)
        assert resumed.iteration == 6
        got = jax.tree_util.tree_map(np.asarray, (
            resumed.state.params, resumed.state.opt_state))
    finally:
        resumed.close()
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(a, b)


def test_the_step_verifies_and_the_counters_add_no_collective():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_health_stats_footprint,
        verify_train_step,
    )

    assert verify_train_step("qwen3next_tiny", "wfbp", batch_size=8) == []
    assert verify_train_step(
        "qwen3next_tiny", "mgwfbp", batch_size=8, norm_clip=1.0) == []
    assert verify_health_stats_footprint("qwen3next_tiny", "wfbp") == []
    _, reducer, leaves = trace_train_step(
        "qwen3next_tiny", "wfbp", batch_size=8)
    # all eight layers and sixteen experts: two periods
    assert len([leaf for leaf in leaves if leaf.ndim == 3]) == 3 * 8
    assert len(leaves) == 2 * (LEAVES - 3) + 3
    assert sorted(i for g in reducer.layout.groups for i in g) \
        == list(range(len(leaves)))


@pytest.mark.parametrize("flag,share,message", [
    ("--experts-held", "14:4", "not among the model's 16"),
    ("--experts-held", "0:0", "not among the model's 16"),
    ("--layers-held", "1:3", "FIRST other than 0"),
], ids=["experts-out-of-range", "no-expert", "a-stage-that-starts-later"])
def test_a_share_that_cannot_be_held_fails_with_mellum2s_message(
        tmp_path, monkeypatch, flag, share, message):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "16")
    args = train_cli.build_parser().parse_args([
        "--dnn", "qwen3next_tiny", flag, share, "--synthetic", "--dataset",
        "tokens", "--vocab-size", "256", "--no-profile-backward", "--logdir",
        str(tmp_path)])
    cfg = train_cli.config_from_args(args)
    with pytest.raises(ValueError, match=message):
        Trainer(cfg, profile_backward=False, synthetic_data=True).close()


@pytest.mark.parametrize("name,share", [
    ("mellum2_tiny", {"experts_held": (2, 2)}),
    ("laguna_xs2_tiny", {"experts_held": (2, 4)}),
    ("granite4h_tiny", {}),
    ("phi4flash_tiny", {}),
])
def test_the_older_language_presets_trace_no_delta_rule_and_no_new_scope(
        name, share):
    """What this model added to shared code is a counter read round the
    step's trace and a head size in `ops/blockattn.py`'s table: the older
    tiny presets' lowered programs name none of the new scopes and count no
    delta rule. (That their text equals the parent commit's was checked
    once, tree against tree: CHANGES.md, PR 40.)"""
    from mgwfbp_tpu.ops import programs

    module, _ = create_model(name, **share)
    x = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(
        lambda: module.init({"params": jax.random.PRNGKey(0)}, x))
    before = programs.LOWERED.copy()
    text = jax.jit(jax.grad(lambda p: jnp.mean(
        module.apply(p, x, targets=x, train=True)[0]))).lower(params).as_text(
            debug_info=True)
    assert programs.lowered_since(before)["delta"] == {
        "kernel": 0, "plain": 0, "programs": 0}
    assert "gdn_" not in text and "triangular" not in text
    assert "lm_head" in text  # the scopes are in the text at all
