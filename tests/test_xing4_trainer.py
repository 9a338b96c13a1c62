"""Xing4.0 (models/xing4.py) on the Trainer's path at the tiny size, data
parallel over four virtual devices, through `train_cli`'s flags: a stage that
starts at layer 1 (`--layers-held 1:3`: one dense and two sparse layers under
their published names) with experts 2 to 5 of 8 trains under `--policy wfbp`,
the routing counters (Mellum 2's names) and the four new ones ride on the
`step` records, the leaves (a (count, hidden, width) expert stack beside
(4 x hidden, 24) mapping matrices, 24- and 3-element mapping vectors and a
selection bias no gradient reaches) are reduced like `lax.pmean`'s, and the
jaxpr verifier finds the step clean. ONE training a file (module scope), read
by every test that needs what it left. The equations are held against the
plain reference in tests/benchmark/test_xing4_reference.py."""

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
    "--dnn", "xing4_tiny", "--dataset", "tokens", "--layers-held", "1:3",
    "--experts-held", "2:4", "--vocab-size", "256", "--num-steps", "64",
    "--batch-size", "2", "--lr", "0.01", "--lr-schedule", "const",
    "--synthetic", "--telemetry", "--no-profile-backward",
    "--num-batches-per-epoch", "6", "--max-epochs", "2", "--seed", "5",
]
LEAVES = 18 + 2 * 23 + 3  # a dense layer's, two sparse layers', embed + out


def build(tmp_path, name, *extra, world=WORLD, flags=FLAGS):
    args = train_cli.build_parser().parse_args(
        [*flags, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    mesh = make_mesh(MeshSpec(data=world, seq=1), devices=jax.devices()[:world])
    return cfg, Trainer(
        cfg, mesh=mesh, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


@pytest.mark.parametrize("layers_held,indices", [
    (None, (0, 1, 2, 3)), ("1:3", (1, 2, 3)), ("2", (0, 1)), ("3:1", (3,)),
], ids=["all-four", "the-stage-of-the-tiny-cell", "a-bare-n-the-dense-two",
        "the-last-layer-alone"])
def test_preset_and_flags_reach_the_factory_and_layers_keep_their_names(
        tmp_path, monkeypatch, layers_held, indices):
    """`--layers-held FIRST:COUNT` holds a stage anywhere in the model, under
    the published layer numbers; a bare N is 0:N."""
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "64")
    flags = [f for f in FLAGS if f not in ("--layers-held", "1:3")]
    extra = [] if layers_held is None else ["--layers-held", layers_held]
    cfg, trainer = build(tmp_path, "a", *extra, world=1, flags=flags)
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        assert trainer.model.layer_indices() == indices
        assert trainer.model.experts_held == (2, 4)
        assert trainer.model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        params = trainer.state.params
        assert set(params) == {
            "embed", "out", *(f"layer_{i}" for i in indices)}
        assert set(params["out"]) == {"norm", "head"}  # untied
        for i in indices:
            leaves = params[f"layer_{i}"]
            assert leaves["attn_phi"].shape == (4 * 32, 24)
            assert leaves["mlp_phi"].shape == (4 * 32, 24)
            assert leaves["attn_b"].shape == (24,)
            assert leaves["mlp_alpha"].shape == (3,)
            assert leaves["w_dkv"].shape == (32, 16 + 8)
            assert leaves["w_ukv"].shape == (16, 4 * 32)
            assert ("mlp_gate" in leaves) == (i < 2)
            assert ("router" in leaves) == (i >= 2)
            if i >= 2:
                assert leaves["router"].shape == (32, 8)  # all 8 scored
                assert leaves["router_bias"].shape == (8,)
                assert leaves["w_gate"].shape == (4, 32, 16)
            # the seeded draws that make the new parts count
            np.testing.assert_array_equal(leaves["attn_alpha"], [0.5] * 3)
            b = np.asarray(leaves["mlp_b"])
            assert not b[:8].any()
            np.testing.assert_array_equal(
                b[8:].reshape(4, 4), 2.0 * np.eye(4))
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
        # what the solver and the buckets are handed
        assert {(4, 32, 16), (4, 16, 32), (128, 24), (24,), (3,), (8,),
                (32, 8), (32, 48), (24, 96)} <= shapes
        assert len(names) == LEAVES
        assert sorted(i for g in reducer.layout.groups for i in g) \
            == list(range(len(names)))
        assert reducer.schedule.num_groups == len(names)

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
            # no gradient reaches the selection bias: the choice carries none
            assert (float(jnp.linalg.norm(want)) > 0) \
                == ("router_bias" not in name), name
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-9, err_msg=name)

        bias_0 = {
            name: np.asarray(leaf) for name, leaf in zip(
                names, jax.tree_util.tree_leaves(trainer.state.params))
            if "router_bias" in name}
        trainer.fit(2)
        assert trainer.iteration == 12
        # ... and the window does not update it
        after = dict(zip(
            names, jax.tree_util.tree_leaves(trainer.state.params)))
        assert len(bias_0) == 2
        for name, before in bias_0.items():
            np.testing.assert_array_equal(np.asarray(after[name]), before)
    finally:
        trainer.close()
    return program_records.read_run(str(tmp_path / policy), cfg, trainer)


@pytest.fixture(scope="module")
def wfbp_run(tmp_path_factory):
    """The file's one training under `wfbp` with the telemetry on, for every
    test that reads what it left."""
    with pytest.MonkeyPatch.context() as patch:
        return trained(tmp_path_factory.mktemp("wfbp"), patch, "wfbp")


def test_trains_with_counters_and_every_leaf_reduces_like_pmean(wfbp_run):
    _, records, _ = wfbp_run
    steps = events_of(records, "step")
    health = {h["step"]: h for h in events_of(records, "health")}
    assert [s["step"] for s in steps] == list(range(1, 13))
    assert set(health) == set(range(1, 13))
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    with_counters = [s for s in steps if "mhc_res_gap" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["moe_dropped"] == 0.0 and "stats_ready" in s
        assert 0.0 < s["moe_here"] < 1.0
        assert s["moe_load_max"] >= s["moe_load_mean"] > 0.0
        # four of eight experts held, 2 x 64 tokens a device choosing 2
        assert s["moe_load_mean"] * 4 <= 2 * 64 * 2
        assert 0.0 < s["mhc_res_gap"] < 5e-3
        assert 0.2 < s["mhc_res_offdiag"] < 0.45
        assert 0.01 < s["mla_kv_latent_rms"] < 1.0
        assert 0.0 < s["moe_bias_swap_share"] < 0.6
    assert not [k for s in steps for k in s if k.startswith("health/")]
    # the report reads the routing counters as it reads Mellum 2's, and the
    # new ones on a line of their own
    import telemetry_report

    report = telemetry_report.format_report(records)
    assert "expert routing" in report
    assert "residual streams" in report
    for said in ("off its diagonal", "c_kv", "selection bias"):
        assert said in report, said


@pytest.mark.parametrize("op,want", [
    # three layers' cores, each sub-layer under a `jax.checkpoint` whose
    # cached trace `counted` counts again; the dense layer has no experts,
    # the two sparse ones 3 grouped products and 2 permutations each
    ("attention", {"kernel": 0, "blocks": 3}),
    ("experts", {"kernel": 0, "ragged": 6, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 4, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 2}),
    ("scan", {"kernel": 0, "plain": 0, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 0, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 0, "programs": 0}),
    # two passes a sub-layer (the mapping with its read, the write-back),
    # six sub-layers: ops/streams.py's plain form on the CPU
    ("streams", {"kernel": 0, "plain": 12, "programs": 0}),
    ("ssd", {"kernel": 0, "plain": 0, "programs": 0}),
], ids=program_records.OPS)
def test_the_step_program_leaves_its_records(wfbp_run, op, want):
    program_records.holds(wfbp_run, op, want)


def test_the_step_verifies_and_the_counters_add_no_collective():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_health_stats_footprint,
        verify_train_step,
    )

    assert verify_train_step("xing4_tiny", "wfbp", batch_size=8) == []
    assert verify_health_stats_footprint("xing4_tiny", "wfbp") == []
    _, reducer, leaves = trace_train_step("xing4_tiny", "wfbp", batch_size=8)
    stacked = [leaf for leaf in leaves if leaf.ndim == 3]
    assert len(stacked) == 3 * 2 and len(leaves) == 2 * 18 + 2 * 23 + 3
    assert sorted(i for g in reducer.layout.groups for i in g) \
        == list(range(len(leaves)))


@pytest.mark.parametrize("flag,share,message", [
    ("--experts-held", "6:4", "not among the model's 8"),
    ("--experts-held", "0:0", "not among the model's 8"),
    ("--layers-held", "3:2", "not among the model's 4"),
    ("--layers-held", "one:2", "neither N nor FIRST:COUNT"),
], ids=["experts-out-of-range", "no-expert", "layers-out-of-range",
        "no-integers"])
def test_a_share_that_cannot_be_held_fails_with_the_families_message(
        tmp_path, monkeypatch, flag, share, message):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "16")
    args = train_cli.build_parser().parse_args([
        "--dnn", "xing4_tiny", "--dataset", "tokens", "--vocab-size", "256",
        flag, share, "--synthetic", "--no-profile-backward", "--logdir",
        str(tmp_path)])
    cfg = train_cli.config_from_args(args)
    with pytest.raises(ValueError, match=message):
        Trainer(cfg, profile_backward=False, synthetic_data=True).close()
