"""Laguna-XS.2 (models/laguna.py) on the Trainer's path at the tiny size,
data parallel over four virtual devices, through `train_cli`'s flags: it
trains under `--policy mgwfbp` and `wfbp`, the routing counters (Mellum 2's
names) and the two new ones ride on the `step` records, the leaves that
differ by layer (a (count, hidden, width) expert stack beside 6- and 8-column
gate leaves and hidden-sized norms) are reduced like `lax.pmean`'s, and the
jaxpr verifier finds the step clean. A share that is out of range, or asked
of a dense family, fails with the message `mellum2` gives. The equations are
held against the plain reference in
tests/benchmark/test_laguna_xs2_reference.py."""

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
    "--dnn", "laguna_xs2_tiny", "--dataset", "tokens", "--experts-held", "2:4",
    "--vocab-size", "256", "--num-steps", "64", "--batch-size", "2",
    "--lr", "0.01", "--lr-schedule", "const", "--synthetic", "--telemetry",
    "--no-profile-backward", "--num-batches-per-epoch", "6",
    "--max-epochs", "2", "--seed", "5",
]


def build(tmp_path, name, *extra, world=WORLD):
    args = train_cli.build_parser().parse_args(
        [*FLAGS, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    mesh = make_mesh(MeshSpec(data=world, seq=1), devices=jax.devices()[:world])
    return cfg, Trainer(
        cfg, mesh=mesh, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


@pytest.mark.parametrize("layers_held,kinds", [
    (None, ["dense", "sparse", "sparse", "sparse", "sparse"]),
    (3, ["dense", "sparse", "sparse"]),
    (1, ["dense"]),
], ids=["all-five", "first-three", "the-dense-layer-alone"])
def test_preset_and_flags_reach_the_factory_and_the_dense_layer_stays_first(
        tmp_path, monkeypatch, layers_held, kinds):
    """`--layers-held n` below 5 keeps the first n of the per-layer lists, as
    `mellum2`: the dense layer first, with its own head count."""
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "64")
    extra = [] if layers_held is None else ["--layers-held", str(layers_held)]
    cfg, trainer = build(tmp_path, "a", *extra, world=1)
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        assert trainer.model.layers_held == layers_held
        assert trainer.model.experts_held == (2, 4)
        assert trainer.model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        params = trainer.state.params
        assert set(params) == {
            "embed", "out", *(f"layer_{i}" for i in range(len(kinds)))}
        assert set(params["out"]) == {"norm", "head"}  # untied
        for i, kind in enumerate(kinds):
            leaves = params[f"layer_{i}"]
            heads = (6, 8, 8, 8, 6)[i]
            assert leaves["wq"].shape == (64, heads * 16)
            assert leaves["wg"].shape == (64, heads)
            assert ("mlp_gate" in leaves) == (kind == "dense")
            assert ("router" in leaves) == (kind == "sparse")
            if kind == "sparse":
                assert leaves["router"].shape == (64, 16)  # all 16 scored
                assert leaves["w_gate"].shape == (4, 64, 32)
                assert leaves["shared_down"].shape == (24, 64)
        assert [k[1] for k in trainer.model.layer_kinds()] == kinds
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
        # what the solver and the buckets are handed: an expert stack beside
        # gate leaves of 6 and 8 columns and hidden-sized norms
        assert {(4, 64, 32), (4, 32, 64), (64, 6), (64, 8), (64,),
                (64, 16), (64, 96)} <= shapes
        assert len(names) == 10 + 4 * 14 + 3
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
    _, records, _ = (
        request.getfixturevalue("wfbp_run") if policy == "wfbp"
        else trained(tmp_path, monkeypatch, policy))
    steps = events_of(records, "step")
    health = {h["step"]: h for h in events_of(records, "health")}
    assert [s["step"] for s in steps] == list(range(1, 13))
    assert set(health) == set(range(1, 13))
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    with_counters = [s for s in steps if "attn_gate_mean" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["moe_dropped"] == 0.0 and "stats_ready" in s
        assert 0.0 < s["moe_here"] < 1.0
        assert s["moe_load_max"] >= s["moe_load_mean"] > 0.0
        # four of sixteen experts held, 2 x 64 tokens a device choosing 2
        assert s["moe_load_mean"] * 4 <= 2 * 64 * 2
        assert 0.3 < s["attn_gate_mean"] < 0.7
        assert 0.0 < s["moe_score_sum"] < 2.0
    # the seeded start: the gate's mean at a half
    assert with_counters[0]["attn_gate_mean"] == pytest.approx(0.5, abs=0.02)
    assert not [k for s in steps for k in s if k.startswith("health/")]
    # the report reads the routing counters as it reads Mellum 2's
    import telemetry_report

    assert "expert routing" in telemetry_report.format_report(records)


@pytest.mark.parametrize("op,want", [
    # five layers' cores (6 | 8 query heads; no layer is under a
    # `jax.checkpoint`, so each is traced); the dense first layer has no
    # experts, the four sparse ones 3 grouped products and 2 permutations
    ("attention", {"kernel": 0, "blocks": 5}),
    ("experts", {"kernel": 0, "ragged": 12, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 8, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 4}),
    ("scan", {"kernel": 0, "plain": 0, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 0, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 0, "programs": 0}),
    ("streams", {"kernel": 0, "plain": 0, "programs": 0}),
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

    assert verify_train_step("laguna_xs2_tiny", "wfbp", batch_size=8) == []
    assert verify_train_step(
        "laguna_xs2_tiny", "mgwfbp", batch_size=8, norm_clip=1.0) == []
    assert verify_health_stats_footprint("laguna_xs2_tiny", "wfbp") == []
    _, reducer, leaves = trace_train_step(
        "laguna_xs2_tiny", "wfbp", batch_size=8)
    stacked = [leaf for leaf in leaves if leaf.ndim == 3]
    assert len(stacked) == 3 * 4 and len(leaves) == 10 + 4 * 14 + 3
    assert sorted(i for g in reducer.layout.groups for i in g) \
        == list(range(len(leaves)))


@pytest.mark.parametrize("dnn,share,message", [
    ("lenet", "0:2", "cannot be held in part"),
    ("granite4h_tiny", "0:2", "is dense"),
    ("laguna_xs2_tiny", "14:4", "not among the model's 16"),
    ("mellum2_tiny", "6:4", "not among the model's 8"),
    ("laguna_xs2_tiny", "0:0", "not among the model's 16"),
    ("laguna_xs2_tiny", "two:4", "is not FIRST:COUNT"),
], ids=["a-dense-image-model", "a-dense-language-model", "out-of-range",
        "mellum2-out-of-range", "no-expert", "no-integers"])
def test_a_share_that_cannot_be_held_fails_with_mellum2s_message(
        tmp_path, monkeypatch, dnn, share, message):
    """`train_cli --experts-held` on a dense family, and an out-of-range
    share on this one, through the Trainer as the CLI builds it."""
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "16")
    flags = ["--dnn", dnn, "--experts-held", share, "--synthetic",
             "--no-profile-backward", "--logdir", str(tmp_path)]
    if dnn != "lenet":
        flags += ["--dataset", "tokens", "--vocab-size", "256"]
    args = train_cli.build_parser().parse_args(flags)
    cfg = train_cli.config_from_args(args)
    with pytest.raises(ValueError, match=message):
        Trainer(cfg, profile_backward=False, synthetic_data=True).close()
