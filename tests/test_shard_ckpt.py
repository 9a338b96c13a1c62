"""Shard-native checkpoints + elastic resize (ISSUE 13).

The format contract under test: each process saves only its OWN shard
rows (rs_opt_ag opt slots, the rs_fwd_ag param carry, the BPTT carry)
plus a process-0 manifest recording world size / mesh axes / per-leaf
shard layout; restore re-slices per leaf straight off the source files,
so an N-way checkpoint restores onto M processes — or a different merge
schedule, or a different comm_op — bitwise, without ever materializing a
world-sized buffer (or even one fully-replicated leaf, for sharded
targets). The supervisor's resize-by-relaunch policy rides exactly this
restore (tools/fault_smoke.py --resize is the live 2-process gate;
these tests pin the re-shard math and the interchange rules in-process
on sub-meshes of the CPU-8 mesh).
"""

from __future__ import annotations

import glob
import os
import time

import jax
import numpy as np
import pytest

from mgwfbp_tpu.checkpoint import CheckpointRestoreError
from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh
from mgwfbp_tpu.train.trainer import Trainer


def _mk(
    world: int, comm_op: str, root, *, seed: int = 3, elastic: bool = False,
    monkeypatch=None, **overrides,
):
    cfg = make_config(
        "mnistnet", batch_size=4, max_epochs=2, logdir="",
        checkpoint_dir=os.path.join(str(root), "ckpt"), seed=seed,
        num_batches_per_epoch=2, comm_op=comm_op, **overrides,
    )
    if elastic:
        assert monkeypatch is not None
        monkeypatch.setenv("MGWFBP_ELASTIC_RESUME", "1")
    try:
        return Trainer(
            cfg, synthetic_data=True, profile_backward=False,
            mesh=make_mesh(
                MeshSpec(data=world), devices=jax.devices()[:world]
            ),
        )
    finally:
        if elastic:
            monkeypatch.delenv("MGWFBP_ELASTIC_RESUME")


def _gathered(t):
    """(params, opt_state) in the replicated interchange form, as host
    arrays — the cross-layout comparison baseline."""
    state = t._to_checkpoint_state(t.state)
    return (
        jax.tree_util.tree_map(np.asarray, state.params),
        jax.tree_util.tree_map(np.asarray, state.opt_state),
    )


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# save@N -> restore@M matrix: the re-shard math is bitwise across world
# sizes, across the replicated<->sharded boundary, and across comm_ops
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comm_op", ["rs_opt_ag", "rs_fwd_ag"])
def test_save_restore_world_matrix_bitwise(tmp_path, monkeypatch, comm_op):
    # save@4 -> restore@{2, 1}; world 1 runs without a merged reducer, so
    # the 4->1 leg is the sharded-source -> replicated-target interchange
    t4 = _mk(4, comm_op, tmp_path / "w4")
    t4.fit(1)
    ref4 = _gathered(t4)
    t4.close()
    for target_world in (2, 1):
        t = _mk(
            target_world, comm_op, tmp_path / "w4",
            elastic=True, monkeypatch=monkeypatch,
        )
        assert t.iteration == 2
        _assert_trees_equal(ref4, _gathered(t))
        t.close()

    # save@2 -> restore@4 (shard rows split finer than they were saved)
    t2 = _mk(2, comm_op, tmp_path / "w2")
    t2.fit(1)
    ref2 = _gathered(t2)
    t2.close()
    t = _mk(4, comm_op, tmp_path / "w2", elastic=True,
            monkeypatch=monkeypatch)
    assert t.iteration == 2
    _assert_trees_equal(ref2, _gathered(t))
    t.close()

    # save@1 (no reducer -> replicated payload) -> restore@4 (sharded
    # target re-slices a replicated source through slot_leaf_index)
    t1 = _mk(1, comm_op, tmp_path / "w1")
    t1.fit(1)
    ref1 = (
        jax.tree_util.tree_map(np.asarray, t1.state.params),
        jax.tree_util.tree_map(np.asarray, t1.state.opt_state),
    )
    t1.close()
    t = _mk(4, comm_op, tmp_path / "w1", elastic=True,
            monkeypatch=monkeypatch)
    assert t.iteration == 2
    _assert_trees_equal(ref1, _gathered(t))
    t.close()


def test_save_restore_cross_comm_op_bitwise(tmp_path, monkeypatch):
    # rs_ag keeps replicated state; its checkpoints must interchange with
    # the sharded ops' shard-native payloads in both directions
    t = _mk(2, "rs_ag", tmp_path)
    t.fit(1)
    ref = (
        jax.tree_util.tree_map(np.asarray, t.state.params),
        jax.tree_util.tree_map(np.asarray, t.state.opt_state),
    )
    t.close()
    t2 = _mk(4, "rs_opt_ag", tmp_path, elastic=True,
             monkeypatch=monkeypatch)
    assert t2.iteration == 2
    _assert_trees_equal(ref, _gathered(t2))
    t2.close()


# ---------------------------------------------------------------------------
# acceptance: no world-sized host buffer on the sharded save/restore path
# ---------------------------------------------------------------------------


def test_no_world_sized_gather_on_sharded_save_restore(
    tmp_path, monkeypatch,
):
    """Per-process save touches only its own shard bytes; restore@M of an
    N-way checkpoint never reconstructs a replicated leaf for the sharded
    target. Pinned by poisoning the host gather/scatter seams: the
    shard-native path must never call them."""
    from mgwfbp_tpu.parallel import allreduce as ar

    t4 = _mk(4, "rs_opt_ag", tmp_path)

    def _banned(name):
        def fn(*a, **k):
            raise AssertionError(
                f"ShardedOptimStep.{name} (world-sized host "
                "materialization) called on the shard-native path"
            )
        return fn

    monkeypatch.setattr(ar.ShardedOptimStep, "gather", _banned("gather"))
    monkeypatch.setattr(
        ar.ShardedOptimStep, "gather_params", _banned("gather_params")
    )
    monkeypatch.setattr(ar.ShardedOptimStep, "scatter", _banned("scatter"))
    t4.fit(1)  # epoch-boundary save rides the shard-native writer
    t4.close()

    # cross-world restore (4 -> 2) with the gathers still poisoned
    t2 = _mk(2, "rs_opt_ag", tmp_path, elastic=True,
             monkeypatch=monkeypatch)
    assert t2.iteration == 2
    t2.close()

    # ... and the payload on disk is exactly the shard bytes, laid out
    # per process (single process here, so p00000 owns every row)
    (tag_dir,) = glob.glob(os.path.join(tmp_path, "ckpt", "*-n4-*"))
    (manifest_path,) = sorted(
        glob.glob(os.path.join(tag_dir, "sharded", "*", "manifest.json"))
    )[-1:]
    import json

    with open(manifest_path) as f:
        manifest = json.load(f)
    assert manifest["opt"]["kind"] == "sharded"
    rows = manifest["processes"]["0"]["rows"]
    assert rows == list(range(manifest["layout"]["world"]))
    step_dir = os.path.dirname(manifest_path)
    for gi, shard in enumerate(manifest["layout"]["shard_sizes"]):
        arr = np.load(
            os.path.join(step_dir, "p00000", f"opt.s0.g{gi}.npy"),
            mmap_mode="r",
        )
        assert arr.shape == (len(rows), shard)


# ---------------------------------------------------------------------------
# restore-time validation: fail fast, naming process/leaf/layout
# ---------------------------------------------------------------------------


def test_missing_shard_file_fails_with_process_and_file(tmp_path):
    t = _mk(2, "rs_opt_ag", tmp_path)
    t.fit(1)
    t.close()
    (tag_dir,) = glob.glob(os.path.join(tmp_path, "ckpt", "*"))
    victim = sorted(glob.glob(
        os.path.join(tag_dir, "sharded", "*", "p00000", "opt.s0.g0.npy")
    ))[-1]
    os.unlink(victim)
    with pytest.raises(CheckpointRestoreError) as ei:
        _mk(2, "rs_opt_ag", tmp_path)
    msg = str(ei.value)
    assert "process 0" in msg
    assert "opt.s0.g0" in msg
    assert "expected" in msg  # names the expected layout


def test_truncated_shard_file_fails_with_expected_vs_found(tmp_path):
    t = _mk(2, "rs_opt_ag", tmp_path)
    t.fit(1)
    t.close()
    (tag_dir,) = glob.glob(os.path.join(tmp_path, "ckpt", "*"))
    victim = sorted(glob.glob(
        os.path.join(tag_dir, "sharded", "*", "p00000", "opt.s0.g0.npy")
    ))[-1]
    full = np.load(victim)
    np.save(victim, full[:1])  # half the rows gone
    with pytest.raises(CheckpointRestoreError) as ei:
        _mk(2, "rs_opt_ag", tmp_path)
    msg = str(ei.value)
    assert "found shape" in msg and "expected" in msg
    assert str(tuple(full.shape)) in msg


def test_replicated_leaf_drift_names_the_leaf(tmp_path):
    t = _mk(2, "all_reduce", tmp_path)
    t.fit(1)
    t.close()
    (tag_dir,) = glob.glob(os.path.join(tmp_path, "ckpt", "*"))
    (manifest_path,) = sorted(glob.glob(
        os.path.join(tag_dir, "sharded", "*", "manifest.json")
    ))[-1:]
    import json

    with open(manifest_path) as f:
        manifest = json.load(f)
    manifest["leaves"][0]["shape"] = [3, 3]  # config-drift simulation
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(CheckpointRestoreError) as ei:
        _mk(2, "all_reduce", tmp_path)
    msg = str(ei.value)
    assert manifest["leaves"][0]["path"] in msg
    assert "(3, 3)" in msg  # saved-vs-expected, both named


# ---------------------------------------------------------------------------
# legacy + escape hatch: --ckpt-format replicated round trip
# ---------------------------------------------------------------------------


def test_replicated_escape_hatch_round_trip_bitwise(tmp_path):
    # legacy-format save (orbax, gathered interchange form)...
    t = _mk(4, "rs_opt_ag", tmp_path, ckpt_format="replicated")
    t.fit(1)
    ref = _gathered(t)
    t.close()
    (tag_dir,) = glob.glob(os.path.join(tmp_path, "ckpt", "*"))
    assert not os.path.exists(os.path.join(tag_dir, "sharded")), (
        "escape hatch wrote the shard-native format"
    )
    # ...restores transparently into a default (sharded-format) trainer
    t2 = _mk(4, "rs_opt_ag", tmp_path)
    assert t2.iteration == 2
    _assert_trees_equal(ref, _gathered(t2))
    # ...which saves shard-native on top; a replicated-format trainer
    # reads THAT back through the template path — full round trip
    t2.fit(1)
    ref2 = _gathered(t2)
    assert t2.iteration == 4
    t2.close()
    assert os.path.exists(os.path.join(tag_dir, "sharded"))
    t3 = _mk(4, "rs_opt_ag", tmp_path, ckpt_format="replicated")
    assert t3.iteration == 4
    _assert_trees_equal(ref2, _gathered(t3))
    t3.close()


# ---------------------------------------------------------------------------
# elastic resize == in-place update_nworker, bitwise (the 1x-equivalence
# acceptance pin, epoch-boundary form)
# ---------------------------------------------------------------------------


def test_relaunch_resize_bitwise_vs_update_nworker(tmp_path, monkeypatch):
    """A run resized by RELAUNCH (shard-native checkpoint re-sharded onto
    the new world) must be bitwise-identical to the same run resized IN
    PLACE by update_nworker — the uninterrupted 1x-equivalent. Both train
    epoch 0 at world 8 and epoch 1 at world 4 on identical data."""
    # reference: one process, in-place resize between the epochs
    c = _mk(8, "rs_opt_ag", tmp_path / "ref")
    c.fit(1)
    c.start_epoch = 1
    c.update_nworker(4)
    c.fit(1)
    ref = _gathered(c)
    ref_iter = c.iteration
    c.close()

    # relaunch path: train at 8, stop, come back at 4 via the sibling-tag
    # cross-world resume (what the supervisor's --resize-to automates)
    a = _mk(8, "rs_opt_ag", tmp_path / "run")
    a.fit(1)
    a.close()
    b = _mk(4, "rs_opt_ag", tmp_path / "run", elastic=True,
            monkeypatch=monkeypatch)
    assert b.start_epoch == 1
    b.fit(1)
    assert b.iteration == ref_iter
    _assert_trees_equal(ref, _gathered(b))
    b.close()


# ---------------------------------------------------------------------------
# carry reader: interleaved per-process row runs reassemble exactly
# ---------------------------------------------------------------------------


def test_carry_reader_reassembles_interleaved_runs(tmp_path):
    """A multi-slice data sharding interleaves a process's batch rows;
    the manifest records the exact run list and the reader must map any
    global row to (process, offset within that process's
    run-concatenated file) — a min/max span would zero-fill the rows a
    peer owns."""
    import json

    from mgwfbp_tpu.checkpoint import ShardSource

    step_dir = tmp_path / "step"
    rows = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    # process 0 owns rows {0,1,4,5}; process 1 owns {2,3,6,7}
    runs = {"0": [[0, 2], [4, 6]], "1": [[2, 4], [6, 8]]}
    for p, r in runs.items():
        pdir = step_dir / f"p{int(p):05d}"
        os.makedirs(pdir)
        block = np.concatenate([rows[a:b] for a, b in r])
        np.save(pdir / "carry.l0.npy", block)
    manifest = {
        "format_version": 1,
        "step": 1,
        "carry": {
            "leaves": [
                {"path": "c", "shape": [8, 3], "dtype": "float32"}
            ],
            "runs": runs,
        },
        "processes": {},
    }
    with open(step_dir / "manifest.json", "w") as f:
        json.dump(manifest, f)
    src = ShardSource(str(step_dir), manifest)
    # every window, including ones crossing run and process boundaries
    for a, b in [(0, 8), (1, 5), (3, 7), (2, 4), (5, 8), (0, 1)]:
        np.testing.assert_array_equal(
            src.read_carry_range(0, a, b), rows[a:b]
        )


# ---------------------------------------------------------------------------
# telemetry: checkpoint events carry the save cost
# ---------------------------------------------------------------------------


def test_checkpoint_event_carries_save_cost(tmp_path):
    from mgwfbp_tpu.telemetry import events_of, read_event_set

    t = _mk(
        2, "rs_opt_ag", tmp_path,
        telemetry=True, telemetry_dir=str(tmp_path / "tel"),
    )
    t.fit(1)
    t.close()
    recs = read_event_set(os.path.join(tmp_path, "tel", "telemetry.jsonl"))
    ckpts = events_of(recs, "checkpoint")
    assert ckpts
    for row in ckpts:
        assert row["format"] == "sharded"
        assert row["duration_s"] >= 0.0
        assert row["bytes"] > 0  # this process's payload, not the world's


# ---------------------------------------------------------------------------
# manifest-addressed partial eval, bitwise vs the gather path
# ---------------------------------------------------------------------------


def test_manifest_eval_params_bitwise(tmp_path, monkeypatch):
    t = _mk(4, "rs_fwd_ag", tmp_path, ckpt_every_steps=2)
    try:
        t.fit(1)
        # wait out the async writer: the manifest path only engages once
        # the CURRENT iteration's commit is visible (a pending commit
        # must fall back to the gather, never read a torn directory)
        deadline = time.time() + 30
        while (
            time.time() < deadline
            and t.checkpointer.entry_format(int(t.iteration)) != "sharded"
        ):
            time.sleep(0.05)
        assert t.checkpointer.entry_format(int(t.iteration)) == "sharded"

        p_manifest = t._eval_params()
        assert t._eval_params_source == "manifest"
        monkeypatch.setattr(t, "_manifest_eval_params", lambda: None)
        p_gather = t._eval_params()
        assert t._eval_params_source == "gather"
        lm = jax.tree_util.tree_leaves(p_manifest)
        lg = jax.tree_util.tree_leaves(p_gather)
        assert len(lm) == len(lg) and lm
        for a, b in zip(lm, lg):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        t.close()
