"""The harness itself, off the chip: it refuses to run without one; at the
tiny size it calls a sound run correct, and a lower-precision wire, a step
that returns its state unchanged and a loss altered where it is produced not
correct. These drive `run_once` past its look for a chip (`rehearsal=True`),
on the CPU mesh the test suite provides."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT, load

CELL = ["--workload", "resnet50-plain-1chip", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *CELL],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(proc):
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout


def test_without_a_chip_exits_nonzero_and_prints_no_metrics(tmp_path):
    proc = _run(ROOT, {"HOME": str(tmp_path)})
    _no_result(proc)
    assert "needs 1 TPU chip" in proc.stderr


def test_without_the_program_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), {"PYTHONPATH": ""})
    _no_result(proc)
    assert "mgwfbp_tpu" in proc.stderr


@pytest.fixture(scope="module")
def run_module():
    return load("run.py")


def _tiny(run_module, tmp_path, seed, control=None):
    """The result line of a rehearsal run with the numbers it compared."""
    spec = run_module.load_cell(None)
    control = spec["config"]["controls"][control] if control else {}
    result, compared = run_module.run_once(
        spec, seed, 0.5, False, str(tmp_path / "out"), control,
        run_module.CompileCounter(), rehearsal=True)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    return {**result, **compared}


def _limit(run_module, name):
    return run_module.load_cell(None)["config"]["limits"][name]


def test_sound_run_is_correct_and_the_bf16_wire_is_not(run_module, tmp_path):
    sound = _tiny(run_module, tmp_path, seed=11)
    print(json.dumps(sound["checks"]))
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] >= 3
    assert set(sound["metrics"]) == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    control = _tiny(run_module, tmp_path, seed=11, control="wire-bf16")
    assert control["correct"] is False
    limit = _limit(run_module, "reduce_rel_l2")
    assert control["checks"]["reduce_rel_l2"] > 3 * limit["max"]
    assert sound["checks"]["reduce_rel_l2"] < limit["max"] / 3


def test_reference_in_float8_in_the_programs_place_is_not_correct(
        run_module, tmp_path):
    result = _tiny(run_module, tmp_path, seed=14, control="ref-fp8")
    assert result["sound"]["correct"] is True
    assert result["correct"] is False
    limit = _limit(run_module, "first_grad_norm_rel")["max"]
    assert result["checks"]["first_grad_norm_rel"] > 3 * limit
    assert result["sound"]["checks"]["first_grad_norm_rel"] < limit / 3


def test_step_that_returns_its_state_unchanged_is_not_correct(
        run_module, tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu.train import trainer as trainer_module

    real_make = trainer_module.make_train_step

    def make_broken(*args, **kwargs):
        step = real_make(*args, **kwargs)

        def broken(state, batch):
            kept = jax.tree_util.tree_map(jnp.copy, state)  # state is donated
            _, metrics = step(state, batch)
            return kept, metrics

        return broken

    monkeypatch.setattr(trainer_module, "make_train_step", make_broken)
    result = _tiny(run_module, tmp_path, seed=12)
    assert result["checks"]["update_rel"] == 0.0
    assert result["correct"] is False


def test_loss_altered_where_it_is_produced_is_not_correct(
        run_module, tmp_path, monkeypatch):
    """The program's loss off by a thousandth (what a dropped share of the
    batch or a wrong mask moves it by) is past the first loss's limit."""
    from mgwfbp_tpu.train import step as step_module

    real = step_module.cross_entropy
    monkeypatch.setattr(
        step_module, "cross_entropy",
        lambda logits, labels: real(logits, labels) * 1.001)
    result = _tiny(run_module, tmp_path, seed=13)
    assert result["checks"]["first_loss_rel"] > 5e-4
    assert result["correct"] is False
