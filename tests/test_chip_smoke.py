"""chip_smoke.py and the compile cache, as far as a CPU can say.

The script's contract without a chip: non-zero exit, no `"ok": true`, in
both modes, and the same in a directory that holds nothing else of the
repo. Its phases are rehearsed here at a tiny size by calling them
directly (the steering lives in the test, the script has no CPU option):
wrong flags, control flow and the 4-device comparison fail here, not on
chip time. The compile cache is placeable from outside and otherwise at a
fixed path inside the checkout.
"""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from mgwfbp_tpu.utils import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(cmd, cwd, **env):
    return subprocess.run(
        cmd, cwd=cwd, capture_output=True, text=True, timeout=240,
        env={**os.environ, **env},
    )


@pytest.mark.parametrize("mode", [[], ["--multichip"]], ids=["one", "four"])
def test_without_tpu_exits_nonzero_and_prints_no_ok(mode):
    r = _run([sys.executable, SCRIPT, *mode], REPO, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
    assert "[device] platform cpu" in r.stdout  # says what it found


def test_alone_in_a_directory_fails_without_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_phases_rehearsed_tiny_on_the_cpu_mesh(monkeypatch, tmp_path):
    """--multichip's whole comparison (production `--policy auto` Trainer vs
    `--policy none`, same seed: trajectories, final params, mesh / batch /
    param placement on distinct devices) and the kernel phase, at lenet
    size on the virtual devices (the attention core's plain blocks there)."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "1024")
    monkeypatch.setenv("MGWFBP_SYNTH_VAL_N", "64")
    chip_smoke.multichip_phase(dnn="lenet", epochs=2)
    chip_smoke.kernel_phase(b=1, t=128, h=2, d=32, iters=2)


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append(k))
    assert plat.enable_compile_cache() == str(tmp_path)
    assert seen == []  # jax reads the variable itself


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = {}
    monkeypatch.setattr(jax.config, "update", seen.__setitem__)
    first, second = plat.enable_compile_cache(), plat.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert seen == {"jax_compilation_cache_dir": first}
    # another process, another pid, another time: the same directory
    r = _run(
        [sys.executable, "-c",
         "from mgwfbp_tpu.utils.platform import enable_compile_cache as e;"
         "import jax; print(e()); print(jax.config.jax_compilation_cache_dir)"],
        REPO, JAX_COMPILATION_CACHE_DIR="", PYTHONPATH=REPO,
    )
    assert r.stdout.split() == [first, first], r.stderr
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_entries_land_in_the_placed_directory(tmp_path):
    r = _run(
        [sys.executable, "-c",
         "from mgwfbp_tpu.utils.platform import enable_compile_cache as e;"
         "import jax, jax.numpy as jnp; e();"
         "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64))).block_until_ready()"],
        str(tmp_path), PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "placed"),
        JAX_ENABLE_COMPILATION_CACHE="true",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
    )
    assert r.returncode == 0, r.stderr
    assert os.listdir(tmp_path / "placed")  # written where it was placed
    assert sorted(os.listdir(tmp_path)) == ["placed"]  # and nowhere else here


def test_supervisor_parent_never_initialises_a_backend():
    """One process per chip: the supervisor only launches children, so
    importing it (which pulls in `import jax`) must leave every backend
    untouched — a parent that held the chip would starve its children."""
    r = _run(
        [sys.executable, "-c",
         "import mgwfbp_tpu.runtime.supervise, mgwfbp_tpu.runtime.supervisor;"
         "from jax._src import xla_bridge as xb;"
         "print(xb.backends_are_initialized())"],
        REPO, PYTHONPATH=REPO,
    )
    assert r.stdout.split() == ["False"], r.stderr
