"""bench.py without a chip: one bounded backend init, then a structured
"skipped" record AND a non-zero exit — a measurement path that finds no
accelerator neither hangs, nor retries for minutes, nor falls back to the
CPU, nor exits 0."""

import json

import pytest

import bench
from mgwfbp_tpu.utils import platform as plat


def test_init_deadline_is_chip_unavailable(monkeypatch):
    calls = {"n": 0}

    def fake_run_with_deadline(fn, timeout_s, what="operation"):
        calls["n"] += 1
        raise plat.DeadlineExceeded(f"{what} timed out")

    monkeypatch.setattr(plat, "run_with_deadline", fake_run_with_deadline)
    with pytest.raises(bench.ChipUnavailable, match="chip unavailable"):
        bench._require_chip()
    assert calls["n"] == 1  # one bounded attempt, no retry/backoff loop


def test_cpu_only_backend_is_chip_unavailable():
    # the test process is pinned to the CPU: that is "no chip", never a
    # CPU run filed under a device metric's name
    with pytest.raises(bench.ChipUnavailable, match="only the cpu platform"):
        bench._require_chip()


def test_main_emits_structured_skip_record_and_fails(monkeypatch, capsys):
    def raise_unavailable():
        raise bench.ChipUnavailable("backend init timed out")

    monkeypatch.setattr(bench, "run_bench", raise_unavailable)
    rc = bench.main()
    payload = json.loads(capsys.readouterr().out.strip())
    assert rc != 0  # no chip means nothing was measured: never exit 0
    assert payload["skipped"] == "chip unavailable"
    assert payload["value"] is None
    assert "error" not in payload
    assert "timed out" in payload["detail"]


def test_main_real_errors_stay_rc1(monkeypatch, capsys):
    def boom():
        raise RuntimeError("genuine breakage")

    monkeypatch.setattr(bench, "run_bench", boom)
    rc = bench.main()
    payload = json.loads(capsys.readouterr().out.strip())
    assert rc == 1
    assert "genuine breakage" in payload["error"]
    assert "skipped" not in payload
