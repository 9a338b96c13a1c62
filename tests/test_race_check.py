"""Host-concurrency race checker (ISSUE 16): THR001..THR005 mutation suite.

Mirrors the SPMD suite's contract: every rule is exercised both ways — a
minimal synthetic module seeded with the defect must fire EXACTLY the
intended rule, and its corrected twin must stay clean. A distilled
version of the async shard-writer WITHOUT its ownership handoff pins the
tentpole/customer coupling (the checker must catch the race the shipped
writer was designed around). The shipped tree itself must check clean
(the check.sh stage-2 pin), the `# graft: thread-safe -- reason` grammar
must round-trip through ANA001 (dead and reason-less pins are findings),
and the THR family must carry its own exit-code bit (32) end to end
through the CLI.
"""

from __future__ import annotations

import time

import pytest

from mgwfbp_tpu.analysis.race_check import (
    check_paths,
    check_sources,
    discover_contexts,
)
from mgwfbp_tpu.analysis.rules import (
    FAMILY_BITS,
    Finding,
    SuppressionTracker,
    exit_code,
)


def _ids(findings):
    return [f.rule_id for f in findings]


def _check(src: str, tracker=None):
    return check_sources({"mod.py": src}, tracker=tracker)


# --------------------------------------------------------------------------
# THR001: shared state written from concurrent contexts without a common
# lock
# --------------------------------------------------------------------------

THR001_SEED = (
    "import threading\n"
    "class Buf:\n"
    "    def __init__(self):\n"
    "        self._rows = []\n"
    "        self._t = threading.Thread(target=self._drain)\n"
    "        self._t.start()\n"
    "    def _drain(self):\n"
    "        while True:\n"
    "            self._rows.pop()\n"
    "    def push(self, x):\n"
    "        self._rows.append(x)\n"
)


def test_thr001_unlocked_shared_buffer():
    findings = _check(THR001_SEED)
    assert _ids(findings) == ["THR001"], [f.format() for f in findings]
    assert "Buf._rows" in findings[0].message


def test_thr001_clean_with_common_lock():
    findings = _check(
        "import threading\n"
        "class Buf:\n"
        "    def __init__(self):\n"
        "        self._rows = []\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._drain)\n"
        "        self._t.start()\n"
        "    def _drain(self):\n"
        "        with self._lock:\n"
        "            self._rows.pop()\n"
        "    def push(self, x):\n"
        "        with self._lock:\n"
        "            self._rows.append(x)\n"
    )
    assert findings == [], [f.format() for f in findings]


def test_thr001_clean_single_context():
    # writes from ONE context only (the main program) are not a race,
    # however many functions touch the attribute
    findings = _check(
        "class Buf:\n"
        "    def __init__(self):\n"
        "        self._rows = []\n"
        "    def push(self, x):\n"
        "        self._rows.append(x)\n"
        "    def drop(self):\n"
        "        self._rows.pop()\n"
    )
    assert findings == [], [f.format() for f in findings]


# --------------------------------------------------------------------------
# THR002: lock-order inversion across concurrent contexts
# --------------------------------------------------------------------------

THR002_SEED = (
    "import threading\n"
    "class S:\n"
    "    def __init__(self):\n"
    "        self._a_lock = threading.Lock()\n"
    "        self._b_lock = threading.Lock()\n"
    "        self._t = threading.Thread(target=self.worker)\n"
    "        self._t.start()\n"
    "    def worker(self):\n"
    "        with self._a_lock:\n"
    "            with self._b_lock:\n"
    "                self.x = 1\n"
    "    def refresh(self):\n"
    "        with self._b_lock:\n"
    "            with self._a_lock:\n"
    "                self.x = 2\n"
)


def test_thr002_abba_inversion():
    findings = _check(THR002_SEED)
    assert "THR002" in _ids(findings), [f.format() for f in findings]
    # the write itself is NOT a THR001: both sites hold both locks
    assert "THR001" not in _ids(findings)


def test_thr002_clean_with_consistent_order():
    findings = _check(
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._a_lock = threading.Lock()\n"
        "        self._b_lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self.worker)\n"
        "        self._t.start()\n"
        "    def worker(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n"
        "                self.x = 1\n"
        "    def refresh(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n"
        "                self.x = 2\n"
    )
    assert findings == [], [f.format() for f in findings]


# --------------------------------------------------------------------------
# THR003: blocking op while holding a lock the serving plane needs
# --------------------------------------------------------------------------

THR003_SEED = (
    "import time\n"
    "import threading\n"
    "from http.server import BaseHTTPRequestHandler\n"
    "class H(BaseHTTPRequestHandler):\n"
    "    def do_GET(self):\n"
    "        with self._lock:\n"
    "            self.payload = 1\n"
    "    def do_POST(self):\n"
    "        with self._lock:\n"
    "            time.sleep(5.0)\n"
)


def test_thr003_blocking_under_serving_lock():
    findings = _check(THR003_SEED)
    assert "THR003" in _ids(findings), [f.format() for f in findings]


def test_thr003_clean_when_blocking_outside_lock():
    findings = _check(
        "import time\n"
        "import threading\n"
        "from http.server import BaseHTTPRequestHandler\n"
        "class H(BaseHTTPRequestHandler):\n"
        "    def do_GET(self):\n"
        "        with self._lock:\n"
        "            self.payload = 1\n"
        "    def do_POST(self):\n"
        "        time.sleep(5.0)\n"
        "        with self._lock:\n"
        "            self.payload = 2\n"
    )
    assert "THR003" not in _ids(findings), [f.format() for f in findings]


# --------------------------------------------------------------------------
# THR004: signal handlers must stay async-signal-safe
# --------------------------------------------------------------------------

THR004_SEED = (
    "import signal\n"
    "import threading\n"
    "class T:\n"
    "    def __init__(self):\n"
    "        self._state_lock = threading.Lock()\n"
    "        signal.signal(signal.SIGTERM, self._on_sig)\n"
    "    def _on_sig(self, signum, frame):\n"
    "        with self._state_lock:\n"
    "            self.flag = True\n"
)


def test_thr004_lock_in_signal_handler():
    findings = _check(THR004_SEED)
    assert "THR004" in _ids(findings), [f.format() for f in findings]


def test_thr004_clean_flag_store_only():
    # the shipped trainer idiom: the handler stores one GIL-atomic flag
    # and the step loop consumes it at boundaries
    findings = _check(
        "import signal\n"
        "class T:\n"
        "    def __init__(self):\n"
        "        signal.signal(signal.SIGTERM, self._on_sig)\n"
        "    def _on_sig(self, signum, frame):\n"
        "        self.flag = True\n"
    )
    assert "THR004" not in _ids(findings), [f.format() for f in findings]


# --------------------------------------------------------------------------
# THR005: stream written concurrently with a close() it does not lock
# against
# --------------------------------------------------------------------------

THR005_SEED = (
    "import threading\n"
    "class W:\n"
    "    def __init__(self):\n"
    "        self._f = open('log.jsonl', 'a')\n"
    "        self._lock = threading.Lock()\n"
    "        self._t = threading.Thread(target=self._worker)\n"
    "        self._t.start()\n"
    "    def _worker(self):\n"
    "        self._f.write('x')\n"
    "    def close(self):\n"
    "        with self._lock:\n"
    "            self._f.close()\n"
)


def test_thr005_unlocked_write_vs_locked_close():
    findings = _check(THR005_SEED)
    assert "THR005" in _ids(findings), [f.format() for f in findings]


def test_thr005_clean_when_write_shares_the_lock():
    findings = _check(
        "import threading\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._f = open('log.jsonl', 'a')\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=self._worker)\n"
        "        self._t.start()\n"
        "    def _worker(self):\n"
        "        with self._lock:\n"
        "            self._f.write('x')\n"
        "    def close(self):\n"
        "        with self._lock:\n"
        "            self._f.close()\n"
    )
    assert "THR005" not in _ids(findings), [f.format() for f in findings]


# --------------------------------------------------------------------------
# the tentpole/customer coupling: the async shard writer's race, distilled
# --------------------------------------------------------------------------

def test_async_writer_without_handoff_is_caught():
    """The shipped writer (checkpoint._AsyncShardSave) moves its
    cross-thread state into a slot object the worker owns until the
    `done` Event publishes it. THIS version — the obvious first draft —
    publishes straight into checkpointer attributes from both threads;
    THR001 must catch it, or the gate the writer ships behind is
    worthless."""
    findings = _check(
        "import threading\n"
        "class AsyncSaver:\n"
        "    def __init__(self):\n"
        "        self._error = None\n"
        "        self._done = False\n"
        "    def submit(self, files):\n"
        "        self._error = None\n"
        "        self._done = False\n"
        "        t = threading.Thread(target=self._worker, args=(files,))\n"
        "        t.start()\n"
        "    def _worker(self, files):\n"
        "        try:\n"
        "            files.clear()\n"
        "        except OSError as e:\n"
        "            self._error = str(e)\n"
        "        self._done = True\n"
        "    def poll(self):\n"
        "        if self._done:\n"
        "            self._error = None\n"
    )
    thr1 = [f for f in findings if f.rule_id == "THR001"]
    assert thr1, [f.format() for f in findings]
    flagged = " ".join(f.message for f in thr1)
    assert "AsyncSaver._done" in flagged or "AsyncSaver._error" in flagged


def test_async_writer_with_slot_handoff_is_clean():
    # the shipped protocol: the worker writes ONLY into the slot it was
    # handed (construction-before-publication + Event as the edge)
    findings = _check(
        "import threading\n"
        "class Slot:\n"
        "    def __init__(self):\n"
        "        self.error = None\n"
        "        self.done = threading.Event()\n"
        "class AsyncSaver:\n"
        "    def __init__(self):\n"
        "        self._slot = None\n"
        "    def submit(self, files):\n"
        "        slot = Slot()\n"
        "        t = threading.Thread(target=self._worker,\n"
        "                             args=(slot, files))\n"
        "        self._slot = slot\n"
        "        t.start()\n"
        "    def _worker(self, slot, files):\n"
        "        try:\n"
        "            files.clear()\n"
        "        except OSError as e:\n"
        "            slot.error = str(e)\n"
        "        finally:\n"
        "            slot.done.set()\n"
        "    def poll(self):\n"
        "        slot = self._slot\n"
        "        if slot is None:\n"
        "            return None\n"
        "        if not slot.done.is_set():\n"
        "            return None\n"
        "        self._slot = None\n"
        "        return slot.error\n"
    )
    assert findings == [], [f.format() for f in findings]


def test_liveness_monitor_on_a_thread_is_caught():
    """The ISSUE-20 liveness monitor, as the obvious first draft: a
    background thread feeding the per-child tracker dicts while the
    policy loop reads/clears them — THR001 must catch it. The shipped
    monitor (runtime/supervisor._poll_liveness) avoids the race by
    construction: tracker state lives entirely in the single-threaded
    `_watch` poll, and this twin is the gate that keeps a future
    'move the scrapes to a thread' refactor honest."""
    findings = _check(
        "import threading\n"
        "class Monitor:\n"
        "    def __init__(self):\n"
        "        self._steps = {}\n"
        "        self._verdicts = {}\n"
        "        t = threading.Thread(target=self._scrape_loop)\n"
        "        t.start()\n"
        "    def _scrape_loop(self):\n"
        "        while True:\n"
        "            self._steps[0] = self._steps.get(0, 0) + 1\n"
        "            self._verdicts[0] = 'wedged'\n"
        "    def heal_policy(self):\n"
        "        v = self._verdicts.pop(0, None)\n"
        "        if v == 'wedged':\n"
        "            self._steps.clear()\n"
        "        return v\n"
    )
    thr1 = [f for f in findings if f.rule_id == "THR001"]
    assert thr1, [f.format() for f in findings]
    flagged = " ".join(f.message for f in thr1)
    assert "Monitor._steps" in flagged or "Monitor._verdicts" in flagged


def test_liveness_monitor_poll_confined_is_clean():
    # the shipped shape: scrapes and verdicts both live in the one
    # poll-loop context; the only thread is elsewhere (no shared state)
    findings = _check(
        "class Monitor:\n"
        "    def __init__(self):\n"
        "        self._steps = {}\n"
        "        self._verdicts = {}\n"
        "    def poll(self, scrape):\n"
        "        self._steps[0] = scrape\n"
        "        if scrape == self._steps.get(0):\n"
        "            self._verdicts[0] = 'wedged'\n"
        "    def heal_policy(self):\n"
        "        return self._verdicts.pop(0, None)\n"
    )
    assert findings == [], [f.format() for f in findings]


# --------------------------------------------------------------------------
# `# graft: thread-safe -- reason` grammar + ANA001 round-trip
# --------------------------------------------------------------------------

def test_thread_safe_pin_suppresses_and_is_consumed():
    tracker = SuppressionTracker()
    src = THR001_SEED.replace(
        "        self._rows.append(x)\n",
        "        # graft: thread-safe -- flushed only after join()\n"
        "        self._rows.append(x)\n",
    )
    findings = _check(src, tracker=tracker)
    assert findings == [], [f.format() for f in findings]
    # the pin was consulted: no dead-marker ANA001, and the suppressed
    # finding is retained for --json
    assert tracker.unused_findings() == [], [
        f.format() for f in tracker.unused_findings()
    ]
    assert any(
        f.rule_id == "THR001" for f in tracker.suppressed_findings
    )


def test_dead_thread_safe_pin_is_ana001():
    tracker = SuppressionTracker()
    findings = _check(
        "class C:\n"
        "    def f(self):\n"
        "        # graft: thread-safe -- nothing here races\n"
        "        return 1\n",
        tracker=tracker,
    )
    assert findings == [], [f.format() for f in findings]
    dead = tracker.unused_findings()
    assert _ids(dead) == ["ANA001"], [f.format() for f in dead]


def test_reasonless_thread_safe_pin_is_ana001():
    tracker = SuppressionTracker()
    src = THR001_SEED.replace(
        "        self._rows.append(x)\n",
        "        self._rows.append(x)  # graft: thread-safe\n",
    )
    _check(src, tracker=tracker)
    assert any(
        f.rule_id == "ANA001" for f in tracker.unused_findings()
    ), "a reason-less thread-safe pin must be rejected by ANA001"


# --------------------------------------------------------------------------
# shipped tree: clean, fast, and the contexts the PR relies on exist
# --------------------------------------------------------------------------

def test_shipped_tree_is_clean_and_fast():
    tracker = SuppressionTracker()
    t0 = time.perf_counter()
    findings = check_paths(tracker=tracker)
    dt = time.perf_counter() - t0
    assert findings == [], [f.format() for f in findings]
    assert dt < 30.0, f"THR pass took {dt:.1f}s (acceptance bound: 30s)"
    # a THR-only run cannot consume RUN/JIT markers — only the
    # thread-safe accounting must be clean here (the CLI gates full
    # ANA001 on all passes having run)
    dead = [
        f for f in tracker.unused_findings() if "thread-safe" in f.message
    ]
    assert dead == [], [f.format() for f in dead]
    # the shipped tree's documented pins are live (they hide real
    # findings the checker would otherwise raise)
    assert any(
        f.rule_id.startswith("THR") for f in tracker.suppressed_findings
    )


def test_shipped_contexts_include_the_async_writer():
    labels = {c[0] for c in discover_contexts()}
    # the first gated customer's writer thread is visible to the checker
    assert "thread:Checkpointer._shard_payload_worker" in labels
    # ... alongside the pre-existing concurrency surfaces
    assert any(lbl.startswith("handler:") for lbl in labels)
    assert any(lbl.startswith("executor:") for lbl in labels)
    assert any(lbl.startswith("observer:") for lbl in labels)
    assert any(lbl.startswith("signal:") for lbl in labels)


# --------------------------------------------------------------------------
# exit codes + CLI
# --------------------------------------------------------------------------

def test_thr_family_exit_bit():
    assert FAMILY_BITS["THR"] == 32
    assert exit_code([Finding("a.py", 1, "THR001", "m")]) == 32
    assert exit_code([
        Finding("a.py", 1, "THR002", "m"),
        Finding("a.py", 2, "RUN001", "m"),
    ]) == 36


@pytest.mark.parametrize("seed", [
    THR001_SEED, THR002_SEED, THR003_SEED, THR004_SEED, THR005_SEED,
])
def test_cli_exit_code_32_per_seeded_rule(tmp_path, seed, capsys):
    from mgwfbp_tpu.analysis.__main__ import main

    f = tmp_path / "seeded.py"
    f.write_text(seed)
    rc = main([
        str(f), "--skip-lint", "--skip-spmd", "--skip-jaxpr",
    ])
    captured = capsys.readouterr()
    assert rc == FAMILY_BITS["THR"] == 32, captured.out + captured.err


def test_cli_json_carries_thr_findings_with_suppression_state(
    tmp_path, capsys
):
    import json as _json

    from mgwfbp_tpu.analysis.__main__ import main

    live = tmp_path / "live.py"
    live.write_text(THR001_SEED)
    pinned = tmp_path / "pinned.py"
    pinned.write_text(THR001_SEED.replace("Buf", "PinnedBuf").replace(
        "        self._rows.append(x)\n",
        "        # graft: thread-safe -- flushed only after join()\n"
        "        self._rows.append(x)\n",
    ))
    rc = main([
        str(live), str(pinned), "--json",
        "--skip-lint", "--skip-spmd", "--skip-jaxpr",
    ])
    doc = _json.loads(capsys.readouterr().out)
    assert rc == doc["exit_code"] == 32
    assert doc["errors_by_family"].get("THR") == 1
    thr = [d for d in doc["findings"] if d["family"] == "THR"]
    assert {d["suppressed"] for d in thr} == {True, False}
    assert all(d["rule"] == "THR001" for d in thr)
