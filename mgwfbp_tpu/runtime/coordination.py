"""Cross-process agreement primitives for the multi-host runtime.

Synchronous data-parallel SGD means every host-side decision that changes
which jitted program runs next — drain on preemption, roll back after K
bad steps, commit an autotune winner — must be IDENTICAL on every
process, or the processes issue mismatched collectives and the group
deadlocks (the failure mode the PR-3 autotuner refused multi-host over).
These primitives make that identity explicit and cheap:

  agree_any / agree_all   boolean consensus over one flag per process
  broadcast_flag          process-`source`'s value, everywhere
  all_argmin              per-candidate times -> one agreed winner index
                          (each candidate priced at its SLOWEST process —
                          a sync group can't run faster than its straggler)
  barrier                 named rendezvous with a real timeout

Transport: one tiny jitted psum/pmax over a throwaway 1-axis mesh of all
global devices (the `jax.experimental.multihost_utils` building block,
re-implemented here because `process_allgather`'s single-device reshard
is unimplemented on the CPU backend this repo's tier-1 runs on). Each
process contributes its payload on its FIRST local device and the
reduction identity elsewhere, so the psum sums exactly once per process.
The collectives carry the `runtime_coord` name scope — declared in
`analysis/jaxpr_check.py` DEFAULT_ALLOWED_SCOPES, so a future step that
traces an agreement into a jitted program stays verifier-clean (SCH004).

Every primitive is a LOCKSTEP COLLECTIVE when `process_count() > 1`:
all processes must call the same primitives in the same order with
same-shaped payloads (the same invariant their jitted steps already
obey). Single-process calls short-circuit on the host — zero device
work, so these are safe to leave in single-host hot paths.

Payloads ride float32 on the device (jax x64 is off): exact for flags,
counts below 2**24, and wall-clock seconds — the only things routed
through here.

Every multi-process call is BOUNDED: the device-transport primitives run
under `MGWFBP_COORD_TIMEOUT_S` (default = the barrier timeout) and the
barrier under `MGWFBP_BARRIER_TIMEOUT_S`; a miss or transport error
raises `CoordinationTimeout` so a dead/wedged peer surfaces as a clean
restart-friendly exit instead of an indefinite hang.
"""

from __future__ import annotations

import collections
import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mgwfbp_tpu.utils.platform import env_float, run_with_deadline

# 1-axis mesh over every global device, used only by these primitives
COORD_AXIS = "coord"
# name scope stamped on the agreement collectives (jaxpr_check SCH004
# allowed scope — keep in sync with analysis/jaxpr_check.py)
COORD_SCOPE = "runtime_coord"

# default barrier timeout; a peer that never arrives means a dead or
# wedged process — fail so the supervisor can tear down and resubmit
BARRIER_TIMEOUT_ENV = "MGWFBP_BARRIER_TIMEOUT_S"
DEFAULT_BARRIER_TIMEOUT_S = 600.0

# real-deadline contract for the DEVICE-transport primitives (ISSUE 20):
# agree_any / agree_all / broadcast_flag / gather_* / agree_uniform /
# all_argmin block inside a gloo/ICI collective when a peer is dead or
# wedged — exactly the hang the barrier's timeout already refuses. The
# same deadline bounds them all; a miss raises CoordinationTimeout so
# the trainer can convert an opaque distributed hang into a clean
# rc-75-style exit the supervisor's healer understands.
COORD_TIMEOUT_ENV = "MGWFBP_COORD_TIMEOUT_S"


class CoordinationTimeout(RuntimeError):
    """A lockstep group operation did not complete within its real
    deadline (or its transport failed outright): a peer process is dead
    or wedged, so the collective can NEVER complete. The process is
    tainted (an abandoned worker thread may hold transport locks) — the
    caller must exit promptly and restart-friendly; train_cli converts
    this to rc 75 (drain-less: no checkpoint barrier can complete
    either) so the supervisor heals the group from the last committed
    step."""

    def __init__(self, op: str, timeout_s: float, detail: str = ""):
        super().__init__(
            f"coordination op {op!r} did not complete within "
            f"{timeout_s:.0f}s{f' ({detail})' if detail else ''}; a peer "
            "process is dead or wedged — exiting restart-friendly so the "
            "supervisor can heal the group"
        )
        self.op = op
        self.timeout_s = timeout_s


def _coord_timeout_s() -> float:
    return env_float(COORD_TIMEOUT_ENV, DEFAULT_BARRIER_TIMEOUT_S)


# ---------------------------------------------------------------------------
# group-operation registry
# ---------------------------------------------------------------------------

# name -> {"blocking": bool, "uniform_result": bool}. Populated by the
# @group_op decorator below; the SPMD lockstep checker
# (analysis/spmd_check.py) discovers its op list from these decorations —
# the checker and the transport cannot drift, because a new primitive is
# a new decoration, and the decoration IS the registration.
GROUP_OPS: dict[str, dict] = {}


def group_op(fn=None, *, blocking: bool = True, uniform_result: bool = True):
    """Mark a function as a LOCKSTEP GROUP OPERATION: when
    ``process_count() > 1`` every process must call it, in the same
    order, with same-shaped payloads, or the group deadlocks.

    ``blocking`` — the call cannot return until every process arrives
    (true for every primitive here: psum/pmax rendezvous on the device,
    barrier on the coordination service). ``uniform_result`` — the return
    value is bitwise-identical on every process, so host decisions
    branching on it keep the group in lockstep (the checker treats such
    results as group-uniform sanitizers).
    """
    def register(f):
        GROUP_OPS[f.__name__] = {
            "blocking": bool(blocking),
            "uniform_result": bool(uniform_result),
        }
        return f

    if fn is not None:
        return register(fn)
    return register


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_primary() -> bool:
    """True on the process that owns exactly-once side effects (sidecar
    index writes, autotune cache persistence, ...)."""
    return jax.process_index() == 0


# ---------------------------------------------------------------------------
# device transport
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _coord_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()), (COORD_AXIS,))


@functools.lru_cache(maxsize=None)
def _reduce_prog(kind: str):
    """Jitted (n_devices, k) -> replicated (k,) reduction program."""
    mesh = _coord_mesh()

    def body(x):
        with jax.named_scope(COORD_SCOPE):
            if kind == "sum":
                return lax.psum(jnp.sum(x, axis=0), COORD_AXIS)
            return lax.pmax(jnp.max(x, axis=0), COORD_AXIS)

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P(COORD_AXIS), out_specs=P()
        )
    )


def _device_reduce(
    vals: Sequence[float], kind: str, op: str = "device_reduce",
) -> np.ndarray:
    """Reduce a per-process float vector across ALL processes ("sum" or
    "max"); returns the identical reduced vector on every process.

    Each process contributes `vals` on its first local device and the
    reduction identity (0 / -inf) on the rest, so device multiplicity
    never double-counts a process. Works single-process too (the tests
    exercise the device path directly); the public primitives
    short-circuit before reaching here when there is nothing to agree.

    Multi-process, the blocking collective runs under the same real
    deadline the barrier already has (MGWFBP_COORD_TIMEOUT_S, default
    the barrier default): a dead or wedged peer means the rendezvous can
    never complete, and a deadline miss — or the transport erroring
    outright (a peer's death can also surface as a connection reset from
    the collective instead of a hang) — raises CoordinationTimeout
    naming `op` so the caller exits restart-friendly instead of hanging
    until the supervisor's hard teardown."""
    row = np.asarray(vals, np.float32).reshape(-1)
    fill = 0.0 if kind == "sum" else -np.inf
    local = np.full((jax.local_device_count(), row.size), fill, np.float32)
    local[0] = row
    sharding = NamedSharding(_coord_mesh(), P(COORD_AXIS))
    garr = jax.make_array_from_process_local_data(sharding, local)
    if jax.process_count() == 1:
        # nothing to rendezvous with: no deadline thread per call on the
        # single-host hot path (and the direct-call unit tests)
        return np.asarray(_reduce_prog(kind)(garr))
    timeout_s = _coord_timeout_s()
    try:
        return run_with_deadline(
            lambda: np.asarray(_reduce_prog(kind)(garr)),
            timeout_s, what=f"coordination op {op!r}",
        )
    except Exception as e:  # noqa: BLE001 — deadline miss and transport
        # failure are ONE structured surface: both mean a peer is gone
        raise CoordinationTimeout(op, timeout_s, detail=str(e)) from e


# ---------------------------------------------------------------------------
# agreement primitives
# ---------------------------------------------------------------------------

@group_op
def agree_any(flag: bool) -> bool:
    """True everywhere iff ANY process passed True (preempt drain: one
    signaled host drains the whole group)."""
    if process_count() == 1:
        return bool(flag)
    return bool(
        _device_reduce([1.0 if flag else 0.0], "sum", op="agree_any")[0]
        > 0.0
    )


@group_op
def agree_all(flag: bool) -> bool:
    """True everywhere iff EVERY process passed True (rollback: only when
    every host can restore; autotune cache hit: only when every host has
    the entry)."""
    if process_count() == 1:
        return bool(flag)
    total = _device_reduce(
        [1.0 if flag else 0.0], "sum", op="agree_all",
    )[0]
    return bool(total >= float(process_count()))


@group_op
def broadcast_flag(value: float, source: int = 0) -> float:
    """Process `source`'s scalar, identical everywhere (the tb-profile
    broadcast pattern, for host decisions: restore-target steps,
    agreed winner indices, ...)."""
    if process_count() == 1:
        return float(value)
    contrib = float(value) if process_index() == source else 0.0
    return float(_device_reduce([contrib], "sum", op="broadcast_flag")[0])


@group_op
def gather_values(value: float) -> list[float]:
    """Every process's scalar, in process order, identical everywhere
    (the live straggler probe: each process contributes its window step
    time; everyone sees the full per-process vector and agrees on who is
    slow). One-hot rows summed — same transport, same lockstep contract
    as every other primitive here."""
    if process_count() == 1:
        return [float(value)]
    row = [0.0] * process_count()
    row[process_index()] = float(value)
    return [
        float(t) for t in _device_reduce(row, "sum", op="gather_values")
    ]


@group_op
def gather_vectors(values: Sequence[float]) -> list[list[float]]:
    """Every process's float VECTOR, in process order, identical
    everywhere — `gather_values` for per-group payloads (the on-demand
    deep-profile window gathers each process's trace-attributed per-group
    device seconds). Every process must pass the SAME length (the
    lockstep-shape contract all primitives here carry; merge-group count
    is group-uniform by construction). One-hot block rows summed through
    the same transport."""
    row = [float(v) for v in values]
    n = process_count()
    if n == 1:
        return [row]
    k = len(row)
    if k == 0:
        return [[] for _ in range(n)]
    flat = [0.0] * (n * k)
    start = process_index() * k
    flat[start:start + k] = row
    reduced = _device_reduce(flat, "sum", op="gather_vectors")
    return [
        [float(t) for t in reduced[i * k:(i + 1) * k]] for i in range(n)
    ]


@group_op
def agree_uniform(value: float) -> bool:
    """True iff every process passed the SAME scalar (max == min across
    the group). The cheap divergence guard for values that MUST be
    group-uniform before a collective side effect — e.g. the step key a
    shard-native checkpoint commit is about to write: processes saving
    different steps means the lockstep invariant already broke, and
    writing a torn manifest would bake the divergence into disk."""
    if process_count() == 1:
        return True
    v = float(value)
    mx = float(_device_reduce([v], "max", op="agree_uniform")[0])
    mn = -float(_device_reduce([-v], "max", op="agree_uniform")[0])
    return mx == mn


@group_op
def all_argmin(values: Sequence[Optional[float]]) -> tuple[int, list[float]]:
    """Agreed argmin over per-candidate timings.

    `values[i]` is this process's measured time for candidate i (None =
    not measured here). Each candidate is reduced to its MAX across
    processes — a synchronous group runs at its straggler's pace, and a
    candidate unmeasured anywhere prices as +inf — then every process
    computes the same argmin over the same reduced vector.

    Returns (winner_index, reduced_times); reduced_times[winner] is
    +inf iff NO candidate was measured on every process.
    """
    vals = [
        float("inf") if v is None or not np.isfinite(v) else float(v)
        for v in values
    ]
    if not vals:
        raise ValueError("all_argmin: empty candidate list")
    if process_count() > 1:
        vals = [
            float(t) for t in _device_reduce(vals, "max", op="all_argmin")
        ]
    return int(np.argmin(vals)), vals


# per-name use counters: barrier keys must be unique per rendezvous, and
# every process mints the same sequence as long as its call order matches
# (the same lockstep invariant every primitive here already requires)
_barrier_seq: collections.Counter = collections.Counter()


@group_op(uniform_result=False)
def barrier(name: str, timeout_s: Optional[float] = None) -> None:
    """Named rendezvous across all processes, with a real timeout.

    Uses the jax.distributed coordination-service barrier (timeout
    enforced server-side). A timeout raises CoordinationTimeout (a
    RuntimeError) — the caller should treat the process group as broken
    and exit so the supervisor can heal it.
    """
    if process_count() == 1:
        return
    if timeout_s is None:
        raw = (os.environ.get(BARRIER_TIMEOUT_ENV) or "").strip()
        if raw:
            try:
                timeout_s = float(raw)
            except ValueError:
                # a garbage value must fail with the variable named, not
                # a bare float() traceback mid-drain
                raise ValueError(
                    f"{BARRIER_TIMEOUT_ENV}={raw!r} is not a number"
                ) from None
        else:
            timeout_s = DEFAULT_BARRIER_TIMEOUT_S
    key = f"mgwfbp:{name}:{_barrier_seq[name]}"
    _barrier_seq[name] += 1
    # Private reach, justified: jax 0.9 has no public accessor for the
    # coordination-service client, and `wait_at_barrier` is the one
    # barrier whose timeout the SERVICE enforces (jax's own
    # multihost_utils reads the same attribute). process_count() > 1
    # means jax.distributed.initialize() ran, so the client exists.
    from jax._src import distributed

    try:
        distributed.global_state.client.wait_at_barrier(
            key, int(timeout_s * 1000)
        )
    except Exception as e:  # noqa: BLE001 — uniform failure surface
        raise CoordinationTimeout(
            f"barrier:{name}", timeout_s, detail=str(e)
        ) from e
