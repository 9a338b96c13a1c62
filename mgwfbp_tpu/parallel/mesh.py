"""Device mesh and process bootstrap.

Replaces the reference's Horovod/MPI bootstrap (`hvd.init/rank/size`,
dist_trainer.py:133; mpirun + hostfiles, dist_mpi.sh:8-16) with
`jax.distributed` + a named `jax.sharding.Mesh`. One process drives all local
chips (subsuming the reference's `nn.DataParallel` intra-node path,
dl_trainer.py:193-198).

Axes:
  dcn   — slice axis of a multi-slice pod (data-parallel OUTER level; only
          present when MeshSpec.dcn > 1). Collectives crossing it ride the
          data-center network, which `costmodel.TwoLevelAlphaBeta` prices
          and `comm_op='hier'` lowers for explicitly.
  data  — data parallelism (the reference's entire parallelism model);
          within a slice, rides ICI.
  seq   — sequence/context parallelism axis; consumed by
          `parallel.ringattn` (ring attention over ppermute). The reference
          has no sequence parallelism (SURVEY.md §5 "Long-context") — this
          axis is the TPU-native long-context extension.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mgwfbp_tpu.telemetry.phases import backend_span

DATA_AXIS = "data"
SEQ_AXIS = "seq"
DCN_AXIS = "dcn"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    data: int = -1  # -1: all remaining devices
    seq: int = 1
    dcn: int = 1  # slices of a multi-slice pod (outer data-parallel level)


def _enable_cpu_collectives() -> None:
    """Multi-process collectives on the CPU backend need the gloo TCP
    implementation; the default ('none') makes EVERY cross-process program
    fail with "Multiprocess computations aren't implemented on the CPU
    backend" — the rot that kept the multi-host path dead code until
    ISSUE 6. Must run before the CPU client is created. Applied
    unconditionally on multi-process launches: the knob only affects CPU
    client construction (a TPU run's secondary CPU backend is unharmed),
    and gating on platform env vars would silently re-kill a CPU-only
    launch that never exported JAX_PLATFORMS."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _env_int(env, name: str) -> Optional[int]:
    """Integer env var; empty/whitespace counts as unset (launcher
    scripts export from possibly-unset shell variables), garbage fails
    with the variable named instead of a bare int() traceback."""
    v = (env.get(name) or "").strip()
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name}={v!r} is not an integer") from None


def resolve_launch_env(
    env=None,
) -> tuple[Optional[str], Optional[int], Optional[int]]:
    """(coordinator, num_processes, process_id) from the launcher ENV
    chain: MGWFBP_COORDINATOR / MGWFBP_NUM_PROCESSES / MGWFBP_PROCESS_ID
    (the supervisor's launch contract) first, then the standard launcher
    envs (SLURM, OpenMPI) — consulted only when the MGWFBP contract is
    silent, and only when they signal a real multi-task allocation (a
    1-task world is not a multi-host signal). This is the ONE owner of
    the env half of the resolution chain; `train_cli.resolve_multihost`
    layers explicit flags and completeness validation on top, and
    `init_distributed` falls back to it for non-CLI entry points."""
    env = os.environ if env is None else env
    coordinator = (env.get("MGWFBP_COORDINATOR") or "").strip() or None
    num = _env_int(env, "MGWFBP_NUM_PROCESSES")
    pid = _env_int(env, "MGWFBP_PROCESS_ID")
    if num is None and pid is None:
        for size_var, rank_var in (
            ("SLURM_NTASKS", "SLURM_PROCID"),
            ("OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK"),
        ):
            n = _env_int(env, size_var)
            if n is not None and n > 1:
                num, pid = n, _env_int(env, rank_var)
                break
    return coordinator, num, pid


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bootstrap (reference: `hvd.init()` / mpirun). No-op when
    single-process or when jax.distributed is already initialized.

    Arguments left None fall back to `resolve_launch_env` (the
    supervisor's MGWFBP_* contract, then SLURM/OpenMPI), so non-CLI entry
    points resolve the same launch train_cli would. Passing
    coordinator_address/process_id signals an explicit multi-host launch;
    silently skipping initialization there would leave each host training
    unsynchronized, so a missing worker count is an error instead.
    """
    env_coord, env_num, env_pid = resolve_launch_env()
    if coordinator_address is None:
        coordinator_address = env_coord
    if process_id is None:
        process_id = env_pid
    explicit = coordinator_address is not None or process_id is not None
    if num_processes is None:
        num_processes = env_num
        if num_processes is None:
            if explicit:
                raise ValueError(
                    "init_distributed: coordinator_address/process_id "
                    "given but num_processes unknown; pass num_processes "
                    "or set MGWFBP_NUM_PROCESSES"
                )
            return
    if num_processes <= 1 and not explicit:
        return
    _enable_cpu_collectives()
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        if "already initialized" not in str(e).lower():
            raise


def make_mesh(
    spec: MeshSpec = MeshSpec(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data, seq) — or, multi-slice, (dcn, data, seq) — mesh over
    the available devices.

    The device order follows jax.devices(), which keeps ICI neighbours
    adjacent on TPU so the data-axis ring rides ICI links; on a multi-slice
    pod jax enumerates slice-by-slice, so the LEADING dcn dimension puts
    each slice's chips contiguously on the inner axes.
    """
    if devices is None:
        # where this is the process's first touch of the backend, its start
        # is a span of the set-up record (telemetry/phases.py)
        with backend_span():
            devices = jax.devices()
    devs = list(devices)
    n = len(devs)
    seq = max(spec.seq, 1)
    dcn = max(spec.dcn, 1)
    if n % (seq * dcn) != 0:
        raise ValueError(
            f"{n} devices not divisible by seq={seq} x dcn={dcn}"
        )
    data = spec.data if spec.data > 0 else n // (seq * dcn)
    if data * seq * dcn != n:
        raise ValueError(f"mesh {dcn}x{data}x{seq} != {n} devices")
    if dcn > 1:
        arr = np.asarray(devs).reshape(dcn, data, seq)
        return Mesh(arr, (DCN_AXIS, DATA_AXIS, SEQ_AXIS))
    arr = np.asarray(devs).reshape(data, seq)
    return Mesh(arr, (DATA_AXIS, SEQ_AXIS))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding for input arrays (reference DistributedSampler
    equivalent: each data-axis member sees 1/N of the global batch)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Parameters are replicated across the mesh — the reference's
    `broadcast_parameters` initial sync (distributed_optimizer.py:474-503)
    becomes a sharding constraint."""
    return NamedSharding(mesh, P())


def gather_replicated(tree, mesh: Mesh, cache: dict):
    """Sharded pytree -> replicated (hence fully-addressable) global
    arrays via ONE cached jitted identity with replicated out_shardings —
    the collective twin of np.asarray, shared by every consumer that
    needs a replicated view of device-sharded state (the
    ShardedOptimStep interchange seam, the trainer's carry snapshot).
    `cache` is caller-owned (keyed by tree structure) so each consumer's
    programs survive across calls without retracing."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return tree
    key = jax.tree_util.tree_structure(tree)
    prog = cache.get(key)
    if prog is None:
        prog = jax.jit(
            lambda t: t, out_shardings=NamedSharding(mesh, P())
        )
        cache[key] = prog
    return prog(tree)


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()
