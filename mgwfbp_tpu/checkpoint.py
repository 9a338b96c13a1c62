"""Checkpoint / resume via orbax.

Parity target (SURVEY.md §5): reference `save_checkpoint` /
`load_model_from_file` (dl_trainer.py:946-947, 307-312 — torch.save of
{'state','epoch','iter'} and counter restore), rank-0 `--pretrain` load +
parameter re-broadcast (dist_trainer.py:32-39,66). Differences by design:
  * orbax writes sharded/replicated jax arrays directly — the "broadcast
    after load" step is a sharding constraint, not a collective we code;
  * the epoch-boundary save the reference constructs but never executes
    (dl_trainer.py:769-777 builds the filename, no write) actually saves here.

Resilience layer (ISSUE 5): checkpoints are **step-indexed** — the orbax
step key is the global optimizer iteration, so a preempted run resumes
from the exact step, not the last epoch boundary. Each snapshot carries
the position needed to rebuild the data stream deterministically
(`epoch`, `epoch_step` — the loader is a pure function of
(seed, epoch, batch index), so position IS the iterator state) plus the
BPTT carry for stateful models; the train-state RNG rides in the state
itself. A sidecar ``steps_index.json`` (written atomically via
``os.replace``) maps steps to epoch metadata so epoch-oriented consumers
(`evaluate --all-epochs`) keep working without restoring every payload;
directories written by the old epoch-keyed format load transparently
(legacy mode: the orbax step IS the epoch).

Checkpoint directory naming encodes the experiment config like the
reference's log/checkpoint dirs (dl_trainer.py:771-777).

Shard-native format (ISSUE 13): the orbax payload above stores the
REPLICATED interchange form, which forces every sharded path
(rs_opt_ag / rs_fwd_ag) to gather its 1/world state to the host before
a save — exactly the idiom that cannot scale to a pod. The sharded
format writes, per step, one `sharded/<step>/p<i>/` subtree PER
PROCESS holding only that process's shard rows as plain ``.npy``
files, plus one ``manifest.json`` (process 0) recording world size,
mesh axes, and the per-leaf shard layout (which merge group and offset
each parameter-tree leaf packs into). Restore re-slices per leaf
straight from the source files (numpy memmaps), so an N-way checkpoint
restores onto M processes — or a different merge schedule — without
ever materializing a world-sized buffer or even one fully-replicated
leaf for a sharded target. Replicated sections (params on the in-step
lowerings, batch stats, the optax tree on unsharded runs) are written
once, by process 0. The ``steps_index.json`` sidecar + commit barrier
below keep the exactly-once semantics for both formats; the legacy
orbax payloads keep loading transparently, and ``--ckpt-format
replicated`` keeps writing them for interchange with old runs. The
format assumes the group shares the checkpoint filesystem (the same
assumption the orbax payload made).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from mgwfbp_tpu.runtime import coordination as coord
from mgwfbp_tpu.train.step import TrainState

INDEX_FILE = "steps_index.json"
INDEX_VERSION = 1

# shard-native format (ISSUE 13)
SHARD_SUBDIR = "sharded"
MANIFEST_FILE = "manifest.json"
SHARD_FORMAT_VERSION = 1


class CheckpointRestoreError(RuntimeError):
    """A checkpoint exists but cannot be restored into the current model/
    optimizer structure. Carries the offending leaves (shape/dtype/
    structure diffs) instead of a raw orbax traceback, and names the
    likely cause: config drift between the saving and restoring run."""

    def __init__(self, message: str, mismatches: Optional[list[str]] = None):
        super().__init__(message)
        self.mismatches = list(mismatches or [])


@dataclasses.dataclass
class Snapshot:
    state: TrainState
    epoch: int
    iteration: int
    # optimizer steps already completed INSIDE `epoch` when this snapshot
    # was taken; 0 on an epoch boundary. With the deterministic loader,
    # (epoch, epoch_step) fully names the data-iterator position.
    epoch_step: int = 0
    mid_epoch: bool = False
    carry: Any = None  # BPTT hidden state (carry models), else None
    # True when `state` is already in LIVE form on the caller's mesh
    # (sharded leaves as global arrays, carry as this process's local
    # block) — the shard-native restore path; the caller must skip the
    # replicate + re-scatter interchange steps
    native: bool = False
    # extra restore facts riding along on the shard-native path (the
    # manifest's meta section: saved world size, steps_per_epoch, the
    # LR-schedule anchor) — None on the replicated/orbax path
    manifest_meta: Optional[dict] = None


# ---------------------------------------------------------------------------
# shard-native payload helpers (ISSUE 13)
# ---------------------------------------------------------------------------


def _np_dtype(name: str) -> np.dtype:
    """Resolve a manifest dtype string, including ml_dtypes extended
    types (bfloat16) that plain np.dtype does not know."""
    return np.dtype(jnp.dtype(str(name)))


def _viewed(arr: np.ndarray, dtype: np.dtype) -> np.ndarray:
    """Reinterpret raw bytes as `dtype`. np.load round-trips extended
    dtypes (bfloat16) as void records of the same itemsize; the manifest
    dtype is authoritative, so view the bytes back."""
    arr = np.asarray(arr)
    if arr.dtype == dtype:
        return arr
    if arr.dtype.itemsize != dtype.itemsize:
        raise ValueError(
            f"cannot view {arr.dtype} as {dtype}: itemsize "
            f"{arr.dtype.itemsize} != {dtype.itemsize}"
        )
    return arr.view(dtype)


def _leaf_doc(path: str, arr: Any) -> dict:
    return {
        "path": str(path),
        "shape": [int(s) for s in getattr(arr, "shape", ())],
        "dtype": jnp.dtype(arr.dtype).name
        if hasattr(arr, "dtype") else "float32",
    }


def _doc_matches(doc: dict, arr: Any) -> bool:
    return (
        tuple(doc.get("shape", ())) == tuple(getattr(arr, "shape", ()))
        and _np_dtype(doc.get("dtype", "float32"))
        == _np_dtype(jnp.dtype(arr.dtype).name)
    )


def _fsync_dir_files(directory: str) -> None:
    """fsync every regular file under `directory` plus the directory
    entry itself (best-effort on filesystems without dir fsync)."""
    for name in os.listdir(directory):
        path = os.path.join(directory, name)
        if not os.path.isfile(path):
            continue
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    try:
        dfd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass


def peek_steps(directory: str) -> list[int]:
    """Committed-looking steps under a checkpoint directory WITHOUT
    opening an orbax manager — the cheap probe the cross-world resume
    scan runs over every sibling tag directory."""
    out: set[int] = set()
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    for name in names:
        if name.isdigit():  # orbax step dirs
            out.add(int(name))
    shard_root = os.path.join(directory, SHARD_SUBDIR)
    try:
        snames = os.listdir(shard_root)
    except OSError:
        snames = []
    for name in snames:
        if name.isdigit() and os.path.exists(
            os.path.join(shard_root, name, MANIFEST_FILE)
        ):
            out.add(int(name))
    return sorted(out)


class ShardSource:
    """Reader over one committed shard-native step directory.

    All file access is through numpy memmaps sliced per element range, so
    a consumer re-slicing an N-way layout onto M shard rows touches only
    the bytes those rows need — never a world-sized buffer, never a full
    replicated leaf unless `read_leaf` (the replicated-target path) is
    called explicitly.
    """

    def __init__(self, step_dir: str, manifest: dict):
        self.step_dir = step_dir
        self.manifest = manifest
        self._mmaps: dict[str, np.ndarray] = {}
        # row -> owning process (lowest-index owner wins, mirroring the
        # save-side dedup rule)
        self._row_owner: dict[int, tuple[int, int]] = {}
        for p, doc in sorted(
            (int(k), v) for k, v in (manifest.get("processes") or {}).items()
        ):
            for pos, r in enumerate(doc.get("rows", ())):
                self._row_owner.setdefault(int(r), (p, pos))

    # -- raw file access ---------------------------------------------------
    def _file(self, proc: int, name: str) -> str:
        return os.path.join(self.step_dir, f"p{proc:05d}", name + ".npy")

    def _mmap(self, proc: int, name: str, shape, dtype: np.dtype):
        key = f"{proc}/{name}"
        mm = self._mmaps.get(key)
        if mm is None:
            path = self._file(proc, name)
            try:
                mm = np.load(path, mmap_mode="r")
            except (OSError, ValueError) as e:
                raise CheckpointRestoreError(
                    f"shard-native checkpoint {self.step_dir!r} is missing "
                    f"or corrupt: process {proc} file {name}.npy "
                    f"({e})"
                ) from e
            self._mmaps[key] = mm
        want = tuple(int(s) for s in shape)
        if tuple(mm.shape) != want:
            raise CheckpointRestoreError(
                f"shard-native checkpoint {self.step_dir!r}: process "
                f"{proc} file {name}.npy has shape {tuple(mm.shape)}, "
                f"manifest expects {want} {np.dtype(dtype).name} — the "
                "payload is truncated or was written by a different run"
            )
        return mm

    # -- manifest accessors ------------------------------------------------
    @property
    def world(self) -> int:
        return int(self.manifest["world"])

    @property
    def meta(self) -> dict:
        return dict(self.manifest.get("meta") or {})

    @property
    def leaves(self) -> list[dict]:
        return list(self.manifest.get("leaves") or [])

    def section_kind(self, section: str) -> str:
        return str((self.manifest.get(section) or {}).get("kind", "none"))

    def section_docs(self, section: str) -> list[dict]:
        """Per-leaf docs of a section. `params` (sharded or replicated)
        and sharded `opt` slots mirror the parameter tree; replicated
        `opt`/`batch_stats` carry their own flattened leaf lists."""
        if section == "params":
            return self.leaves
        doc = self.manifest.get(section) or {}
        if section == "opt" and doc.get("kind") == "sharded":
            return self.leaves
        return list(doc.get("leaves") or [])

    def opt_slots(self) -> int:
        return int((self.manifest.get("opt") or {}).get("slots", 0))

    # -- sharded-section readers -------------------------------------------
    def leaf_slice_reader(
        self, section: str, slot: Optional[int] = None
    ) -> Callable[[int, int, int], np.ndarray]:
        """Returns read(leaf_index, start, stop) -> flat array of that
        element range of tree leaf `leaf_index`, regardless of whether the
        source section is stored sharded (group-row files) or replicated
        (per-leaf files). For the replicated `opt` section a `slot`
        addresses the optax tree through the saver-recorded
        slot_leaf_index map (slot s of params-tree leaf j -> flat optax
        leaf), so a sharded target can re-slice a replicated-opt source."""
        kind = self.section_kind(section)
        prefix = section if slot is None else f"{section}.s{slot}"
        if kind == "replicated":
            docs = self.section_docs(section)
            remap = None
            if section == "opt" and slot is not None:
                idx_map = (self.manifest.get("opt") or {}).get(
                    "slot_leaf_index"
                )
                if idx_map is None:
                    raise CheckpointRestoreError(
                        f"checkpoint {self.step_dir!r}: replicated "
                        "optimizer section has no slot_leaf_index map — "
                        "cannot re-slice it onto a sharded optimizer"
                    )
                remap = [int(x) for x in idx_map[int(slot)]]

            def read_rep(j: int, a: int, b: int) -> np.ndarray:
                k = remap[j] if remap is not None else j
                doc = docs[k]
                dt = _np_dtype(doc["dtype"])
                mm = self._mmap(0, f"{section}.l{k}", doc["shape"], dt)
                flat = np.asarray(mm).reshape(-1)
                return _viewed(flat[a:b], dt)

            return read_rep
        if kind != "sharded":
            raise CheckpointRestoreError(
                f"checkpoint {self.step_dir!r} has no {section!r} section "
                f"(kind={kind!r}) — saved under a different configuration"
            )
        layout = self.manifest["layout"]
        shard_sizes = [int(s) for s in layout["shard_sizes"]]
        dtypes = [_np_dtype(d) for d in layout["group_dtypes"]]
        slots = [tuple(int(x) for x in s) for s in layout["leaf_slots"]]

        def read(j: int, a: int, b: int) -> np.ndarray:
            gi, off = slots[j]
            s = shard_sizes[gi]
            dt = dtypes[gi]
            out = np.empty((b - a,), dt)
            lo = off + a
            hi = off + b
            pos = lo
            while pos < hi:
                r = pos // s
                owner = self._row_owner.get(r)
                if owner is None:
                    raise CheckpointRestoreError(
                        f"checkpoint {self.step_dir!r}: shard row {r} of "
                        f"group {gi} belongs to no process in the manifest"
                    )
                proc, local = owner
                nrows = len(self.manifest["processes"][str(proc)]["rows"])
                mm = self._mmap(proc, f"{prefix}.g{gi}", (nrows, s), dt)
                c0 = pos - r * s
                c1 = min(hi - r * s, s)
                seg = _viewed(mm[local, c0:c1], dt)
                out[pos - lo : pos - lo + (c1 - c0)] = seg
                pos = r * s + c1
            return out

        return read

    def read_leaf(self, section: str, j: int, slot: Optional[int] = None):
        """One FULL leaf (replicated-target path — materializes the
        leaf, by design). With `slot`, `j` indexes the parameter tree
        (slot subtrees mirror it); otherwise the section's own docs."""
        docs = self.leaves if slot is not None else self.section_docs(section)
        doc = docs[j]
        n = int(np.prod(doc["shape"])) if doc["shape"] else 1
        read = self.leaf_slice_reader(section, slot=slot)
        return read(j, 0, n).reshape([int(s) for s in doc["shape"]])

    def read_rows(
        self,
        section: str,
        slot: Optional[int],
        dst_leaf_slots: list[tuple[int, int]],
        dst_shard_sizes: list[int],
        dst_group_dtypes: list[np.dtype],
        rows: list[int],
    ) -> list[np.ndarray]:
        """Re-slice the source section onto a DESTINATION padded-bucket
        layout: returns, per destination group, the (len(rows), shard)
        buffer holding exactly `rows` of the destination's (world, shard)
        global buffer. Padding regions are zero (bitwise-identical to what
        a fresh scatter packs). Only the source bytes those rows cover are
        read — no world-sized intermediate, no full leaf."""
        read = self.leaf_slice_reader(section, slot=slot)
        leaves = self.leaves
        sizes = [
            int(np.prod(doc["shape"])) if doc["shape"] else 1
            for doc in leaves
        ]
        # destination group -> [(leaf j, offset)] members
        members: dict[int, list[tuple[int, int]]] = {}
        for j, (gi, off) in enumerate(dst_leaf_slots):
            members.setdefault(int(gi), []).append((j, int(off)))
        out = []
        row_pos = {r: k for k, r in enumerate(rows)}
        for gi, s in enumerate(dst_shard_sizes):
            buf = np.zeros((len(rows), int(s)), dst_group_dtypes[gi])
            for j, off in members.get(gi, ()):
                n = sizes[j]
                for r in rows:
                    lo = max(off, r * s)
                    hi = min(off + n, (r + 1) * s)
                    if lo >= hi:
                        continue
                    seg = read(j, lo - off, hi - off)
                    buf[row_pos[r], lo - r * s : hi - r * s] = seg
            out.append(buf)
        return out

    # -- carry -------------------------------------------------------------
    def carry_doc(self) -> Optional[dict]:
        return self.manifest.get("carry") or None

    def _carry_runs(self) -> list[tuple[int, int, int, int]]:
        """(start, stop, process, offset-in-file) per saved run: each
        process's file concatenates its runs in manifest order, so the
        file offset of a run is the length of that process's earlier
        runs. Runs may interleave across processes (multi-slice data
        shardings do); the reader never assumes contiguity."""
        out = []
        for p, runs in (self.carry_doc().get("runs") or {}).items():
            off = 0
            for a, b in runs:
                out.append((int(a), int(b), int(p), off))
                off += int(b) - int(a)
        return sorted(out)

    def read_carry_range(self, li: int, start: int, stop: int) -> np.ndarray:
        """Rows [start, stop) of carry leaf `li` along dim 0, assembled
        from whichever processes' local blocks cover them."""
        doc = self.carry_doc()
        leaf = doc["leaves"][li]
        dt = _np_dtype(leaf["dtype"])
        gshape = [int(s) for s in leaf["shape"]]
        runs = self._carry_runs()
        file_rows = {}
        for a, b, p, _ in runs:
            file_rows[p] = file_rows.get(p, 0) + (b - a)
        pieces = []
        pos = start
        while pos < stop:
            hit = None
            for a, b, p, off in runs:
                if a <= pos < b:
                    hit = (a, b, p, off)
                    break
            if hit is None:
                raise CheckpointRestoreError(
                    f"checkpoint {self.step_dir!r}: carry rows "
                    f"[{pos}, {stop}) of leaf {li} are covered by no "
                    "process in the manifest"
                )
            a, b, p, off = hit
            mm = self._mmap(
                p, f"carry.l{li}", [file_rows[p]] + gshape[1:], dt
            )
            hi = min(b, stop)
            lo_f = off + (pos - a)
            hi_f = off + (hi - a)
            pieces.append(_viewed(mm[lo_f:hi_f], dt))
            pos = hi
        return np.concatenate(pieces) if len(pieces) > 1 else np.array(
            pieces[0]
        )

    # -- validation (satellite: fail fast, named) ---------------------------
    def validate(self) -> None:
        """Probe every file the manifest promises; a missing/truncated/
        mis-shaped shard fails HERE with the process, section, and
        expected-vs-found layout — never a raw numpy traceback deep in a
        restore."""
        problems: list[str] = []
        m = self.manifest
        layout = m.get("layout") or {}
        shard_sizes = [int(s) for s in layout.get("shard_sizes", ())]
        dtypes = [str(d) for d in layout.get("group_dtypes", ())]
        sharded_sections: list[tuple[str, Optional[int]]] = []
        if self.section_kind("params") == "sharded":
            sharded_sections.append(("params", None))
        if self.section_kind("opt") == "sharded":
            for s in range(self.opt_slots()):
                sharded_sections.append(("opt", s))
        for p_str, doc in sorted((m.get("processes") or {}).items()):
            p = int(p_str)
            rows = list(doc.get("rows", ()))
            for section, slot in sharded_sections:
                prefix = section if slot is None else f"{section}.s{slot}"
                for gi, s in enumerate(shard_sizes):
                    name = f"{prefix}.g{gi}"
                    want = (len(rows), s)
                    problems.extend(
                        self._check_file(p, name, want, dtypes[gi])
                    )
            carry = m.get("carry") or None
            if carry and p_str in (carry.get("runs") or {}):
                nrows = sum(
                    int(b) - int(a) for a, b in carry["runs"][p_str]
                )
                for li, leaf in enumerate(carry["leaves"]):
                    want = tuple(
                        [nrows] + [int(x) for x in leaf["shape"][1:]]
                    )
                    problems.extend(self._check_file(
                        p, f"carry.l{li}", want, leaf["dtype"],
                    ))
        for section in ("params", "opt", "batch_stats"):
            kind = self.section_kind(section)
            if kind != "replicated":
                continue
            docs = (
                self.leaves if section == "params"
                else (self.manifest.get(section) or {}).get("leaves") or []
            )
            for j, doc in enumerate(docs):
                problems.extend(self._check_file(
                    0, f"{section}.l{j}", tuple(doc["shape"]), doc["dtype"],
                    leaf=doc.get("path"),
                ))
        if problems:
            raise CheckpointRestoreError(
                f"shard-native checkpoint step {m.get('step')} in "
                f"{self.step_dir!r} failed validation; offending "
                "shard(s):\n  " + "\n  ".join(problems[:20]),
                mismatches=problems,
            )

    def _check_file(
        self, proc: int, name: str, want_shape, want_dtype,
        leaf: Optional[str] = None,
    ) -> list[str]:
        where = f"process {proc}, file {name}.npy"
        if leaf:
            where += f" (leaf {leaf})"
        path = self._file(proc, name)
        try:
            mm = np.load(path, mmap_mode="r")
        except FileNotFoundError:
            return [f"{where}: missing (expected "
                    f"{tuple(want_shape)} {want_dtype})"]
        except (OSError, ValueError) as e:
            return [f"{where}: unreadable ({e}); expected "
                    f"{tuple(want_shape)} {want_dtype}"]
        if tuple(mm.shape) != tuple(want_shape):
            return [f"{where}: found shape {tuple(mm.shape)}, expected "
                    f"{tuple(want_shape)} {want_dtype}"]
        if mm.dtype.itemsize != _np_dtype(want_dtype).itemsize:
            return [f"{where}: found dtype {mm.dtype}, expected "
                    f"{want_dtype}"]
        return []


class _AsyncShardSave:
    """One in-flight asynchronous shard-native save (single slot).

    Ownership protocol (what makes this race-free, and what the THR
    checker's THR001 is calibrated against): the submitting (step-loop)
    thread fills every field, hands the slot to the writer thread, and
    touches nothing but `done` until `done.is_set()` — the writer thread
    owns `error` exclusively until then, and `done.set()` is the
    publication edge (threading.Event carries the memory ordering). All
    group operations — step agreement, dedup vote, payload barrier,
    manifest commit — happen on the submitting thread (submit_sharded /
    poll_async); the writer thread performs ONLY local filesystem I/O,
    so the SPMD lockstep contract (collectives issued from one thread in
    one program order) is untouched.
    """

    def __init__(self, step: int, manifest: dict, entry: dict,
                 nbytes: int):
        self.step = step
        self.manifest = manifest
        self.entry = entry
        self.nbytes = nbytes
        self.t0 = time.perf_counter()
        self.final: Optional[str] = None  # payload dir, set by the writer
        self.error: Optional[str] = None
        self.done = threading.Event()
        self.thread: Optional[threading.Thread] = None


class Checkpointer:
    """Step-indexed checkpoint manager over one run directory."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        self._dir = os.path.abspath(directory)
        self._max_to_keep = max_to_keep
        os.makedirs(self._dir, exist_ok=True)
        # GC is ours, not orbax's: retention must be CLASS-aware (see
        # _gc) — orbax's flat max_to_keep would let a burst of
        # --ckpt-every-steps saves evict the per-epoch history that
        # `evaluate --all-epochs` / model averaging read
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(create=True),
            # register the handler up front: a FRESH manager must be able
            # to read item_metadata of existing steps (the proactive
            # shape/dtype drift check) before any save taught it the type
            item_handlers=ocp.StandardCheckpointHandler(),
        )
        self._index = self._load_index()
        # single-slot async shard-native save (ISSUE 16): at most one
        # in-flight background payload write; a new save drains it first
        self._async: Optional[_AsyncShardSave] = None

    # -- sidecar index ----------------------------------------------------
    def _index_path(self) -> str:
        return os.path.join(self._dir, INDEX_FILE)

    def _load_index(self) -> dict:
        try:
            with open(self._index_path()) as f:
                idx = json.load(f)
        except (OSError, ValueError):
            return {}
        if idx.get("version") != INDEX_VERSION:
            return {}
        return dict(idx.get("steps", {}))

    def _write_index(self) -> None:
        # drop entries whose payload was garbage-collected, then
        # write-temp + rename so a mid-write kill never corrupts the index
        live = {str(s) for s in self.all_steps()}
        self._index = {k: v for k, v in self._index.items() if k in live}
        if not coord.is_primary():  # graft: noqa[RUN004] -- the save paths commit-barrier after every sidecar write; the restore-path heal is an opportunistic p0 repair peers never read mid-restore
            # multi-host: exactly ONE writer for the sidecar — every
            # process keeps the same in-memory index (the save/restore
            # calls are collective), but two processes racing the
            # tmp+rename on a shared FS could commit a torn view; the
            # commit barrier in save() orders everyone behind process 0
            return
        tmp = self._index_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": INDEX_VERSION, "steps": self._index}, f)
        os.replace(tmp, self._index_path())

    # -- shard-native payload (ISSUE 13) ----------------------------------
    def _shard_root(self) -> str:
        return os.path.join(self._dir, SHARD_SUBDIR)

    def _shard_step_dir(self, step: int) -> str:
        return os.path.join(self._shard_root(), f"{int(step):08d}")

    def _sharded_steps(self) -> list[int]:
        """Committed (manifest present) shard-native steps."""
        out = []
        try:
            names = os.listdir(self._shard_root())
        except OSError:
            return []
        for name in names:
            if not name.isdigit():
                continue
            if os.path.exists(os.path.join(
                self._shard_root(), name, MANIFEST_FILE
            )):
                out.append(int(name))
        return sorted(out)

    def all_steps(self) -> list[int]:
        """Every committed step, both formats."""
        return sorted(set(self._mgr.all_steps()) | set(self._sharded_steps()))

    def entry_format(self, step: int) -> Optional[str]:
        """'sharded' | 'orbax' | None for an uncommitted step."""
        if os.path.exists(os.path.join(
            self._shard_step_dir(step), MANIFEST_FILE
        )):
            return "sharded"
        if step in self._mgr.all_steps():
            return "orbax"
        return None

    def open_sharded(self, step: int) -> ShardSource:
        """Validated reader over a committed shard-native step."""
        step_dir = self._shard_step_dir(step)
        path = os.path.join(step_dir, MANIFEST_FILE)
        try:
            with open(path) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as e:
            raise CheckpointRestoreError(
                f"shard-native checkpoint step {step} in {self._dir!r} "
                f"has no readable manifest ({e}) — the save never "
                "committed or the directory is torn"
            ) from e
        if manifest.get("format_version") != SHARD_FORMAT_VERSION:
            raise CheckpointRestoreError(
                f"shard-native checkpoint step {step} in {self._dir!r} "
                f"has format_version {manifest.get('format_version')!r}; "
                f"this build reads version {SHARD_FORMAT_VERSION}"
            )
        src = ShardSource(step_dir, manifest)
        src.validate()
        return src

    def save_sharded(
        self,
        manifest: dict,
        files: dict[str, np.ndarray],
        wait: bool = False,
    ) -> dict:
        """Shard-native save: write THIS process's `files` under its own
        subtree, then commit via the manifest + sidecar (process 0) behind
        the same barriers `save` uses. `manifest` is the trainer-built
        document (world/mesh/layout/leaves/processes/meta — see the module
        docstring); `files` maps file stems to this process's local
        arrays (replicated sections included on process 0 only).

        Saving onto an already-committed step only promotes the index
        entry, exactly like the orbax path (an epoch boundary landing on
        a fresh --ckpt-every-steps snapshot). Returns
        {"duration_s", "bytes"} for the telemetry `checkpoint` event.
        """
        # single writer slot: an in-flight async save commits before a
        # new snapshot of the same state family starts (collective —
        # every process drains here before its step agreement below)
        self.drain_async(durable=wait)
        t0 = time.perf_counter()
        step, entry, nbytes, already = self._sharded_head(manifest, files)
        # graft: group-uniform -- 'already' is the agree_all dedup vote from _sharded_head: every process holds the same value
        if already:
            self._promote_sharded(step, manifest, entry)
            return {
                "duration_s": time.perf_counter() - t0, "bytes": 0,
            }
        self._write_shard_payload(step, files, wait=wait)
        self._commit_sharded(step, manifest, entry, wait=wait)
        return {"duration_s": time.perf_counter() - t0, "bytes": nbytes}

    def _sharded_head(
        self, manifest: dict, files: dict[str, np.ndarray]
    ) -> tuple[int, dict, int, bool]:
        """Group-agreed preamble of every shard-native save: the step-key
        uniformity check, the sidecar entry, the payload size, and the
        collective dedup decision. Runs on the submitting thread for the
        async path too — the writer thread never issues a collective."""
        step = int(manifest["step"])
        if coord.process_count() > 1 and not coord.agree_uniform(
            float(step)
        ):
            raise RuntimeError(
                f"shard-native save: processes disagree on the step key "
                f"(this process: {step}) — the group diverged; refusing "
                "to commit a torn checkpoint"
            )
        meta = manifest.get("meta") or {}
        entry = {
            "format": "sharded",
            "epoch": int(meta.get("epoch", 0)),
            "epoch_step": int(meta.get("epoch_step", 0)),
            "mid_epoch": bool(meta.get("mid_epoch", False)),
            "has_carry": bool(manifest.get("carry")),
        }
        nbytes = int(sum(np.asarray(a).nbytes for a in files.values()))
        already = step in self.all_steps()
        if coord.process_count() > 1:
            # the dedup decision reads host-local filesystem state (the
            # sidecar + shard dirs); a host with a torn local view taking
            # the promote-only early path would skip the payload barrier
            # its peers still enter (RUN003). Promote only when EVERY
            # process sees the step committed; otherwise all re-save —
            # the payload write is idempotent (tmp + os.replace)
            already = coord.agree_all(already)
        return step, entry, nbytes, already

    def _promote_sharded(
        self, step: int, manifest: dict, entry: dict
    ) -> None:
        """Index-entry promotion for an already-committed step (an epoch
        boundary landing on a fresh --ckpt-every-steps snapshot)."""
        meta = manifest.get("meta") or {}
        prev = self._index.get(str(step), {})
        if prev:
            # same dedup/promotion contract as the orbax path: the
            # payload at this step is immutable, only the entry's
            # epoch/boundary class may move (and never backwards)
            entry = dict(prev)
            entry["epoch"] = int(meta.get("epoch", entry.get("epoch", 0)))
            if not meta.get("mid_epoch", False):
                entry["mid_epoch"] = False
        self._index[str(step)] = entry
        self._gc()
        self._write_index()
        self._commit_barrier(step)

    def _write_shard_payload(
        self, step: int, files: dict[str, np.ndarray], wait: bool
    ) -> str:
        """THIS process's payload subtree: tmp dir + np.save + os.replace.
        Purely local filesystem work — no group ops, no Checkpointer
        state writes — which is exactly what licenses running it on the
        async writer thread. Returns the committed subtree path."""
        step_dir = self._shard_step_dir(step)
        pid = coord.process_index()
        os.makedirs(step_dir, exist_ok=True)
        tmp = os.path.join(step_dir, f".tmp.p{pid:05d}.{os.getpid()}")
        os.makedirs(tmp, exist_ok=True)
        for name, arr in files.items():
            np.save(os.path.join(tmp, name + ".npy"), np.asarray(arr))
        final = os.path.join(step_dir, f"p{pid:05d}")
        if os.path.isdir(final):  # a torn previous attempt never committed
            shutil.rmtree(final)
        os.replace(tmp, final)
        if wait:
            # the drain path's durability request: np.save leaves the
            # bytes in the page cache; a preempting machine may go away
            # right after the rc-75 exit, so flush this process's files
            # (and the dir entry) before the commit barriers release
            _fsync_dir_files(final)
        return final

    def _commit_sharded(
        self, step: int, manifest: dict, entry: dict, wait: bool
    ) -> None:
        """Commit a written payload: payload barrier, p0 manifest +
        sidecar, group success vote, commit barrier. Collective — always
        runs on the submitting thread, never the async writer."""
        # every process's subtree must be durable before the manifest
        # (the commit record) appears
        if coord.process_count() > 1:
            coord.barrier(f"ckpt_shard_payload_{step}")
        # the window between the payload barrier and the commit barrier
        # must stay BALANCED: if p0's manifest/sidecar write raised while
        # its peers marched on to the commit barrier, they would wait out
        # the full barrier timeout on a process that already unwound (the
        # latent multi-host hang the SPMD checker's RUN003 formalizes).
        # A local failure therefore becomes a GROUP decision: everyone
        # agrees on commit success and everyone raises together.
        step_dir = self._shard_step_dir(step)
        commit_err: Optional[str] = None
        try:
            if coord.is_primary():
                mpath = os.path.join(step_dir, MANIFEST_FILE)
                mtmp = mpath + ".tmp"
                with open(mtmp, "w") as f:
                    json.dump(manifest, f)
                    if wait:
                        f.flush()
                        os.fsync(f.fileno())
                os.replace(mtmp, mpath)
            self._index[str(step)] = entry
            self._gc()
            self._write_index()
            if wait and coord.is_primary():
                # the COMMIT RECORD must be at least as durable as the
                # payload it commits: flush the manifest's directory entry
                # and the sidecar, or a power cut after the rc-75 exit can
                # keep the payload while losing the fact it committed
                _fsync_dir_files(step_dir)
                try:
                    fd = os.open(self._index_path(), os.O_RDONLY)
                    try:
                        os.fsync(fd)
                    finally:
                        os.close(fd)
                except OSError:
                    pass
        except (OSError, ValueError, TypeError) as e:
            commit_err = f"{type(e).__name__}: {e}"
        ok = commit_err is None
        if coord.process_count() > 1:
            ok = coord.agree_all(ok)
        if not ok:
            raise RuntimeError(
                f"shard-native commit of step {step} failed "
                f"({commit_err or 'on a peer process'}); no process "
                "recorded the step as committed — restore falls back to "
                "the previous checkpoint"
            )
        self._commit_barrier(step)

    # -- async shard-native save (ISSUE 16) -------------------------------
    def submit_sharded(
        self, manifest: dict, files: dict[str, np.ndarray]
    ) -> Optional[dict]:
        """Start a shard-native save WITHOUT blocking the step loop on
        the payload write. The group-agreed preamble (step uniformity +
        dedup vote) still runs here, synchronously — it is collective —
        but the np.save of this process's subtree moves to a background
        thread; the commit (also collective) happens later, on the
        calling thread, via poll_async()/drain_async().

        Ownership contract: the caller hands `files` over — the arrays
        must not be mutated after submission (the trainer's payload
        builder materializes fresh host copies per call, so the step
        loop updating device state cannot touch them).

        Returns None when the save is now in flight, or the sync-path
        stats dict when the step was already committed (dedup promotes
        the index entry immediately — there is no payload to write).
        """
        self.drain_async()  # single slot: retire any previous save first
        t0 = time.perf_counter()
        step, entry, nbytes, already = self._sharded_head(manifest, files)
        # graft: group-uniform -- 'already' is the agree_all dedup vote from _sharded_head: every process holds the same value
        if already:
            self._promote_sharded(step, manifest, entry)
            return {
                "duration_s": time.perf_counter() - t0, "bytes": 0,
            }
        slot = _AsyncShardSave(step, manifest, entry, nbytes)
        slot.thread = threading.Thread(
            target=self._shard_payload_worker, args=(slot, files),
            name=f"ckpt-shard-writer-{step}", daemon=True,
        )
        self._async = slot
        slot.thread.start()
        return None

    def _shard_payload_worker(
        self, slot: _AsyncShardSave, files: dict[str, np.ndarray]
    ) -> None:
        """Async writer thread body: local payload I/O only (see the
        _AsyncShardSave ownership protocol). Group ops are off-limits
        here — the commit waits for poll_async on the loop thread."""
        try:
            slot.final = self._write_shard_payload(
                slot.step, files, wait=False
            )
        except Exception as e:  # noqa: BLE001 — the error crosses the
            # thread boundary through the slot; poll_async re-raises it
            # on the loop thread as a group-agreed commit failure
            slot.error = f"{type(e).__name__}: {e}"
        finally:
            slot.done.set()

    def poll_async(
        self, block: bool = False, durable: bool = False
    ) -> Optional[dict]:
        """Retire the in-flight async save if (on multi-host: the whole
        group's) payload write has finished; otherwise return None.

        COLLECTIVE on multi-host — every process must call it at the
        same point in its program (the trainer polls at the same
        agree-interval cadence that gates preemption agreement), because
        the completion check is a group vote: committing when only THIS
        process's payload landed would publish a manifest over peers'
        unwritten subtrees. With block=True, waits for the local writer
        first (the drain paths). durable=True upgrades the commit to the
        fsync'd rc-75 contract, flushing the payload post-hoc.

        Returns the telemetry fields for the `checkpoint` event
        ({"step", "duration_s", "bytes", "async", "meta"}) once the save
        commits; raises if any process's payload write failed (all
        processes raise together — the agree_all vote below).
        """
        slot = self._async
        if slot is None:
            return None
        if block:
            slot.done.wait()
        done = slot.done.is_set()
        if coord.process_count() > 1:
            done = coord.agree_all(done)
        if not done:
            return None
        self._async = None
        if slot.thread is not None:
            slot.thread.join()
        ok = slot.error is None
        if coord.process_count() > 1:
            ok = coord.agree_all(ok)
        if not ok:
            raise RuntimeError(
                f"async shard payload write for step {slot.step} failed "
                f"({slot.error or 'on a peer process'}); no process "
                "committed the step — restore falls back to the previous "
                "checkpoint"
            )
        if durable and slot.final is not None:
            # the payload was written lazily (page cache); the rc-75
            # drain wants it durable before the commit record appears
            _fsync_dir_files(slot.final)
        self._commit_sharded(
            slot.step, slot.manifest, slot.entry, wait=durable
        )
        return {
            "step": slot.step,
            "duration_s": time.perf_counter() - slot.t0,
            "bytes": slot.nbytes,
            "async": True,
            "meta": dict(slot.manifest.get("meta") or {}),
        }

    def drain_async(self, durable: bool = False) -> Optional[dict]:
        """Block until any in-flight async save has committed (collective
        on multi-host, like poll_async). No-op when the slot is empty."""
        return self.poll_async(block=True, durable=durable)

    def abandon_async(self) -> Optional[int]:
        """Drop the in-flight async save WITHOUT committing (the rollback
        path: the snapshot comes from the suspect regime, and its step
        key may be re-reached after the replay). Purely local — no
        collectives, so it is safe at any group state as long as every
        process takes the same decision (rollback is broadcast-agreed).
        The manifest never appears, so restore ignores the payload and a
        later save of the same step overwrites it. Returns the abandoned
        step, or None when the slot was empty."""
        slot = self._async
        if slot is None:
            return None
        self._async = None
        if slot.thread is not None:
            # wait out the local writer: a replayed save can re-reach
            # this step key and must not race the old worker's tmp dir
            slot.thread.join()
        return slot.step

    def pending_async_step(self) -> Optional[int]:
        """Step key of the in-flight async save, or None."""
        slot = self._async
        return None if slot is None else slot.step

    # -- save -------------------------------------------------------------
    def save(self, snap: Snapshot, wait: bool = False) -> None:
        """Atomic step-indexed save (orbax commits via tmp-dir + rename).

        The orbax step key is the GLOBAL iteration. Saving a step that
        already exists (an epoch boundary landing on a just-written
        ``--ckpt-every-steps`` checkpoint) only updates the index metadata
        — the state payload is identical by construction.

        Multi-host: `save` is a COLLECTIVE — every process calls it with
        the same snapshot (orbax coordinates the payload so the tmp-dir +
        atomic-rename commit happens exactly once, on the primary); the
        sidecar index is written by process 0 only (`_write_index`), and
        a commit barrier at the end keeps any process from returning —
        and, on the preemption-drain path, EXITING — before the commit is
        durable, so a preempt mid-save can never leave torn state."""
        step = int(snap.iteration)
        entry = {
            "epoch": int(snap.epoch),
            "epoch_step": int(snap.epoch_step),
            "mid_epoch": bool(snap.mid_epoch),
            "has_carry": snap.carry is not None,
        }
        already = step in self.all_steps()
        if coord.process_count() > 1:
            # same contract as save_sharded: the dedup reads host-local
            # filesystem state, and a split decision is a split save
            # protocol (the promote path and the payload path issue
            # different collective sequences) — agree before branching
            already = coord.agree_all(already)
        if already:
            prev = self._index.get(str(step), {})
            if prev:
                # the stored payload is immutable (identical state), so
                # the existing entry keeps describing it — has_carry and
                # epoch_step MUST stay (a boundary re-save over a
                # mid-epoch save does not strip the payload's carry); an
                # epoch-boundary re-save only PROMOTES the entry (never
                # demote a boundary back to mid-epoch)
                entry = dict(prev)
                entry["epoch"] = int(snap.epoch)
                if not snap.mid_epoch:
                    entry["mid_epoch"] = False
            self._index[str(step)] = entry
            self._gc()  # a promotion changes class budgets too
            self._write_index()
            if wait:
                # the payload at this step may still be an in-flight async
                # save; an explicit durability request (preemption drain)
                # must not be dropped just because the bytes are deduped
                self._mgr.wait_until_finished()
            self._commit_barrier(step)
            return
        payload = {
            "state": snap.state,
            "meta": {
                "epoch": int(snap.epoch),
                "iteration": int(snap.iteration),
                "epoch_step": int(snap.epoch_step),
                "mid_epoch": int(snap.mid_epoch),
            },
        }
        if snap.carry is not None:
            payload["carry"] = snap.carry
        self._mgr.save(step, args=ocp.args.StandardSave(payload))
        self._index[str(step)] = entry
        self._gc()
        self._write_index()
        if wait:
            self._mgr.wait_until_finished()
        self._commit_barrier(step)

    def _commit_barrier(self, step: int) -> None:
        """Multi-host rendezvous at the end of every save: no process may
        proceed until process 0's sidecar commit (and, for wait=True, the
        orbax payload commit) is on disk. No-op single-process."""
        if coord.process_count() > 1:
            coord.barrier(f"ckpt_commit_{step}")

    def _gc(self) -> None:
        """Class-aware retention: keep the newest `max_to_keep`
        epoch-BOUNDARY checkpoints AND, separately, the newest
        `max_to_keep` mid-epoch STEP checkpoints, so frequent
        --ckpt-every-steps saves never evict the per-epoch history."""
        if not self._max_to_keep or self._max_to_keep <= 0:
            return
        bounds: list[int] = []
        mids: list[int] = []
        for step in self.all_steps():
            e = self._index.get(str(step))
            if e is not None and e.get("mid_epoch", False):
                mids.append(step)
            else:
                bounds.append(step)  # boundary, or legacy epoch-keyed
        keep = set(bounds[-self._max_to_keep:])
        keep |= set(mids[-self._max_to_keep:])
        sharded = set(self._sharded_steps())
        for step in bounds + mids:
            if step in keep:
                continue
            if step in sharded:
                # shard-native payloads live on the shared checkpoint FS;
                # one deleter (the sidecar owner) keeps peers from racing
                # the rmtree
                if coord.is_primary():
                    shutil.rmtree(
                        self._shard_step_dir(step), ignore_errors=True
                    )
            else:
                self._mgr.delete(step)

    # -- listing ----------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return max(steps) if steps else None

    def _epoch_boundaries(self) -> dict[int, int]:
        """{epoch: step} for every epoch-boundary snapshot. Orbax steps
        absent from the index are legacy epoch-keyed saves (step == epoch)."""
        out: dict[int, int] = {}
        sharded = set(self._sharded_steps())
        for step in self.all_steps():
            entry = self._index.get(str(step))
            if entry is None and step in sharded:
                # sidecar lost mid-drain: the manifest's own meta is the
                # payload's bookkeeping — heal from it, never misread a
                # shard-native step as a legacy epoch-keyed one
                entry = self._heal_sharded_entry(step)
            if entry is None:  # legacy format
                out[int(step)] = int(step)
            elif not entry.get("mid_epoch", False):
                out[int(entry["epoch"])] = int(step)
        return out

    def _heal_sharded_entry(self, step: int) -> dict:
        """Index entry rebuilt from a committed shard-native manifest
        (the sidecar write was killed between the payload commit and
        os.replace). Repairs the in-memory index; the next save persists
        it."""
        try:
            with open(os.path.join(
                self._shard_step_dir(step), MANIFEST_FILE
            )) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        meta = doc.get("meta") or {}
        entry = {
            "format": "sharded",
            "epoch": int(meta.get("epoch", 0)),
            "epoch_step": int(meta.get("epoch_step", 0)),
            "mid_epoch": bool(meta.get("mid_epoch", False)),
            "has_carry": bool(doc.get("carry")),
        }
        self._index[str(step)] = entry
        return entry

    def latest_epoch(self) -> Optional[int]:
        bounds = self._epoch_boundaries()
        return max(bounds) if bounds else None

    def all_epochs(self) -> list[int]:
        return sorted(self._epoch_boundaries())

    # -- restore ----------------------------------------------------------
    def restore(
        self,
        target_state: TrainState,
        epoch: Optional[int] = None,
        step: Optional[int] = None,
        carry_template: Any = None,
    ) -> Optional[Snapshot]:
        """Restore into the structure of `target_state` (shapes/dtypes must
        match the current model/optimizer — the reference has the same
        contract via load_state_dict). `epoch` selects that epoch's
        boundary snapshot, `step` an exact iteration; default is the
        latest snapshot of any kind. Structure/shape/dtype mismatches
        raise `CheckpointRestoreError` naming the offending leaves."""
        if step is None:
            if epoch is not None:
                step = self._epoch_boundaries().get(int(epoch))
            else:
                step = self.latest_step()
        if step is None or step not in self.all_steps():
            return None
        if self.entry_format(step) == "sharded":
            # shard-native payload: reconstruct the REPLICATED interchange
            # form this template path promises (per-leaf reads; sharded
            # consumers restore natively via open_sharded instead)
            return self._restore_sharded_template(
                int(step), target_state, carry_template
            )
        entry = self._index.get(str(step))
        healed = False
        if entry is None:
            # no index entry: either a genuine legacy epoch-keyed payload,
            # or a NEW-format step whose sidecar write was killed between
            # the orbax commit and os.replace (the preemption grace period
            # expiring mid-drain). Probe the stored metadata — misreading
            # a new payload as legacy would turn a mid-epoch snapshot into
            # an epoch boundary and silently skip the rest of the epoch.
            entry = self._probe_format(int(step))
            healed = entry is not None
        if entry is None:
            return self._restore_legacy(target_state, int(step))
        template: dict[str, Any] = {
            "state": target_state,
            "meta": {
                "epoch": 0, "iteration": 0, "epoch_step": 0, "mid_epoch": 0,
            },
        }
        if entry.get("has_carry", False):
            if carry_template is None:
                raise CheckpointRestoreError(
                    f"checkpoint step {step} in {self._dir!r} carries a "
                    "model carry (BPTT hidden state) but no carry template "
                    "was supplied — restore through a trainer built for "
                    "the same stateful model"
                )
            template["carry"] = carry_template
        restored = self._restore_checked(int(step), template)
        meta = restored["meta"]
        if healed:
            # repair the sidecar from the payload's own bookkeeping so the
            # next open doesn't have to probe again
            self._index[str(step)] = {
                "epoch": int(meta["epoch"]),
                "epoch_step": int(meta["epoch_step"]),
                "mid_epoch": bool(int(meta["mid_epoch"])),
                "has_carry": "carry" in restored,
            }
            self._write_index()
            entry = self._index[str(step)]
        # the INDEX is authoritative for epoch/mid_epoch: a boundary save
        # deduped onto an earlier mid-epoch payload promotes the entry
        # while the payload's meta still says mid_epoch — trusting the
        # payload would make the promoted boundary resume as mid-epoch
        mid_epoch = bool(entry.get("mid_epoch", int(meta["mid_epoch"])))
        return Snapshot(
            state=restored["state"],
            epoch=int(entry.get("epoch", meta["epoch"])),
            iteration=int(meta["iteration"]),
            epoch_step=int(meta["epoch_step"]),
            mid_epoch=mid_epoch,
            carry=restored.get("carry"),
        )

    # -- shard-native template reconstruction -----------------------------
    @staticmethod
    def _tree_docs(tree: Any) -> list[tuple[str, Any]]:
        return [
            (jax.tree_util.keystr(kp), leaf)
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
        ]

    def _diff_leaf_docs(
        self, docs: list[dict], template: Any, what: str
    ) -> list[str]:
        """(path: saved vs expected) diffs between manifest leaf docs and
        the restore template's leaves — the shard-native twin of
        `_template_diff`."""
        want = {p: leaf for p, leaf in self._tree_docs(template)}
        saved = {d["path"]: d for d in docs}
        out = []
        for path in sorted(set(saved) | set(want)):
            s, w = saved.get(path), want.get(path)
            if s is None:
                out.append(f"{what}{path}: missing in checkpoint "
                           f"(expected {_leaf_desc(w)})")
            elif w is None:
                out.append(f"{what}{path}: present in checkpoint "
                           f"({s['dtype']}{tuple(s['shape'])}) but not in "
                           "the current structure")
            elif not _doc_matches(s, w):
                out.append(
                    f"{what}{path}: checkpoint has "
                    f"{s['dtype']}{tuple(s['shape'])}, current structure "
                    f"wants {_leaf_desc(w)}"
                )
        return out

    def _restore_sharded_template(
        self,
        step: int,
        target_state: TrainState,
        carry_template: Any = None,
    ) -> Snapshot:
        """Rebuild the replicated interchange Snapshot from a shard-native
        payload: per-leaf reads off the source files, whichever layout
        (sharded group buffers or per-leaf replicated files) the saver
        used. This is the path template-driven consumers (`evaluate
        --all-epochs`, tools, cross-comm-op interchange) ride; sharded
        trainers restore natively through `open_sharded` instead."""
        src = self.open_sharded(step)
        mismatches = self._diff_leaf_docs(
            src.leaves, target_state.params, "params"
        )
        meta = src.meta
        opt_kind = src.section_kind("opt")
        if opt_kind == "replicated":
            mismatches += self._diff_leaf_docs(
                (src.manifest.get("opt") or {}).get("leaves") or [],
                target_state.opt_state, "opt_state",
            )
        if mismatches:
            raise CheckpointRestoreError(
                self._drift_message(step, mismatches), mismatches=mismatches
            )
        # params + batch stats
        p_treedef = jax.tree_util.tree_structure(target_state.params)
        params = jax.tree_util.tree_unflatten(
            p_treedef,
            [
                jnp.asarray(src.read_leaf("params", j))
                for j in range(len(src.leaves))
            ],
        )
        bs_docs = (src.manifest.get("batch_stats") or {}).get("leaves") or []
        bs_diff = self._diff_leaf_docs(
            bs_docs, target_state.batch_stats, "batch_stats"
        )
        if bs_diff:
            raise CheckpointRestoreError(
                self._drift_message(step, bs_diff), mismatches=bs_diff
            )
        bs_treedef = jax.tree_util.tree_structure(target_state.batch_stats)
        batch_stats = jax.tree_util.tree_unflatten(
            bs_treedef,
            [
                jnp.asarray(src.read_leaf("batch_stats", j))
                for j in range(len(bs_docs))
            ],
        )
        # optimizer state
        if opt_kind == "replicated":
            o_docs = (src.manifest.get("opt") or {}).get("leaves") or []
            o_treedef = jax.tree_util.tree_structure(target_state.opt_state)
            opt_state = jax.tree_util.tree_unflatten(
                o_treedef,
                [
                    jnp.asarray(src.read_leaf("opt", j))
                    for j in range(len(o_docs))
                ],
            )
        elif opt_kind == "sharded":
            opt_state = self._opt_from_sharded(src, target_state, meta)
        else:  # "none": a save that carried no optimizer state
            opt_state = target_state.opt_state
        rng = target_state.rng
        if src.manifest.get("rng") is not None:
            rng = jnp.asarray(
                np.asarray(src.manifest["rng"], np.uint32), rng.dtype
            )
        state = target_state.replace(
            step=jnp.asarray(
                int(meta.get("train_step", meta.get("iteration", step))),
                target_state.step.dtype,
            ),
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            rng=rng,
        )
        carry = None
        if src.carry_doc():
            if carry_template is None:
                raise CheckpointRestoreError(
                    f"checkpoint step {step} in {self._dir!r} carries a "
                    "model carry (BPTT hidden state) but no carry template "
                    "was supplied — restore through a trainer built for "
                    "the same stateful model"
                )
            cdoc = src.carry_doc()
            carry = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(carry_template),
                [
                    src.read_carry_range(
                        li, 0, int(leaf["shape"][0])
                    ).reshape([int(s) for s in leaf["shape"]])
                    for li, leaf in enumerate(cdoc["leaves"])
                ],
            )
        entry = self._index.get(str(step)) or self._heal_sharded_entry(step)
        return Snapshot(
            state=state,
            epoch=int(entry.get("epoch", meta.get("epoch", 0))),
            iteration=int(meta.get("iteration", step)),
            epoch_step=int(meta.get("epoch_step", 0)),
            mid_epoch=bool(entry.get(
                "mid_epoch", meta.get("mid_epoch", False)
            )),
            carry=carry,
            manifest_meta=meta,
        )

    def _opt_from_sharded(
        self, src: ShardSource, target_state: TrainState, meta: dict
    ) -> Any:
        """Sharded opt slots -> the replicated optax structure of the
        template: slot s's per-leaf reads land in the s-th params-shaped
        subtree of the optax tree, count leaves take the saved count."""
        from mgwfbp_tpu.parallel.allreduce import (
            _map_count_leaves,
            _map_params_subtrees,
        )

        slots = src.opt_slots()
        p_treedef = jax.tree_util.tree_structure(target_state.params)
        slot_trees = []
        for s in range(slots):
            slot_trees.append(jax.tree_util.tree_unflatten(
                p_treedef,
                [
                    jnp.asarray(src.read_leaf("opt", j, slot=s))
                    for j in range(len(src.leaves))
                ],
            ))
        it = iter(slot_trees)
        consumed = []

        def take(sub):
            try:
                new = next(it)
            except StopIteration:
                raise CheckpointRestoreError(
                    f"checkpoint in {self._dir!r}: optimizer template "
                    f"carries more params-shaped subtrees than the saved "
                    f"{slots} slot(s) — optimizer config drift"
                ) from None
            consumed.append(new)
            return jax.tree_util.tree_map(
                lambda ref, a: jnp.asarray(a, ref.dtype), sub, new
            )

        out = _map_params_subtrees(
            target_state.opt_state, target_state.params, take
        )
        if len(consumed) != slots:
            raise CheckpointRestoreError(
                f"cannot restore checkpoint step {src.manifest.get('step')} "
                f"from {self._dir!r}: saved optimizer has {slots} sharded "
                f"slot(s) but the current optimizer template consumes "
                f"{len(consumed)} — optimizer config drift"
            )
        count = jnp.asarray(int(meta.get("opt_count", 0)), jnp.int32)
        return _map_count_leaves(
            out, lambda leaf: jnp.asarray(count, leaf.dtype)
        )

    def _probe_format(self, step: int) -> Optional[dict]:
        """Minimal index entry inferred from stored metadata for an
        UNINDEXED step, or None when the payload really is the legacy
        epoch-keyed format (2-key meta, no epoch_step)."""
        try:
            md = self._mgr.item_metadata(step)
        except Exception:  # noqa: BLE001 — undecidable: treat as legacy
            return None
        # the installed orbax hands the stored tree back wrapped in a
        # TreeMetadata object (the keys sit under `.tree`); older ones
        # returned the dict itself
        md = getattr(md, "tree", md)
        if not isinstance(md, dict) or not isinstance(md.get("meta"), dict):
            return None
        if "epoch_step" not in md["meta"]:
            return None
        return {"has_carry": "carry" in md}

    def _restore_legacy(
        self, target_state: TrainState, step: int
    ) -> Snapshot:
        """Epoch-keyed payloads from the pre-resilience format: the orbax
        step is the epoch, meta has only {'epoch','iteration'}."""
        template = {
            "state": target_state,
            "meta": {"epoch": 0, "iteration": 0},
        }
        restored = self._restore_checked(step, template)
        return Snapshot(
            state=restored["state"],
            epoch=int(restored["meta"]["epoch"]),
            iteration=int(restored["meta"]["iteration"]),
        )

    def _restore_checked(self, step: int, template: Any) -> Any:
        # proactive shape/dtype validation: orbax's StandardRestore does
        # NOT fail on a mismatched template — it hands back the saved
        # shapes, deferring the blow-up to the first jitted dispatch with
        # an inscrutable shape error. Diff the stored metadata against the
        # template FIRST and fail here, naming the drifted leaves.
        mismatches = self._template_diff(step, template)
        if mismatches:
            raise CheckpointRestoreError(
                self._drift_message(step, mismatches), mismatches=mismatches
            )
        try:
            return self._mgr.restore(
                step, args=ocp.args.StandardRestore(template)
            )
        except CheckpointRestoreError:
            raise
        except Exception as e:  # noqa: BLE001 — rewrapped with context
            raise CheckpointRestoreError(
                self._drift_message(step, []) + f" (orbax: {e})"
            ) from e

    def _drift_message(self, step: int, mismatches: list[str]) -> str:
        detail = (
            "; offending leaves:\n  " + "\n  ".join(mismatches[:20])
            if mismatches
            else ""
        )
        return (
            f"cannot restore checkpoint step {step} from {self._dir!r} "
            "into the current model/optimizer structure — likely config "
            "drift (the checkpoint was saved under a different --dnn / "
            f"optimizer / precision configuration){detail}"
        )

    def _template_diff(self, step: int, template: Any) -> list[str]:
        """Human-readable (path: saved vs expected) diffs between the
        stored payload's metadata and the restore template — best effort;
        metadata unavailable degrades to the wrapped orbax message."""
        try:
            saved_md = self._mgr.item_metadata(step)
            saved = {
                _path_str(kp): v
                for kp, v in jax.tree_util.tree_flatten_with_path(saved_md)[0]
            }
            want = {
                _path_str(kp): v
                for kp, v in jax.tree_util.tree_flatten_with_path(
                    jax.eval_shape(lambda: template)
                )[0]
            }
        except Exception:  # noqa: BLE001 — diffing is best-effort
            return []
        if not saved or not any(
            hasattr(v, "shape") for v in saved.values()
        ):
            # metadata unavailable/uninterpretable: no diff evidence —
            # let the actual restore decide instead of crying drift
            return []
        out = []
        for path in sorted(set(saved) | set(want)):
            if path.startswith("meta."):
                continue  # bookkeeping ints; never the drifted leaves
            s, w = saved.get(path), want.get(path)
            if s is None:
                out.append(f"{path}: missing in checkpoint (expected "
                           f"{_leaf_desc(w)})")
            elif w is None:
                out.append(f"{path}: present in checkpoint "
                           f"({_leaf_desc(s)}) but not in the current "
                           "structure")
            elif _leaf_desc(s) != _leaf_desc(w):
                out.append(f"{path}: checkpoint has {_leaf_desc(s)}, "
                           f"current structure wants {_leaf_desc(w)}")
        return out

    def wait(self) -> None:
        """Durability point: both async machineries (orbax's background
        commit and the shard-native writer slot) are drained. Collective
        on multi-host when a shard save is pending — call it from the
        same program point on every process (the trainer's callers do)."""
        self.drain_async()
        self._mgr.wait_until_finished()

    def close(self) -> None:
        slot = self._async
        if slot is not None:
            if coord.process_count() == 1:
                # single process: the drain is pure local work + commit;
                # finishing it is strictly better than dropping the save
                try:
                    self.drain_async()
                except RuntimeError:
                    pass  # a failed payload write must not block close
            else:
                # multi-host close is the DISORDERLY path (orderly exits
                # drain at a boundary save / wait() first): peers may
                # already be gone, so the collective commit could hang on
                # a dead process. Abandon the uncommitted save — the
                # manifest never appeared, so restore ignores the torn
                # subtrees and falls back to the last committed step.
                self._async = None
                import warnings

                warnings.warn(
                    f"close() with async shard save of step {slot.step} "
                    "still in flight on a multi-host run: abandoning the "
                    "uncommitted save (restore uses the previous "
                    "committed step)",
                    RuntimeWarning,
                    stacklevel=2,
                )
        self._mgr.close()


def _path_str(kp) -> str:
    """Canonical dotted path for a tree_flatten_with_path key path.

    Orbax metadata comes back as plain nested dicts while the restore
    template carries dataclass pytrees (TrainState), so DictKey vs
    GetAttrKey must compare equal for the same logical leaf."""
    names = []
    for entry in kp:
        name = getattr(entry, "key", None)
        if name is None:
            name = getattr(entry, "name", None)
        if name is None:
            name = getattr(entry, "idx", None)
        names.append(str(name))
    return ".".join(names)


def _leaf_desc(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None and dtype is None:
        return type(leaf).__name__
    return f"{np.dtype(dtype).name if dtype is not None else '?'}" \
           f"{tuple(shape) if shape is not None else ''}"
