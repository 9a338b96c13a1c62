"""In-memory array dataset + sharded epoch loader.

Replaces the reference's torch `DataLoader` + `DistributedSampler` pairs
(reference dl_trainer.py:317-539) with a NumPy pipeline: datasets expose
indexable arrays; the loader owns the epoch permutation (sharded via
`sharding.shard_indices`), batching, and normalization, and yields host
numpy batches ready for device put (the trainer lays them out on the mesh).

`ShardedLoader` is synchronous and deterministic (same seed -> same
batches, rank-disjoint): `load_batch(epoch, b)` gathers a batch's uint8
images and runs the transform over them, for images one native pass that
writes the normalized float32 the step consumes (mgwfbp_tpu/native; NumPy
where the library did not build, to the same bits). The train path wraps it
in `PrefetchLoader` (`data_prepare`), whose thread pool assembles batches
ahead of the loop; the native pass runs without the GIL, so the workers
run side by side. Batches go to the device in the trainer's `place`.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import weakref
from typing import Callable, Iterator, Optional

import numpy as np

from mgwfbp_tpu import native
from mgwfbp_tpu.data.sharding import ShardInfo, shard_indices


@dataclasses.dataclass
class ArrayDataset:
    """data[N, ...], labels[N] (+ optional per-sample aux like lengths)."""

    data: np.ndarray
    labels: np.ndarray
    num_classes: int

    def __post_init__(self):
        if len(self.data) != len(self.labels):
            raise ValueError("data/labels length mismatch")

    def __len__(self) -> int:
        return len(self.data)


class ShardedLoader:
    """Epoch-based sharded batch iterator.

    `set_epoch` reshuffles deterministically (reference
    train_sampler.set_epoch, dl_trainer.py:778-779). Batches are per-process
    (weak scaling: the reference's batch_size is per worker,
    dl_trainer.py:153-156).
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        shard: ShardInfo = ShardInfo(),
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
        transform: Optional[Callable[[np.ndarray], np.ndarray]] = None,
    ):
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        self.dataset = dataset
        self.batch_size = batch_size
        self.shard = shard
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.transform = transform
        self.epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def set_batch_size(self, batch_size: int) -> None:
        """Re-batch the same shard (e.g. a larger eval batch,
        MGWFBP_EVAL_BATCH); batching here is lazy so the attribute IS the
        behavior."""
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        self.batch_size = batch_size

    @property
    def num_batches(self) -> int:
        per_rank = len(
            shard_indices(
                len(self.dataset), self.shard, 0, self.shuffle, self.seed,
                self.drop_last,
            )
        )
        if self.drop_last:
            return per_rank // self.batch_size
        return (per_rank + self.batch_size - 1) // self.batch_size

    def __len__(self) -> int:
        return self.num_batches

    def _epoch_indices(self, epoch: int) -> np.ndarray:
        if getattr(self, "_idx_epoch", None) != epoch:
            self._idx = shard_indices(
                len(self.dataset), self.shard, epoch, self.shuffle,
                self.seed, self.drop_last,
            )
            self._idx_epoch = epoch
        return self._idx

    def prime_epoch(self, epoch: int) -> None:
        """Precompute the epoch's shard permutation (PrefetchLoader calls
        this once before fanning load_batch jobs to its pool, so workers
        never race to build the cache)."""
        self._epoch_indices(epoch)

    def load_batch(
        self, epoch: int, b: int, alloc=None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Assemble batch `b` of `epoch` (gather + transform), independently
        of iterator state — the unit of work `PrefetchLoader` farms out to a
        thread pool. Deterministic: (seed, epoch, rank, batch) fully name
        the batch, so prefetched and inline assembly are bit-identical.

        `alloc(shape)` hands a transform that `takes_out` the float32 array
        to write the batch into (`PrefetchLoader`'s recycled arrays);
        without it every batch is a fresh array."""
        idx = self._epoch_indices(epoch)
        sel = idx[b * self.batch_size : (b + 1) * self.batch_size]
        x = _gather(self.dataset.data, sel)
        y = self.dataset.labels[sel]
        if self.transform is not None:
            kw = {}
            if alloc is not None and getattr(self.transform, "takes_out", False):
                kw["out"] = alloc(x.shape)
            if getattr(self.transform, "wants_rng", False):
                # per-(seed, epoch, rank, batch) stream: augmentation is
                # deterministic per epoch and decorrelated across ranks
                rng = np.random.default_rng(
                    [self.seed, epoch, self.shard.rank, b]
                )
                x = self.transform(x, rng, **kw)
            else:
                x = self.transform(x, **kw)
        return x, y

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        idx = self._epoch_indices(self.epoch)
        if self.drop_last:
            nb = len(idx) // self.batch_size
        else:
            nb = (len(idx) + self.batch_size - 1) // self.batch_size
        for b in range(nb):
            yield self.load_batch(self.epoch, b)


def _gather(data, sel: np.ndarray) -> np.ndarray:
    """Fancy-index `data[sel]` for ndarray OR h5py dataset backends.

    h5py only accepts strictly-increasing duplicate-free index lists, while
    shuffled/padded shard indices are neither; read the sorted unique set and
    scatter back (one HDF5 read per batch, still sequential-ish on disk).
    """
    if isinstance(data, np.ndarray):
        return data[sel]
    usel, inverse = np.unique(sel, return_inverse=True)
    return np.asarray(data[usel.tolist()])[inverse]


class _Lease:
    """One hand-out of a recycled block: what every array made from it, and
    every view, slice or device transfer of those, keeps alive through
    `.base`. NumPy stops collapsing `.base` chains at an object that is not
    an ndarray, so nothing can refer to the block's memory and not to this."""

    def __init__(self, block: np.ndarray, shape: tuple):
        self._block = block
        self.__array_interface__ = {
            "data": (block.ctypes.data, False), "shape": tuple(shape),
            "typestr": block.dtype.str, "version": 3,
        }


class _OutputRing:
    """Float32 blocks the pool's workers write their batches into, handed
    out again once nothing refers to the last hand-out.

    A fresh 77 MB array is 18,816 pages the kernel has to fault in and
    zero: on the chip machine 80 to 100 ms a batch against 4.5 ms for the
    normalize kernel itself (PERF.md, PR 27). The proof that a block is
    free is the allocator's own: a batch goes out as an array whose `.base`
    is a `_Lease`; the trainer, its views and `jax.device_put` (which keeps
    the host array referenced until the copy to the device has finished,
    and for good where the CPU backend aliases it) all hold the lease, and
    the lease's finalizer puts the block back. So a block returns exactly
    when a fresh array's memory would have been freed. With every block out
    (a consumer that keeps batches) `take` hands out a fresh array."""

    def __init__(self, size: int):
        # None: a block not allocated yet. deque operations are atomic:
        # workers take, whichever thread drops a lease last gives back.
        self._free: collections.deque = collections.deque([None] * size)

    def take(self, shape: tuple) -> np.ndarray:
        n = math.prod(shape)
        try:
            block = self._free.popleft()
        except IndexError:
            return np.empty(shape, np.float32)
        if block is None or block.size != n:  # first use, or a new batch size
            block = np.empty(n, np.float32)
        lease = _Lease(block, shape)
        weakref.finalize(lease, self._free.append, block)
        return np.asarray(lease)


class PrefetchLoader:
    """Background-prefetching wrapper around an epoch loader.

    The reference feeds its GPUs through
    `DataLoader(num_workers=NUM_CPU_THREADS, pin_memory=True)` (reference
    dl_trainer.py:353, :405); this is the same role without torch: batch
    assembly (index gather + augmentation) runs in a thread pool AHEAD of
    consumption, and each ready batch is optionally `jax.device_put` early
    so the host->device transfer overlaps the previous step's compute
    (double buffering; the put is async, the jitted step just consumes the
    committed arrays). The native transforms (mgwfbp_tpu/native) run
    without the GIL, so threads give real parallelism without pickling
    costs; the NumPy fallbacks' fancy indexing holds it.

    Two modes:
      * inner exposes `load_batch(epoch, b)` (ShardedLoader): `workers`
        assemble batches concurrently, results consumed IN ORDER — output
        is bit-identical to the inline loader for any worker count.
      * otherwise (audio bucketing etc.): a single background thread runs
        the inner iterator `depth` batches ahead.
    """

    def __init__(
        self,
        inner,
        workers: int = 2,
        depth: int = 2,
        device_put: bool = False,
    ):
        self.inner = inner
        self.workers = max(int(workers), 0)
        self.depth = max(int(depth), 1)
        self.device_put = device_put
        # the running pool's deque of outstanding batches (`ready_batches`)
        self._futs = None
        # whether the pool's batch handed out last came from the native
        # pass (`native_batch`)
        self._native: Optional[int] = None
        # output arrays for the transforms that take one: all the pool can
        # hold (`workers + depth`), the batch in the loop's hands and the
        # one still on its way to the device
        self._ring = _OutputRing(self.workers + self.depth + 2)

    # epoch/batch-size/len plumbing passes through to the inner loader
    def set_epoch(self, epoch: int) -> None:
        self.inner.set_epoch(epoch)

    def set_batch_size(self, batch_size: int) -> None:
        self.inner.set_batch_size(batch_size)

    @property
    def epoch(self):
        return self.inner.epoch

    @property
    def batch_size(self):
        return self.inner.batch_size

    @property
    def dataset(self):
        return self.inner.dataset

    @property
    def num_batches(self) -> int:
        return len(self.inner)

    def __len__(self) -> int:
        return len(self.inner)

    def ready_batches(self) -> Optional[int]:
        """Batches the pool holds finished right now (telemetry's `ready`
        counter asks before each `next`); None where no pool runs."""
        futs = self._futs
        return None if futs is None else sum(f.done() for f in futs)

    def native_batch(self) -> Optional[int]:
        """1 when the batch the pool handed out last was transformed by a
        native kernel (mgwfbp_tpu/native), 0 when it took NumPy or has no
        transform (telemetry's `native` counter asks after each `next`);
        None where no pool runs."""
        return self._native

    def _finalize(self, batch):
        if not self.device_put:
            return batch
        import jax

        if jax.process_count() > 1:
            # multi-host assembly pulls host numpy back out of the batch
            # (make_array_from_process_local_data); early device_put would
            # just bounce the bytes
            return batch
        return jax.device_put(batch)

    def __iter__(self):
        if self.workers == 0:
            for batch in self.inner:
                yield self._finalize(batch)
            return
        if hasattr(self.inner, "load_batch"):
            yield from self._iter_pool()
        else:
            yield from self._iter_thread()

    def _iter_pool(self):
        from concurrent.futures import ThreadPoolExecutor

        nb = len(self.inner)
        epoch = self.inner.epoch
        # indices are epoch-cached on the inner loader; prime the cache once
        # on this thread so pool workers only read it
        if nb and hasattr(self.inner, "prime_epoch"):
            self.inner.prime_epoch(epoch)
        with ThreadPoolExecutor(max_workers=self.workers) as ex:

            def job(b):
                # a native pass counts itself on the thread that made it
                before = native.passes()
                batch = self.inner.load_batch(epoch, b, alloc=self._ring.take)
                return self._finalize(batch), int(native.passes() > before)

            ahead = self.workers + self.depth
            futs = collections.deque(
                ex.submit(job, b) for b in range(min(ahead, nb))
            )
            next_b = len(futs)
            self._futs = futs
            try:
                while futs:
                    # in-order consumption
                    out, self._native = futs.popleft().result()
                    if next_b < nb:
                        futs.append(ex.submit(job, next_b))
                        next_b += 1
                    yield out
            finally:
                self._futs = self._native = None

    def _iter_thread(self):
        import queue
        import threading

        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        _END = object()

        def put(item) -> bool:
            # bounded put that gives up when the consumer abandoned the
            # iterator (otherwise an early `break` in the consumer — e.g. a
            # step-capped epoch — would leave this thread blocked on a full
            # queue forever, leaking it and its buffered batches)
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def feed():
            try:
                for batch in self.inner:
                    if not put(self._finalize(batch)):
                        return
                put(_END)
            except BaseException as e:  # propagate into the consumer
                put(e)

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            t.join(timeout=5)


def infinite_batches(loader: ShardedLoader, start_epoch: int = 0):
    """Auto-restarting iterator with epoch bumping (reference `data_iter`,
    dl_trainer.py:568-576). Yields (epoch, batch)."""
    epoch = start_epoch
    while True:
        loader.set_epoch(epoch)
        for batch in loader:
            yield epoch, batch
        epoch += 1


def normalize_images(
    mean: tuple[float, ...], std: tuple[float, ...]
) -> Callable[[np.ndarray], np.ndarray]:
    """uint8 HWC images -> normalized float32 (the reference's torchvision
    transforms.Normalize equivalents, dl_trainer.py:369-409).

    uint8 batches go through the fused native kernel when available
    (mgwfbp_tpu.native.normalize_u8); the NumPy fallback uses the same
    px*scale - shift affine so both round identically in float32."""
    mean_a = np.asarray(mean, dtype=np.float32)
    std_a = np.asarray(std, dtype=np.float32)
    scale = (1.0 / (255.0 * std_a)).astype(np.float32)
    shift = (mean_a / std_a).astype(np.float32)

    def _t(x: np.ndarray, out: Optional[np.ndarray] = None) -> np.ndarray:
        if x.dtype == np.uint8 and x.ndim >= 1:
            out = native.normalize_u8(x, mean_a, std_a, out=out)
            if out is not None:
                return out
        return x.astype(np.float32) * scale - shift

    _t.takes_out = True  # the native pass writes where it is told to
    return _t
