"""Merged-gradient collectives: the TPU lowering of the MG-WFBP schedule.

The reference launches one Horovod `allreduce_async_` per merge group from the
autograd hook of the group's last-arriving member, then blocks in
`synchronize()` before the optimizer step (reference
distributed_optimizer.py:334-431). Under XLA the same overlap is obtained
structurally: each group's flat bucket depends on exactly its member
gradients, so one `lax.psum` per bucket gives XLA's latency-hiding scheduler
the freedom to run early groups' all-reduces concurrently with the remaining
backward compute. The merge schedule controls the bucket sizes — the same
startup-amortization vs overlap trade the paper optimizes.

No handles, no flags, no explicit synchronize: dataflow is the schedule.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mgwfbp_tpu.optim import OptimSpec
from mgwfbp_tpu.parallel import buckets as buckets_lib
from mgwfbp_tpu.parallel.buckets import BucketLayout, build_layout
from mgwfbp_tpu.parallel.solver import (
    LayerSpec,
    MergeSchedule,
    build_schedule,
    check_unique,
    effective_cost_fn,
    is_two_level,
    predict_group_times,
    simulate_groups,
    size_prior_tb,
)

# Name-scope prefix stamped on every merge-group collective (the group index
# is appended, zero-padded). XLA/jaxpr preserve the scope in op metadata, so
# `mgwfbp_tpu.analysis.jaxpr_check` can statically match the collectives the
# lowered program ACTUALLY issues against the MergeSchedule that promised
# them. Keep in sync with analysis/jaxpr_check.py.
GROUP_SCOPE_PREFIX = "mgwfbp_group"

# Name scope of the ONE extra collective the rs_opt_ag lowering may issue: a
# cross-group psum of per-shard squared gradient norms, required for
# global-norm clipping (the clip threshold is a property of the WHOLE grad
# tree, but each device only holds 1/world of each bucket between the
# reduce-scatter and the update). analysis/jaxpr_check whitelists exactly
# this scope; keep the two in sync.
CLIP_NORM_SCOPE = "sharded_clip_norm"

# Name-scope prefix of the hier lowering's cross-slice (DCN) collectives:
# one outer all-reduce per DCN group of the nested schedule, over the
# concatenated member shards. Scoped SEPARATELY from the inner
# mgwfbp_groupNNNN legs so the jaxpr verifier can pin the DCN contract
# (count/payload/dtype, no stray cross-pod collectives — SCH009) and so
# trace attribution can split a bucket's time into its ICI and DCN legs.
# Keep in sync with analysis/jaxpr_check.py.
DCN_GROUP_SCOPE_PREFIX = "mgwfbp_dcngroup"


def group_scope_name(gi: int) -> str:
    """Name-scope label for merge group `gi` (introspection hook)."""
    return f"{GROUP_SCOPE_PREFIX}{gi:04d}"


def dcn_group_scope_name(di: int) -> str:
    """Name-scope label for DCN group `di` (hier lowering)."""
    return f"{DCN_GROUP_SCOPE_PREFIX}{di:04d}"


_DIGITS = re.compile(r"(\d+)")


def _natural_key(name: str) -> tuple:
    """Digit-aware sort key: 'Block_10' sorts after 'Block_2'."""
    return tuple(int(t) if t.isdigit() else t for t in _DIGITS.split(name))


def forward_order(names: Sequence[str]) -> list[int]:
    """Indices of `names` in natural (digit-aware) path order.

    Flax auto-names sibling modules Type_0..Type_N, but pytree flattening
    sorts dict keys LEXICOGRAPHICALLY (Block_0, Block_1, Block_10, Block_11,
    ..., Block_2, ...), which scrambles definition order for any model with
    10+ sibling blocks. Natural ordering restores the definition (≈forward)
    order the merge schedule needs.
    """
    return sorted(range(len(names)), key=lambda i: _natural_key(names[i]))


def arrival_order(
    num_leaves: int,
    perm: Optional[Sequence[int]] = None,
    names: Optional[Sequence[str]] = None,
) -> list[int]:
    """Default gradient-arrival permutation over pytree leaves.

    Arrival order is the reverse of forward order — gradients of the last
    forward layer exist first. The reference measures the true order with
    profiling hooks (profiling.py:31-48); pass that as `perm` when available.
    Otherwise, with `names` (leaf key paths) the forward order is recovered by
    natural-sorting the paths; with neither, leaves are assumed already in
    forward order.
    """
    if perm is not None:
        if sorted(perm) != list(range(num_leaves)):
            raise ValueError("perm must be a permutation of range(num_leaves)")
        return list(perm)
    if names is not None:
        return list(reversed(forward_order(names)))
    return list(reversed(range(num_leaves)))


def _scatter_mid_gather(
    buf: jax.Array, scatter_axes, mean_div: int, mid=None
) -> jax.Array:
    """Shared frame of the decomposed bucket all-reduces: pad the bucket to
    scatter-axis divisibility, reduce-scatter over `scatter_axes`, apply an
    optional `mid` transform to the shard, divide by `mean_div` (1 = sum
    semantics), all-gather back, trim the pad."""
    n = buf.shape[0]
    # static extents: mesh axis sizes are known at trace time
    parts = lax.axis_size(scatter_axes)
    pad = (-n) % parts
    if pad:
        buf = jnp.pad(buf, (0, pad))
    shard = lax.psum_scatter(
        buf, scatter_axes, scatter_dimension=0, tiled=True
    )
    if mid is not None:
        shard = mid(shard)
    if mean_div != 1:
        shard = shard / mean_div
    full = lax.all_gather(shard, scatter_axes, axis=0, tiled=True)
    return full[:n] if pad else full


def _rs_ag_allreduce(buf: jax.Array, axes, mean: bool) -> jax.Array:
    """Bucket all-reduce as reduce-scatter + all-gather (the DeAR-style
    decomposition, arXiv:2302.12445): each phase moves half a ring
    all-reduce's bytes, and XLA may overlap the all-gather of group k with
    other work more aggressively than a monolithic all-reduce. Numerically
    identical to pmean/psum."""
    world = lax.axis_size(axes)
    return _scatter_mid_gather(buf, axes, world if mean else 1)


def _check_hier_axes(comm_op: str, axis_name) -> None:
    if comm_op == "hier" and (
        isinstance(axis_name, str) or len(axis_name) != 2
    ):
        raise ValueError(
            "comm_op='hier' needs axis_name=(inner_ici_axis, outer_dcn_axis)"
        )


def _hierarchical_allreduce(
    buf: jax.Array, inner_axis: str, outer_axis: str, mean: bool
) -> jax.Array:
    """Two-level bucket all-reduce for multi-slice meshes — the lowering
    whose cost `costmodel.TwoLevelAlphaBeta` models: reduce-scatter over the
    fast INNER axis (ICI within a slice), all-reduce the resulting shard
    over the slow OUTER axis (DCN across slices), then all-gather back over
    the inner axis. The full payload rides ICI; DCN carries only
    1/inner_size of it — the standard pod-slice hierarchy a flat psum over
    both axes leaves to XLA's discretion, made explicit so the solver's
    two-level cost predictions describe the actual wire traffic."""
    world = lax.axis_size((inner_axis, outer_axis))
    return _scatter_mid_gather(
        buf,
        (inner_axis,),
        world if mean else 1,
        mid=lambda shard: lax.psum(shard, outer_axis),
    )


# ---------------------------------------------------------------------------
# Sharded optimizer in the communication path (comm_op='rs_opt_ag').
#
# The rs_ag decomposition already splits each bucket all-reduce into
# reduce-scatter + all-gather; between those two phases every device holds
# the fully REDUCED 1/world shard of the bucket — the one moment in the step
# where running the optimizer costs 1/world the FLOPs and optimizer-state
# HBM traffic of the replicated update (DeAR's fine-grained RS/AG pipeline,
# arXiv:2302.12445, plus Optimizer Fusion's update-in-the-comm-path
# locality argument, arXiv:2104.00237). The all-gather then carries updated
# PARAMS instead of gradients: same wire bytes, and the optimizer state
# (momentum / Adam moments) never needs to exist outside its shard — a
# ZeRO-1-style ~1/world optimizer-state memory footprint.
# ---------------------------------------------------------------------------


class ShardedOptState:
    """Optimizer state of the rs_opt_ag path: per-(slot, group) flat shard
    buffers of GLOBAL shape (world, shard_len) — sharded over the data axes
    between steps — plus one replicated step count (lr schedules, Adam bias
    correction). `slots[s][gi]` mirrors `BucketLayout` group `gi` for
    params-shaped state leaf `s` (SGD momentum: 1 slot; Adam m/v: 2)."""

    def __init__(self, count, slots):
        self.count = count
        self.slots = tuple(tuple(g for g in s) for s in slots)

    def __repr__(self):
        return (
            f"ShardedOptState(count={self.count!r}, "
            f"slots={len(self.slots)}x{len(self.slots[0]) if self.slots else 0})"
        )


jax.tree_util.register_pytree_node(
    ShardedOptState,
    lambda s: ((s.count, s.slots), None),
    lambda _, ch: ShardedOptState(count=ch[0], slots=ch[1]),
)


class ShardedParams:
    """Parameters in cross-step carry form (comm_op='rs_fwd_ag'): one flat
    (world, shard_len) buffer per merge group, sharded over the data axes
    between steps exactly like `ShardedOptState` buffers.

    This is the state the DeAR-style lowering (arXiv:2302.12445) carries
    across the step boundary: step N's reduce-scatter + shard optimizer
    update produce these buffers, and step N+1's FORWARD all-gathers each
    group just-in-time before its first consuming layer. The canonical
    replicated pytree exists only transiently (inside the step after the
    gathers, and host-side at checkpoint/eval boundaries via
    `ShardedOptimStep.gather_params`/`scatter_params`)."""

    def __init__(self, groups):
        self.groups = tuple(groups)

    def __repr__(self):
        return f"ShardedParams(groups={len(self.groups)})"


jax.tree_util.register_pytree_node(
    ShardedParams,
    lambda s: ((s.groups,), None),
    lambda _, ch: ShardedParams(groups=ch[0]),
)


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedOptimStep:
    """(layout, optimizer-update-on-flat-buffers) for the rs_opt_ag seam.

    Interprets an elementwise `optim.OptimSpec` (SGD/momentum/Adam/AdamW,
    coupled or decoupled weight decay, global-norm clipping) on the flat
    1/world bucket shards the reduce-scatter produces. Per-LEAF
    hyperparameters (the ndim>1 decay mask) become per-ELEMENT host
    constants over the padded bucket (`buckets.group_mask_vector`) sliced to
    the device's shard at trace time, so shard boundaries may cut leaves
    arbitrarily.

    `world` is static (mesh extent at construction); the traced path
    re-derives it from the bound axes and refuses to run on a mismatched
    mesh — a silently wrong shard split would corrupt every parameter.
    """

    spec: OptimSpec
    layout: BucketLayout
    shapes: tuple[tuple[int, ...], ...]  # leaf shapes, arrival order
    perm: tuple[int, ...]  # tree-position -> arrival-position permutation
    axes: tuple[str, ...]
    world: int

    @property
    def num_slots(self) -> int:
        return self.spec.num_slots

    def shard_size(self, gi: int) -> int:
        return buckets_lib.shard_size(self.layout, gi, self.world)

    def padded_size(self, gi: int) -> int:
        return buckets_lib.padded_group_size(self.layout, gi, self.world)

    def decay_mask_vec(self, gi: int) -> Optional[np.ndarray]:
        """Padded per-element decay mask for group gi (None = no decay)."""
        if not self.spec.weight_decay:
            return None
        flags = [
            (len(s) > 1) if self.spec.mask_ndim_gt1 else True
            for s in self.shapes
        ]
        return buckets_lib.group_mask_vector(
            self.layout, gi, flags, self.shapes, self.world
        )

    # -- state construction / accounting ---------------------------------
    def init(self) -> ShardedOptState:
        """Fresh sharded state (zeros), global (world, shard_len) buffers."""
        slots = tuple(
            tuple(
                jnp.zeros(
                    (self.world, self.shard_size(gi)), self.layout.dtypes[gi]
                )
                for gi in range(self.layout.num_groups)
            )
            for _ in range(self.num_slots)
        )
        return ShardedOptState(count=jnp.zeros((), jnp.int32), slots=slots)

    def partition_spec(self) -> ShardedOptState:
        """Pytree of PartitionSpecs matching `init()`'s structure: shard
        buffers split over the data axes, the count replicated."""
        from jax.sharding import PartitionSpec as P

        slots = tuple(
            tuple(P(self.axes) for _ in range(self.layout.num_groups))
            for _ in range(self.num_slots)
        )
        return ShardedOptState(count=P(), slots=slots)

    def state_bytes_per_device(self) -> int:
        """Optimizer-state bytes each device holds on the sharded path."""
        per_slot = sum(
            self.shard_size(gi) * jnp.dtype(self.layout.dtypes[gi]).itemsize
            for gi in range(self.layout.num_groups)
        )
        return self.num_slots * per_slot + 4  # + int32 count

    def replicated_state_bytes(self) -> int:
        """Bytes of the params-shaped state leaves every device would hold
        on the replicated path (the 1/world comparison baseline)."""
        per_slot = sum(
            self.layout.group_sizes[gi]
            * jnp.dtype(self.layout.dtypes[gi]).itemsize
            for gi in range(self.layout.num_groups)
        )
        return self.num_slots * per_slot

    # -- checkpoint interchange (host-side, numpy) -----------------------
    # Checkpoints always store the REPLICATED optax structure, whichever
    # path wrote them: the sharded layout depends on (mesh extent, merge
    # schedule), both of which may differ at restore time, while the optax
    # structure depends only on the optimizer — so gather on save, scatter
    # on load keeps all_reduce- and rs_opt_ag-run checkpoints freely
    # interchangeable (and elastic resizes re-scatter through the same
    # pair).

    def _unpack_slot(self, slot_bufs: Sequence[Any]) -> list[np.ndarray]:
        """One slot's buffers -> per-leaf arrays in TREE order."""
        arr: list[Any] = [None] * len(self.shapes)
        for gi in range(self.layout.num_groups):
            flat = np.asarray(slot_bufs[gi]).reshape(-1)
            for i, a in buckets_lib.unpack_group_host(
                flat, self.layout, gi, self.shapes
            ).items():
                arr[i] = a
        restored: list[Any] = [None] * len(arr)
        for k, j in enumerate(self.perm):
            restored[j] = arr[k]
        return restored

    def _pack_slot(self, tree_leaves: Sequence[Any]) -> tuple[np.ndarray, ...]:
        """Per-leaf arrays in TREE order -> one slot's (world, shard)
        buffers."""
        arr = [np.asarray(tree_leaves[j]) for j in self.perm]
        return tuple(
            buckets_lib.pack_group_host(
                arr, self.layout, gi, self.world
            ).reshape(self.world, self.shard_size(gi))
            for gi in range(self.layout.num_groups)
        )

    def gather(self, state: ShardedOptState, tx: Any, params: Any) -> Any:
        """Sharded state -> the replicated optax state `tx.init(params)`
        would produce after the same update history."""
        treedef = jax.tree_util.tree_structure(params)
        slot_trees = [
            jax.tree_util.tree_unflatten(treedef, self._unpack_slot(bufs))
            for bufs in state.slots
        ]
        it = iter(slot_trees)
        template = tx.init(params)
        out = _map_params_subtrees(
            template, params,
            lambda sub: jax.tree_util.tree_map(
                lambda ref, new: jnp.asarray(new, ref.dtype), sub, next(it)
            ),
        )
        count = jnp.asarray(np.asarray(state.count))
        return _map_count_leaves(
            out, lambda leaf: jnp.asarray(count, leaf.dtype)
        )

    # -- cross-step param carry (comm_op='rs_fwd_ag') --------------------
    # Params use the SAME padded-shard layout as the opt-state slots, so
    # one layout/world pair describes grads, params, and optimizer state;
    # the traced step's all-gather and the host-side interchange below can
    # never disagree on where a leaf's elements live.

    def params_partition_spec(self) -> ShardedParams:
        """PartitionSpecs matching `scatter_params` output: every group
        buffer split over the data axes (the shard each device owns)."""
        from jax.sharding import PartitionSpec as P

        return ShardedParams(
            tuple(P(self.axes) for _ in range(self.layout.num_groups))
        )

    def params_struct(self) -> ShardedParams:
        """Abstract ShardedParams (ShapeDtypeStructs) matching
        `scatter_params` output — for tracing-only consumers (the jaxpr
        verifier), where no concrete params exist to scatter."""
        return ShardedParams(
            tuple(
                jax.ShapeDtypeStruct(
                    (self.world, self.shard_size(gi)),
                    self.layout.dtypes[gi],
                )
                for gi in range(self.layout.num_groups)
            )
        )

    def scatter_params(self, params: Any) -> ShardedParams:
        """Replicated param pytree -> the cross-step sharded carry form
        (host-side numpy pack; checkpoint-restore / init path)."""
        return ShardedParams(
            tuple(
                jnp.asarray(b)
                for b in self._pack_slot(jax.tree_util.tree_leaves(params))
            )
        )

    def gather_params(self, shards: ShardedParams, params_template: Any):
        """Sharded carry -> the canonical replicated param pytree
        (host-side numpy unpack; checkpoint-save / eval path).
        `params_template` supplies structure and leaf dtypes (arrays or
        ShapeDtypeStructs)."""
        leaves = self._unpack_slot(shards.groups)
        treedef = jax.tree_util.tree_structure(params_template)
        refs = jax.tree_util.tree_leaves(params_template)
        return jax.tree_util.tree_unflatten(
            treedef,
            [jnp.asarray(a, r.dtype) for a, r in zip(leaves, refs)],
        )

    def scatter(self, opt_state: Any, params: Any) -> ShardedOptState:
        """Replicated optax state -> the sharded representation."""
        collected: list[Any] = []

        def collect(sub):
            collected.append(sub)
            return sub

        _map_params_subtrees(opt_state, params, collect)
        if len(collected) != self.num_slots:
            raise ValueError(
                f"opt state carries {len(collected)} params-shaped "
                f"subtree(s), the spec expects {self.num_slots} "
                f"(kind={self.spec.kind!r}, momentum={self.spec.momentum})"
            )
        slots = tuple(
            self._pack_slot(jax.tree_util.tree_leaves(sub))
            for sub in collected
        )
        counts: list[int] = []
        _map_count_leaves(
            opt_state, lambda leaf: counts.append(int(leaf)) or leaf
        )
        count = jnp.asarray(counts[0] if counts else 0, jnp.int32)
        return ShardedOptState(
            count=count,
            slots=tuple(
                tuple(jnp.asarray(b) for b in s) for s in slots
            ),
        )

    # -- multi-host interchange (ISSUE 13) --------------------------------
    # The host pack/unpack above needs every buffer locally addressable,
    # which is exactly what a multi-host mesh denies. These helpers close
    # the seam COLLECTIVELY: `replicate` all-gathers the sharded buffers
    # into replicated (hence addressable) global arrays through one jitted
    # identity program, after which the host unpack works unchanged and
    # bitwise; `scatter_onto`/`scatter_params_onto` place host-packed
    # buffers back as P(axes)-sharded GLOBAL arrays (every process holds
    # the full replicated source, so the callback slices locally — no
    # cross-host device_put). Used only where a replicated view is
    # genuinely needed (eval, autotune hot-swap, the --ckpt-format
    # replicated escape hatch); checkpoints proper are shard-native.

    def _prog_cache(self) -> dict:
        cache = self.__dict__.get("_progs")
        if cache is None:
            object.__setattr__(self, "_progs", {})
            cache = self.__dict__["_progs"]
        return cache

    def replicate(self, tree: Any) -> Any:
        """All-gather every leaf of a sharded pytree into replicated
        global arrays (`mesh.gather_replicated`); single-process trees
        come back unchanged — they are already addressable."""
        if jax.process_count() == 1:
            return tree
        mesh = None
        for leaf in jax.tree_util.tree_leaves(tree):
            sharding = getattr(leaf, "sharding", None)
            if hasattr(sharding, "mesh"):
                mesh = sharding.mesh
                break
        if mesh is None:
            return tree
        from mgwfbp_tpu.parallel.mesh import gather_replicated

        return gather_replicated(tree, mesh, self._prog_cache())

    def _shard_put(self, host_buf: np.ndarray, mesh) -> jax.Array:
        """One host-packed (world, shard) buffer -> the P(axes)-sharded
        global array (each process materializes only its own rows)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(self.axes))
        buf = np.asarray(host_buf)
        return jax.make_array_from_callback(
            buf.shape, sharding, lambda idx: buf[idx]
        )

    def scatter_params_onto(self, params: Any, mesh) -> ShardedParams:
        """`scatter_params` that lands as sharded GLOBAL arrays on `mesh`
        (multi-host-safe; each process's devices get only their rows)."""
        packed = self._pack_slot(jax.tree_util.tree_leaves(params))
        return ShardedParams(
            tuple(self._shard_put(b, mesh) for b in packed)
        )

    def scatter_onto(
        self, opt_state: Any, params: Any, mesh
    ) -> ShardedOptState:
        """`scatter` that lands as sharded GLOBAL arrays on `mesh`."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        state = self.scatter(opt_state, params)
        rep = NamedSharding(mesh, P())
        return ShardedOptState(
            count=jax.device_put(state.count, rep),
            slots=tuple(
                tuple(self._shard_put(np.asarray(b), mesh) for b in s)
                for s in state.slots
            ),
        )

    # -- shard-native checkpoint layout (ISSUE 13) ------------------------
    def manifest_layout(self) -> dict:
        """The per-leaf shard layout the checkpoint manifest records:
        for every PARAMETER-TREE leaf (canonical tree order), which merge
        group its elements pack into and at what offset within the padded
        bucket — plus the per-group shard geometry. A restore onto any
        world size / merge schedule re-slices leaves through this map."""
        # arrival index k -> (group, offset) from the bucket layout
        arrival_slot: dict[int, tuple[int, int]] = {}
        for gi, (members, offsets) in enumerate(
            zip(self.layout.groups, self.layout.offsets)
        ):
            for k, off in zip(members, offsets):
                arrival_slot[int(k)] = (gi, int(off))
        # tree leaf j = perm[k] for arrival position k
        tree_slot: list[Optional[tuple[int, int]]] = [None] * len(self.perm)
        for k, j in enumerate(self.perm):
            tree_slot[int(j)] = arrival_slot[int(k)]
        return {
            "world": int(self.world),
            "shard_sizes": [
                int(self.shard_size(gi))
                for gi in range(self.layout.num_groups)
            ],
            "group_dtypes": [
                jnp.dtype(d).name for d in self.layout.dtypes
            ],
            "leaf_slots": [list(s) for s in tree_slot],
        }

    # -- the fused shard update ------------------------------------------
    def update_shard(
        self,
        gi: int,
        grad: jax.Array,
        param: jax.Array,
        slots_in: Sequence[jax.Array],
        count: jax.Array,
        clip_scale: Optional[jax.Array],
        rank: jax.Array,
    ) -> tuple[jax.Array, tuple[jax.Array, ...]]:
        """One group's optimizer step on its shard. Mirrors the optax chain
        `spec.make_tx()` builds, term for term (see optax.trace /
        scale_by_adam / add_decayed_weights / scale_by_learning_rate):
        `count` is the number of COMPLETED optimizer steps (lr schedules
        read it pre-increment, Adam bias correction post-increment, exactly
        optax's conventions)."""
        spec = self.spec
        g = grad
        if clip_scale is not None:
            # clip_scale carries (g_norm, max_norm); mirror optax's exact
            # arithmetic — lax.select(trigger, t, (t / g_norm) * max_norm)
            # — so the only clip-path difference vs the replicated chain is
            # the norm's summation order, not an extra rounding step
            g_norm, max_norm = clip_scale
            g = lax.select(
                jnp.broadcast_to(g_norm < max_norm, g.shape),
                g,
                (g / g_norm.astype(g.dtype)) * max_norm.astype(g.dtype),
            )
        mask = None
        if spec.weight_decay:
            vec = jnp.asarray(self.decay_mask_vec(gi), g.dtype)
            mask = lax.dynamic_slice_in_dim(
                vec, rank * self.shard_size(gi), self.shard_size(gi)
            )
        lr = spec.learning_rate(count)
        if spec.kind == "sgd":
            if spec.weight_decay:
                g = g + spec.weight_decay * param * mask
            if spec.momentum:
                mu = g + spec.momentum * slots_in[0]
                u = g + spec.momentum * mu if spec.nesterov else mu
                new_slots = (mu,)
            else:
                u, new_slots = g, ()
        else:  # adam / adamw
            mu = spec.b1 * slots_in[0] + (1.0 - spec.b1) * g
            nu = spec.b2 * slots_in[1] + (1.0 - spec.b2) * g * g
            c = (count + 1).astype(g.dtype)
            mu_hat = mu / (1.0 - spec.b1**c)
            nu_hat = nu / (1.0 - spec.b2**c)
            u = mu_hat / (jnp.sqrt(nu_hat) + spec.eps)
            if spec.weight_decay:  # decoupled (adamw): after preconditioner
                u = u + spec.weight_decay * param * mask
            new_slots = (mu, nu)
        new_param = param - jnp.asarray(lr, u.dtype) * u
        return new_param, new_slots


def _map_params_subtrees(opt_state: Any, params: Any, fn) -> Any:
    """Rebuild `opt_state` with every subtree STRUCTURALLY identical to
    `params` replaced by `fn(subtree)`, in deterministic traversal order.

    This is the generic bridge between an opaque optax state pytree and the
    sharded representation: the params-shaped subtrees (optax.trace's
    momentum, scale_by_adam's mu/nu) are exactly the leaves worth sharding,
    and every elementwise optax transform stores them as such. Scalar
    state (counts, empty states) passes through untouched."""
    p_def = jax.tree_util.tree_structure(params)

    def is_mirror(x: Any) -> bool:
        try:
            return jax.tree_util.tree_structure(x) == p_def
        except Exception:
            return False

    leaves, treedef = jax.tree_util.tree_flatten(opt_state, is_leaf=is_mirror)
    return jax.tree_util.tree_unflatten(
        treedef, [fn(l) if is_mirror(l) else l for l in leaves]
    )


def _map_count_leaves(opt_state: Any, fn) -> Any:
    """Apply fn to every integer scalar leaf (optax step counters)."""
    def visit(leaf):
        if (
            hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.integer)
            and getattr(leaf, "ndim", None) == 0
        ):
            return fn(leaf)
        return leaf

    return jax.tree_util.tree_map(visit, opt_state)


def _device_rank(axes: Sequence[str]) -> jax.Array:
    """Linear index of this device over `axes` (first listed slowest-
    varying) — the shard-assignment convention of `lax.psum_scatter` /
    `lax.all_gather` over multiple named axes, verified against both."""
    r = lax.axis_index(axes[0])
    for a in axes[1:]:
        r = r * lax.axis_size(a) + lax.axis_index(a)
    return r


def _chain_token(buf: jax.Array, token) -> jax.Array:
    """Thread the sequential-ordering token into `buf` (see merged_psum's
    docstring for why this survives every XLA simplifier pass)."""
    if token is None or not jnp.issubdtype(buf.dtype, jnp.inexact):
        return buf
    clean = jnp.where(jnp.isfinite(token), token, jnp.zeros_like(token))
    return buf + jnp.zeros((), buf.dtype) * clean.astype(buf.dtype)


def _order_after(buf: jax.Array, token) -> jax.Array:
    """`buf` with ONE element made to depend on `token`: merged_psum's
    ordering edge (its docstring has the why).

    `_chain_token` adds the token to the whole bucket, and XLA fuses that
    add into the kernel that PRODUCES the bucket's gradient, so the kernel
    takes the previous group's reduced value as an operand. Here the
    bucket's first element is rewritten in place (slice, add, update: the
    bucket is a fresh value, so no copy and no pass over it), which the
    TPU compiler keeps as a one-element update between the producer and
    the collective: only the collective's operand waits for the token.
    """
    if (
        token is None
        or buf.shape[0] == 0
        or not jnp.issubdtype(buf.dtype, jnp.inexact)
    ):
        return buf
    head = _chain_token(lax.slice_in_dim(buf, 0, 1), token)
    return lax.dynamic_update_slice_in_dim(buf, head, 0, axis=0)


def _rs_phase(
    g_arr, layout, optim, axes, world, mean, comm_dtype, sequential, token
):
    """Reduce-scatter every group's grad bucket (shared by the rs_opt_ag
    and rs_fwd_ag lowerings). Returns (per-group reduced mean shards,
    last token)."""
    g_shards: list[jax.Array] = []
    for gi in range(layout.num_groups):
        with jax.named_scope(group_scope_name(gi)):
            buf = buckets_lib.pack_group(g_arr, layout, gi)
            orig_dtype = buf.dtype
            if comm_dtype is not None and buf.dtype != comm_dtype:
                buf = buf.astype(comm_dtype)
            if sequential:
                buf = _chain_token(buf, token)
            pad = optim.padded_size(gi) - buf.shape[0]
            if pad:
                buf = jnp.pad(buf, (0, pad))
            shard = lax.psum_scatter(
                buf, axes, scatter_dimension=0, tiled=True
            )
            token = shard[0]
            if shard.dtype != orig_dtype:
                shard = shard.astype(orig_dtype)
            if mean:
                shard = shard / world
            g_shards.append(shard)
    return g_shards, token


def _clip_phase(g_shards, optim, axes):
    """Global-norm clip scale: one cross-group psum of shard squared norms
    (scope CLIP_NORM_SCOPE) — the only way a global norm exists while every
    bucket is scattered. None when the spec does not clip."""
    if optim.spec.norm_clip is None:
        return None
    with jax.named_scope(CLIP_NORM_SCOPE):
        local = sum(
            jnp.sum(s.astype(jnp.float32) ** 2) for s in g_shards
        )
        g_norm = jnp.sqrt(lax.psum(local, axes))
        # (g_norm, threshold) pair; the shard update applies optax's
        # exact clip arithmetic (see update_shard)
        return (g_norm, jnp.float32(optim.spec.norm_clip))


def merged_fwd_allgather(
    param_shards: ShardedParams,
    layout: BucketLayout,
    perm: Sequence[int],
    axis_name: str | tuple[str, ...],
    optim: ShardedOptimStep,
    treedef: Any,
    sequential: bool = True,
) -> Any:
    """The cross-step lowering's FORWARD half: all-gather each merge
    group's carried param shard (produced by the PREVIOUS step's
    reduce-scatter + shard update) back into full leaves, group by group
    under the same `mgwfbp_groupNNNN` scopes.

    Groups are issued in REVERSE arrival order — the forward-consumption
    order: group G-1 holds the first forward layers (gradient-arrival
    index 0 is the LAST forward layer), so its gather must land first,
    while group 0's gather has the whole forward pass to hide behind. The
    sequential token chain serializes the gathers in that order (the
    solver's one-collective-at-a-time link model) and keeps XLA's
    AllGatherCombiner from re-merging them; dataflow alone guarantees each
    layer's forward waits for exactly its own group's gather — the
    AG-before-first-use deadline the cross-step cost model prices.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    out: list[Any] = [None] * len(optim.shapes)
    token = None
    for gi in reversed(range(layout.num_groups)):
        with jax.named_scope(group_scope_name(gi)):
            shard = param_shards.groups[gi].reshape(-1)
            if sequential:
                shard = _chain_token(shard, token)
            full = lax.all_gather(shard, axes, axis=0, tiled=True)
            token = full[0]
            n = layout.group_sizes[gi]
            if full.shape[0] != n:
                full = full[:n]
            unpacked = buckets_lib.unpack_group(
                full, layout, gi, optim.shapes
            )
        for i, a in unpacked.items():
            out[i] = a
    restored: list[Any] = [None] * len(out)
    for k, j in enumerate(perm):
        restored[j] = out[k]
    return jax.tree_util.tree_unflatten(treedef, restored)


def merged_rs_defer(
    grads: Any,
    param_shards: ShardedParams,
    opt_state: ShardedOptState,
    layout: BucketLayout,
    perm: Sequence[int],
    axis_name: str | tuple[str, ...],
    optim: ShardedOptimStep,
    mean: bool = True,
    comm_dtype: Optional[Any] = None,
    sequential: bool = True,
) -> tuple[ShardedParams, ShardedOptState]:
    """The cross-step lowering's BACKWARD half: reduce-scatter each merge
    group's grad bucket, update the carried param/opt-state shard — and
    STOP. No all-gather is issued: the updated shards ride out of the step
    as carried state, and the NEXT step's forward gathers them
    (`merged_fwd_allgather`). This is what moves each group's gather off
    the backward-side critical path and onto the next step's forward
    timeline (DeAR, arXiv:2302.12445).

    Numerically identical to `merged_rs_opt_ag` per step — same
    reduce-scatter, same fused shard update, same clip psum — only the
    gather's position in the program moves; params gathered at step N+1
    equal the values an rs_opt_ag step N would have gathered in-step.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    world = lax.axis_size(axes)
    if world != optim.world:
        raise ValueError(
            f"rs_fwd_ag: mesh extent {world} over {axes} != the "
            f"ShardedOptimStep's world {optim.world}; rebuild the reducer "
            "for this mesh"
        )
    g_leaves = jax.tree_util.tree_leaves(grads)
    g_arr = [g_leaves[j] for j in perm]
    rank = _device_rank(axes)

    g_shards, token = _rs_phase(
        g_arr, layout, optim, axes, world, mean, comm_dtype, sequential,
        token=None,
    )
    clip_scale = _clip_phase(g_shards, optim, axes)

    new_groups: list[jax.Array] = []
    new_slots: list[list[jax.Array]] = [
        [None] * layout.num_groups for _ in range(optim.num_slots)
    ]
    count = opt_state.count
    for gi in range(layout.num_groups):
        with jax.named_scope(group_scope_name(gi)):
            p_shard = param_shards.groups[gi].reshape(-1)
            slots_in = tuple(
                opt_state.slots[s][gi].reshape(-1)
                for s in range(optim.num_slots)
            )
            new_p, slots_out = optim.update_shard(
                gi, g_shards[gi], p_shard, slots_in, count, clip_scale, rank
            )
            new_groups.append(new_p[None, :])
            for s in range(optim.num_slots):
                new_slots[s][gi] = slots_out[s][None, :]
    return (
        ShardedParams(tuple(new_groups)),
        ShardedOptState(
            count=count + 1, slots=tuple(tuple(s) for s in new_slots)
        ),
    )


def merged_rs_opt_ag(
    grads: Any,
    params: Any,
    opt_state: ShardedOptState,
    layout: BucketLayout,
    perm: Sequence[int],
    axis_name: str | tuple[str, ...],
    optim: ShardedOptimStep,
    mean: bool = True,
    comm_dtype: Optional[Any] = None,
    sequential: bool = True,
) -> tuple[Any, ShardedOptState]:
    """Reduce-scatter grads, update the param/opt-state shard, all-gather
    updated params — one merge group at a time, under the same
    `mgwfbp_groupNNNN` scopes the other lowerings stamp.

    Three phases, all inside the one jitted step:
      1. per group: pack grads, (wire-cast,) reduce-scatter over the data
         axes — after this each device owns the REDUCED mean shard;
      2. when the spec clips: one cross-group psum of shard squared norms
         (scope `sharded_clip_norm`) — the only way a global norm exists
         while every bucket is scattered;
      3. per group: slice this device's shard of the packed param bucket,
         run the fused optimizer update against the shard's opt-state
         buffers, all-gather the UPDATED param shard, unpack.

    The sequential token chain threads through both collective phases, for
    the same two reasons as merged_psum: it realizes the solver's
    one-collective-at-a-time link model, and it stops XLA's collective
    combiners from re-merging the buckets.

    Returns (updated params pytree, new ShardedOptState). Gradients are
    consumed; callers skip `tx.update` entirely on this path.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    world = lax.axis_size(axes)
    if world != optim.world:
        raise ValueError(
            f"rs_opt_ag: mesh extent {world} over {axes} != the "
            f"ShardedOptimStep's world {optim.world}; rebuild the reducer "
            "for this mesh"
        )
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = jax.tree_util.tree_leaves(params)
    g_arr = [g_leaves[j] for j in perm]
    p_arr = [p_leaves[j] for j in perm]
    shapes = [l.shape for l in g_arr]
    rank = _device_rank(axes)
    num_groups = layout.num_groups

    # ---- phase 1: reduce-scatter every group's grad bucket ----
    g_shards, token = _rs_phase(
        g_arr, layout, optim, axes, world, mean, comm_dtype, sequential,
        token=None,
    )

    # ---- phase 2: global-norm clip scale (cross-group psum) ----
    clip_scale = _clip_phase(g_shards, optim, axes)

    # ---- phase 3: shard update + param all-gather ----
    out: list[Any] = [None] * len(g_arr)
    new_slots: list[list[jax.Array]] = [
        [None] * num_groups for _ in range(optim.num_slots)
    ]
    count = opt_state.count
    for gi in range(num_groups):
        with jax.named_scope(group_scope_name(gi)):
            pbuf = buckets_lib.pack_group(p_arr, layout, gi)
            pad = optim.padded_size(gi) - pbuf.shape[0]
            if sequential:
                pbuf = _chain_token(pbuf, token)
            if pad:
                pbuf = jnp.pad(pbuf, (0, pad))
            n = optim.shard_size(gi)
            p_shard = lax.dynamic_slice_in_dim(pbuf, rank * n, n)
            slots_in = tuple(
                opt_state.slots[s][gi].reshape(-1)
                for s in range(optim.num_slots)
            )
            new_p, slots_out = optim.update_shard(
                gi, g_shards[gi], p_shard, slots_in, count, clip_scale, rank
            )
            full = lax.all_gather(new_p, axes, axis=0, tiled=True)
            # token taken POST-gather (like merged_psum's post-collective
            # buf[0]): the next group's gather then depends on this one,
            # which both realizes the serial link model and denies XLA's
            # AllGatherCombiner the reordering it needs to re-merge buckets
            token = full[0]
            if pad:
                full = full[: layout.group_sizes[gi]]
            unpacked = buckets_lib.unpack_group(full, layout, gi, shapes)
            for s in range(optim.num_slots):
                new_slots[s][gi] = slots_out[s][None, :]
        for i, a in unpacked.items():
            out[i] = a
    restored: list[Any] = [None] * len(g_leaves)
    for k, j in enumerate(perm):
        restored[j] = out[k]
    new_params = jax.tree_util.tree_unflatten(treedef, restored)
    new_state = ShardedOptState(
        count=count + 1,
        slots=tuple(tuple(s) for s in new_slots),
    )
    return new_params, new_state


def merged_hier_allreduce(
    tree: Any,
    layout: BucketLayout,
    dcn_groups: Sequence[Sequence[int]],
    perm: Sequence[int],
    axis_name: tuple[str, ...],
    mean: bool = True,
    comm_dtype: Optional[Any] = None,
    sequential: bool = True,
) -> Any:
    """The hierarchical lowering of a NESTED schedule (comm_op='hier'):
    three token-chained phases realizing exactly the two-link timeline
    `solver.simulate_groups_two_level` prices.

      1. per inner group, under its ``mgwfbp_groupNNNN`` scope: pack the
         grad bucket, (wire-cast,) pad to inner-axis divisibility,
         reduce-scatter over the INNER (ICI) axis — each device now holds
         the slice-reduced 1/ici shard;
      2. per DCN group, under its ``mgwfbp_dcngroupNNNN`` scope: ONE
         all-reduce over the OUTER (DCN) axis of the members'
         concatenated shards — the per-link merge decision made real:
         small buckets amortize the DCN startup together while keeping
         their ICI granularity;
      3. per inner group, under its group scope again: mean-divide,
         all-gather over the inner axis, trim the pad, unpack.

    The token chains are PER LINK, mirroring the simulator's two serial
    links exactly: the ICI chain threads RS0..RSn and then seeds the AG
    phase (AGs start after the RS queue drains — the ici_free carry-over
    of `simulate_groups_two_level`); the DCN collectives carry their OWN
    chain, depending on each other plus — through ordinary dataflow on
    the member shards — on exactly their members' reduce-scatters, and
    each AG depends on its own post-DCN shard. A single global chain
    would serialize the DCN hops behind the LAST reduce-scatter, which is
    precisely the cross-link concurrency the two-link cost model prices
    (DCN group 0 overlapping later RS legs); per-link chains keep the
    issued dependency structure and the priced timeline the same shape.
    The chains still stop XLA's collective combiners from re-merging
    buckets or fusing the deliberately-separate DCN collectives.

    Numerically identical to a flat psum/pmean over both axes: psum is
    elementwise, so reducing concatenated shards together or apart
    cannot change any element's value."""
    if len(axis_name) != 2:
        raise ValueError(
            "merged_hier_allreduce needs axis_name=(inner_ici, outer_dcn)"
        )
    inner, outer = axis_name
    world = lax.axis_size(axis_name)
    ici = lax.axis_size((inner,))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arr = [leaves[j] for j in perm]
    shapes = [l.shape for l in arr]
    from mgwfbp_tpu.parallel.solver import (
        check_dcn_partition,
        singleton_dcn_groups,
    )

    if not dcn_groups:
        dcn_groups = singleton_dcn_groups(layout.num_groups)
    check_dcn_partition(dcn_groups, layout.num_groups)

    # ---- phase 1: per-group reduce-scatter over the inner (ICI) axis ----
    ici_token = None
    shards: list[jax.Array] = []
    orig_dtypes: list[Any] = []
    for gi in range(layout.num_groups):
        with jax.named_scope(group_scope_name(gi)):
            buf = buckets_lib.pack_group(arr, layout, gi)
            orig_dtypes.append(buf.dtype)
            if comm_dtype is not None and buf.dtype != comm_dtype:
                buf = buf.astype(comm_dtype)
            if sequential:
                buf = _chain_token(buf, ici_token)
            pad = (-buf.shape[0]) % ici
            if pad:
                buf = jnp.pad(buf, (0, pad))
            shard = lax.psum_scatter(
                buf, (inner,), scatter_dimension=0, tiled=True
            )
            ici_token = shard[0]
            shards.append(shard)

    # ---- phase 2: one cross-slice all-reduce per DCN group ----
    # the DCN link's OWN chain: group di waits for di-1 (serial link) and
    # — via the concatenated member shards themselves — for exactly its
    # members' reduce-scatters, NOT the whole RS phase
    dcn_token = None
    for di, d in enumerate(dcn_groups):
        members = [int(gi) for gi in d]
        if len({shards[gi].dtype for gi in members}) > 1:
            raise ValueError(
                f"hier dcn group {di} mixes bucket dtypes "
                f"{[str(shards[gi].dtype) for gi in members]}; split it at "
                "dtype boundaries (solver.align_dcn_groups)"
            )
        with jax.named_scope(dcn_group_scope_name(di)):
            cat = (
                shards[members[0]]
                if len(members) == 1
                else jnp.concatenate([shards[gi] for gi in members])
            )
            if sequential:
                cat = _chain_token(cat, dcn_token)
            red = lax.psum(cat, outer)
            dcn_token = red[0]
            if len(members) == 1:
                shards[members[0]] = red
            else:
                off = 0
                for gi in members:
                    ln = shards[gi].shape[0]
                    shards[gi] = red[off:off + ln]
                    off += ln

    # ---- phase 3: per-group all-gather over the inner axis, unpack ----
    # back on the ICI chain: the AG queue opens once the RS queue drained
    # (ici_token still carries the last reduce-scatter), and each gather's
    # input is its own post-DCN shard — the same gating the simulator's
    # max(ici_free, dcn_done) start expresses
    out: list[Any] = [None] * len(arr)
    for gi in range(layout.num_groups):
        with jax.named_scope(group_scope_name(gi)):
            shard = shards[gi]
            if mean:
                shard = shard / world
            if sequential:
                shard = _chain_token(shard, ici_token)
            full = lax.all_gather(shard, (inner,), axis=0, tiled=True)
            ici_token = full[0]
            n = layout.group_sizes[gi]
            if full.shape[0] != n:
                full = full[:n]
            if full.dtype != orig_dtypes[gi]:
                full = full.astype(orig_dtypes[gi])
            unpacked = buckets_lib.unpack_group(full, layout, gi, shapes)
        for i, a in unpacked.items():
            out[i] = a
    restored: list[Any] = [None] * len(leaves)
    for k, j in enumerate(perm):
        restored[j] = out[k]
    return jax.tree_util.tree_unflatten(treedef, restored)


def merged_psum(
    tree: Any,
    layout: BucketLayout,
    perm: Sequence[int],
    axis_name: str | tuple[str, ...],
    mean: bool = True,
    comm_dtype: Optional[Any] = None,
    compressor: Optional[Any] = None,
    sequential: bool = True,
    comm_op: str = "all_reduce",
    dcn_groups: Sequence[Sequence[int]] = (),
) -> Any:
    """All-reduce a gradient pytree group-by-group per the bucket layout.

    Must be called inside shard_map/pmap with `axis_name` bound. `comm_dtype`
    optionally casts buckets for the wire (the reference's FP16 path,
    distributed_optimizer.py:398-399 / settings.FP16) and casts back.
    `compressor` (parallel.compression) swaps the dense pmean for a sparse
    top-k allgather per bucket (reference --compressor seam).

    `sequential=True` threads a dataflow token from each group's reduced
    bucket into the next group's input. This does two load-bearing things:
      1. It IS the MG-WFBP comm model: the solver's recurrence
         taoc[l] = max(taoc[l+1] + tc[l+1], taob[l] + tb[l]) (reference
         distributed_optimizer.py:187-192) assumes collectives execute one
         at a time in arrival order — the token chain makes XLA honor that
         order while leaving comm free to overlap BACKWARD COMPUTE.
      2. It stops XLA's AllReduceCombiner from re-merging the buckets into
         one giant collective (combining across a dependency is illegal).
         That pass is the XLA analogue of Horovod's fusion buffer, which
         the reference explicitly zeroes so MG-WFBP alone controls merging
         (reference dist_trainer.py:16-17, HOROVOD_FUSION_THRESHOLD=0).
    The token rides as `+ 0.0 * where(isfinite(t), t, 0)`: XLA cannot fold
    `0*x` (IEEE: 0*x is not 0 for NaN/inf) and has no finiteness range
    analysis to see through the `where`, so the dependency survives every
    simplifier pass — while the `where` guarantees a NaN/inf in one bucket
    never leaks into later buckets' gradients.

    WHAT the token hangs on (`_order_after`, PR 29): ONE element of the
    bucket, rewritten in place between the kernel that produces the bucket
    and the collective. Only the collective's operand depends on the
    previous group's reduced value; the gradient's producer does not.
    Until PR 29 the token was added to the whole bucket, XLA fused that
    add into the kernel that computes the weight gradient
    (`convert_add_fusion`, 14.4 ms a step on four-chip VGG-16), and that
    kernel took the previous group's reduced bucket as an operand:
    harmless while every all-reduce is synchronous, but the train step is
    now compiled with asynchronous all-reduces on a multi-chip TPU mesh
    (train/step.py `async_collective_options`), and a producer that waits
    for the previous collective's `-done` puts the backward pass behind
    the exchange instead of beside it. It also pinned the order in which
    the weight gradients are computed, which cost 1.75 GiB of temporaries
    on that step (5.569 against 3.814 GiB compiled without the options;
    PERF.md, PR 29). tests/test_exchange_order.py
    states the property on the traced program. The other lowerings
    (`_chain_token`) keep the whole-bucket token: no cell runs them.
    (`lax.optimization_barrier((buf, token))` would say the same thing
    more plainly, but inside `shard_map` both the CPU's and the TPU's
    compiler drop it before the all-reduce combiner runs: the 32 buckets
    of four-chip VGG-16 came out as 3 combined all-reduces, PERF.md, PR 29.)
    """
    if comm_op not in ("all_reduce", "rs_ag", "hier"):
        raise ValueError(
            f"unknown comm_op {comm_op!r}; expected 'all_reduce', 'rs_ag' "
            "or 'hier' (the 'rs_opt_ag' lowering consumes params/opt-state "
            "too — call MergedAllreduce.reduce_and_update; 'rs_fwd_ag' "
            "splits across the step boundary — gather_params / "
            "reduce_and_defer)"
        )
    if compressor is not None and comm_op != "all_reduce":
        raise ValueError(
            f"comm_op={comm_op!r} cannot combine with a sparsifying "
            "compressor (the compressor replaces the bucket collective)"
        )
    _check_hier_axes(comm_op, axis_name)
    if comm_op == "hier":
        # the hierarchical lowering realizes a NESTED schedule (per-group
        # inner RS/AG + per-DCN-group outer collectives) — its own three-
        # phase program, not a per-group swap-in
        return merged_hier_allreduce(
            tree, layout, dcn_groups, perm, tuple(axis_name),
            mean=mean, comm_dtype=comm_dtype, sequential=sequential,
        )
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arr = [leaves[j] for j in perm]
    shapes = [l.shape for l in arr]
    out: list[Any] = [None] * len(arr)
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    token = None
    for gi in range(layout.num_groups):
        # The named scope is the verifier's introspection hook: every
        # primitive issued for this group (pack, the collective, unpack)
        # carries group_scope_name(gi) in its jaxpr/XLA op metadata, so
        # analysis.jaxpr_check can match lowered collectives to schedule
        # groups without runtime instrumentation.
        with jax.named_scope(group_scope_name(gi)):
            buf = buckets_lib.pack_group(arr, layout, gi)
            orig_dtype = buf.dtype
            if comm_dtype is not None and buf.dtype != comm_dtype:
                buf = buf.astype(comm_dtype)
            if sequential:
                buf = _order_after(buf, token)
            if compressor is not None and jnp.issubdtype(
                buf.dtype, jnp.floating
            ):
                buf = compressor.allreduce(buf, axes, mean)
            elif comm_op == "rs_ag":
                buf = _rs_ag_allreduce(buf, axes, mean)
            else:
                buf = lax.pmean(buf, axes) if mean else lax.psum(buf, axes)
            token = buf[0]
            if buf.dtype != orig_dtype:
                buf = buf.astype(orig_dtype)
            unpacked = buckets_lib.unpack_group(buf, layout, gi, shapes)
        for i, a in unpacked.items():
            out[i] = a
    restored: list[Any] = [None] * len(leaves)
    for k, j in enumerate(perm):
        restored[j] = out[k]
    return jax.tree_util.tree_unflatten(treedef, restored)


@dataclasses.dataclass(frozen=True)
class MergedAllreduce:
    """Bound (schedule, layout, permutation) for one model's grad pytree.

    The functional analogue of the reference's `DistributedOptimizer` wrapper
    (distributed_optimizer.py:435-471): construct once from the parameter
    structure + timing profile, then apply inside the jitted train step.
    """

    schedule: MergeSchedule
    layout: BucketLayout
    perm: tuple[int, ...]
    axis_name: str | tuple[str, ...]
    mean: bool = True
    comm_dtype: Optional[Any] = None
    compressor: Optional[Any] = None
    sequential: bool = True
    comm_op: str = "all_reduce"  # all_reduce | rs_ag (DeAR decomposition) |
    # hier (two-level ICI+DCN; needs axis_name=(inner_ici, outer_dcn) —
    # the trainer wires it via --dcn-slices + --comm-op hier) |
    # rs_opt_ag (sharded optimizer between RS and AG; needs `optim`) |
    # rs_fwd_ag (cross-step: RS + shard update at backward, the param
    # all-gather deferred into the NEXT step's forward; needs `optim`,
    # params carried as ShardedParams)
    optim: Optional[ShardedOptimStep] = None  # rs_opt_ag / rs_fwd_ag only
    # pytree structure of the param/grad tree (rs_fwd_ag's in-step gather
    # rebuilds the full params from shards without a tree-shaped argument)
    treedef: Optional[Any] = None

    def __call__(self, grads: Any) -> Any:
        if self.comm_op in ("rs_opt_ag", "rs_fwd_ag"):
            raise ValueError(
                f"comm_op={self.comm_op!r} folds the optimizer into the "
                "collective; call reduce_and_update / reduce_and_defer "
                "instead of the grads-only reduction"
            )
        return merged_psum(
            grads,
            self.layout,
            self.perm,
            self.axis_name,
            mean=self.mean,
            comm_dtype=self.comm_dtype,
            compressor=self.compressor,
            sequential=self.sequential,
            comm_op=self.comm_op,
            dcn_groups=self.schedule.dcn_groups,
        )

    def reduce_and_update(
        self, grads: Any, params: Any, opt_state: ShardedOptState
    ) -> tuple[Any, ShardedOptState]:
        """The rs_opt_ag step: reduced grads never materialize — params
        come back updated and the sharded opt state advanced."""
        if self.comm_op != "rs_opt_ag" or self.optim is None:
            raise ValueError(
                "reduce_and_update requires comm_op='rs_opt_ag' (built via "
                "make_merged_allreduce(..., optim_spec=..., world_size=...))"
            )
        return merged_rs_opt_ag(
            grads,
            params,
            opt_state,
            self.layout,
            self.perm,
            self.axis_name,
            self.optim,
            mean=self.mean,
            comm_dtype=self.comm_dtype,
            sequential=self.sequential,
        )

    # -- the cross-step (rs_fwd_ag) halves --------------------------------
    def gather_params(self, param_shards: ShardedParams) -> Any:
        """The FORWARD half of the cross-step step: gather the carried
        shards into the full param pytree, group by group in
        forward-consumption order (traced; see merged_fwd_allgather)."""
        if self.comm_op != "rs_fwd_ag" or self.optim is None:
            raise ValueError(
                "gather_params requires comm_op='rs_fwd_ag' (built via "
                "make_merged_allreduce(..., optim_spec=..., world_size=...))"
            )
        return merged_fwd_allgather(
            param_shards,
            self.layout,
            self.perm,
            self.axis_name,
            self.optim,
            self.treedef,
            sequential=self.sequential,
        )

    def reduce_and_defer(
        self,
        grads: Any,
        param_shards: ShardedParams,
        opt_state: ShardedOptState,
    ) -> tuple[ShardedParams, ShardedOptState]:
        """The BACKWARD half of the cross-step step: reduce-scatter grads,
        update the carried shards, defer the gather to the next step's
        forward (traced; see merged_rs_defer)."""
        if self.comm_op != "rs_fwd_ag" or self.optim is None:
            raise ValueError(
                "reduce_and_defer requires comm_op='rs_fwd_ag' (built via "
                "make_merged_allreduce(..., optim_spec=..., world_size=...))"
            )
        return merged_rs_defer(
            grads,
            param_shards,
            opt_state,
            self.layout,
            self.perm,
            self.axis_name,
            self.optim,
            mean=self.mean,
            comm_dtype=self.comm_dtype,
            sequential=self.sequential,
        )


def make_merged_allreduce(
    params_or_shapes: Any,
    *,
    axis_name: str | tuple[str, ...],
    policy: str = "mgwfbp",
    tb: Optional[Sequence[float]] = None,
    tf: Optional[Sequence[float]] = None,
    cost_model: Any = None,
    threshold: int = 0,
    perm: Optional[Sequence[int]] = None,
    names: Optional[Sequence[str]] = None,
    mean: bool = True,
    comm_dtype: Optional[Any] = None,
    compressor: Optional[Any] = None,
    comm_op: str = "all_reduce",
    optim_spec: Optional[OptimSpec] = None,
    world_size: Optional[int] = None,
    groups: Optional[Sequence[Sequence[int]]] = None,
    dcn_groups: Optional[Sequence[Sequence[int]]] = None,
    policy_detail: Optional[str] = None,
) -> MergedAllreduce:
    """Build the merged-allreduce transform for a parameter pytree.

    params_or_shapes: pytree of arrays or ShapeDtypeStructs (the grad tree
    structure). tb: per-arrival backward durations (seconds); when absent and
    policy='mgwfbp', falls back to a size-proportional estimate — sizes are
    the dominant term of backward time for conv/dense layers, so the schedule
    degrades gracefully before profiling has run.

    comm_op='rs_opt_ag' (and the cross-step 'rs_fwd_ag') additionally
    needs `optim_spec` (the elementwise optimizer to run on the bucket
    shards, optim.OptimSpec) and `world_size` (the static extent of the
    data axes — shard layouts must exist before any mesh axis is bound).
    For 'rs_fwd_ag', `tf` is the arrival-ordered per-layer FORWARD profile
    the cross-step simulate prices AG-before-first-use deadlines against
    (falls back to `solver.forward_prior_tf(tb)` when absent).

    groups: an EXPLICIT arrival-order grouping that bypasses the policy
    solve (autotuner candidates / schedule-cache hits; see
    `solver.build_schedule`), labeled by `policy_detail`. For
    comm_op='hier', `dcn_groups` is the matching explicit OUTER (DCN)
    partition of the inner groups; absent, the solve (policy='auto'
    under a two-level cost model) or the one-DCN-collective-per-group
    default applies. The issued partition is re-aligned to the final
    bucket layout (dtype splits) before anything lowers.
    """
    leaves = jax.tree_util.tree_leaves(params_or_shapes)
    n = len(leaves)
    if names is None:
        paths = jax.tree_util.tree_flatten_with_path(params_or_shapes)[0]
        all_names = [jax.tree_util.keystr(kp) for kp, _ in paths]
    else:
        all_names = list(names)
    # fail at construction, not at first traced call
    _check_hier_axes(comm_op, axis_name)
    if comm_op in ("rs_opt_ag", "rs_fwd_ag"):
        if optim_spec is None or world_size is None:
            raise ValueError(
                f"comm_op={comm_op!r} requires optim_spec and world_size"
            )
        if compressor is not None:
            raise ValueError(
                f"comm_op={comm_op!r} cannot combine with a sparsifying "
                "compressor (the shard update needs the dense reduction)"
            )
    p = arrival_order(n, perm, names=all_names)
    arr = [leaves[j] for j in p]
    names_arr = [all_names[j] for j in p]
    check_unique(names_arr)
    def _numel(l):
        sz = 1
        for d in l.shape:
            sz *= int(d)
        return sz

    specs = [
        LayerSpec(name=nm, size=_numel(l), itemsize=jnp.dtype(l.dtype).itemsize)
        for nm, l in zip(names_arr, arr)
    ]
    if policy in ("mgwfbp", "auto") and tb is None:
        # Fallback prior when no measured profile exists (solver.
        # size_prior_tb: shape from parameter volume, scale from the cost
        # model). A measured tb (Trainer._profile_backward) always takes
        # precedence.
        tb = size_prior_tb(specs, cost_model)
    if comm_op == "rs_fwd_ag" and tb is not None and tf is None:
        from mgwfbp_tpu.parallel.solver import forward_prior_tf

        tf = forward_prior_tf(tb)
    schedule = build_schedule(
        specs, tb, tf=tf, policy=policy, cost_model=cost_model,
        threshold=threshold, comm_op=comm_op,
        groups=groups, dcn_groups=dcn_groups, policy_detail=policy_detail,
    )
    layout = build_layout(arr, schedule.groups)
    dcn_part = None
    if comm_op == "hier":
        # the DCN partition must describe the groups ACTUALLY issued:
        # remap it across any dtype split of the inner groups, then split
        # DCN groups themselves at dtype boundaries (one concatenated
        # shard buffer per DCN collective needs one dtype)
        from mgwfbp_tpu.parallel.solver import (
            align_dcn_groups,
            remap_dcn_groups,
            singleton_dcn_groups,
        )

        dcn_part = [list(d) for d in schedule.dcn_groups] or (
            singleton_dcn_groups(len(schedule.groups))
        )
        if layout.groups != schedule.groups:
            dcn_part = remap_dcn_groups(
                schedule.groups, layout.groups, dcn_part
            )
        if comm_dtype is None:
            # a wire cast unifies every shard's dtype, so mixed-dtype DCN
            # groups concat legally there — splitting anyway would pay an
            # extra cross-slice alpha per step for nothing
            dcn_part = align_dcn_groups(dcn_part, layout.dtypes)
    layout_changed = layout.groups != schedule.groups
    dcn_changed = comm_op == "hier" and tuple(
        tuple(d) for d in dcn_part
    ) != schedule.dcn_groups
    if layout_changed or dcn_changed:
        # build_layout split one or more groups at dtype boundaries (or
        # the DCN partition re-aligned); each split adds a real collective
        # (and its alpha), so re-simulate the predictions on the schedule
        # actually issued.
        schedule = dataclasses.replace(
            schedule,
            groups=layout.groups,
            dcn_groups=(
                tuple(tuple(int(i) for i in d) for d in dcn_part)
                if dcn_part is not None
                else schedule.dcn_groups
            ),
        )
        if tb is not None and cost_model is not None:
            cost_fn = effective_cost_fn(cost_model, comm_op)
            sizes_b = [s.nbytes for s in specs]
            if comm_op == "rs_fwd_ag":
                from mgwfbp_tpu.parallel.solver import (
                    cross_step_phase_costs,
                    simulate_cross_step,
                )

                rs_cost, ag_cost = cross_step_phase_costs(cost_model)
                total, nonoverlap, comm = simulate_cross_step(
                    layout.groups, sizes_b, tb, tf, rs_cost, ag_cost,
                    float(getattr(cost_model, "gamma", 0.0)),
                    float(getattr(cost_model, "overlap", 1.0)),
                    float(getattr(cost_model, "pack_beta", 0.0)),
                )
            elif comm_op == "hier" and is_two_level(cost_model):
                from mgwfbp_tpu.parallel.solver import (
                    simulate_groups_two_level,
                    two_level_leg_costs,
                )

                rs_c, dcn_c, ag_c = two_level_leg_costs(cost_model)
                total, nonoverlap, comm = simulate_groups_two_level(
                    layout.groups, dcn_part, sizes_b, tb,
                    rs_c, dcn_c, ag_c,
                    gamma=float(getattr(cost_model.ici, "gamma", 0.0)),
                    dcn_gamma=float(getattr(cost_model.dcn, "gamma", 0.0)),
                    overlap=float(getattr(cost_model, "overlap", 1.0)),
                    pack_beta=float(getattr(cost_model, "pack_beta", 0.0)),
                )
            else:
                total, nonoverlap, comm = simulate_groups(
                    layout.groups, sizes_b, tb, cost_fn,
                    float(getattr(cost_model, "gamma", 0.0)),
                    float(getattr(cost_model, "overlap", 1.0)),
                    float(getattr(cost_model, "pack_beta", 0.0)),
                )
            schedule = dataclasses.replace(
                schedule,
                predicted_total_time=total,
                predicted_nonoverlap_time=nonoverlap,
                predicted_comm_time=comm,
                predicted_group_times=predict_group_times(
                    layout.groups, sizes_b, cost_fn
                ),
            )
    optim = None
    if comm_op in ("rs_opt_ag", "rs_fwd_ag"):
        axes = (
            (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        )
        optim = ShardedOptimStep(
            spec=optim_spec,
            layout=layout,
            shapes=tuple(tuple(int(d) for d in l.shape) for l in arr),
            perm=tuple(p),
            axes=axes,
            world=int(world_size),
        )
    return MergedAllreduce(
        schedule=schedule,
        layout=layout,
        perm=tuple(p),
        axis_name=axis_name,
        mean=mean,
        comm_dtype=comm_dtype,
        compressor=compressor,
        comm_op=comm_op,
        optim=optim,
        treedef=jax.tree_util.tree_structure(params_or_shapes),
    )
