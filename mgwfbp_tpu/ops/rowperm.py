"""The experts' row permutations: `take_rows` packs the tokens' rows into
groups (dispatch) and `combine_rows` adds a token's weighted rows back
(combine). Each is the other's transpose.

**The contract.** `order` (M,) lists the M = N x k (token, k) assignments
sorted by group and, inside a group, by token (a stable sort by group);
`inverse` (M,) is where each assignment went; `sizes` (G,) the groups' row
counts, `valid` their sum. Sorted rows from `valid` on are in no group. k is
the `weights`' second dimension, whatever the router's: where a token chooses
more experts than are held, `lm_parts._grouped_experts` hands over a token's
held choices alone, k = the experts held.

  * `take_rows(src (N, D), order, inverse, sizes)` -> (M, D): row r is
    `src[order[r] // k]` for r < `valid`; rows from `valid` on hold ANYTHING
    (what `groupmm.grouped_product` promises of its result too).
  * `combine_rows(rows (M, D), order, inverse, weights (N, k), sizes)` ->
    (N, D): `y[n] = sum_j [inverse[n, j] < valid] weights[n, j] *
    rows[inverse[n, j]]`, accumulated in float32 in the order j = 0 .. k - 1
    and rounded once to the rows' dtype; rows from `valid` on are never read.

With weights of one `combine_rows` is `take_rows`'s transpose, and d `rows`
of `combine_rows` is the cotangent's row of every sorted assignment times
its weight; d `weights` is the row-wise dot of that same row with `rows`,
unsorted as M scalars: the backward pass needs no `rows[inverse]`. Neither
transpose reads a row past `valid`.

**Where the time was, and what each way down is** (my chip runs, PR 37; a
v5e, Mellum 2's share: N 16,384, k 8, D 2,304, 16 groups, 35% of the M =
131,072 rows in a group; ms a call, host clock over five chained calls).
XLA gathers rows from a table at 38 ns a row, EXCEPT from a table that fits
VMEM: `src[order // k]` over all M rows takes 1.08 ms (8 ns a row, the rate
at which 604 MB can be written), `rows[inverse]` over the same M rows 5.0.
So the dispatch was never the cost; the three gathers from an (M, D) table
were (the combine's, and both transposes as the parent wrote them), with the
masked select and the weighted sum beside them (6.8 ms a combine).

  * `take_rows` is XLA's gather on the chip as off it. What was tried
    against it and lost: a kernel of this module's own that held the packed
    table in VMEM and moved a row with one dynamic-sublane load and store
    (0.55 ms at 35%, 1.27 at 100%, plus 0.8 to pack the bfloat16 rows into
    32-bit words: 1.35 and 2.07; a Mosaic DMA cannot move ONE row of a tiled
    (rows, D) array: "slice shape along dimension 0 must be aligned to
    tiling (8)"), and a `lax.while_loop` of block gathers for as many blocks
    as `valid` needs (1.86 at 35%, 5.1 at 100%: inside the loop XLA's gather
    runs at 39 ns a row, the table in HBM).
  * `combine_rows` is, where the step is traced for a TPU and the shape fits
    (`_kernel_plan`), ONE kernel of this module's own: a grid over blocks of
    `_TOKENS` tokens. The rows a block's tokens need lie, for each group, in
    ONE contiguous range of the sorted rows (rows are sorted by token inside
    a group): the wrapper lists the 16-row chunks those ranges touch (from
    `sizes` and a count of the block's assignments a group), the
    kernel copies exactly those chunks into a window in VMEM (asynchronous
    copies, the next block's issued before this block is summed), widens the
    window to float32 and adds each token's k rows in the order j. An
    assignment in no group points at a row of zeros: the scalar branch round
    it cost more than the 18 loads and multiply-adds it saved (1.86 against
    1.71 ms at 35%). As committed 1.53 ms at 35%, 2.14 at 100% (1.45 with no
    row in a group: the tokens' own loop), where XLA's gather, select and
    sum take 6.8 whatever the share; Laguna-XS.2's share (N 8,192, D 2,048,
    32 groups) 0.63 at 20% and 0.97 at 100% against 2.8. 256 tokens a block
    beat 128 at the cells' shares (1.71 / 1.81; 0.79 / 0.87) and lost at
    100% (2.33 / 2.16); two tokens a turn of the inner loop gained 2%. The
    lists alone read 0.5 to 0.6 ms, the host clock's floor for any program
    that small.
  * the transpose of `combine_rows` (`_cotangent_rows`) is on the chip a
    `lax.while_loop` of XLA's gathers, `_ROWS` rows a turn for as many turns
    as `valid` needs, with the weights and the dots folded into each turn
    and the result written over `rows`: 2.27 ms at 35% where the gather and
    one pass over (M, D) beside it take 3.8 whatever the share (6.0 at 100%;
    the parent's transposes there: 7.7), and one (M, D) array alive, not
    three. Blocks of 2,048 to 16,384 rows read the same to 5%.

Everywhere else (the CPU, every tier-1 test, a shape that misfits) the plain
`jax.numpy` gathers `models/mellum.py` had.

**One kernel program a step.** Every call reaches the kernel through
`_combine_kernel`, ONE `jax.jit`ted function, so jax traces and lowers each
distinct (shapes, dtype) once per step program: `combine_rows` of the result
and of d `src` are one program. `take_rows` traces it on the way forward
(`jax.eval_shape` over its own transpose), where a trace costs a fifth of
what it costs inside the backward pass (PERF.md, PR 35).

Each permutation (the forward calls, 2 a sparse layer) notes what it moves
(`ops/programs.py`, op `rows`): "rows_held" only the rows in a group (the
kernel), "rows_all" all N x k (XLA's gather), and the kernel program it and
its transpose need, for the Trainer's `experts_program` telemetry record.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

_LANES = 128
_CHUNK = 16  # rows of one copy into the window: a bfloat16 tile's sublanes
# rows one turn of the transpose's loop gathers
_ROWS = 8192
# tokens a grid step of the combine kernel sums
_TOKENS = 256
# of the chip's 128 MiB of VMEM: what the kernel may ask for, and the most
# its windows may take of that beside the pipeline's buffers
_VMEM_LIMIT = 110 * 2 ** 20
_VMEM_WINDOW = 80 * 2 ** 20
# a one-dimensional block in SMEM is whole tiles of this many elements
_SMEM_TILE = 1024


class Plan(NamedTuple):
    """The static sizes of the two ways for one (N, k, D, G, dtype)."""

    rows: int  # rows one turn of the transpose's loop gathers
    tokens: int  # tokens a step of the combine kernel
    chunks: int  # most chunks a block of tokens can need


def _kernel_plan(n: int, k: int, d: int, groups: int,
                 dtype) -> Optional[Plan]:
    """The sizes, or None where the plain gathers stay: rows that are not
    bfloat16 or float32, a D that is no whole number of lane tiles, an M the
    loop's block or an N the kernel's block does not divide, or a window that
    VMEM cannot hold."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return None
    rows = min(_ROWS, n * k)
    if d % _LANES or (n * k) % rows or n % _TOKENS:
        return None
    # a group's range in a block of tokens ends inside at most two chunks
    # that hold another's rows
    chunks = _TOKENS * k // _CHUNK + 2 * groups
    # two landing buffers, and a float32 window where the rows are narrower
    if (chunks + 1) * _CHUNK * d * 8 > _VMEM_WINDOW:
        return None
    return Plan(rows=rows, tokens=_TOKENS, chunks=chunks)


def _pallas():
    """Pallas, imported where a kernel is wanted (the CPU and the models
    without experts never pay for it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _cotangent_rows(g, index, valid, scale, rows, plan: Optional[Plan]):
    """`combine_rows`' transpose but for the unsort: (d rows (M, D), dots (M,)
    float32) with d rows[r] = scale[r] * g[index[r]] and dots[r] =
    g[index[r]] . rows[r] for r < `valid`, anything from there on.

    On the chip a loop of XLA's own gathers, `plan.rows` rows a turn for as
    many turns as `valid` needs, WRITTEN OVER `rows`, block by block as each
    block's dots are taken: the transpose then holds one (M, D) array and not
    three (the gathered cotangent, d rows and `rows`; 0.56 GiB each at Mellum
    2's size, where `peak_hbm_gib` may move 0.125)."""
    if plan is None:
        wide = g[index].astype(jnp.float32)
        dots = jnp.sum(wide * rows.astype(jnp.float32), axis=1)
        return (wide * scale[:, None]).astype(rows.dtype), dots
    block, d = plan.rows, rows.shape[1]

    def turn(carry):
        i, out, dots = carry
        at = i * block
        wide = g[lax.dynamic_slice(index, (at,), (block,))].astype(
            jnp.float32)
        beside = lax.dynamic_slice(out, (at, 0), (block, d))
        dots = lax.dynamic_update_slice(
            dots, jnp.sum(wide * beside.astype(jnp.float32), axis=1), (at,))
        out = lax.dynamic_update_slice(
            out, (wide * lax.dynamic_slice(scale, (at,), (block,))[:, None]
                  ).astype(out.dtype), (at, 0))
        return i + 1, out, dots

    _, out, dots = lax.while_loop(
        lambda carry: carry[0] * block < valid, turn,
        (jnp.int32(0), rows, jnp.zeros((rows.shape[0],), jnp.float32)))
    return out, dots


def _window_lists(index, sizes, plan: Plan):
    """For `combine_rows`'s kernel, from index (N, k) and sizes (G,): the
    chunks of sorted rows each block of tokens needs, (blocks x plan.chunks,)
    int32 with their number a block (blocks,), and every assignment's row in
    its block's window, (N x k,) int32; an assignment in no group is given
    the window's row of zeros, after its last chunk."""
    n, k = index.shape
    groups, tokens = sizes.shape[0], plan.tokens
    blocks, most = n // tokens, plan.chunks
    # (groups, blocks, a block's assignments): the long dimension minor-most
    flat = index.reshape(1, blocks, tokens * k)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    of_group = ((flat >= starts[:, None, None])
                & (flat < ends[:, None, None]))
    # a block's assignments a group, and where its first row is: the rows of
    # a group are sorted by token
    counts = jnp.sum(of_group, axis=2, dtype=jnp.int32)  # (groups, blocks)
    first = starts[:, None] + jnp.cumsum(counts, axis=1) - counts
    first_chunk = first // _CHUNK
    needed = jnp.where(
        counts > 0, -(-(first + counts) // _CHUNK) - first_chunk, 0)
    stop = jnp.cumsum(needed, axis=0)
    base = stop - needed  # a group's first chunk in the block's window
    # the window's chunk p is chunk p - base of the group whose range holds p
    # (summed over the groups under a mask: a gather of these few thousand
    # scalars costs 0.4 ms on the chip, one index at a time)
    p = jnp.arange(most)[None, None, :]
    holds = (p >= base[:, :, None]) & (p < stop[:, :, None])
    chunk = p[0] + jnp.sum(
        jnp.where(holds, (first_chunk - base)[:, :, None], 0), axis=0)
    chunk = jnp.clip(chunk, 0, n * k // _CHUNK - 1)
    # an assignment's row in the window: its group's base, plus its distance
    # from the group's first chunk
    shift = ((base - first_chunk) * _CHUNK)[:, :, None]
    where = jnp.where(
        flat[0] < ends[-1],
        flat[0] + jnp.sum(jnp.where(of_group, shift, 0), axis=0),
        most * _CHUNK)
    return (chunk.reshape(-1).astype(jnp.int32), stop[-1].astype(jnp.int32),
            where.reshape(-1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _combine_kernel(rows, index, weights, sizes, *, plan: Plan,
                    interpret: bool = False):
    """rows (M, D) sorted by group and token; index (N, k) int32, each
    assignment's row; weights (N, k) float32; sizes (G,). Returns (N, D)."""
    pl, pltpu = _pallas()
    n, k = index.shape
    d, dtype = rows.shape[1], rows.dtype
    tokens, most = plan.tokens, plan.chunks
    blocks = n // tokens
    chunks, count, where = _window_lists(index, sizes, plan)
    weights = weights.reshape(-1)
    # a block's tokens x k scalars in whole SMEM tiles: at k 8 they are (256
    # x 8 = 2 tiles); at k 10 each block's 2,560 are padded to 3 tiles, which
    # the kernel never reads (Mosaic refuses a block of 2,560: "not divisible
    # by tiling", the chip's compiler asked here, PR 40)
    span = -(-tokens * k // _SMEM_TILE) * _SMEM_TILE
    if span != tokens * k:
        where, weights = (
            jnp.pad(a.reshape(blocks, tokens * k),
                    ((0, 0), (0, span - tokens * k))).reshape(-1)
            for a in (where, weights))
    direct = dtype == jnp.float32  # copied straight into the float32 window
    zeros = pl.ds(most * _CHUNK, _CHUNK)  # the window's rows of zeros

    def kernel(count_ref, chunks_ref, where_ref, weight_ref, rows_hbm,
               out_ref, *scratch):
        if direct:
            landing, sum_ref, sems = scratch
        else:
            landing, window_ref, sum_ref, sems = scratch
        b = pl.program_id(0)

        def copies(block, slot, start: bool):
            """The chunk copies of `block` into half `slot` of the landing
            buffer: started, or waited for."""
            def one(c, carry):
                row = pl.multiple_of(
                    chunks_ref[block * most + c] * _CHUNK, _CHUNK)
                copy = pltpu.make_async_copy(
                    rows_hbm.at[pl.ds(row, _CHUNK)],
                    landing.at[slot, pl.ds(
                        pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)],
                    sems.at[slot])
                if start:
                    copy.start()
                else:
                    copy.wait()
                return carry

            lax.fori_loop(0, count_ref[block], one, 0)

        slot = b % 2

        @pl.when(b == 0)
        def _():
            for ref in ((landing.at[0], landing.at[1]) if direct
                        else (window_ref,)):
                ref[zeros] = jnp.zeros((_CHUNK, d), jnp.float32)
            copies(b, slot, True)

        @pl.when(b + 1 < blocks)
        def _():
            copies(b + 1, 1 - slot, True)

        copies(b, slot, False)
        if direct:
            window = landing.at[slot]
        else:
            window = window_ref

            def widen(c, carry):
                at = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
                window[at] = landing[slot, at].astype(jnp.float32)
                return carry

            lax.fori_loop(0, count_ref[b], widen, 0)

        def token(t):
            total = jnp.zeros((1, d), jnp.float32)
            for j in range(k):
                at = where_ref[t * k + j]
                weight = weight_ref[t * k + j]
                # an assignment in no group adds its weight times the
                # window's zeros: cheaper than a branch round it
                total = total + weight * window[pl.ds(at, 1)]
            sum_ref[pl.ds(t, 1), :] = total

        def pair(i, carry):
            token(2 * i)
            token(2 * i + 1)
            return carry

        lax.fori_loop(0, tokens // 2, pair, 0)
        out_ref[...] = sum_ref[...].astype(dtype)

    window = ((most + 1) * _CHUNK, d)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks,),
            in_specs=[
                pl.BlockSpec((span,), lambda b, *_: (b,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((span,), lambda b, *_: (b,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tokens, d), lambda b, *_: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, *window), dtype),
                *(() if direct else (pltpu.VMEM(window, jnp.float32),)),
                pltpu.VMEM((tokens, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="combine_rows",
    )(count, chunks, where, weights, rows)


def _planned(held: bool, n: int, k: int, d: int, sizes, dtype):
    """The plan of a permutation of this shape traced now (None: the plain
    gathers), noted: by whether it moves only the rows held when there is a
    plan, and with the key of the kernel program that it or its transpose
    then needs, as jax tells programs apart."""
    plan = None
    if programs.traced_for_tpu():
        plan = _kernel_plan(n, k, d, sizes.shape[0], dtype)
    programs.note(
        "rows", "rows_held" if held and plan is not None else "rows_all",
        () if plan is None else [
            ("combine_rows", n, k, d, sizes.shape[0], jnp.dtype(dtype).name,
             plan)])
    return plan


def _combine(rows, inverse, weights, sizes, plan: Optional[Plan],
             interpret: bool = False):
    n, k = weights.shape
    index = inverse.reshape(n, k)
    if plan is not None:
        return _combine_kernel(
            rows, index, weights.astype(jnp.float32), sizes, plan=plan,
            interpret=interpret)
    held = index < jnp.sum(sizes)
    out = jnp.where(
        held[..., None],
        rows[inverse].reshape(n, k, -1).astype(jnp.float32), 0.0)
    return jnp.sum(out * weights[..., None], axis=1).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _taken(src, order, inverse, sizes, plan, interpret):
    return src[order // (order.shape[0] // src.shape[0])]


def _taken_fwd(src, order, inverse, sizes, plan, interpret):
    return (_taken(src, order, inverse, sizes, plan, interpret),
            (inverse, sizes, src.shape[0]))


def _taken_bwd(plan, interpret, res, g):
    inverse, sizes, n = res
    ones = jnp.ones((n, inverse.shape[0] // n), jnp.float32)
    return _combine(g, inverse, ones, sizes, plan, interpret), None, None, None


_taken.defvjp(_taken_fwd, _taken_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combined(rows, order, inverse, weights, sizes, plan, interpret):
    return _combine(rows, inverse, weights, sizes, plan, interpret)


def _combined_fwd(rows, order, inverse, weights, sizes, plan, interpret):
    return (_combine(rows, inverse, weights, sizes, plan, interpret),
            (rows, order, inverse, weights, sizes))


def _combined_bwd(plan, interpret, res, g):
    rows, order, inverse, weights, sizes = res
    valid = jnp.sum(sizes)
    # the cotangent's row of every sorted assignment, once, for both
    d_rows, dots = _cotangent_rows(
        g, order // weights.shape[1], valid,
        weights.reshape(-1)[order].astype(jnp.float32), rows, plan)
    d_weights = jnp.where(inverse < valid, dots[inverse], 0.0)
    return (d_rows, None, None,
            d_weights.reshape(weights.shape).astype(weights.dtype), None)


_combined.defvjp(_combined_fwd, _combined_bwd)


def take_rows(src: jax.Array, order: jax.Array, inverse: jax.Array,
              sizes: jax.Array) -> jax.Array:
    """src (N, D); order, inverse (M,) int32 with M = N x k; sizes (G,)
    int32. Returns (M, D) in src's dtype: row r is src[order[r] // k] for r
    under the sizes' sum; rows from there on hold anything, and their
    cotangent is never read."""
    (n, d), k = src.shape, order.shape[0] // src.shape[0]
    plan = _planned(False, n, k, d, sizes, src.dtype)
    out = _taken(src, order, inverse, sizes, plan, False)
    if plan is not None:
        # the transpose's kernel program is traced here, on the way forward
        # (ops/groupmm.py has the measurement)
        jax.eval_shape(lambda: _taken_bwd(
            plan, False, (inverse, sizes, n), out))
    return out


def combine_rows(rows: jax.Array, order: jax.Array, inverse: jax.Array,
                 weights: jax.Array, sizes: jax.Array) -> jax.Array:
    """rows (M, D); order, inverse (M,) int32; weights (N, k); sizes (G,).
    Returns (N, D) in rows' dtype: token n's rows `rows[inverse[n * k + j]]`
    that lie under the sizes' sum, times their weights, summed in float32 in
    the order j = 0 .. k - 1 and rounded once. Rows from the sizes' sum on
    are never read, and their cotangent holds anything."""
    (n, k), d = weights.shape, rows.shape[1]
    plan = _planned(True, n, k, d, sizes, rows.dtype)
    return _combined(rows, order, inverse, weights, sizes, plan, False)
