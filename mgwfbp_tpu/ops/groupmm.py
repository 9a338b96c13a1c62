"""The experts' grouped product: rows sorted by group, one weight matrix a
group, `out[r] = lhs[r] @ rhs[group of r]`. One entry point,
`grouped_product`, and two ways down from it.

**The tiled kernel.** Where the step is traced for a TPU and the shape fits
(`_kernel_tiles`), the product and both of its transposes are the installed
`jax.experimental.pallas.ops.tpu.megablox` kernels under a `custom_vjp` of
this module's own: `gmm` for the product and for d lhs, `tgmm` for d rhs. Each walks the row tiles that hold a
group's rows and no others, accumulates in a float32 scratch in VMEM and
rounds once to the operands' dtype, as `lax.ragged_dot` does on the chip.

**The kernel programs are shared, and few.** Every distinct kernel program
of a step costs its first step a Python trace of the library's wrapper (0.2
to 0.4 s on the chip's host), a Mosaic lowering and a share of the
executable's load (my chip runs, PR 35). `gmm` and `tgmm` are `jax.jit`ted in
the library and every call here reaches them through `_gmm` / `_tgmm` with
one signature, so jax traces and lowers each DISTINCT (shapes, dtype, tiles)
once per step program and calls it from every layer, forward, recomputed
forward and backward alike. And d lhs is written so as to need no program of
its own: it is the product with the weights transposed beforehand (a pass
over the weights, 0.2 ms, and 0.06 GiB LESS scratch in the compiled step)
where a `gmm` that reads them transposed would be one program more. d rhs
keeps a `tgmm` for each shape: one `tgmm` with the wider operand first and
the result transposed cost the compiled Mellum 2 step 0.16 GiB more, more
than `peak_hbm_gib` may move. A sparse layer whose gate and up weights are
(K, N) and whose down weight is (N, K) has at most FOUR distinct programs
(`gmm` K to N, `gmm` N to K, a `tgmm` for each), whatever the number of
layers: `tests/test_groupmm.py` counts them in the lowered text.

**`lax.ragged_dot`.** Everywhere else (the CPU, every tier-1 test, a shape
the tiles do not divide), called as the models called it before: the lowered
text of a CPU step is what it was.

Both leave the rows past the last group UNWRITTEN on the chip, forward and in
d lhs (`tgmm` masks them out of d rhs): the caller never reads them
(`ops/rowperm.py`'s two permutations stop at the groups' sum), and no caller
may rely on either way zeroing them.

Each product notes the way it went and the kernel programs it needs,
transposes included (`ops/programs.py`, op `experts`), for the Trainer's
`experts_program` telemetry record.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

_LANES = 128  # K and N are whole lane tiles, or the kernel is not asked


class Tiles(NamedTuple):
    """(rows, contraction, output columns) of a tile, for each kernel: d lhs
    contracts over N; d rhs's are (rows, K, N)."""

    product: tuple[int, int, int]
    d_lhs: tuple[int, int, int]
    d_rhs: tuple[int, int, int]


# The tiles, from a sweep of the three kernels alone on a v5e (my chip run,
# PR 35; ms a call, bf16, host clock over four chained calls). Mellum 2's
# share, 131,072 rows of which 51,000 in 16 groups, (K, N) = (2,304, 896) and
# (896, 2,304): `lax.ragged_dot` 4.19 to 4.63 forward, 4.15 to 4.54 d lhs,
# 5.51 to 5.77 d rhs. `gmm` (rows, K, N) (256, whole, whole) 1.42 to 1.43
# forward and d lhs alike; (512, 1,152, 896) 1.55; (512, 1,024, 896), whose
# third K tile is a remainder, 2.02; (256, 512, 512) 2.88; 1,024 rows at best
# 1.84, and refused by the compiler beside a K tile of 1,024 or more (VMEM).
# `tgmm` (256, 1,152, 896) 1.59 and (512, 896, 1,152) 1.58, with the larger of
# K and N whole refused (a float32 block of K x N beside the output's two).
# Laguna-XS.2's share, 65,536 rows of which 16,384 in 32 groups, (2,048, 512)
# and (512, 2,048): `ragged_dot` 0.62 to 0.70; `gmm` (256, whole, whole) 0.49
# to 0.58, `tgmm` the same tiles 0.52 to 0.56, 512 rows 0.03 to 0.06 more,
# 1,024 rows 0.73 to 0.85. So: 256 rows, K and N whole, and the larger of the
# two halved while the kernel's blocks would not fit.
_ROWS = 256
# of the 16 MiB a kernel may use: blocks of 13.9 MB fit, of 15.2 did not
_VMEM = 14 * 2 ** 20


def _fitted(need, k: int, n: int) -> tuple[int, int]:
    """(K tile, N tile): whole, the larger halved (to whole lane tiles) while
    `need(tk, tn)` bytes exceed `_VMEM`."""
    tk, tn = k, n
    while need(tk, tn) > _VMEM and max(tk, tn) > _LANES:
        if tk >= tn:
            tk = _LANES * -(-tk // (2 * _LANES))
        else:
            tn = _LANES * -(-tn // (2 * _LANES))
    return tk, tn


def _kernel_tiles(m: int, k: int, n: int, dtype) -> Optional[Tiles]:
    """The kernels' tiles for (M, K) x (G, K, N), or None where
    `lax.ragged_dot` stays: operands that are not bfloat16 or float32, a K or
    N that is no whole number of lane tiles, or an M the row tile does not
    divide."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if k % _LANES or n % _LANES or m % _ROWS:
        return None
    size = jnp.dtype(dtype).itemsize

    # both operands' blocks and the output's twice (the pipeline's two
    # buffers) beside the float32 accumulator: `gmm` accumulates a block of
    # rows x N, `tgmm` one of K x N
    def gmm_need(tk, tn):
        return 2 * size * (_ROWS * (tk + tn) + tk * tn) + 4 * _ROWS * tn

    def tgmm_need(tk, tn):
        return 2 * size * (_ROWS * (tk + tn) + tk * tn) + 4 * tk * tn

    return Tiles(
        product=(_ROWS, *_fitted(gmm_need, k, n)),
        d_lhs=(_ROWS, *_fitted(gmm_need, n, k)),
        d_rhs=(_ROWS, *_fitted(tgmm_need, k, n)))


def _megablox():
    """The library's kernels (`gmm`, `tgmm`: both `jax.jit`ted there),
    imported where a kernel is wanted. The package's own `gmm` is these under
    a `custom_vjp` with one tiling for all three."""
    from jax.experimental.pallas.ops.tpu.megablox import ops

    return ops.backend


def _gmm(lhs, rhs, sizes, tiling, interpret: bool):
    """lhs (M, K) x rhs (G, K, N): (M, N) in lhs's dtype. The one way to the
    library's jitted `gmm`, so that equal programs are one cached trace and
    one lowering."""
    return _megablox().gmm(
        lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiling,
        interpret=interpret)


def _tgmm(lhs, g, sizes, tiling, groups: int, dtype, interpret: bool):
    """lhs (M, K) and g (M, N): (G, K, N) of `dtype`, group i's block the
    product of its rows of lhs, transposed, and of g; an empty group's block
    is zero. The one way to the library's jitted `tgmm`."""
    # the library takes lhs as (K, M) and swaps it back before its kernel:
    # the two transposes cancel in the compiler
    return _megablox().tgmm(
        lhs.swapaxes(0, 1), g, sizes, preferred_element_type=dtype,
        tiling=tiling, num_actual_groups=groups, interpret=interpret)


def _programs(lhs, rhs, tiles: Tiles) -> list[tuple]:
    """The keys of the three kernel programs one product needs, as jax tells
    programs apart: kernel, operand shapes, dtype, tiles."""
    (m, k), (groups, _, n), dtype = lhs.shape, rhs.shape, lhs.dtype.name
    return [
        ("gmm", m, groups, k, n, dtype, tiles.product),
        ("gmm", m, groups, n, k, dtype, tiles.d_lhs),
        ("tgmm", m, groups, k, n, dtype, tiles.d_rhs),
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_product(lhs, rhs, sizes, tiles: Tiles, interpret: bool = False):
    """The tiled kernel with its transposes. `interpret` runs it without a
    TPU (the tests' way in)."""
    return _gmm(lhs, rhs, sizes, tiles.product, interpret)


def _kernel_product_fwd(lhs, rhs, sizes, tiles, interpret):
    out = _gmm(lhs, rhs, sizes, tiles.product, interpret)
    return out, (lhs, rhs, sizes)


def _kernel_product_bwd(tiles, interpret, res, g):
    lhs, rhs, sizes = res
    d_lhs = _gmm(g, rhs.swapaxes(1, 2), sizes, tiles.d_lhs, interpret)
    d_rhs = _tgmm(
        lhs, g, sizes, tiles.d_rhs, rhs.shape[0], rhs.dtype, interpret)
    return d_lhs, d_rhs, None


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)


def grouped_product(lhs: jax.Array, rhs: jax.Array,
                    group_sizes: jax.Array) -> jax.Array:
    """lhs (M, K), its rows sorted by group; rhs (G, K, N); group_sizes (G,)
    int32, their sum at most M. Returns (M, N) in lhs's dtype: row r of group
    i is lhs[r] @ rhs[i], accumulated in float32. Rows past the last group
    hold anything, in the result and in lhs's cotangent."""
    m, k = lhs.shape
    tiles = None
    if programs.traced_for_tpu() and lhs.dtype == rhs.dtype:
        tiles = _kernel_tiles(m, k, rhs.shape[2], lhs.dtype)
    if tiles is None:
        programs.note("experts", "ragged")
        return lax.ragged_dot(lhs, rhs, group_sizes)
    programs.note("experts", "kernel", _programs(lhs, rhs, tiles))
    out = _kernel_product(lhs, rhs, group_sizes, tiles)
    # the transposes' programs are traced HERE, into jax's cache of traces,
    # and found there by the backward pass: first traced in the backward
    # pass, under its stack of interpreters, each `tgmm` cost the first step
    # 0.42 s on the chip's host where the product's `gmm`, traced on the way
    # forward, cost 0.07 (my chip runs, PR 35)
    jax.eval_shape(lambda: _kernel_product_bwd(
        tiles, False, (lhs, rhs, group_sizes), out))
    return out

