"""The short causal convolution of the state-space and linear-attention
mixers together with the SiLU that always follows it, with a backward pass.
One entry point, `causal_conv_silu`, and two ways down from it.

Per channel c of C, with K taps w[., c] (w[K - 1] on the current position),
an optional bias and zeros before a sequence's first position:

    s_t = bias + sum_i w[i] x[t - (K - 1) + i]          (float32)
    y_t = silu(round(s_t))                               (float32 of the
                                                          ROUNDED sum)

with both roundings to x's dtype: what Granite 4.0-H's and Phi-4-mini-flash's
Mamba mixers and Qwen3-Next's Gated DeltaNet mixer do to their inputs
(K = 4).

**The plain form** (`plain_conv_silu`): the whole array padded along T,
widened to float32, K shifted slices summed, rounded, SiLU, rounded. Plain
`jax.numpy`; autodiff derives the backward pass. The CPU, every tier-1 test,
every shape the kernels do not take, and the kernels' reference.

**The kernels.** Where the step is traced for a TPU and the shape fits
(`_kernel_tiles`: the channels whole lane tiles, T a multiple of the block of
positions, no more taps than one sublane tile, x bfloat16 or float32), two
Pallas programs of this module's own under a `custom_vjp`, each ONE pass over
its bytes. Both run a grid over (strip of `cols` channels, sequence, block of
`rows` positions), the blocks innermost. A block comes in with the tile of
positions before it through a second window on x (zeros where the block is
a sequence's first: a block never reads the sequence before it); inside, a
loop takes `_CHUNK` positions of `_GROUP` channels at a time in registers:
widened to float32, the taps' shifted views made by sublane rotations of the
chunk and the eight positions before it. The taps and the bias are handed
over with each row eight times, (K, 8, C) and (8, C) float32, so that a turn
loads whole tiles and broadcasts nothing.

  * forward: bias + the taps summed IN THE PLAIN FORM'S ORDER, rounded to
    x's dtype, SiLU in float32 of the rounded value, rounded again: the same
    arithmetic and the same two roundings. x comes in once, y goes out once,
    nothing float32 reaches HBM.
  * backward: the residuals are x, w and bias alone. The blocks of a
    sequence go in REVERSE: a chunk recomputes its pre-activation from x,
    forms g = dy . silu'(rounded pre-activation) in float32, writes d x as
    the taps' transpose over g (the K - 1 positions AFTER a chunk come from
    the chunk taken before it, across blocks through 8 rows of VMEM
    scratch), and adds g . x[shifted] into d w and g into d bias, which are
    summed in registers over a block and in VMEM over the blocks of a strip
    of channels: no partial sum reaches HBM. (Autodiff of the plain form
    rounds g to x's dtype on its way; here it stays float32.)

Every call reaches the programs through `_forward_kernel` /
`_backward_kernel`, each ONE `jax.jit`ted function, so every layer's forward,
its recomputation under the layer's `jax.checkpoint` and its backward share
TWO kernel programs; the backward's is traced on the way forward
(`jax.eval_shape`; `ops/groupmm.py` has the measurement).

Each call notes the way it went ("kernel", "plain") and the kernel programs
it needs (`ops/programs.py`, op `conv`), for the Trainer's `conv_program`
telemetry record.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

_LANES = 128
_SUBLANES = 8  # of a float32 tile; a two-byte dtype's tile has twice as many
# positions one turn of a kernel's loop takes: one tile of a two-byte dtype,
# two of float32
_CHUNK = 16
# channels one turn of the loop holds in registers: at 512 a chunk, its
# shifted views and the sums are about 40 of the 64 vector registers
_GROUP = 512
# of the chip's 128 MiB of VMEM: the backward's three blocks, buffered twice,
# are 15 MiB at the tiles below (30 for float32)
_VMEM_LIMIT = 64 * 2 ** 20


class Tiles(NamedTuple):
    """A grid step of either kernel: `rows` positions of `cols` channels."""

    rows: int
    cols: int


# Positions a block; channels a strip, the widest whole number of lane tiles
# that divides C up to `_COLS`. From a sweep of the two kernels alone on a
# v5e at T 8,192, bf16 (my chip run, PR 42; ms, host clock, forward / forward
# + backward): the rows hardly matter from 256 up (2 x 8,192 x 8,192: 256 x
# 1,024 1.16 / 3.07, 512 x 1,024 1.08 / 2.96, 1,024 x 1,024 1.08 / 2.94), a
# narrow strip costs (DMA rows of 512 bytes: 512 x 256 1.59 / 3.87 there;
# 8,192 x 4,352: 512 x 256 0.48 / 1.12, 512 x 2,176 0.38 / 0.95; 8,192 x
# 5,120: 512 x 256 0.56 / 1.30, 512 x 1,024 0.40 / 1.08, 512 x 2,560 0.41 /
# 1.07), and from 1,024 channels up nothing moves.
_ROWS = 512
_COLS = 2560


def _kernel_tiles(t: int, c: int, k: int, dtype) -> Optional[Tiles]:
    """The kernels' tiles for T positions of C channels under K taps, or None
    where the plain form stays: x not bfloat16 or float32, a C that is no
    whole number of lane tiles, more taps than a sublane tile holds, or a T
    the block of positions does not divide."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if c % _LANES or not 1 <= k <= _SUBLANES or t % _ROWS:
        return None
    tiles = c // _LANES
    wide = max(n for n in range(1, _COLS // _LANES + 1) if tiles % n == 0)
    return Tiles(_ROWS, wide * _LANES)


def _pallas():
    """Pallas, imported where a kernel is wanted (the CPU and the models
    without a short convolution never pay for it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _groups(cols: int) -> list[slice]:
    """A strip's channels in runs of at most `_GROUP`, as even as whole lane
    tiles allow."""
    tiles = cols // _LANES
    runs = -(-tiles // (_GROUP // _LANES))
    sizes = [tiles // runs + (i < tiles % runs) for i in range(runs)]
    bounds = [sum(sizes[:i]) * _LANES for i in range(runs + 1)]
    return [slice(a, b) for a, b in zip(bounds, bounds[1:])]


def _halo_rows(dtype) -> int:
    """Rows of the window on the positions before a block: one tile."""
    return _SUBLANES * 4 // jnp.dtype(dtype).itemsize


def _shifted(pltpu, before, chunk, k: int) -> list:
    """The K views of a chunk (n, L) float32 that the taps see: view i holds
    x[t - (K - 1) + i] at row t, with `before` (8, L) the eight positions
    before the chunk. View K - 1 is the chunk itself. Whole tiles rotated
    along the sublanes and cut on a tile's edge, so that every view lies as
    the chunk does."""
    ext = jnp.concatenate([before, chunk], axis=0)
    return [pltpu.roll(ext, k - 1 - i, 0)[_SUBLANES:]
            for i in range(k - 1)] + [chunk]


def _following(pltpu, chunk, after, k: int) -> list:
    """The K views of g (n, L) float32 that the taps' transpose sees: view j
    holds g[t + j] at row t, with `after` (8, L) the eight positions after
    the chunk. View 0 is the chunk itself."""
    n = chunk.shape[0]
    ext = jnp.concatenate([chunk, after], axis=0)
    return [chunk] + [
        pltpu.roll(ext, n + _SUBLANES - j, 0)[:n] for j in range(1, k)]


def _rows(tile):
    """A tile (8, L) under itself: a chunk's rows of a tap or the bias."""
    return jnp.concatenate([tile] * (_CHUNK // _SUBLANES), axis=0)


def _pre_activation(views, taps, bias):
    """bias + the taps' sum in the plain form's order (Python's `sum` starts
    from 0, and 0 + a is a)."""
    s = views[0] * taps[0]
    for view, tap in zip(views[1:], taps[1:]):
        s = s + view * tap
    return s if bias is None else bias + s


def _call(kernel, name: str, interpret: bool, **spec):
    """`pl.pallas_call` over a grid of (strip of channels, sequence, block of
    positions), every axis in order: the backward carries g and the sums."""
    pl, pltpu = _pallas()
    return pl.pallas_call(
        kernel, **spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


def _specs(pl, x, k: int, tiles: Tiles, back: bool):
    """The windows on x (or an array of its shape), on the tile of positions
    before a block, on w and on bias; `back`: the blocks from a sequence's
    last to its first."""
    rows, cols = tiles
    blocks = x.shape[1] // rows
    halo = _halo_rows(x.dtype)

    def at(i):
        return blocks - 1 - i if back else i

    block = pl.BlockSpec((1, rows, cols), lambda j, s, i: (s, at(i), j))
    before = pl.BlockSpec(
        (1, halo, cols),
        lambda j, s, i: (s, jnp.maximum(at(i) * (rows // halo) - 1, 0), j))
    taps = pl.BlockSpec((k, _SUBLANES, cols), lambda j, s, i: (0, 0, j))
    bias = pl.BlockSpec((_SUBLANES, cols), lambda j, s, i: (0, j))
    return block, before, taps, bias


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _forward_kernel(x, w, bias, *, tiles: Tiles, interpret: bool = False):
    """x (B, T, C); w (K, 8, C) float32, a tap's row eight times; bias (8, C)
    float32 likewise, or None. Returns y (B, T, C) in x's dtype."""
    pl, pltpu = _pallas()
    rows, cols = tiles
    (bsz, t, c), k = x.shape, w.shape[0]
    halo = _halo_rows(x.dtype)
    f32 = jnp.float32

    def kernel(x_ref, before_ref, w_ref, *rest):
        b_ref, y_ref = rest if bias is not None else (None, *rest)
        first = pl.program_id(2) == 0
        for lanes in _groups(cols):
            # zeros before a sequence's first position
            before = jnp.where(
                first, 0.0,
                before_ref[0, :, lanes].astype(f32)[halo - _SUBLANES:])

            def turn(q, before):
                r = pl.multiple_of(q * _CHUNK, _CHUNK)
                chunk = x_ref[0, pl.ds(r, _CHUNK), lanes].astype(f32)
                s = _pre_activation(
                    _shifted(pltpu, before, chunk, k),
                    [_rows(w_ref[i, :, lanes]) for i in range(k)],
                    None if b_ref is None else _rows(b_ref[:, lanes]))
                s = s.astype(y_ref.dtype).astype(f32)
                y_ref[0, pl.ds(r, _CHUNK), lanes] = jax.nn.silu(s).astype(
                    y_ref.dtype)
                return chunk[_CHUNK - _SUBLANES:]

            lax.fori_loop(0, rows // _CHUNK, turn, before)

    block, before, taps, bias_spec = _specs(pl, x, k, tiles, back=False)
    return _call(
        kernel, "causal_conv_silu_forward", interpret,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(c // cols, bsz, t // rows),
        in_specs=[block, before, taps] + [bias_spec] * (bias is not None),
        out_specs=block,
    )(x, x, w, *(() if bias is None else (bias,)))


def _silu_slope(v):
    """d silu(v) / d v."""
    sig = jax.nn.sigmoid(v)
    return sig * (1.0 + v * (1.0 - sig))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _backward_kernel(x, w, bias, dy, *, tiles: Tiles, interpret: bool = False):
    """The forward's arguments and the cotangent of y. Returns d x (x's
    dtype), d w (K, C) float32 and d bias (1, C) float32 or None."""
    pl, pltpu = _pallas()
    rows, cols = tiles
    (bsz, t, c), k = x.shape, w.shape[0]
    halo = _halo_rows(x.dtype)
    turns = rows // _CHUNK
    f32 = jnp.float32

    def kernel(x_ref, before_ref, dy_ref, w_ref, *rest):
        if bias is not None:
            b_ref, dx_ref, dw_ref, db_ref, g_ref = rest
        else:
            (dx_ref, dw_ref, g_ref), b_ref, db_ref = rest, None, None
        s, i = pl.program_id(1), pl.program_id(2)
        first, last = i == t // rows - 1, i == 0  # of a sequence, in reverse

        @pl.when((s == 0) & last)
        def _():
            dw_ref[...] = jnp.zeros(dw_ref.shape, f32)
            if db_ref is not None:
                db_ref[...] = jnp.zeros(db_ref.shape, f32)

        for lanes in _groups(cols):
            edge = jnp.where(
                first, 0.0,
                before_ref[0, :, lanes].astype(f32)[halo - _SUBLANES:])
            # g of the eight positions after the block: zeros after a
            # sequence's last position
            after = jnp.where(last, 0.0, g_ref[:, lanes])

            def turn(q, carry):
                after, dw, db = carry
                r = pl.multiple_of((turns - 1 - q) * _CHUNK, _CHUNK)
                chunk = x_ref[0, pl.ds(r, _CHUNK), lanes].astype(f32)
                above = x_ref[0, pl.ds(pl.multiple_of(
                    jnp.maximum(r - _CHUNK, 0), _CHUNK), _CHUNK), lanes]
                before = jnp.where(
                    r == 0, edge, above.astype(f32)[_CHUNK - _SUBLANES:])
                views = _shifted(pltpu, before, chunk, k)
                taps = [_rows(w_ref[i, :, lanes]) for i in range(k)]
                pre = _pre_activation(
                    views, taps,
                    None if b_ref is None else _rows(b_ref[:, lanes]))
                pre = pre.astype(x_ref.dtype).astype(f32)
                g = dy_ref[0, pl.ds(r, _CHUNK), lanes].astype(f32) \
                    * _silu_slope(pre)
                ahead = _following(pltpu, g, after, k)
                dx = ahead[0] * taps[k - 1]
                for j in range(1, k):
                    dx = dx + ahead[j] * taps[k - 1 - j]
                dx_ref[0, pl.ds(r, _CHUNK), lanes] = dx.astype(dx_ref.dtype)

                def tile(v):  # (16, L) -> (8, L): a chunk's tiles summed
                    return v[:_SUBLANES] + v[_SUBLANES:]

                dw = tuple(
                    acc + tile(g * view) for acc, view in zip(dw, views))
                return (g[:_SUBLANES], dw,
                        None if db is None else db + tile(g))

            zero = jnp.zeros((_SUBLANES, lanes.stop - lanes.start), f32)
            after, dw, db = lax.fori_loop(
                0, turns, turn,
                (after, (zero,) * k, None if db_ref is None else zero))
            g_ref[:, lanes] = after
            for q in range(k):
                dw_ref[q:q + 1, lanes] += jnp.sum(
                    dw[q], axis=0, keepdims=True)
            if db_ref is not None:
                db_ref[:, lanes] += jnp.sum(db, axis=0, keepdims=True)

    block, before, taps, bias_spec = _specs(pl, x, k, tiles, back=True)
    summed = [((k, c), pl.BlockSpec((k, cols), lambda j, s, i: (0, j)))]
    if bias is not None:
        summed.append(((1, c), pl.BlockSpec((1, cols), lambda j, s, i: (0, j))))
    out = _call(
        kernel, "causal_conv_silu_backward", interpret,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] + [
            jax.ShapeDtypeStruct(shape, f32) for shape, _ in summed],
        grid=(c // cols, bsz, t // rows),
        in_specs=[block, before, block, taps] + [bias_spec] * (
            bias is not None),
        out_specs=[block] + [spec for _, spec in summed],
        scratch_shapes=[pltpu.VMEM((_SUBLANES, cols), f32)],
    )(x, x, dy, w, *(() if bias is None else (bias,)))
    return (*out, None) if bias is None else tuple(out)


def _across_sublanes(x, w, bias):
    """The kernels' arguments: every tap's row and the bias as whole (8, C)
    tiles, so that a loop's turn loads them and broadcasts nothing."""
    def tiled(v):
        return jnp.broadcast_to(
            v[..., None, :], (*v.shape[:-1], _SUBLANES, v.shape[-1]))

    return x, tiled(w), None if bias is None else tiled(bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_conv(x, w, bias, tiles: Tiles, interpret: bool = False):
    """The two kernels as one differentiable function: x (B, T, C), w (K, C)
    float32, bias (C,) float32 or None -> y (B, T, C) in x's dtype.
    `interpret` runs them without a TPU (the tests' way in)."""
    return _forward_kernel(
        *_across_sublanes(x, w, bias), tiles=tiles, interpret=interpret)


def _kernel_conv_fwd(x, w, bias, tiles, interpret):
    y = _forward_kernel(
        *_across_sublanes(x, w, bias), tiles=tiles, interpret=interpret)
    return y, (x, w, bias)


def _kernel_conv_bwd(tiles, interpret, res, dy):
    dx, dw, db = _backward_kernel(
        *_across_sublanes(*res), dy, tiles=tiles, interpret=interpret)
    return dx, dw, None if db is None else db[0]


_kernel_conv.defvjp(_kernel_conv_fwd, _kernel_conv_bwd)


def plain_conv_silu(
    x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None,
) -> jax.Array:
    """The plain form of `causal_conv_silu`, whose arguments and result these
    are: float32 sums, x's dtype out of the convolution and out of the SiLU."""
    k, t = w.shape[0], x.shape[1]
    if bias is None:
        # what Qwen3-Next handed `granite.causal_conv`, which this was: its
        # plain program's text stays what it was
        bias = jnp.zeros((), jnp.float32)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    w = w.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        padded[:, i:i + t] * w[i] for i in range(k))
    out = out.astype(x.dtype)
    return jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype)


def _programs(x, k: int, bias, tiles: Tiles) -> list[tuple]:
    """The keys of the two kernel programs one call needs, as jax tells
    programs apart: kernel, shapes, dtypes, tiles."""
    shape = (*x.shape, k, x.dtype.name, bias is not None, tiles)
    return [("forward", *shape), ("backward", *shape)]


def causal_conv_silu(
    x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None,
) -> jax.Array:
    """silu(depthwise causal convolution along T + bias): x (B, T, C), w (K,
    C) with w[K - 1] on the current position, bias (C,) or None. Float32
    sums; x's dtype out of the convolution and out of the SiLU."""
    (_, t, c), k = x.shape, w.shape[0]
    tiles = None
    if programs.traced_for_tpu():
        tiles = _kernel_tiles(t, c, k, x.dtype)
    if tiles is None:
        programs.note("conv", "plain")
        return plain_conv_silu(x, w, bias)
    programs.note("conv", "kernel", _programs(x, k, bias, tiles))
    args = (x, w.astype(jnp.float32),
            None if bias is None else bias.astype(jnp.float32))
    y = _kernel_conv(*args, tiles, False)
    # the backward program is traced HERE, into jax's cache of traces, and
    # found there by the backward pass (ops/groupmm.py has the measurement)
    jax.eval_shape(functools.partial(_kernel_conv_bwd, tiles, False), args, y)
    return y

