"""The attention core (scores, mask, softmax, values) of the language models:
causal, optionally a sliding window, grouped-query heads, trainable at sequence
lengths whose (T, T) score matrix does not fit. One entry point,
`blockwise_attention`, and two ways down from it.

**The fused kernel.** Where the step is traced for a TPU and the shape fits
(`_kernel_tiles`), the core is the installed
`jax.experimental.pallas.ops.tpu.splash_attention`: one Pallas kernel forward
and its backward kernels under a `custom_vjp`, whose score tiles live in VMEM
and never reach HBM. The backward recomputes the probabilities from the saved
row log-sum-exp. `scale` is folded into `q` (in float32, then back to q's
dtype), the batch into the heads (query head b * H + h is served by key head
b * Hkv + h // G, which is the kernel's own grouping), and the kernel object,
whose mask information is host work, is built once per (T, heads, window,
tiles) and cached. The tiles are constants chosen from the shape, fitted on a
v5e from the trace by scope (PERF.md section 6, PR 31).

**The plain blocks.** Everywhere else (the CPU, a T the tiles do not divide,
a head size other than 64, 128, 192 or 256), and as the kernel's reference: the
queries are cut into blocks of `block` positions and each block is scored against the
one static slice of keys it can see:

  * full causal attention: keys [0, end of the block), so the blocks above
    the diagonal are never computed;
  * a sliding window of `window` positions (query i sees keys j with
    0 <= i - j < window): keys [block start - window + 1, end of the block),
    rounded down to a multiple of 128, so a window layer does the work of its
    band and not of the triangle.

Every block is plain `jax.numpy` under `jax.checkpoint`: the forward keeps a
block's output and nothing of its scores, the backward recomputes the block's
probabilities. The largest temporary is one block's (B, H, block, range)
float32 scores, in HBM.

Both: scores and the softmax are float32 whatever the operands' dtype, every
causal pair is computed, and no (T, T) array exists in either pass. The blocks
cast the exponentials to the values' dtype for the second product; the
kernel's forward keeps them float32 there (its backward casts them).

Each call notes the way it went (`ops/programs.py`, op `attention`), for
the Trainer's `attention_program` telemetry record. Written for one device's
whole sequence.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

_NEG_INF = -1e30  # finite mask value, as parallel/ringattn.py
_ALIGN = 128  # key ranges start on a lane-tile boundary

# The fused kernel's tiles as (tile, keys of a tile taken at a time, fused
# backward), fitted on a v5e at T 8,192 (PERF.md section 6, PR 31). One tile
# serves all the kernels. Fused backward: dq comes out of the dk/dv kernel, a
# partial sum per key tile added up outside it, instead of a kernel of its own
# that computes the probabilities again. A window layer's tiles overhang its
# band, so it takes smaller ones, and its partial sums cost more than the
# second kernel. Chosen by shape, that is by `window` alone: every call shape
# of the cells has D 64, 128 or 256 and T 8,192. Fitted at a window of 1,024
# (Mellum 2, 2 x 32 query heads: 17.0 ms forward + backward alone on the chip;
# 256 tiles 24.9, 1,024 tiles 18.8, fused backward 21.6; PR 31) and again at
# a window of 512 (Laguna-XS.2, 64 query heads over 8 key heads, where a 512
# tile overhangs the band by a whole tile: 512 x 512 with its own dq kernel
# 11.18 ms; keys 256 at a time 11.82; 256 tiles 15.23; 128 tiles 30.01; 1,024
# tiles 16.54; fused backward 16.97 at 512 and 34.56 at 256; the plain blocks
# of 128 queries 13.2; my chip run, PR 32): the smaller tiles' worse rate
# costs more than the overhang they save, so both windows keep 512. Laguna's
# full layers (48 query heads, groups of SIX to a key head) keep the full
# tiles: 1,024 x 1,024, keys 512 at a time, fused backward 23.33 ms; a dq
# kernel of its own 28.08; keys 1,024 at a time 23.95; 512 tiles 29.26; the
# plain blocks 74.65; 2,048 x 512 does not fit VMEM (PR 32).
# Qwen3-Next's full layers (D 256, 2 x 16 query heads over 2 x 2 key heads)
# keep the full tiles too: 1,024 x 1,024, keys 512 at a time, fused backward
# 30.29 ms forward + backward alone on the chip (9.63 forward); keys 1,024 at
# a time 30.59; 512 tiles 33.64; a dq kernel of its own 36.31 to 37.77 at
# every tile; the plain blocks 54.07 to 56.08; 2,048 x 512 does not fit VMEM
# (my chip run, PR 40).
_TILES_FULL = (1024, 512, True)
_TILES_WINDOW = (512, 512, False)
# half a lane tile, one, one and a half (latent attention's score width: 128
# + 64 rotary, beside values of 128) and two
_KERNEL_HEAD_DIMS = (64, 128, 192, 256)


def key_range(start: int, stop: int, window: Optional[int]) -> tuple[int, int]:
    """The static slice of keys that queries [start, stop) can see."""
    if window is None:
        return 0, stop
    lo = max(start - window + 1, 0)
    return lo - lo % _ALIGN, stop


def _one_block(q, k, v, q_start: int, k_start: int, window: Optional[int],
               scale: float):
    """q (B, Hkv, G, Tq, D) against k (B, Hkv, Tk, D) and v (B, Hkv, Tk, Dv):
    (B, Hkv, G, Tq, Dv).
    `q_start`, `k_start`: the global positions of the first query and key;
    `scale` multiplies the scores.
    A key head's G query heads are rows of ONE (G * Tq, D) x (D, Tk) product
    per (batch, key head): as a five-dimensional einsum with the group as an
    output dimension of its own, the TPU compiler lays the scores out with
    that dimension of 8 minor-most, padded to 128 lanes."""
    b, hkv, g, tq, d = q.shape
    s = jnp.einsum(
        "bhmd,bhkd->bhmk", q.reshape(b, hkv, g * tq, d), k,
        preferred_element_type=jnp.float32,
    ) * scale
    qi = q_start + jnp.arange(tq)[:, None]
    kj = k_start + jnp.arange(k.shape[2])[None, :]
    mask = kj <= qi
    if window is not None:
        mask = mask & (qi - kj < window)
    s = jnp.where(jnp.tile(mask, (g, 1)), s, _NEG_INF)
    # every query sees itself, so no row is empty and the softmax is plain.
    # The row maximum only shifts the exponent (no gradient), and it goes
    # through a barrier: fused with the subtraction that consumes it, the
    # TPU compiler rewrites "reduce, broadcast, subtract" into a sliding
    # reduce-window of 2 Tk - 1 keys per element, 94 ms for a block that
    # reads 1 GB (my chip run, PR 26)
    m = lax.optimization_barrier(
        lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - m)
    # normalized after the second product: the division runs over (Tq, D)
    # and not over (Tq, Tk), one pass over the scores fewer
    out = jnp.einsum(
        "bhmk,bhkd->bhmd", e.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ) / jnp.sum(e, axis=-1, keepdims=True)
    out = out.astype(v.dtype)
    return out.reshape(b, hkv, g, tq, v.shape[-1])


def _blocks(q, k, v, window: Optional[int], block: int, scale: float):
    """The plain blocks: q (B, T, H, D), k (B, T, Hkv, D), v (B, T, Hkv,
    Dv)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    # heads before positions, once for all blocks
    q = q.reshape(b, t, hkv, h // hkv, d).transpose(0, 2, 3, 1, 4)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    one = jax.checkpoint(_one_block, static_argnums=(3, 4, 5, 6))
    out = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        lo, hi = key_range(start, stop, window)
        out.append(one(q[:, :, :, start:stop], k[:, :, lo:hi], v[:, :, lo:hi],
                       start, lo, window, scale))
    out = jnp.concatenate(out, axis=3)  # (B, Hkv, G, T, Dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, v.shape[-1])


def _splash():
    """The library, imported where a kernel is wanted: a second of imports
    that the CPU and the models without attention never pay."""
    from jax.experimental.pallas.ops.tpu import splash_attention

    return splash_attention


def _kernel_tiles(t: int, d: int, window: Optional[int],
                  dv: Optional[int] = None):
    """The fused kernel's tiles (splash_attention's BlockSizes) for a call of
    this shape (`d` the width the scores sum over, `dv` the values' where it
    is another), each cut to T where T is shorter, or None where the plain
    blocks stay: a head size the kernel has no tile for, or a T that its
    tiles do not divide."""
    block, compute, fused = _TILES_FULL if window is None else _TILES_WINDOW
    block, compute = min(block, t), min(compute, t)
    if (d not in _KERNEL_HEAD_DIMS or (dv or d) not in _KERNEL_HEAD_DIMS
            or t % _ALIGN or t % block or block % compute):
        return None
    return _splash().BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=compute,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=compute,
        **(dict(use_fused_bwd_kernel=True) if fused
           else dict(block_q_dq=block, block_kv_dq=block)),
    )


@functools.lru_cache(maxsize=None)
def _splash_kernel(t: int, heads: int, window: Optional[int], tiles,
                   interpret: bool):
    """The kernel object for `heads` query heads of T positions: its mask
    information is host work over (T / tile)^2 entries, so it is built once
    per shape and never under a trace (its arrays are constants of whichever
    program closes over it)."""
    sa = _splash()
    if window is None:
        mask = sa.CausalMask((t, t))
    else:
        mask = sa.LocalMask((t, t), (window - 1, 0), 0)
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha(
            sa.MultiHeadMask((mask,) * heads),  # one mask: deduplicated there
            block_sizes=tiles, head_shards=1, q_seq_shards=1,
            interpret=interpret,
        )


def _fused(q, k, v, window: Optional[int], scale: float, tiles,
           interpret: bool = False):
    """The fused kernel: q (B, T, H, D), k (B, T, Hkv, D), v (B, T, Hkv, Dv),
    `tiles` a BlockSizes. `interpret` runs it without a TPU (the tests' way
    in)."""
    b, t, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    kernel = _splash_kernel(t, b * h, window, tiles, interpret)
    # the kernel has no scale of its own: the scores' factor goes into q
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = kernel(
        q.transpose(0, 2, 1, 3).reshape(b * h, t, d),
        k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d),
        v.transpose(0, 2, 1, 3).reshape(b * hkv, t, dv),
    )
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    window: Optional[int] = None, block: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal (optionally windowed) attention. q: (B, T, H, D); k: (B, T,
    Hkv, D); v: (B, T, Hkv, Dv), Dv = D unless the model sums values of
    another width than it scores over (latent attention: 192 and 128), with H
    a multiple of Hkv, query head i served by key head i // (H / Hkv).
    Returns (B, T, H, Dv) in v's dtype. `scale` multiplies the
    scores before the softmax: 1 / sqrt(D) unless a model publishes its own.
    `block` is the plain blocks' query block (T need not be a multiple of it:
    the last block is shorter); the fused kernel has its own tiles."""
    b, t, h, d = q.shape
    if h % k.shape[2]:
        raise ValueError(
            f"{h} query heads do not divide over {k.shape[2]} key heads")
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    tiles = (_kernel_tiles(t, d, window, v.shape[3])
             if programs.traced_for_tpu() else None)
    if tiles is None:
        programs.note("attention", "blocks")
        return _blocks(q, k, v, window, block, scale)
    programs.note("attention", "kernel")
    return _fused(q, k, v, window, scale, tiles)
