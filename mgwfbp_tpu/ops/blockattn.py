"""Causal attention in query blocks against static key ranges: trainable at
sequence lengths whose (T, T) score matrix does not fit.

`models/transformer.py`'s dense path holds a (B, H, T, T) float32 array (8.6
GB a sequence at T 8,192 with 32 heads) and `ops/flashattn.py`'s Pallas kernel
has no backward. Here the queries are cut into blocks of `block` positions and
each block is scored against the one static slice of keys it can see:

  * full causal attention: keys [0, end of the block), so the blocks above
    the diagonal are never computed;
  * a sliding window of `window` positions (query i sees keys j with
    0 <= i - j < window): keys [block start - window + 1, end of the block),
    rounded down to a multiple of 128, so a window layer does the work of its
    band and not of the triangle.

Every block is plain `jax.numpy` under `jax.checkpoint`: the forward keeps a
block's output and nothing of its scores, the backward recomputes the block's
probabilities. No (T, T) array exists in either pass; the largest temporary is
one block's (B, H, block, range) float32 scores. Grouped-query attention is
native: `k`/`v` carry fewer heads than `q` and each serves a group of
consecutive query heads. Scores and the softmax are float32 whatever the
operands' dtype; the exponentials are cast to the values' dtype for the
second product (as the other attention paths of this repo cast their
probabilities) and the row sums divide its float32 result.

Written for one device's whole sequence. No Pallas: giving the kernel a
backward and a window is a later PR's, measured against this.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30  # finite mask value, as parallel/ringattn.py
_ALIGN = 128  # key ranges start on a lane-tile boundary


def key_range(start: int, stop: int, window: Optional[int]) -> tuple[int, int]:
    """The static slice of keys that queries [start, stop) can see."""
    if window is None:
        return 0, stop
    lo = max(start - window + 1, 0)
    return lo - lo % _ALIGN, stop


def _one_block(q, k, v, q_start: int, k_start: int, window: Optional[int],
               scale: float):
    """q (B, Hkv, G, Tq, D) against k, v (B, Hkv, Tk, D): (B, Hkv, G, Tq, D).
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
    return out.reshape(b, hkv, g, tq, d)


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    window: Optional[int] = None, block: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal (optionally windowed) attention. q: (B, T, H, D); k, v:
    (B, T, Hkv, D) with H a multiple of Hkv, query head i served by key head
    i // (H / Hkv). Returns (B, T, H, D) in q's dtype. T need not be a
    multiple of `block`: the last block is shorter. `scale` multiplies the
    scores before the softmax: 1 / sqrt(D) unless a model publishes its own."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads do not divide over {hkv} key heads")
    # heads before positions, once for all blocks
    q = q.reshape(b, t, hkv, h // hkv, d).transpose(0, 2, 3, 1, 4)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    one = jax.checkpoint(_one_block, static_argnums=(3, 4, 5, 6))
    out = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        lo, hi = key_range(start, stop, window)
        out.append(one(q[:, :, :, start:stop], k[:, :, lo:hi], v[:, :, lo:hi],
                       start, lo, window, scale))
    out = jnp.concatenate(out, axis=3)  # (B, Hkv, G, T, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)
