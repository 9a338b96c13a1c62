"""What more than one of the decoders (mellum.py, granite.py, laguna.py,
phi4flash.py, qwen3next.py) is built from: the leaves' declaration, the norm,
the rotary embedding, the dense MLPs, the softmax router with the held
experts' part of a sparse block and its counters, the loss a block of tokens
at a time, and the state-space mixers' initializers. What one decoder alone
uses is in its own file; no decoder imports another's.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops.groupmm import grouped_product
from mgwfbp_tpu.ops.rowperm import combine_rows, take_rows

SLIDING, FULL = "sliding_attention", "full_attention"
# the step's metrics carry the routing counts under these keys (HEALTH_PREFIX
# of train/step.py, so they leave the chip by the health statistics' road)
MOE_TOKENS_KEY = "health/moe_tokens"
MOE_DROPPED_KEY = "health/moe_dropped"
_CONV_TAPS = 4  # of every mixer's short convolution (`mamba_conv`, `linear_conv`)


class _Leaves(nn.Module):
    """Declares a group of parameters and hands them back as a dict."""

    # ((name, shape, init), ...): True a norm's scale (ones), False a weight
    # (normal 0.02), or an initializer of the leaf's own
    shapes: tuple

    @nn.compact
    def __call__(self) -> dict:
        return {
            name: self.param(
                name,
                init if callable(init)
                else nn.initializers.ones if init
                else nn.initializers.normal(0.02),
                shape,
            )
            for name, shape, init in self.shapes
        }


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def plain_inv_freq(dim: int, theta: float) -> jax.Array:
    """theta ** (-2i / dim) for the dim / 2 pairs of a rotation over `dim`
    dimensions of a head."""
    return theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN as `transformers._compute_yarn_parameters`, over the `dim`
    dimensions of a head that rotate: interpolate (divide by the factor) the
    low frequencies, keep the high ones, blend linearly between the two
    correction dimensions."""
    base = plain_inv_freq(dim, theta)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(
            original_len / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return (1 - ramp) * base + ramp * base / factor


def apply_rope(x: jax.Array, inv_freq: jax.Array, factor: float) -> jax.Array:
    """x (B, T, H, D) rotated by position in the half-split ("rotate_half")
    layout, float32 inside, x's dtype out."""
    t = x.shape[1]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def partial_rope(x: jax.Array, inv_freq: jax.Array, factor: float,
                 scale: float = 1.0) -> jax.Array:
    """x (B, T, H, D): its first 2 x len(inv_freq) dimensions rotated by
    position (half-split inside them, cos and sin times `factor`), the rest
    passed through; all of it times `scale`, in float32, rounded once."""
    rotary = 2 * inv_freq.shape[0]
    if rotary == x.shape[-1]:
        return apply_rope(x, inv_freq, factor * scale)
    passed = (x[..., rotary:].astype(jnp.float32) * scale).astype(x.dtype)
    return jnp.concatenate(
        [apply_rope(x[..., :rotary], inv_freq, factor * scale), passed],
        axis=-1)


def swiglu(v: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    mid = jax.nn.silu((v @ w_gate).astype(jnp.float32)) \
        * (v @ w_up).astype(jnp.float32)
    return mid.astype(v.dtype) @ w_down


def gated_mlp(p: dict, v: jax.Array, shape) -> jax.Array:
    with jax.named_scope("mlp"):
        pq = v @ p["w1"]
        f = shape.intermediate_size
        mid = jax.nn.silu(pq[..., :f].astype(jnp.float32)) \
            * pq[..., f:].astype(jnp.float32)
        return mid.astype(v.dtype) @ p["w2"]


def route(u: jax.Array, router: jax.Array, top_k: int):
    """Softmax router over ALL experts in float32 (operands as they are
    stored, product at `highest`): (indices (N, k), weights (N, k) summing to
    one over the k chosen)."""
    logits = jnp.dot(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(probs, top_k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def held_experts(u, idx, weights, w_gate, w_up, w_down, first: int):
    """The held experts' part of the sparse block for tokens u.

    u (N, D); idx, weights (N, k) from `route`; w_gate, w_up (E, D, F) and
    w_down (E, F, D) the E held experts, expert `first` of the model first.
    Returns (y (N, D), tokens per held expert (E,), assignments to a held
    expert that no group took (a count; 0 by construction))."""
    count = w_gate.shape[0]
    local = idx - first
    held = (local >= 0) & (local < count)
    # unheld assignments sort behind every held expert, into no group
    keys = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(keys, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(
        keys[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    # (N * k, D), grouped by expert; the rows past the last group belong to
    # no expert: a grouped product leaves them UNWRITTEN on the chip (zero
    # only on the CPU), forward and backward, and neither permutation moves
    # or reads them (ops/rowperm.py): never trusted
    rows = take_rows(u, order, inverse, sizes)
    gate = grouped_product(rows, w_gate, sizes)
    up = grouped_product(rows, w_up, sizes)
    mid = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32))
    out = grouped_product(mid.astype(u.dtype), w_down, sizes)
    y = combine_rows(out, order, inverse, weights, sizes)
    dropped = jnp.sum(held) - jnp.sum(sizes)
    return y, sizes, dropped


def routing_counters(stats: dict, assignments: int) -> dict:
    """The `step` record's routing counters from one step's statistics as
    host arrays (MOE_TOKENS_KEY (sparse layers held, experts held),
    MOE_DROPPED_KEY); `assignments` a layer's (token, expert) pairs on one
    device. Shared by every model that routes through `held_experts`."""
    held = stats[MOE_TOKENS_KEY]
    worst = int(held.max(axis=1).argmax())  # the layer of the fullest
    return {
        "moe_here": float(held.sum(axis=1).mean() / assignments),
        "moe_load_max": float(held[worst].max()),
        "moe_load_mean": float(held[worst].mean()),
        "moe_dropped": float(stats[MOE_DROPPED_KEY]),
    }


def token_losses(h: jax.Array, head: jax.Array, targets: jax.Array,
                 block: int) -> jax.Array:
    """-log softmax(h @ head)[target] per token, float32, `block` tokens at a
    time: a block's (block, vocabulary) logits live only inside its own
    forward and (recomputed) backward."""
    n = h.shape[0]
    block = block if n % block == 0 else n

    def one(args):
        hb, yb = args
        with jax.named_scope("lm_head"):
            logits = jnp.dot(hb, head, preferred_element_type=jnp.float32)
        with jax.named_scope("loss"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return lse - picked

    return lax.map(jax.checkpoint(one), (
        h.reshape(n // block, block, -1), targets.reshape(n // block, block),
    )).reshape(n)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a log-uniform time step in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(key, shape, dtype=jnp.float32):
    bound = 1.0 / math.sqrt(_CONV_TAPS)
    return jax.random.uniform(key, shape, dtype, -bound, bound)
