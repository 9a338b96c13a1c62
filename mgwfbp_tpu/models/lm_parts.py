"""What more than one of the decoders (mellum.py, granite.py, laguna.py,
phi4flash.py, qwen3next.py, xing4.py, nemotronh.py) is built from: the
leaves' declaration, the norm, the rotary embedding, the dense MLPs, the
softmax router and the sigmoid router with a selection bias, the held
experts' part of a sparse block (experts of three products or of two) and
its counters, the Mamba-2 mixer, the loss a block of tokens at a time, and
the state-space mixers' initializers. What one decoder alone uses is in its
own file; no decoder imports another's.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs, shortconv
from mgwfbp_tpu.ops.groupmm import grouped_product
from mgwfbp_tpu.ops.rowperm import combine_rows, take_rows
from mgwfbp_tpu.ops.ssd import ssd_scan, ssd_scan_in_place

SLIDING, FULL = "sliding_attention", "full_attention"
# the step's metrics carry the routing counts under these keys (HEALTH_PREFIX
# of train/step.py, so they leave the chip by the health statistics' road)
MOE_TOKENS_KEY = "health/moe_tokens"
MOE_DROPPED_KEY = "health/moe_dropped"
_CONV_TAPS = 4  # of every mixer's short convolution (`mamba_conv`, `linear_conv`)
# the layers of PERF.md's map that a `jax.named_scope` of a decoder belongs
# to: a decoder's `scopes` gives each scope it enters one of them, and
# `profiling.classify` reads an instruction's name stack by that declaration
ATTENTION, EXPERTS, STATE_SPACE = "attention", "experts", "state space"
LINEAR_ATTENTION, STREAMS = "linear attention", "residual streams"
MLP, HEAD = "mlp", "head and loss"
# the scopes this file's functions enter, for the `scopes` of a decoder that
# calls them (tests/test_step_map.py holds both to the code)
SCOPES = {
    "gated_mlp": {"mlp": MLP},
    "token_losses": {"lm_head": HEAD, "loss": HEAD},
    "mamba2_mixer": {
        "ssm_in_proj": STATE_SPACE, "ssm_conv": STATE_SPACE,
        "ssm_scan": STATE_SPACE, "ssm_gate_norm": STATE_SPACE,
        "ssm_out_proj": STATE_SPACE,
    },
}


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


def sigmoid_bias_route(u: jax.Array, router: jax.Array, bias: jax.Array,
                       top_k: int, scaling: float, eps: float = 0.0):
    """`noaux_tc`: s = sigmoid(u W_r) over ALL experts in float32 (operands
    as stored, product at `highest`); the `top_k` largest of s + bias are
    chosen, and weigh s (not s + bias) over (the sum of the chosen + `eps`),
    times `scaling`. (indices (N, k), weights (N, k), share of the N x k
    choices that s alone would not have made)."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ))
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    weights = top / (total + eps if eps else total) * scaling
    _, unbiased = lax.top_k(scores, top_k)
    swapped = jnp.mean(jnp.all(
        idx[:, :, None] != unbiased[:, None, :], axis=-1).astype(jnp.float32))
    return idx, weights, lax.stop_gradient(swapped)


def _held_first(local, held, weights, count: int):
    """A token's held choices first, in their order j, over `count` slots:
    (keys (N, count), weights (N, count)), an empty slot keyed `count` (no
    group) under weight 0. `lax.top_k` hands a token DISTINCT experts, so
    no token holds more than `count`. A masked sum over an (N, k, count)
    one-hot, exact (one term a slot is non-zero) and its own transpose's
    mask: XLA's gathers of single scalars read 1.84 ms each on the chip at
    these sizes (PERF.md section 5), a scatter-add on the way back more."""
    slot = jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1
    into = held[:, :, None] & (slot[:, :, None] == jnp.arange(count))
    # an empty slot sums nothing and keeps `count`
    keys = count + jnp.sum(
        jnp.where(into, (local - count)[:, :, None], 0), axis=1)
    return keys, jnp.sum(jnp.where(into, weights[:, :, None], 0), axis=1)


def _grouped_experts(u, idx, weights, count: int, first: int, experts):
    """The held experts' part of the sparse block for tokens u (N, D): the
    N x c assignments that CAN be held, c = min(k, count), sorted by held
    expert, `experts(rows (N * c, D), sizes) -> (out (N * c, D'), a
    statistic)` on the rows as grouped, and the c terms of a token added up
    under their weights. Where a token routes to more experts than are held
    (k > count) its held choices are compacted to c slots first
    (`_held_first`): the grouped arrays then have N x count rows for any
    routing, the rows in a group the same in the same order; where k <=
    count not one operation is added. Which of the two a call took is noted
    (`ops/programs.py`, op `groups`). Returns (y (N, D'), tokens per held
    expert (E,), assignments to a held expert that no group took (a count; 0
    by construction, `held` counted BEFORE the compaction), the
    statistic)."""
    local = idx - first
    held = (local >= 0) & (local < count)
    if idx.shape[1] > count:
        programs.note("groups", "bounded")
        keys, weights = _held_first(local, held, weights, count)
    else:
        programs.note("groups", "whole")
        # unheld assignments sort behind every held expert, into no group
        keys = jnp.where(held, local, count)
    keys = keys.reshape(-1)
    order = jnp.argsort(keys, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(
        keys[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    # (N * c, D), grouped by expert; the rows past the last group belong to
    # no expert: a grouped product leaves them UNWRITTEN on the chip (zero
    # only on the CPU), forward and backward, and neither permutation moves
    # or reads them (ops/rowperm.py): never trusted
    rows = take_rows(u, order, inverse, sizes)
    out, stat = experts(rows, sizes)
    y = combine_rows(out, order, inverse, weights, sizes)
    dropped = jnp.sum(held) - jnp.sum(sizes)
    return y, sizes, dropped, stat


def held_experts(u, idx, weights, w_gate, w_up, w_down, first: int):
    """The held experts' part of the sparse block for tokens u, experts of
    three products (SwiGLU).

    u (N, D); idx, weights (N, k) from `route`; w_gate, w_up (E, D, F) and
    w_down (E, F, D) the E held experts, expert `first` of the model first;
    the grouped arrays have N x min(k, E) rows (`_grouped_experts`).
    Returns (y (N, D), tokens per held expert (E,), assignments to a held
    expert that no group took (a count; 0 by construction))."""
    def experts(rows, sizes):
        gate = grouped_product(rows, w_gate, sizes)
        up = grouped_product(rows, w_up, sizes)
        mid = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32))
        return grouped_product(mid.astype(u.dtype), w_down, sizes), None

    return _grouped_experts(
        u, idx, weights, w_gate.shape[0], first, experts)[:3]


def held_relu2_experts(u, idx, weights, w_up, w_down, first: int):
    """`held_experts` for experts of two products and no gate: relu(u
    W_up)^2 W_down, w_up (E, D, F), w_down (E, F, D). Returns a fourth
    value: the share of the held experts' hidden units, over the rows in a
    group, that relu left above zero (float32, no gradient; 0 where no row
    is in a group). The passes over the hidden units (the count, relu^2 and
    their transposes) run over all N x min(k, E) grouped rows, in a group or
    not: `_grouped_experts` keeps that number at what a routing can fill."""
    def experts(rows, sizes):
        up = grouped_product(rows, w_up, sizes).astype(jnp.float32)
        # the rows past the last group are unwritten: not counted
        in_groups = jnp.sum(sizes)
        grouped = (jnp.arange(up.shape[0]) < in_groups)[:, None]
        active = jnp.sum((up > 0) & grouped, dtype=jnp.float32) / jnp.maximum(
            in_groups * up.shape[1], 1).astype(jnp.float32)
        mid = jnp.square(jax.nn.relu(up)).astype(u.dtype)
        return (grouped_product(mid, w_down, sizes),
                lax.stop_gradient(active))

    return _grouped_experts(u, idx, weights, w_up.shape[0], first, experts)


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


def _bias_init(width: float):
    """A selection bias's seeded draw, uniform in +-`width`."""
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -width, width)

    return init


def _conv_init(key, shape, dtype=jnp.float32):
    bound = 1.0 / math.sqrt(_CONV_TAPS)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def mamba2_leaves(hidden: int, heads: int, head_dim: int, state: int,
                  groups: int, out_init=False) -> tuple:
    """A Mamba-2 mixer's leaves for `_Leaves`: `heads` heads of `head_dim`
    over `groups` pairs of B and C of `state` each; `out_init` the output
    projection's initializer (False: normal 0.02)."""
    inner = heads * head_dim
    channels = inner + 2 * groups * state
    return (
        ("in_proj", (hidden, inner + channels + heads), False),
        ("conv_w", (_CONV_TAPS, channels), _conv_init),
        ("conv_b", (channels,), _conv_init),
        ("dt_bias", (heads,), _dt_bias_init), ("a_log", (heads,), _a_log_init),
        ("d", (heads,), True), ("gate_norm", (inner,), True),
        ("out_proj", (inner, hidden), out_init))


def mamba2_mixer(p: dict, u: jax.Array, *, heads: int, head_dim: int,
                 state: int, groups: int, chunk: int, eps: float,
                 scan_block: int):
    """The Mamba-2 mixer on the normed input u (B, T, hidden): (y (B, T,
    hidden), root mean square of the final state, most negative chunk sum of
    log-decays). [z | xBC | dt] = u W_in with xBC = [xs (heads x head_dim) |
    B (groups x state) | C (groups x state)]; head h reads group h // (heads
    / groups); the gated norm runs over each group's heads x head_dim /
    groups channels apart, gate first (one group: over all of them at once).
    One group goes down `ssd_scan_in_place` (x, B and C where the
    convolution left them); several go a group at a time down `ssd_scan`."""
    b, t, _ = u.shape
    inner = heads * head_dim
    channels = inner + 2 * groups * state
    f32 = jnp.float32
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = u @ p["in_proj"]
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + channels]
        dt = zxbcdt[..., inner + channels:]
    with jax.named_scope("ssm_conv"):
        xbc = shortconv.causal_conv_silu(xbc, p["conv_w"], p["conv_b"])
    with jax.named_scope("ssm_scan"):
        xs = xbc[..., :inner].reshape(b, t, heads, head_dim)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        a = -jnp.exp(p["a_log"].astype(f32))
        if groups == 1:
            # x, B and C where the convolution left them: the kernels pick
            # their columns out of xbc, the plain form cuts B and C out; y
            # comes back float32 with the `D x` skip on it
            y, last, low = ssd_scan_in_place(
                xbc, xs, dt, a, p["d"], chunk=chunk, block=scan_block)
        else:
            per = heads // groups

            def one(g):  # group g's heads over its own B and C
                own = slice(g * per, (g + 1) * per)
                at = inner + g * state
                return ssd_scan(
                    xs[:, :, own], dt[..., own], a[own],
                    xbc[..., at:at + state],
                    xbc[..., at + groups * state:at + (groups + 1) * state],
                    chunk=chunk, block=scan_block)

            ys, lasts, lows = zip(*map(one, range(groups)))
            y = jnp.concatenate(ys, axis=2).astype(f32) \
                + p["d"].astype(f32)[:, None] * xs.astype(f32)
            last = jnp.concatenate(lasts, axis=1)
            low = jnp.min(jnp.stack(lows))
        state_rms = jnp.sqrt(jnp.mean(jnp.square(last)))
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(b, t, inner) * jax.nn.silu(z.astype(f32))
        scale = p["gate_norm"]
        if groups > 1:
            y = y.reshape(b, t, groups, inner // groups)
            scale = scale.reshape(groups, inner // groups)
        y = rms_norm(y, scale, eps).reshape(b, t, inner).astype(u.dtype)
    with jax.named_scope("ssm_out_proj"):
        return y @ p["out_proj"], lax.stop_gradient(state_rms), low
