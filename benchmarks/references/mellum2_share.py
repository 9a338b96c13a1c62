"""Plain reference: one chip's share of Mellum2-12B-A2.5B, forward pass, loss
and gradient in float32.

Source: https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct
(config.json; SHAPE below copies its widths). Straight `jax.numpy`, every
product at `highest` precision, dense attention with the causal / window mask
written out, the experts as a plain loop. It imports nothing of `mgwfbp_tpu`;
it is handed the program's initial parameters as a flat `{"a/b/c": array}`
dict (random draws from the seed, nothing the program computed).

Per layer l of `layer_types`, x the residual stream:

    h  = x + W_o Attn(rope_l(W_q u), rope_l(W_k u), W_v u),  u = RMSNorm(x)
    x' = h + sum_k w_k W_down,e_k (silu(W_gate,e_k v) * W_up,e_k v),
                                                             v = RMSNorm(h)

RMSNorm(u) = g * u / sqrt(mean(u^2) + 1e-6). 32 query heads of 128 over 4
key/value heads (each serves 8 consecutive query heads), scores q.k /
sqrt(128), position i sees j <= i and, on a `sliding_attention` layer, only
i - j < 1,024. Rotary embedding over the whole head in the half-split layout:
inv_freq_i = 500000^(-2i/128) on window layers; on full layers YaRN (factor
16, original length 8,192, beta_fast 32, beta_slow 1), cos and sin times the
attention factor. Router: softmax over all 64 experts, the 8 largest,
renormalized to sum to one. Then a final RMSNorm and an untied head; the loss
is the mean over tokens of -log softmax(logits)[next token].

**The share.** The parameters hold the first `layers` of the model's layers,
`count` of the 64 experts (the stacked expert leaves' leading dimension)
starting at expert SHARE["first_expert"], and the embedding's and head's rows
of the held vocabulary. The router still scores all 64 and renormalizes over
all 8 chosen; only the held experts' terms are added. What the absent experts
would have added is left out here exactly as in the program.

Departures from a textbook forward, for memory only: a sequence at a time, a
layer at a time, an expert at a time (`jax.checkpoint`; the loops over query
blocks and experts are `lax.map` / `lax.scan` so that they run one after the
other), and the attention a block of QUERY_BLOCK queries at a time against
ALL keys of the sequence under the written-out mask (a (heads, block, T)
score array instead of (heads, T, T)).

Assumed (config.json names none): no query/key normalization, no router
bias, no load-balancing loss, no multi-token-prediction head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

SLIDING, FULL = "sliding_attention", "full_attention"
SHAPE = {
    "hidden_size": 2304,
    "num_attention_heads": 32,
    "num_key_value_heads": 4,
    "head_dim": 128,
    "num_experts": 64,
    "num_experts_per_tok": 8,
    "moe_intermediate_size": 896,
    "sliding_window": 1024,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "rms_norm_eps": 1e-6,
    "rope_theta": 500000.0,
    "yarn": {"factor": 16.0, "original_max_position_embeddings": 8192,
             "beta_fast": 32.0, "beta_slow": 1.0,
             "attention_factor": 1.2772588722239782},
}
# what `forward_macs` and `first_step` take for the share where the
# parameters cannot say it: 4 of 28 layers, experts 0..15 of 64
SHARE = {"layers": 4, "first_expert": 0, "experts": 16}
QUERY_BLOCK = 256
HI = lax.Precision.HIGHEST


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def inv_freq(shape: dict, kind: str):
    """(inverse frequencies (head_dim / 2,), factor on cos and sin)."""
    dim, theta = shape["head_dim"], shape["rope_theta"]
    base = jnp.asarray(
        [theta ** (-2.0 * i / dim) for i in range(dim // 2)], jnp.float32)
    if kind == SLIDING:
        return base, 1.0
    yarn = shape["yarn"]

    def c(rotations):
        return dim * math.log(
            yarn["original_max_position_embeddings"]
            / (2 * math.pi * rotations)) / (2 * math.log(theta))

    low = max(math.floor(c(yarn["beta_fast"])), 0)
    high = min(math.ceil(c(yarn["beta_slow"])), dim - 1)
    ramp = jnp.asarray(
        [min(max((i - low) / (high - low), 0.0), 1.0)
         for i in range(dim // 2)], jnp.float32)
    return (1 - ramp) * base + ramp * base / yarn["factor"], \
        yarn["attention_factor"]


def rope(x, freqs, factor):
    """x (T, heads, head_dim): x cos + rotate_half(x) sin by position."""
    t, _, dim = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * (jnp.cos(angle) * factor) + rotated * (jnp.sin(angle) * factor)


def rms_norm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def attention(q, k, v, window, dtype=None):
    """q (T, H, D), k, v (T, Hkv, D) of one sequence -> (T, H, D). Dense:
    every query against every key, under the mask written out."""
    t, h, d = q.shape
    groups = h // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)  # query head i uses key head i // groups
    v = jnp.repeat(v, groups, axis=1)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(qb, start):
        i = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", _stored(qb, dtype), _stored(k, dtype),
                       precision=HI) / math.sqrt(d)
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _stored(p, dtype), _stored(v, dtype),
                          precision=HI)

    # whole blocks one after the other (`lax.map`, so that one block's
    # scores are alive at a time), then the shorter last block
    whole = t // QUERY_BLOCK
    out = []
    if whole:
        out.append(lax.map(
            lambda qs: block(*qs),
            (q[:whole * QUERY_BLOCK].reshape(whole, QUERY_BLOCK, h, d),
             jnp.arange(whole) * QUERY_BLOCK),
        ).reshape(whole * QUERY_BLOCK, h, d))
    if t % QUERY_BLOCK:
        out.append(block(q[whole * QUERY_BLOCK:], whole * QUERY_BLOCK))
    return jnp.concatenate(out, axis=0)


def route(u, router, top_k):
    """(indices (T, k), weights (T, k) summing to one). Float32 as stored:
    the control leaves the router alone, as the configuration's precision
    states a float32 router."""
    probs = jax.nn.softmax(jnp.dot(u, router, precision=HI), axis=-1)
    top, idx = lax.top_k(probs, top_k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def sparse_block(p, u, shape, first, dtype=None):
    """Held experts' part of the block for tokens u (T, hidden): experts
    first .. first + count - 1, one after the other, each over all tokens
    with the weight the router gave it (zero where it was not chosen)."""
    idx, w = route(u, p["router"], shape["num_experts_per_tok"])

    @jax.checkpoint
    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        gate = jnp.dot(_stored(u, dtype), _stored(w_gate, dtype), precision=HI)
        up = jnp.dot(_stored(u, dtype), _stored(w_up, dtype), precision=HI)
        out = jnp.dot(_stored(jax.nn.silu(gate) * up, dtype),
                      _stored(w_down, dtype), precision=HI)
        return y + w_e[:, None] * out, None

    y, _ = lax.scan(add_expert, jnp.zeros_like(u), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def layer(p, x, kind, shape, first, dtype=None):
    t = x.shape[0]
    hd = shape["head_dim"]
    eps = shape["rms_norm_eps"]
    u = rms_norm(x, p["attn_norm"], eps)

    def proj(name, heads):
        return jnp.dot(_stored(u, dtype), _stored(p[name], dtype),
                       precision=HI).reshape(t, heads, hd)

    freqs, factor = inv_freq(shape, kind)
    q = rope(proj("wq", shape["num_attention_heads"]), freqs, factor)
    k = rope(proj("wk", shape["num_key_value_heads"]), freqs, factor)
    v = proj("wv", shape["num_key_value_heads"])
    a = attention(q, k, v,
                  shape["sliding_window"] if kind == SLIDING else None, dtype)
    h = x + jnp.dot(_stored(a.reshape(t, -1), dtype), _stored(p["wo"], dtype),
                    precision=HI)
    return h + sparse_block(
        p, rms_norm(h, p["moe_norm"], eps), shape, first, dtype)


def _tree(params: dict) -> dict:
    """{"layer_0/wq": a, ...} -> {"layer_0": {"wq": a}, ...} in float32."""
    tree: dict = {}
    for key, value in params.items():
        group, name = key.split("/")
        tree.setdefault(group, {})[name] = jnp.asarray(value, jnp.float32)
    return tree


def hidden_states(tree, x, shape, first, dtype=None):
    """Final-norm output (T, hidden) of one sequence x (T,) of token ids."""
    h = tree["embed"]["embedding"][x]
    n_layers = sum(1 for k in tree if k.startswith("layer_"))
    for i in range(n_layers):
        h = jax.checkpoint(
            functools.partial(layer, kind=shape["layer_types"][i],
                              shape=shape, first=first, dtype=dtype)
        )(tree[f"layer_{i}"], h)
    return rms_norm(h, tree["out"]["norm"], shape["rms_norm_eps"])


def logits(params: dict, x, *, shape=None, first=None, dtype=None):
    """x (T,) token ids of ONE sequence -> (T, held vocabulary) float32."""
    shape = SHAPE if shape is None else shape
    first = SHARE["first_expert"] if first is None else first
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, first, dtype)
    return jnp.dot(_stored(h, dtype), _stored(tree["out"]["head"], dtype),
                   precision=HI)


def sequence_loss(params: dict, x, y, *, shape=None, first=None, dtype=None):
    """Mean over the sequence's tokens of -log softmax(logits)[y]."""
    lg = logits(params, x, shape=shape, first=first, dtype=dtype)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(3,))
def _loss_and_grad_sumsq(params, x, y, dtype):
    """(mean over the rows of x of the sequence's loss, sum over all
    parameters of its gradient squared). The rows go one after the other (a
    scan whose body is recomputed in the backward pass), so one sequence's
    float32 activations and ONE gradient tree are all the device holds
    beside the parameters: 595 M parameters are 2.4 GB in float32, and a
    gradient tree per row added up outside would be three of those."""
    dtype = None if dtype is None else jnp.dtype(dtype)

    def batch_loss(p):
        row = jax.checkpoint(
            lambda p, xi, yi: sequence_loss(p, xi, yi, dtype=dtype))

        def body(acc, xy):
            return acc + row(p, *xy), None

        total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (x, y))
        return total / x.shape[0]

    loss, grads = jax.value_and_grad(batch_loss)(params)
    return loss, sum(jnp.sum(jnp.square(g)) for g in grads.values())


def first_step(
    params: dict, x, y, *, seed: int, shards: int, dtype=None,
) -> dict:
    """What training step 1 on batch (x, y) at `params` computes: `loss`, the
    mean over all tokens of the batch, and `grad_norm`, the L2 norm over all
    parameters of its gradient (no weight decay, no clipping: the gradient as
    the optimizer gets it). Sequences have one length, so the mean of their
    means is the mean over tokens, whatever `shards` devices the rows were
    dealt to. No dropout, so `seed` draws nothing. `dtype` (a name, e.g.
    "float8_e4m3fn") computes the control: every product's operands except
    the router's rounded to it first."""
    del seed, shards
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, sumsq = _loss_and_grad_sumsq(
            params, jnp.asarray(x), jnp.asarray(y), dtype)
    return {"loss": float(loss), "grad_norm": float(sumsq) ** 0.5}


def needed_pairs(t: int, window=None) -> int:
    """(query, key) pairs the mask lets through in a sequence of t."""
    if window is None:
        return t * (t + 1) // 2
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def forward_macs(shape=(8192,), vocab: int = 24576) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers and experts held; `vocab`: the vocabulary held).
    `shape` is (sequence length,). Counted: the four attention projections;
    the score and value products over the pairs the mask lets through (the
    triangle on a full layer, the band on a window layer: not the square);
    the router over all 64 experts; the EXPECTED expert work, 8 x held / 64
    evaluations a token (2 at 16 of 64: under uniform routing, which seeded
    weights give to within a few per cent); the held head. Not counted: the
    embedding lookup, norms, rotary embedding, softmax, the recomputation
    the program's checkpoints add, the optimizer."""
    (t,) = shape
    s = SHAPE
    d, hd = s["hidden_size"], s["head_dim"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    proj = d * heads * hd * 2 + d * kv * hd * 2
    router = d * s["num_experts"]
    expert = 3 * d * s["moe_intermediate_size"]
    evaluations = s["num_experts_per_tok"] * SHARE["experts"] / s["num_experts"]
    macs = 0
    for kind in s["layer_types"][: SHARE["layers"]]:
        window = s["sliding_window"] if kind == SLIDING else None
        macs += t * (proj + router) + t * evaluations * expert
        macs += needed_pairs(t, window) * heads * hd * 2
    return int(macs + t * d * vocab)
