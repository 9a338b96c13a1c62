"""Plain reference: one chip's share of Laguna-XS.2 (poolside, 33.4B-A3B),
forward pass, loss and gradient in float32.

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json
(SHAPE below copies its keys). Straight `jax.numpy`, every product at
`highest` precision, dense attention with the causal / window mask written
out, the experts as a plain loop. It imports nothing of `mgwfbp_tpu`; it is
handed the program's initial parameters as a flat `{"a/b/c": array}` dict
(random draws from the seed, nothing the program computed).

Per layer l, x the residual stream, n_l = num_attention_heads_per_layer[l]:

    u = RMSNorm(x);  q = rope_l(W_q u) (n_l x 128), k = rope_l(W_k u),
                     v = W_v u (8 x 128);  g = sigmoid(u W_g) (n_l)
    h = x + W_o [ g_head * Attn_l(q, k, v) ]
    v' = RMSNorm(h)
    mlp_layer_types[l] dense:   x' = h + W_down (silu(W_gate v') * W_up v')
    sparse:  x' = h + Shared(v') + 2.5 sum_{k in top 8} w_k Expert_{e_k}(v')

RMSNorm(u) = g * u / sqrt(mean(u^2) + 1e-6). 8 key/value heads of 128, each
serving n_l / 8 consecutive query heads (6 on full layers, 8 on window
layers), scores q.k / sqrt(128), position i sees j <= i and, on a
`sliding_attention` layer, only i - j < 512. rope_l rotates the first
head_dim x partial_rotary_factor dimensions of a head in the half-split layout
and passes the rest through: 64 on full layers (YaRN: theta 500,000, factor
64 over 4,096 positions, beta_fast 64, beta_slow 1, cos and sin times
1.4158883083359672), all 128 on window layers (theta 10,000). Shared and
Expert are SwiGLU blocks of width 512. Then a final RMSNorm and an untied
head; the loss is the mean over tokens of -log softmax(logits)[next token].

**Assumed** (config.json does not settle them; each in one function here):
`attention_gate`: `gating: true` is a headwise sigmoid gate on the attention
output (arXiv:2505.06708; the published 33.4 B excludes a gate per channel);
`route`: s = sigmoid(v' W_r), the 8 largest, w_k = s_k / sum of the chosen,
times `moe_routed_scaling_factor` (arXiv:2412.19437 eq. 12 to 15 without the
selection bias). No query/key norm, no router bias, no balancing loss.

**The share.** The parameters hold the first `layers` of the model's layers,
`count` of the 256 routed experts (the stacked expert leaves' leading
dimension) starting at expert SHARE["first_expert"], and the embedding's and
head's rows of the held vocabulary. The router still scores all 256 and
normalises over all 8 chosen; only the held experts' terms are added. The
shared expert, like the attention and the dense MLP, is whole on every chip:
it is added entire. What the absent experts would have added is left out here
exactly as in the program.

Departures from a textbook forward, for memory only: a sequence at a time, a
layer at a time, an expert at a time (`jax.checkpoint`; the loops over query
blocks and experts are `lax.map` / `lax.scan` so that they run one after the
other), and the attention a block of QUERY_BLOCK queries at a time against
ALL keys of the sequence under the written-out mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
SHAPE = {
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "num_experts": 256,
    "num_experts_per_tok": 8,
    "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512,
    "moe_routed_scaling_factor": 2.5,
    "sliding_window": 512,
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 10,
    "mlp_layer_types": [DENSE] + [SPARSE] * 39,
    "rms_norm_eps": 1e-6,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0,
               "original_max_position_embeddings": 4096, "beta_fast": 64.0,
               "beta_slow": 1.0, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                  "partial_rotary_factor": 1.0},
    },
}
# what `forward_macs` and `first_step` take for the share where the
# parameters cannot say it: 5 of 40 layers, experts 0..31 of 256
SHARE = {"layers": 5, "first_expert": 0, "experts": 32}
QUERY_BLOCK = 256
HI = lax.Precision.HIGHEST


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def inv_freq(shape: dict, kind: str):
    """(inverse frequencies, one for each PAIR of the dimensions of a head
    that rotate on a layer of `kind`; factor on cos and sin)."""
    rope = shape["rope_parameters"][kind]
    dim = int(shape["head_dim"] * rope["partial_rotary_factor"])
    theta = rope["rope_theta"]
    base = jnp.asarray(
        [theta ** (-2.0 * i / dim) for i in range(dim // 2)], jnp.float32)
    if rope["rope_type"] == "default":
        return base, 1.0

    def c(rotations):
        return dim * math.log(
            rope["original_max_position_embeddings"]
            / (2 * math.pi * rotations)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = jnp.asarray(
        [min(max((i - low) / (high - low), 0.0), 1.0)
         for i in range(dim // 2)], jnp.float32)
    return (1 - ramp) * base + ramp * base / rope["factor"], \
        rope["attention_factor"]


def rope(x, freqs, factor):
    """x (T, heads, head_dim): the first 2 x len(freqs) dimensions of every
    head as x cos + rotate_half(x) sin by position, the rest as they are."""
    t = x.shape[0]
    rotary = 2 * freqs.shape[0]
    turn, rest = x[..., :rotary], x[..., rotary:]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate(
        [-turn[..., rotary // 2:], turn[..., :rotary // 2]], -1)
    turned = turn * (jnp.cos(angle) * factor) + half * (jnp.sin(angle) * factor)
    return jnp.concatenate([turned, rest], axis=-1)


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


def attention_gate(u, w_gate, dtype=None):
    """ASSUMED reading of `gating: true`: one sigmoid scalar a head and
    token, g = sigmoid(u W_g) (T, heads), multiplying that head's attention
    output before W_o."""
    return jax.nn.sigmoid(
        jnp.dot(_stored(u, dtype), _stored(w_gate, dtype), precision=HI))


def route(u, router, top_k, scaling):
    """ASSUMED score: s = sigmoid(u W_r) over all experts, the `top_k`
    largest, each over the sum of the chosen, times `scaling`. (indices
    (T, k), weights (T, k)). Float32 as stored: the control leaves the router
    alone, as the configuration's precision states a float32 router."""
    scores = jax.nn.sigmoid(jnp.dot(u, router, precision=HI))
    top, idx = lax.top_k(scores, top_k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) * scaling


def swiglu(u, w_gate, w_up, w_down, dtype=None):
    gate = jnp.dot(_stored(u, dtype), _stored(w_gate, dtype), precision=HI)
    up = jnp.dot(_stored(u, dtype), _stored(w_up, dtype), precision=HI)
    return jnp.dot(_stored(jax.nn.silu(gate) * up, dtype),
                   _stored(w_down, dtype), precision=HI)


def shared_expert(p, u, dtype=None):
    """What every chip of the group computes alike, for every token."""
    return swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"], dtype)


def routed_experts(p, u, shape, first, dtype=None):
    """Held routed experts' part of the block for tokens u (T, hidden):
    experts first .. first + count - 1, one after the other, each over all
    tokens with the weight the router gave it (zero where it was not
    chosen)."""
    idx, w = route(u, p["router"], shape["num_experts_per_tok"],
                   shape["moe_routed_scaling_factor"])

    @jax.checkpoint
    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(u, w_gate, w_up, w_down, dtype), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(u), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def sparse_block(p, u, shape, first, dtype=None):
    return shared_expert(p, u, dtype) + routed_experts(
        p, u, shape, first, dtype)


def layer(p, x, index, shape, first, dtype=None):
    t = x.shape[0]
    hd = shape["head_dim"]
    eps = shape["rms_norm_eps"]
    kind = shape["layer_types"][index]
    heads = shape["num_attention_heads_per_layer"][index]
    u = rms_norm(x, p["attn_norm"], eps)

    def proj(name, n):
        return jnp.dot(_stored(u, dtype), _stored(p[name], dtype),
                       precision=HI).reshape(t, n, hd)

    freqs, factor = inv_freq(shape, kind)
    q = rope(proj("wq", heads), freqs, factor)
    k = rope(proj("wk", shape["num_key_value_heads"]), freqs, factor)
    v = proj("wv", shape["num_key_value_heads"])
    a = attention(q, k, v,
                  shape["sliding_window"] if kind == SLIDING else None, dtype)
    a = a * attention_gate(u, p["wg"], dtype)[:, :, None]
    h = x + jnp.dot(_stored(a.reshape(t, -1), dtype), _stored(p["wo"], dtype),
                    precision=HI)
    v2 = rms_norm(h, p["mlp_norm"], eps)
    if shape["mlp_layer_types"][index] == DENSE:
        return h + swiglu(
            v2, p["mlp_gate"], p["mlp_up"], p["mlp_down"], dtype)
    return h + sparse_block(p, v2, shape, first, dtype)


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
            functools.partial(layer, index=i, shape=shape, first=first,
                              dtype=dtype)
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
    beside the parameters (692 M parameters are 2.8 GB in float32)."""
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


def forward_macs(shape=(8192,), vocab: int = 12544) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers and routed experts held; `vocab`: the vocabulary
    held). `shape` is (sequence length,). Counted per layer: the four
    attention projections at the layer's own head count and the gate's; the
    score and value products over the pairs the mask lets through (the
    triangle on a full layer, the band on a window layer); the dense MLP, or
    the router over all 256 experts, the shared expert ONCE a token and the
    EXPECTED routed work, 8 x held / 256 evaluations a token (1 at 32 of
    256: under uniform routing, which seeded weights give to within a few
    per cent); and the held head. Not counted: the embedding lookup, norms,
    rotary embedding, softmax and sigmoids, the recomputation the program's
    checkpoints add, the optimizer."""
    (t,) = shape
    s = SHAPE
    d, hd, kv = s["hidden_size"], s["head_dim"], s["num_key_value_heads"]
    evaluations = s["num_experts_per_tok"] * SHARE["experts"] / s["num_experts"]
    macs = 0
    for i in range(SHARE["layers"]):
        heads = s["num_attention_heads_per_layer"][i]
        window = s["sliding_window"] if s["layer_types"][i] == SLIDING else None
        macs += t * (d * heads * hd * 2 + d * kv * hd * 2 + d * heads)
        macs += needed_pairs(t, window) * heads * hd * 2
        if s["mlp_layer_types"][i] == DENSE:
            macs += t * 3 * d * s["intermediate_size"]
        else:
            macs += t * (
                d * s["num_experts"]
                + 3 * d * s["shared_expert_intermediate_size"]
                + evaluations * 3 * d * s["moe_intermediate_size"])
    return int(macs + t * d * vocab)
