"""Plain reference: one chip's share of Qwen3-Next-80B-A3B-Instruct (Qwen),
forward pass, loss and gradient in float32.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
(SHAPE below copies its keys). Straight `jax.numpy`, every product at
`highest` precision, the gated delta rule as the LITERAL RECURRENCE over
tokens, attention as a literal softmax under the causal mask written out, the
router and both gates written out, the experts as a plain loop. It imports
nothing of `mgwfbp_tpu`; it is handed the program's initial parameters as a
flat `{"a/b/c": array}` dict (random draws from the seed, nothing the program
computed).

x is the residual stream; RMS0(x; w) = x / sqrt(mean(x^2) + 1e-6) . (1 + w)
(the model's zero-centred norm: residual, final, q and k norms). Layer l is
full attention where (l + 1) % 4 == 0, Gated DeltaNet elsewhere:

    every layer:  x = x + mixer_l(RMS0(x));  x = x + moe(RMS0(x))
    out:          logits = RMS0(x) W_head

    Gated DeltaNet (16 key heads and 32 value heads of 128):
        [q | k | v | z] = u W_qkvz, a key head's columns together:
                          [q 128 | k 128 | v 2 x 128 | z 2 x 128]
        [b | a] = u W_ba   (32 + 32)
        [q | k | v] = silu(causal depthwise conv of 4 taps over [q | k | v])
        beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
        q = q / ||q|| / sqrt(128), k = k / ||k|| over a head's 128
        value head h reads key head h // 2
        S_0 = 0;  S' = exp(g_t) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
        o_t = S_t^T q_t;  out = (RMSNorm_128(o_t; w_n) . silu(z_t)) W_out
    full attention (16 query heads, 2 key heads of 256, scores q.k / 16):
        [q | gate] = u W_q, a head's [q 256 | gate 256];  k = u W_k, v = u W_v
        q = RMS0_256(q; w_q), k = RMS0_256(k; w_k);  the FIRST 64 of a head's
        256 rotated by position (theta 1e7, half-split), the rest as they are
        key head h // 8 serves query head h;  out = (a . sigmoid(gate)) W_o
    moe:  p = softmax(u W_r) over all 512;  the 10 largest, each over their sum
          y = sum_j w_j E_{i_j}(u) + sigmoid(u . w_s) E_shared(u)
          E(u) = (silu(u W_g) . u W_u) W_d

The loss is the mean over tokens of -log softmax(logits)[next token].

**Assumed** (config.json does not settle them): the L2 norm as x rsqrt(sum x^2
+ 1e-6); W_ba's columns as [b of the 32 value heads | a of the 32]; no
convolution bias; RMSNorm_128's weight a plain scale (it starts at one); the
multi-token-prediction module left out (config.json declares no such layer).

**The share.** The parameters hold the first `layers` of the model's layers,
`count` of each layer's 512 routed experts (the stacked expert leaves' leading
dimension) starting at expert SHARE["first_expert"], and the embedding's and
head's rows of the held vocabulary. The router still scores all 512 and
normalises over all 10 chosen; only the held experts' terms are added. The
shared expert under its gate, like the mixers, is whole on every chip: it is
added entire. What the absent experts would have added is left out here
exactly as in the program.

Departures from a textbook forward, for memory only: a sequence at a time, a
layer at a time, an expert at a time, TIME_BLOCK positions of the recurrence at
a time (`jax.checkpoint`; the loops are `lax.scan` / `lax.map` so that they run
one after the other), the attention a block of QUERY_BLOCK queries at a time
against ALL keys under the written-out mask, the head LOSS_BLOCK tokens at a
time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

GDN, FULL = "linear_attention", "full_attention"
SHAPE = {
    "hidden_size": 2048,
    "num_hidden_layers": 48,
    "full_attention_interval": 4,
    "num_attention_heads": 16,
    "num_key_value_heads": 2,
    "head_dim": 256,
    "partial_rotary_factor": 0.25,
    "rope_theta": 10000000,
    "linear_num_key_heads": 16,
    "linear_num_value_heads": 32,
    "linear_key_head_dim": 128,
    "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4,
    "num_experts": 512,
    "num_experts_per_tok": 10,
    "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512,
    "rms_norm_eps": 1e-6,
    "l2_norm_eps": 1e-6,  # assumed
}
# what `forward_macs` and `first_step` take for the share where the
# parameters cannot say it: 4 of 48 layers, experts 0..31 of 512, 18,992 ids
SHARE = {"layers": 4, "first_expert": 0, "experts": 32, "vocab": 18992}
TIME_BLOCK = 64
QUERY_BLOCK = 256
LOSS_BLOCK = 2048
HI = lax.Precision.HIGHEST


def layer_kind(index: int, shape: dict) -> str:
    return FULL if (index + 1) % shape["full_attention_interval"] == 0 else GDN


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _dot(a, b, dtype):
    return jnp.dot(_stored(a, dtype), _stored(b, dtype), precision=HI)


def rms_norm0(x, w, eps):
    """The zero-centred norm: (1 + w) x / rms(x)."""
    return (1.0 + w) * x * lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rms_norm(x, w, eps):
    return w * x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def l2_norm(x, eps):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """x (T, C), w (K, C): out_t = sum_k w_k x_{t-K+1+k}, no bias."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(w[i] * padded[i:i + t] for i in range(k))


def delta_recurrence(q, k, v, g, beta, dtype=None):
    """The gated delta rule of one sequence, position by position.

    q, k (T, H, K) (already a value head's own), v (T, H, V), g, beta (T, H)
    -> (o (T, H, V), final state (H, K, V)). `dtype`: the control rounds the
    operands of the three products (S' and k_t; k_t and what is written; S_t
    and q_t); the state carried from position to position stays float32."""
    t, h, dk = q.shape

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, None, None] * s
        held = jnp.sum(
            _stored(s, dtype) * _stored(k_t, dtype)[:, :, None], axis=1)
        u = b_t[:, None] * (v_t - held)
        s = s + _stored(k_t, dtype)[:, :, None] * _stored(u, dtype)[:, None]
        return s, jnp.sum(
            _stored(s, dtype) * _stored(q_t, dtype)[:, :, None], axis=1)

    @jax.checkpoint
    def block(s, inp):
        return lax.scan(step, s, inp)

    s = jnp.zeros((h, dk, v.shape[-1]), jnp.float32)
    whole = t // TIME_BLOCK
    out = []
    if whole:
        cut = whole * TIME_BLOCK
        s, o = lax.scan(block, s, tuple(
            x[:cut].reshape(whole, TIME_BLOCK, *x.shape[1:])
            for x in (q, k, v, g, beta)))
        out.append(o.reshape(cut, h, -1))
    if t % TIME_BLOCK:
        s, o = block(s, tuple(
            x[whole * TIME_BLOCK:] for x in (q, k, v, g, beta)))
        out.append(o)
    return jnp.concatenate(out, axis=0), s


def delta_inputs(p, u, shape, dtype=None):
    """From the normed input u (T, hidden): (q, k (T, Hv, 128) as each value
    head reads them, v (T, Hv, 128), g, beta (T, Hv), z (T, Hv, 128))."""
    t = u.shape[0]
    hk, hv = shape["linear_num_key_heads"], shape["linear_num_value_heads"]
    dk, dv = shape["linear_key_head_dim"], shape["linear_value_head_dim"]
    r = hv // hk
    qkvz = _dot(u, p["w_qkvz"], dtype).reshape(t, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, hv * dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    ba = _dot(u, p["w_ba"], dtype)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    mixed = jax.nn.silu(causal_conv(jnp.concatenate(
        [q.reshape(t, hk * dk), k.reshape(t, hk * dk), v], axis=-1),
        p["conv_w"]))
    q = l2_norm(mixed[:, :hk * dk].reshape(t, hk, dk),
                shape["l2_norm_eps"]) / dk ** 0.5
    k = l2_norm(mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk),
                shape["l2_norm_eps"])
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    # value head h reads key head h // r
    return jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, g, beta, z


def delta_mixer(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (out (T, hidden), final state (Hv, 128,
    128), beta (T, Hv))."""
    q, k, v, g, beta, z = delta_inputs(p, u, shape, dtype)
    o, state = delta_recurrence(q, k, v, g, beta, dtype)
    o = rms_norm(o, p["gate_norm"], shape["rms_norm_eps"]) * jax.nn.silu(z)
    return _dot(o.reshape(u.shape[0], -1), p["w_out"], dtype), state, beta


def rope(x, dims, theta):
    """x (T, heads, head_dim): the first `dims` dimensions of every head as
    x cos + rotate_half(x) sin by position, the rest as they are."""
    t = x.shape[0]
    freqs = jnp.asarray(
        [theta ** (-2.0 * i / dims) for i in range(dims // 2)], jnp.float32)
    turn, rest = x[..., :dims], x[..., dims:]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-turn[..., dims // 2:], turn[..., :dims // 2]], -1)
    return jnp.concatenate(
        [turn * jnp.cos(angle) + half * jnp.sin(angle), rest], axis=-1)


def softmax_attention(q, k, v, dtype=None):
    """q (T, H, D), k, v (T, Hkv, D) of one sequence -> (T, H, D): every
    query against every key under the causal mask written out, scores
    q.k / sqrt(D), QUERY_BLOCK queries at a time."""
    t, h, d = q.shape
    groups = h // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)  # query head i reads key head i // groups
    v = jnp.repeat(v, groups, axis=1)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(qb, start):
        i = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", _stored(qb, dtype), _stored(k, dtype),
                       precision=HI) / d ** 0.5
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _stored(p, dtype), _stored(v, dtype),
                          precision=HI)

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


def attention_mixer(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (T, hidden): the gated full attention."""
    t = u.shape[0]
    h, hkv = shape["num_attention_heads"], shape["num_key_value_heads"]
    hd, eps = shape["head_dim"], shape["rms_norm_eps"]
    qg = _dot(u, p["wq"], dtype).reshape(t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _dot(u, p["wk"], dtype).reshape(t, hkv, hd)
    v = _dot(u, p["wv"], dtype).reshape(t, hkv, hd)
    dims = int(hd * shape["partial_rotary_factor"])
    q = rope(rms_norm0(q, p["q_norm"], eps), dims, shape["rope_theta"])
    k = rope(rms_norm0(k, p["k_norm"], eps), dims, shape["rope_theta"])
    a = softmax_attention(q, k, v, dtype) * jax.nn.sigmoid(gate)
    return _dot(a.reshape(t, h * hd), p["wo"], dtype)


def route(u, router, top_k):
    """p = softmax(u W_r) over ALL experts, the `top_k` largest, each over
    the sum of the chosen: (indices (T, k), weights (T, k)). Float32 as
    stored: the control leaves the router alone, as the configuration's
    precision states a float32 router."""
    probs = jax.nn.softmax(jnp.dot(u, router, precision=HI), axis=-1)
    top, idx = lax.top_k(probs, top_k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def swiglu(u, w_gate, w_up, w_down, dtype=None):
    return _dot(jax.nn.silu(_dot(u, w_gate, dtype)) * _dot(u, w_up, dtype),
                w_down, dtype)


def shared_gate(p, u, dtype=None):
    """sigmoid(u . w_s): one scalar a token, (T,)."""
    return jax.nn.sigmoid(_dot(u, p["shared_gate_w"], dtype))


def shared_expert(p, u, dtype=None):
    """What every chip of the group computes alike, for every token: the
    shared expert under its gate."""
    return shared_gate(p, u, dtype)[:, None] * swiglu(
        u, p["shared_gate"], p["shared_up"], p["shared_down"], dtype)


def routed_experts(p, u, shape, first, dtype=None):
    """Held routed experts' part of the block for tokens u (T, hidden):
    experts first .. first + count - 1, one after the other, each over all
    tokens with the weight the router gave it (zero where it was not
    chosen)."""
    idx, w = route(u, p["router"], shape["num_experts_per_tok"])

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
    """Layer `index` on the residual stream x (T, hidden)."""
    eps = shape["rms_norm_eps"]
    u = rms_norm0(x, p["attn_norm"], eps)
    if layer_kind(index, shape) == GDN:
        y, _, _ = delta_mixer(p, u, shape, dtype)
    else:
        y = attention_mixer(p, u, shape, dtype)
    x = x + y
    return x + sparse_block(
        p, rms_norm0(x, p["moe_norm"], eps), shape, first, dtype)


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
        h = jax.checkpoint(functools.partial(
            layer, index=i, shape=shape, first=first, dtype=dtype,
        ))(tree[f"layer_{i}"], h)
    return rms_norm0(h, tree["out"]["norm"], shape["rms_norm_eps"])


def logits(params: dict, x, *, shape=None, first=None, dtype=None):
    """x (T,) token ids of ONE sequence -> (T, held vocabulary) float32."""
    shape = SHAPE if shape is None else shape
    first = SHARE["first_expert"] if first is None else first
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, first, dtype)
    return _dot(h, tree["out"]["head"], dtype)


def token_losses(params: dict, x, y, *, shape=None, first=None, dtype=None):
    """-log softmax(logits)[y] of every token of one sequence, (T,), the
    head LOSS_BLOCK tokens at a time."""
    shape = SHAPE if shape is None else shape
    first = SHARE["first_expert"] if first is None else first
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, first, dtype)
    head = tree["out"]["head"]

    @jax.checkpoint
    def block(hb, yb):
        lg = _dot(hb, head, dtype)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    return jnp.concatenate([
        block(h[i:i + LOSS_BLOCK], y[i:i + LOSS_BLOCK])
        for i in range(0, h.shape[0], LOSS_BLOCK)])


def sequence_loss(params: dict, x, y, **kw):
    """Mean over the sequence's tokens of -log softmax(logits)[y]."""
    return jnp.mean(token_losses(params, x, y, **kw))


@functools.partial(jax.jit, static_argnums=(3,))
def _loss_and_grad_sumsq(params, x, y, dtype):
    """(mean over the rows of x of the sequence's loss, sum over all
    parameters of its gradient squared). The rows go one after the other (a
    scan whose body is recomputed in the backward pass): 626 M parameters
    are 2.5 GB in float32 and their gradient as much, and one sequence's
    float32 activations are all the device holds beside them."""
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
    the router's rounded to it first, the recurrence's three among them."""
    del seed, shards
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, sumsq = _loss_and_grad_sumsq(
            params, jnp.asarray(x), jnp.asarray(y), dtype)
    return {"loss": float(loss), "grad_norm": float(sumsq) ** 0.5}


def causal_pairs(t: int) -> int:
    """(query, key) pairs the causal mask lets through: the triangle."""
    return t * (t + 1) // 2


def delta_macs(t: int) -> int:
    """Multiply-accumulates of one layer's delta rule over a sequence of t,
    each of the recurrence's products once: per token and value head S'^T k,
    the outer product k u^T and S^T q, 3 x 128 x 128 (1,572,864 a token over
    the 32 heads). The decay's product and the exponentials are not
    counted."""
    s = SHAPE
    return 3 * t * s["linear_num_value_heads"] \
        * s["linear_key_head_dim"] * s["linear_value_head_dim"]


def forward_macs(shape=(8192,), vocab: int = SHARE["vocab"]) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers and routed experts held; `vocab`: the vocabulary
    held). `shape` is (sequence length,). Counted per layer: a Gated DeltaNet
    mixer's W_qkvz, W_ba, four-tap convolution, delta rule (`delta_macs`) and
    W_out, or the full mixer's W_q (queries and gates), W_k, W_v, the score
    and value products over the causal pairs and W_o; the router over all 512
    experts, the shared expert and its gate ONCE a token and the EXPECTED
    routed work, 10 x held / 512 evaluations a token (0.625 at 32 of 512:
    under uniform routing, which seeded weights give to within a few per
    cent); and the held head. Not counted: the embedding lookup, norms, L2
    norms, rotary embedding, softmax, sigmoids, softplus, the recomputation
    the program's checkpoints add, the optimizer."""
    (t,) = shape
    s = SHAPE
    d = s["hidden_size"]
    key_dim = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    value_dim = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    dq = s["num_attention_heads"] * s["head_dim"]
    dkv = s["num_key_value_heads"] * s["head_dim"]
    evaluations = s["num_experts_per_tok"] * SHARE["experts"] / s["num_experts"]
    macs = 0
    for i in range(SHARE["layers"]):
        if layer_kind(i, s) == GDN:
            macs += t * (
                d * (2 * key_dim + 2 * value_dim)
                + d * 2 * s["linear_num_value_heads"]
                + s["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
                + value_dim * d) + delta_macs(t)
        else:
            macs += t * (d * 2 * dq + 2 * d * dkv + dq * d) \
                + causal_pairs(t) * dq * 2
        macs += t * (
            d * s["num_experts"] + d
            + 3 * d * s["shared_expert_intermediate_size"]
            + evaluations * 3 * d * s["moe_intermediate_size"])
    return int(macs + t * d * vocab)


def delta_flops_and_bytes(t: int = 8192, batch: int = 2,
                          bytes_per_element: int = 2) -> dict:
    """What ONE Gated DeltaNet layer's delta rule needs for `batch` sequences
    of t, forward and backward passes together, for its roofline share
    (device time under the scope `gdn_delta`; PERF.md).

    flops: 2 x `delta_macs` forward, twice that again backward (each product
    has two transposes); the chunked form's extra products, the solve,
    exponentials and recomputation are not counted. bytes: the least traffic
    to memory, every operand read and every result written once: forward
    reads q, k (16 heads), v (32 heads) (elements of `bytes_per_element`), g
    and beta (float32) and writes o; backward reads those and o's cotangent
    and writes the five cotangents. Nothing of the state: a fused kernel
    keeps it on the chip."""
    s = SHAPE
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    inputs = t * (2 * hk * dk + hv * dv) * bytes_per_element + t * 2 * hv * 4
    o = t * hv * dv * bytes_per_element
    return {
        "flops": batch * 3 * 2 * delta_macs(t),
        "bytes": batch * ((inputs + o) + (inputs + o + inputs)),
    }
