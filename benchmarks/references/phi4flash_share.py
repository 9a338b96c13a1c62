"""Plain reference: one chip's share of Phi-4-mini-flash-reasoning, forward
pass, loss and gradient in float32.

Source: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
(config.json, `model_type` phi4flash; SHAPE below copies its widths and names
the sizes it lacks), the equations as the published `modeling_phi4flash.py`
computes them. Straight `jax.numpy`, every product at `highest` precision. It
imports nothing of `mgwfbp_tpu`; it is handed the program's initial
parameters as a flat `{"layer_14/in_proj": array}` dict (random draws from the
seed, nothing the program computed), the layers named by their PUBLISHED
index.

x is the residual stream; LN(u) = w (u - mean u) / sqrt(var u + 1e-5) + b.
Layer l of n = 32:

    every layer:  x = x + mixer_l(LN1(x));  [g | u] = LN2(x) W_1 (no bias)
                  x = x + (silu(g) u) W_2 (no bias)
    out:          logits = LN_f(x) E^T, the SAME E as the lookup, no bias
    kind of l:    l even -> Mamba-1 if l <= 16, else GMU
                  l odd  -> attention: window 512 if l < 16, full if l = 17,
                            cross if l >= 19 (reads layer 17's K and V)
                  (0..15 = 8 x [Mamba, SWA]; 16 = Mamba*; 17 = Full*;
                   18..31 = 7 x [GMU, Cross])
    Mamba-1:      [xs (5120) | z (5120)] = u W_in
                  xs = silu(conv(xs) + b): depthwise, causal, width 4
                       (position t sees t - 3 .. t; w[3] multiplies t)
                  [dl (160) | B (16) | C (16)] = xs W_x
                  dt = softplus(dl W_dt + b_dt);  A = -exp(A_log) (5120 x 16)
                  per channel and state, h_0 = 0, ONE POSITION AFTER ANOTHER:
                      h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t xs_t) (x) B_t
                      m_t = h_t C_t + D . xs_t
                  out = (m . silu(z)) W_out;  layer 16's m is what GMUs read
    GMU:          out = (m . silu(u W_g)) W_o
    attention:    (no position term; W_qkv, W_o WITH bias; scores / 8)
                  q (40 x 64), k, v (20 x 64) = u W_qkv + b;  q1 = even, q2 =
                  odd query heads (20 each); k1, k2, v1, v2 likewise (10
                  each; key head j serves query heads 2j, 2j + 1 of its half)
                  a1 = [Att(q1,k1,v1) | Att(q1,k1,v2)]      (128 wide a pair)
                  a2 = [Att(q2,k2,v1) | Att(q2,k2,v2)]
                  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
                  lam0 = 0.8 - 0.6 exp(-0.3 l)
                  a = RMSNorm_128(a1 - lam a2; weight, eps 1e-5) (1 - lam0)
                  out = reshape(a, 2560) W_o + b_o
                  Att: causal softmax attention, EACH OF THE FOUR WRITTEN
                  OUT; on a window layer query i sees keys i - 511 .. i
    cross:        q = u W_q + b_q only; k1, k2, v1, v2 are layer 17's

The loss is the mean over tokens of -log softmax(logits)[next token].

**The scan is the literal recurrence**: `lax.scan` over positions carrying h,
never the chunked form the program computes, so a comparison with this file
holds the chunked algorithm (its chunk boundaries, carried states and the
order of its steps) and not only its arithmetic.

**The share.** The parameters hold the layers of one pipeline stage (SHARE:
layers 14 to 19, the stage round the hinge; whichever `layer_<l>` groups the
dict has are the stage) and the rows of the tied embedding of the held
vocabulary; ids, logits and loss are over that slice. A stage that holds a
GMU or a cross layer holds the layer it reads.

Departures from a textbook forward, for memory only: a sequence at a time, a
layer at a time (`jax.checkpoint`; the memory and the keys and values leave
their layer as outputs), the recurrence in blocks of TIME_BLOCK positions (a
checkpointed scan over blocks of an inner scan), the attention a block of
QUERY_BLOCK queries at a time against all keys under the written-out mask,
and the head and loss LOSS_BLOCK tokens at a time.

Departures from the publisher's code: none in the equations. Its dropouts
have rate 0; its cache, its position ids and its padding mask serve
inference and ragged batches, which this path has not.

Assumed (config.json names none): d_state 16, d_conv 4, expand 2, dt_rank
160, the biases named above (and the convolution's), the even / odd pairing,
lam0's formula, the sub-norm's eps 1e-5; sequences of one length, no
document mask, the state zero at a sequence's start and never reset inside
it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

MAMBA, GMU = "mamba", "gmu"
WINDOW, FULL, CROSS = "sliding_attention", "full_attention", "cross_attention"
SHAPE = {
    "hidden_size": 2560,
    "intermediate_size": 10240,
    "num_attention_heads": 40,
    "num_key_value_heads": 20,
    "head_dim": 64,  # hidden_size / num_attention_heads
    "num_hidden_layers": 32,
    "mb_per_layer": 2,
    "sliding_window": 512,
    "layer_norm_eps": 1e-5,
    # assumed: config.json has none of these
    "mamba_d_state": 16,
    "mamba_d_conv": 4,
    "mamba_expand": 2,
    "mamba_dt_rank": 160,
    "sub_norm_eps": 1e-5,
}
# what `forward_macs` takes for the share: layers 14 to 19 of 32
SHARE = {"first_layer": 14, "layers": 6}
TIME_BLOCK = 128
QUERY_BLOCK = 256
LOSS_BLOCK = 2048
HI = lax.Precision.HIGHEST


def layer_kind(index: int, shape: dict) -> str:
    half = shape["num_hidden_layers"] // 2
    if index % shape["mb_per_layer"] == 0:
        return MAMBA if index <= half else GMU
    if index < half:
        return WINDOW
    return FULL if index == half + 1 else CROSS


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _dot(a, b, dtype):
    return jnp.dot(_stored(a, dtype), _stored(b, dtype), precision=HI)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return w * (x - mean) / jnp.sqrt(var + eps) + b


def rms_norm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w, bias):
    """x (T, C), w (K, C), bias (C,): out_t = bias + sum_k w_k x_{t-K+1+k}."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return bias + sum(w[i] * padded[i:i + t] for i in range(k))


def recurrence(xs, dt, a, b, c, dtype=None):
    """h_t = exp(dt_t (x) a) . h_{t-1} + (dt_t xs_t) (x) B_t, y_t = h_t C_t
    for one sequence, position by position. xs, dt (T, D), a (D, N), b, c
    (T, N) -> (y (T, D), final state (D, N)). `dtype`: the control rounds the
    operands of the two products (dt_t xs_t and B_t; h_t and C_t); the state
    carried from position to position stays float32."""
    t, d = xs.shape

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h \
            + _stored(dt_t * x_t, dtype)[:, None] * _stored(b_t, dtype)[None]
        return h, jnp.sum(_stored(h, dtype) * _stored(c_t, dtype)[None], -1)

    @jax.checkpoint
    def block(h, inp):
        return lax.scan(step, h, inp)

    whole = t // TIME_BLOCK
    h = jnp.zeros((d, a.shape[1]), jnp.float32)
    out = []
    if whole:
        cut = whole * TIME_BLOCK
        h, y = lax.scan(block, h, tuple(
            v[:cut].reshape(whole, TIME_BLOCK, v.shape[1])
            for v in (xs, dt, b, c)))
        out.append(y.reshape(cut, d))
    if t % TIME_BLOCK:
        h, y = block(h, tuple(v[whole * TIME_BLOCK:] for v in (xs, dt, b, c)))
        out.append(y)
    return jnp.concatenate(out, axis=0), h


def mamba_mixer(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (out (T, hidden), the memory m (T, d_inner),
    the final state (d_inner, N))."""
    inner = shape["mamba_expand"] * shape["hidden_size"]
    n, rank = shape["mamba_d_state"], shape["mamba_dt_rank"]
    xz = _dot(u, p["in_proj"], dtype)
    xs, z = xz[:, :inner], xz[:, inner:]
    xs = jax.nn.silu(causal_conv(xs, p["conv_w"], p["conv_b"]))
    dbc = _dot(xs, p["x_proj"], dtype)
    dt = jax.nn.softplus(
        _dot(dbc[:, :rank], p["dt_proj"], dtype) + p["dt_bias"])
    y, state = recurrence(
        xs, dt, -jnp.exp(p["a_log"]), dbc[:, rank:rank + n],
        dbc[:, rank + n:], dtype)
    m = y + p["d"] * xs
    return _dot(m * jax.nn.silu(z), p["out_proj"], dtype), m, state


def gated_memory(p, u, m, dtype=None):
    return _dot(m * jax.nn.silu(_dot(u, p["w_g"], dtype)), p["w_o"], dtype)


def softmax_attention(q, k, v, window, dtype=None):
    """Causal softmax attention written out: q (T, H, D) against k, v (T, H,
    D), scores over sqrt(D); with `window`, query i sees keys i - window + 1
    .. i. A block of QUERY_BLOCK queries at a time against ALL keys."""
    t, h, hd = q.shape
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(qb, start):
        i = start + jnp.arange(qb.shape[0])[:, None]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        s = jnp.einsum("qhd,khd->hqk", _stored(qb, dtype), _stored(k, dtype),
                       precision=HI) / math.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _stored(prob, dtype),
                          _stored(v, dtype), precision=HI)

    whole = t // QUERY_BLOCK
    out = []
    if whole:
        out.append(lax.map(
            lambda qs: block(*qs),
            (q[:whole * QUERY_BLOCK].reshape(whole, QUERY_BLOCK, h, hd),
             jnp.arange(whole) * QUERY_BLOCK),
        ).reshape(whole * QUERY_BLOCK, h, hd))
    if t % QUERY_BLOCK:
        out.append(block(q[whole * QUERY_BLOCK:], whole * QUERY_BLOCK))
    return jnp.concatenate(out, axis=0)


def differential(p, q, k, v, index, window, shape, dtype=None):
    """q (T, 40, 64), k, v (T, 20, 64) -> (T, hidden): the four softmax
    products of every pair, the subtraction, the sub-norm, W_o."""
    t = q.shape[0]
    q1, q2 = q[:, 0::2], q[:, 1::2]
    # key head j of a half serves query heads 2j and 2j + 1 of that half
    k1, k2, v1, v2 = (
        jnp.repeat(a, q1.shape[1] // (k.shape[1] // 2), axis=1)
        for a in (k[:, 0::2], k[:, 1::2], v[:, 0::2], v[:, 1::2]))
    att = functools.partial(softmax_attention, window=window, dtype=dtype)
    a1 = jnp.concatenate([att(q1, k1, v1), att(q1, k1, v2)], axis=-1)
    a2 = jnp.concatenate([att(q2, k2, v1), att(q2, k2, v2)], axis=-1)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
    a = rms_norm(a1 - lam * a2, p["sub_norm"], shape["sub_norm_eps"])
    a = (a * (1.0 - lam0)).reshape(t, -1)
    return _dot(a, p["wo"], dtype) + p["bo"]


def layer(p, x, read, *, index, shape, dtype=None):
    """Published layer `index` on one sequence's residual stream x (T,
    hidden). `read`: the memory (GMU), (k, v) (cross), else None. Returns (x',
    what the layer publishes: m on layer n / 2, (k, v) on layer n / 2 + 1)."""
    kind = layer_kind(index, shape)
    eps = shape["layer_norm_eps"]
    t = x.shape[0]
    h, hkv = shape["num_attention_heads"], shape["num_key_value_heads"]
    hd = shape["head_dim"]
    half = shape["num_hidden_layers"] // 2
    u = layer_norm(x, p["norm"], p["norm_b"], eps)
    published = None
    if kind == MAMBA:
        y, m, _ = mamba_mixer(p, u, shape, dtype)
        if index == half:
            published = m
    elif kind == GMU:
        y = gated_memory(p, u, read, dtype)
    elif kind == CROSS:
        q = (_dot(u, p["wq"], dtype) + p["bq"]).reshape(t, h, hd)
        y = differential(p, q, *read, index, None, shape, dtype)
    else:
        qkv = _dot(u, p["wqkv"], dtype) + p["bqkv"]
        q = qkv[:, :h * hd].reshape(t, h, hd)
        k = qkv[:, h * hd:(h + hkv) * hd].reshape(t, hkv, hd)
        v = qkv[:, (h + hkv) * hd:].reshape(t, hkv, hd)
        window = shape["sliding_window"] if kind == WINDOW else None
        y = differential(p, q, k, v, index, window, shape, dtype)
        if index == half + 1:
            published = (k, v)
    x = x + y
    f = shape["intermediate_size"]
    gu = _dot(layer_norm(x, p["mlp_norm"], p["mlp_norm_b"], eps), p["w1"],
              dtype)
    return x + _dot(jax.nn.silu(gu[:, :f]) * gu[:, f:], p["w2"], dtype), \
        published


def _tree(params: dict) -> dict:
    """{"layer_14/wq": a, ...} -> {"layer_14": {"wq": a}, ...} in float32."""
    tree: dict = {}
    for key, value in params.items():
        group, name = key.split("/")
        tree.setdefault(group, {})[name] = jnp.asarray(value, jnp.float32)
    return tree


def held_layers(tree: dict) -> list[int]:
    return sorted(int(k.split("_")[1]) for k in tree if k.startswith("layer_"))


def hidden_states(tree, x, shape, dtype=None):
    """Final-norm output (T, hidden) of one sequence x (T,) of token ids."""
    h = tree["embed"]["embedding"][x]
    reads = {GMU: None, CROSS: None}
    for index in held_layers(tree):
        kind = layer_kind(index, shape)
        h, published = jax.checkpoint(functools.partial(
            layer, index=index, shape=shape, dtype=dtype,
        ))(tree[f"layer_{index}"], h, reads.get(kind))
        if published is not None:
            reads[GMU if kind == MAMBA else CROSS] = published
    return layer_norm(
        h, tree["out"]["norm"], tree["out"]["norm_b"], shape["layer_norm_eps"])


def logits(params: dict, x, *, shape=None, dtype=None):
    """x (T,) token ids of ONE sequence -> (T, held vocabulary) float32."""
    shape = SHAPE if shape is None else shape
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, dtype)
    return _dot(h, tree["embed"]["embedding"].T, dtype)


def sequence_loss(params: dict, x, y, *, shape=None, dtype=None):
    """Mean over the sequence's tokens of -log softmax(logits)[y], the head
    LOSS_BLOCK tokens at a time."""
    shape = SHAPE if shape is None else shape
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, dtype)
    embedding = tree["embed"]["embedding"]

    @jax.checkpoint
    def block_sum(hb, yb):
        lg = _dot(hb, embedding.T, dtype)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        return -jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=-1))

    t = h.shape[0]
    total = sum(
        block_sum(h[i:i + LOSS_BLOCK], y[i:i + LOSS_BLOCK])
        for i in range(0, t, LOSS_BLOCK))
    return total / t


@functools.partial(jax.jit, static_argnums=(3,))
def _loss_and_grad_sumsq(params, x, y, dtype):
    """(mean over the rows of x of the sequence's loss, sum over all
    parameters of its gradient squared). The rows go one after the other (a
    scan whose body is recomputed in the backward pass): 697 M parameters
    are 2.8 GB in float32 and their gradient as much, and one sequence's
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
    the optimizer gets it; the tied embedding's is the sum of what the lookup
    and the head give it; layer 16's and 17's hold what every reader of their
    memory, keys and values sends back). Sequences have one length, so the
    mean of their means is the mean over tokens, whatever `shards` devices
    the rows were dealt to. No dropout, so `seed` draws nothing. `dtype` (a
    name, e.g. "float8_e4m3fn") computes the control: the operands of every
    product, the recurrence's two among them, rounded to it first."""
    del seed, shards
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, sumsq = _loss_and_grad_sumsq(
            params, jnp.asarray(x), jnp.asarray(y), dtype)
    return {"loss": float(loss), "grad_norm": float(sumsq) ** 0.5}


def causal_pairs(t: int, window=None) -> int:
    """(query, key) pairs the mask lets through: the triangle, or the band."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def scan_macs(t: int) -> int:
    """Multiply-accumulates of one layer's selective scan over a sequence of
    t, each of the recurrence's products once: per token, channel and state
    the decay times the state, (dt x) times B, and the state times C (3 x
    5,120 x 16 = 245,760 a token). The exponentials are not counted."""
    s = SHAPE
    return 3 * t * s["mamba_expand"] * s["hidden_size"] * s["mamba_d_state"]


def forward_macs(shape=(8192,), vocab: int = 25008) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers held; `vocab`: the rows of the tied embedding held).
    `shape` is (sequence length,). Counted: the gated MLP of every layer; on
    a Mamba layer W_in, the convolution (4 a channel), W_x, W_dt, the scan
    (`scan_macs`) and W_out; on a GMU W_g and W_o; on an attention layer
    W_qkv (W_q on a cross layer), W_o and the cores over the causal pairs
    (the triangle or the band, not the square): per pair 40 x 64 for the
    scores of q1.k1 and q2.k2 and 40 x 128 for their values [v1 | v2] (the
    two halves of a1 share one softmax, so the scores are counted ONCE: the
    four literal products above, and the program's stacked core, compute them
    twice); the head over the held rows. Not counted: the lookup, norms,
    softplus, exponentials, gates, the subtraction and its norm, the
    recomputation the program's checkpoints add, the optimizer."""
    (t,) = shape
    s = SHAPE
    d, f = s["hidden_size"], s["intermediate_size"]
    inner = s["mamba_expand"] * d
    n, rank = s["mamba_d_state"], s["mamba_dt_rank"]
    heads, kv, hd = (
        s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"])
    macs = 0
    first = SHARE["first_layer"]
    for index in range(first, first + SHARE["layers"]):
        kind = layer_kind(index, s)
        macs += t * 3 * d * f  # W_1 is d x 2f, W_2 f x d
        if kind == MAMBA:
            macs += t * d * 2 * inner + t * s["mamba_d_conv"] * inner
            macs += t * inner * (rank + 2 * n) + t * rank * inner
            macs += scan_macs(t) + t * inner * d
        elif kind == GMU:
            macs += 2 * t * d * inner
        else:
            dq = heads * hd
            macs += t * d * (dq if kind == CROSS else dq + 2 * kv * hd)
            macs += t * dq * d
            window = s["sliding_window"] if kind == WINDOW else None
            macs += causal_pairs(t, window) * heads * hd * 3
    return int(macs + t * d * vocab)


def scan_flops_and_bytes(t: int = 8192, batch: int = 1,
                         bytes_per_element: int = 2) -> dict:
    """What ONE Mamba layer's selective scan needs for `batch` sequences of
    t, forward and backward passes together, for its roofline share (device
    time under the scope `ssm_sel_scan`; PERF.md).

    flops: 2 x `scan_macs` forward, twice that again backward (each product
    has two transposes); exponentials and recomputation are not counted.
    bytes: the least traffic to memory, every operand read and every result
    written once: forward reads xs, B, C (elements of `bytes_per_element`)
    and dt (float32) and writes m; backward reads those and m's cotangent
    and writes the four cotangents. Nothing of the state: a fused kernel
    keeps it on the chip."""
    s = SHAPE
    inner = s["mamba_expand"] * s["hidden_size"]
    inputs = t * (inner + 2 * s["mamba_d_state"]) * bytes_per_element \
        + t * inner * 4
    m = t * inner * bytes_per_element
    return {
        "flops": batch * 3 * 2 * scan_macs(t),
        "bytes": batch * ((inputs + m) + (inputs + m + inputs)),
    }
