"""Plain reference: one chip's share of granite-4.0-h-micro, forward pass, loss
and gradient in float32.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro
(config.json, `model_type` granitemoehybrid; SHAPE below copies its widths),
the equations as `transformers`' `modeling_granitemoehybrid.py` computes them.
Straight `jax.numpy`, every product at `highest` precision. It imports
nothing of `mgwfbp_tpu`; it is handed the program's initial parameters as a
flat `{"a/b/c": array}` dict (random draws from the seed, nothing the program
computed).

x is the residual stream; RMSNorm(u) = g u / sqrt(mean(u^2) + 1e-5); no bias
but the convolution's.

    x = 12 E[ids]
    layer of kind `mamba`:
        u = RMSNorm(x);  [z (4096) | xBC (4352) | dt (64)] = u W_in
        xBC = silu(conv(xBC) + b): depthwise, causal, width 4 (position t
              sees t - 3 .. t; w[3] multiplies position t)
        [xs (64 heads x 64) | B (128) | C (128)] = xBC
        dt = softplus(dt + dt_bias);  A = -exp(A_log), one per head
        per head, S in R^(64 x 128), S_0 = 0, ONE POSITION AFTER ANOTHER:
            S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T
            y_t = S_t C_t + D xs_t
        (B and C are the same for all heads: `mamba_n_groups` 1)
        x = x + 0.22 (RMSNorm(y silu(z)) W_out)   the norm over all 4,096
    layer of kind `attention` (indices 5, 15, 25, 35):
        u = RMSNorm(x); 32 query heads of 64 over 8 key/value heads (each
        serves 4 consecutive query heads); NO rotary, no position term;
        causal softmax of (q . k) * 0.015625;  x = x + 0.22 (a W_o)
    every layer then:
        v = RMSNorm(x);  [p | q] = v W_1;  x = x + 0.22 ((silu(p) q) W_2)
    out: h = RMSNorm(x);  logits = (h E^T) / 8 with the SAME E

The loss is the mean over tokens of -log softmax(logits)[next token].

**The scan is the literal recurrence**: `lax.scan` over positions carrying S,
never the chunked form the program computes, so a comparison with this file
holds the chunked algorithm (its chunk boundaries, masks and carried states)
and not only its arithmetic.

**The share.** The parameters hold the first `layers` of the model's layers
(SHARE) and the rows of the tied embedding of the held vocabulary; ids,
logits and loss are over that slice.

Departures from a textbook forward, for memory only: a sequence at a time, a
layer at a time (`jax.checkpoint`), the recurrence in blocks of TIME_BLOCK
positions (a checkpointed scan over blocks of an inner scan, so the backward
pass keeps one state per block and per position of ONE block: 64 + 128
states of 2 MB at 8,192 positions, not 8,192), the attention a block of
QUERY_BLOCK queries at a time against all keys under the written-out mask,
and the head and loss LOSS_BLOCK tokens at a time.

Assumed (config.json names none): sequences of one length, no document mask,
the state zero at a sequence's start and never reset inside it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

MAMBA, ATTENTION = "mamba", "attention"
SHAPE = {
    "hidden_size": 2048,
    "shared_intermediate_size": 8192,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "attention_head_dim": 64,  # hidden_size / num_attention_heads
    "mamba_n_heads": 64,
    "mamba_d_head": 64,
    "mamba_d_state": 128,
    "mamba_d_conv": 4,
    "mamba_n_groups": 1,
    "mamba_chunk_size": 256,  # `forward_macs` only: nothing here is chunked
    "layer_types": ([MAMBA] * 5 + [ATTENTION] + [MAMBA] * 4) * 4,
    "rms_norm_eps": 1e-5,
    "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625,
    "logits_scaling": 8.0,
}
# what `forward_macs` takes for the share: 10 of 40 layers
SHARE = {"layers": 10}
TIME_BLOCK = 128
QUERY_BLOCK = 256
LOSS_BLOCK = 2048
HI = lax.Precision.HIGHEST


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _dot(a, b, dtype):
    return jnp.dot(_stored(a, dtype), _stored(b, dtype), precision=HI)


def rms_norm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w, bias):
    """x (T, C), w (K, C), bias (C,): out_t = bias + sum_k w_k x_{t-K+1+k}."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return bias + sum(w[i] * padded[i:i + t] for i in range(k))


def recurrence(xs, dt, a, b, c, dtype=None):
    """S_t = exp(dt_t a) S_{t-1} + dt_t xs_t B_t^T, y_t = S_t C_t for one
    sequence, position by position. xs (T, H, P), dt (T, H), a (H,), b, c
    (T, N) -> (y (T, H, P), final state (H, P, N)). `dtype`: the control
    rounds the operands of the two products (dt_t xs_t and B_t; S_t and C_t);
    the state carried from position to position stays float32."""
    t, h, p = xs.shape

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s + jnp.einsum(
            "hp,n->hpn", _stored(x_t * dt_t[:, None], dtype),
            _stored(b_t, dtype), precision=HI)
        y_t = jnp.einsum(
            "hpn,n->hp", _stored(s, dtype), _stored(c_t, dtype), precision=HI)
        return s, y_t

    @jax.checkpoint
    def block(s, inp):
        return lax.scan(step, s, inp)

    whole = t // TIME_BLOCK
    s = jnp.zeros((h, p, b.shape[-1]), jnp.float32)
    out = []
    if whole:
        cut = whole * TIME_BLOCK
        s, y = lax.scan(block, s, tuple(
            v[:cut].reshape(whole, TIME_BLOCK, *v.shape[1:])
            for v in (xs, dt, b, c)))
        out.append(y.reshape(cut, h, p))
    if t % TIME_BLOCK:
        s, y = block(s, tuple(v[whole * TIME_BLOCK:] for v in (xs, dt, b, c)))
        out.append(y)
    return jnp.concatenate(out, axis=0), s


def mamba_mixer(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (T, hidden) before the residual multiplier."""
    t = u.shape[0]
    heads, hd, n = (
        shape["mamba_n_heads"], shape["mamba_d_head"], shape["mamba_d_state"])
    if shape["mamba_n_groups"] != 1:
        raise ValueError("B and C are written here for one group")
    inner = heads * hd
    zxbcdt = _dot(u, p["in_proj"], dtype)
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * n]
    dt = zxbcdt[:, 2 * inner + 2 * n:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[:, :inner].reshape(t, heads, hd)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y, _ = recurrence(
        xs, dt, a, xbc[:, inner:inner + n], xbc[:, inner + n:], dtype)
    y = (y + p["d"][:, None] * xs).reshape(t, inner)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], shape["rms_norm_eps"])
    return _dot(y, p["out_proj"], dtype)


def attention(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (T, hidden). Dense: every query against every
    key under the causal mask written out, no position term."""
    t = u.shape[0]
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    hd = shape["attention_head_dim"]
    q = _dot(u, p["wq"], dtype).reshape(t, h, hd)
    k = jnp.repeat(_dot(u, p["wk"], dtype).reshape(t, kv, hd), h // kv, axis=1)
    v = jnp.repeat(_dot(u, p["wv"], dtype).reshape(t, kv, hd), h // kv, axis=1)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(qb, start):
        i = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", _stored(qb, dtype), _stored(k, dtype),
                       precision=HI) * shape["attention_multiplier"]
        prob = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
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
    a = jnp.concatenate(out, axis=0).reshape(t, h * hd)
    return _dot(a, p["wo"], dtype)


def layer(p, x, kind, shape, dtype=None):
    eps, r = shape["rms_norm_eps"], shape["residual_multiplier"]
    u = rms_norm(x, p["norm"], eps)
    mixer = mamba_mixer if kind == MAMBA else attention
    x = x + r * mixer(p, u, shape, dtype)
    f = shape["shared_intermediate_size"]
    pq = _dot(rms_norm(x, p["mlp_norm"], eps), p["w1"], dtype)
    return x + r * _dot(jax.nn.silu(pq[:, :f]) * pq[:, f:], p["w2"], dtype)


def _tree(params: dict) -> dict:
    """{"layer_0/wq": a, ...} -> {"layer_0": {"wq": a}, ...} in float32."""
    tree: dict = {}
    for key, value in params.items():
        group, name = key.split("/")
        tree.setdefault(group, {})[name] = jnp.asarray(value, jnp.float32)
    return tree


def hidden_states(tree, x, shape, dtype=None):
    """Final-norm output (T, hidden) of one sequence x (T,) of token ids."""
    h = shape["embedding_multiplier"] * tree["embed"]["embedding"][x]
    n_layers = sum(1 for k in tree if k.startswith("layer_"))
    for i in range(n_layers):
        h = jax.checkpoint(functools.partial(
            layer, kind=shape["layer_types"][i], shape=shape, dtype=dtype,
        ))(tree[f"layer_{i}"], h)
    return rms_norm(h, tree["out"]["norm"], shape["rms_norm_eps"])


def logits(params: dict, x, *, shape=None, dtype=None):
    """x (T,) token ids of ONE sequence -> (T, held vocabulary) float32."""
    shape = SHAPE if shape is None else shape
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, dtype)
    return _dot(h, tree["embed"]["embedding"].T, dtype) \
        / shape["logits_scaling"]


def sequence_loss(params: dict, x, y, *, shape=None, dtype=None):
    """Mean over the sequence's tokens of -log softmax(logits)[y], the head
    LOSS_BLOCK tokens at a time."""
    shape = SHAPE if shape is None else shape
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, dtype)
    embedding = tree["embed"]["embedding"]

    @jax.checkpoint
    def block_sum(hb, yb):
        lg = _dot(hb, embedding.T, dtype) / shape["logits_scaling"]
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
    scan whose body is recomputed in the backward pass): 772 M parameters
    are 3.1 GB in float32 and their gradient as much, and one sequence's
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
    and the head give it). Sequences have one length, so the mean of their
    means is the mean over tokens, whatever `shards` devices the rows were
    dealt to. No dropout, so `seed` draws nothing. `dtype` (a name, e.g.
    "float8_e4m3fn") computes the control: the operands of every product,
    the recurrence's two among them, rounded to it first."""
    del seed, shards
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, sumsq = _loss_and_grad_sumsq(
            params, jnp.asarray(x), jnp.asarray(y), dtype)
    return {"loss": float(loss), "grad_norm": float(sumsq) ** 0.5}


def scan_macs(t: int) -> int:
    """Multiply-accumulates of one layer's scan over a sequence of t in the
    CHUNKED form at the published chunk (the form every implementation
    trains with; the literal recurrence above needs 2 x heads x head size x
    state = 1.05 M a token and T sequential steps). Per chunk of Q: the
    pairs s <= t the mask lets through, Q (Q + 1) / 2 of them, each the
    score C_t . B_s (state size, once for all heads) and the mixing of
    xs_s into y_t (heads x head size); the chunk's contribution to the state
    and the carried state's to the output, heads x head size x state a
    token each. At chunk 256: 1.59 M a token (2.13 M with the masked half
    of each chunk's square, which a dense product computes and discards:
    not counted, as the attention's masked half is not)."""
    s = SHAPE
    q = min(s["mamba_chunk_size"], t)
    inner = s["mamba_n_heads"] * s["mamba_d_head"]
    whole, rest = divmod(t, q)
    pairs = whole * q * (q + 1) // 2 + rest * (rest + 1) // 2
    return pairs * (s["mamba_d_state"] + inner) \
        + 2 * t * inner * s["mamba_d_state"]


def forward_macs(shape=(8192,), vocab: int = 12544) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers held; `vocab`: the rows of the tied embedding held).
    `shape` is (sequence length,). Counted: on a Mamba layer the input and
    output projections, the convolution (4 a channel) and the scan in its
    chunked form (`scan_macs`); on the attention layer the four projections
    and the score and value products over the causal pairs (the triangle,
    not the square); the gated MLP of every layer; the head over the held
    rows. Not counted: the embedding lookup, norms, softplus, exponentials,
    gates, the recomputation the program's checkpoints add, the optimizer."""
    (t,) = shape
    s = SHAPE
    d, f = s["hidden_size"], s["shared_intermediate_size"]
    inner = s["mamba_n_heads"] * s["mamba_d_head"]
    channels = inner + 2 * s["mamba_d_state"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = s["attention_head_dim"]
    macs = 0
    for kind in s["layer_types"][: SHARE["layers"]]:
        macs += t * 3 * d * f  # W_1 is d x 2f, W_2 f x d
        if kind == MAMBA:
            macs += t * d * (inner + channels + s["mamba_n_heads"])
            macs += t * s["mamba_d_conv"] * channels
            macs += scan_macs(t) + t * inner * d
        else:
            macs += t * d * hd * (2 * heads + 2 * kv)
            macs += t * (t + 1) // 2 * heads * hd * 2
    return int(macs + t * d * vocab)


def scan_flops_and_bytes(t: int = 8192, batch: int = 1,
                         bytes_per_element: int = 2) -> dict:
    """What ONE Mamba layer's scan needs for `batch` sequences of t, forward
    and backward passes together, for its roofline share (device time under
    the scope `ssm_scan`; PERF.md).

    flops: 2 x `scan_macs` forward, twice that again backward (each product
    has two transposes); recomputation is not counted. bytes: the least
    traffic to memory, every operand read and every result written once:
    forward reads xs, B, C (elements of `bytes_per_element`) and dt
    (float32) and writes y; backward reads those and y's cotangent and
    writes the four cotangents. The (chunk, chunk) matrices and the states
    are temporaries a fused kernel would keep on the chip."""
    s = SHAPE
    inner = s["mamba_n_heads"] * s["mamba_d_head"]
    inputs = t * (inner + 2 * s["mamba_d_state"]) * bytes_per_element \
        + t * s["mamba_n_heads"] * 4
    y = t * inner * bytes_per_element
    return {
        "flops": batch * 3 * 2 * scan_macs(t),
        "bytes": batch * ((inputs + y) + (inputs + y + inputs)),
    }
