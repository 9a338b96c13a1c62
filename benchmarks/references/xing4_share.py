"""Plain reference: one chip's share of Xing4.0-29B-A4B (XingChen-AGI),
forward pass, loss and gradient in float32.

Source: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
(SHAPE below copies its keys). Straight `jax.numpy`, every product at
`highest` precision, the Sinkhorn loop written out, the attention a softmax
written out over concatenated [nope | rope] scores under the causal mask, the
experts a plain loop over the held ones. It imports nothing of `mgwfbp_tpu`;
it is handed the program's initial parameters as a flat `{"a/b/c": array}`
dict (random draws from the seed, nothing the program computed).

n = `hc_mult` = 4 residual streams X (T, n, C) of one sequence, C = 3,584.
X_0[i] = Emb(t) for every i. A sub-layer F (a layer's attention, then its
feed-forward), with its own phi (nC x (n^2 + 2n)), b, alpha = (alpha_pre,
alpha_post, alpha_res) and norm g:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)             (nC; stream i's
                                                            C first for i = 0)
    [a_pre (n) | a_post (n) | a_res (n x n, rows first)] = x~ phi
    H_pre  = sigmoid(alpha_pre a_pre + b_pre)
    H_post = 2 sigmoid(alpha_post a_post + b_post)
    H_res  = SK(clip(alpha_res a_res + b_res, mhc_h_res_clamp_min, _max))
    SK(m): M = exp(m); hc_sinkhorn_iters times: every row over (its sum +
           hc_eps), then every column over (its sum + hc_eps)
    u = sum_i H_pre[i] X[i];   y = F(RMSNorm(u; g))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

After the last held layer h = sum_i X[i], a final RMSNorm and an untied head;
the loss is the mean over tokens of -log softmax(logits)[next token].
RMSNorm(u; g) = g * u / sqrt(mean(u^2) + rms_norm_eps).

F, attention (multi-head latent attention, 32 heads):

    c_q = RMSNorm(u W_dq) (q_lora_rank);  a head's [q_n 128 | q_r 64] = c_q W_uq
    [c_kv (kv_lora_rank) | k_r 64] = u W_dkv
    a head's [k_n 128 | v 128] = RMSNorm(c_kv) W_ukv
    q_r and the ONE k_r all heads share are rotated by position (YaRN over
    the 64: theta 10,000, factor 64 over 4,096, beta_fast 32, beta_slow 1;
    half-split layout; cos and sin times mscale-ratio = 1)
    scores = [q_n | q_r] . [k_n | k_r] x 192^-1/2 x m^2,  m = 0.1 ln 64 + 1
    causal softmax; o = P v (128 a head); out = concat(o) W_o

F, feed-forward: layers below `first_k_dense_replace` a SwiGLU MLP of width
9,216; the others s = sigmoid(u W_r) over all 64 experts, the 4 largest of
s + bias CHOSEN, weights s_k / (sum of the chosen s) x routed_scaling_factor,
output = sum of the chosen experts' SwiGLU (width 1,024) + Shared(u).

**Assumed** (config.json does not settle them; each in one function here):
`hidden_states`: n copies of the embedding in, the sum of the streams out
(arXiv:2409.19606's); `stream_maps` / `sinkhorn`: rows before columns, eps on
the sums, the clamp on the exponent, no learned scale in the norm before phi
(arXiv:2512.24880 as the config's keys name it); `route`: DeepSeek-V3's
`noaux_tc` (arXiv:2412.19437 eq. 12 to 16), the bias in the choice alone.
The multi-token-prediction module (`num_nextn_predict_layers` 1) is not here:
it is the model's last layer and lies on another pipeline stage.

**The share.** The parameters hold the layers `layer_<i>` under their
published indices i, `count` of the 64 routed experts (the stacked expert
leaves' leading dimension) starting at expert SHARE["first_expert"], and the
embedding's and head's rows of the held vocabulary. The router still scores
all 64 and normalises over all 4 chosen; only the held experts' terms are
added. The shared expert, like the attention, the mappings and the dense MLP,
is whole on every chip: it is added entire. What the absent experts would
have added is left out here exactly as in the program.

Departures from a textbook forward, for memory only: a sequence at a time, a
sub-layer at a time, an expert at a time (`jax.checkpoint`; the loops over
query blocks and experts are `lax.map` / `lax.scan` so that they run one
after the other), and the attention a block of QUERY_BLOCK queries at a time
against ALL keys of the sequence under the written-out mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

SHAPE = {
    "hidden_size": 3584,
    "intermediate_size": 9216,
    "first_k_dense_replace": 2,
    "num_attention_heads": 32,
    "q_lora_rank": 768,
    "kv_lora_rank": 512,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "v_head_dim": 128,
    "n_routed_experts": 64,
    "num_experts_per_tok": 4,
    "moe_intermediate_size": 1024,
    "n_shared_experts": 1,
    "routed_scaling_factor": 2.0,
    "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0,
    "rope_scaling": {
        "type": "yarn", "factor": 64.0, "beta_fast": 32.0, "beta_slow": 1.0,
        "mscale": 1.0, "mscale_all_dim": 1.0,
        "original_max_position_embeddings": 4096},
    "hc_mult": 4,
    "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30.0,
    "mhc_h_res_clamp_max": 30.0,
}
# what `forward_macs` takes for the share where the parameters cannot say
# it: layers 1 to 5 of 40 (one dense, four sparse), experts 0..7 of 64
SHARE = {"first_layer": 1, "layers": 5, "first_expert": 0, "experts": 8}
QUERY_BLOCK = 256
HI = lax.Precision.HIGHEST


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _dot(a, b, dtype=None):
    return jnp.dot(_stored(a, dtype), _stored(b, dtype), precision=HI)


def yarn_mscale(factor: float, scale: float) -> float:
    return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(shape: dict):
    """(inverse frequencies, one for each PAIR of the rotary dimensions;
    factor on cos and sin)."""
    rope = shape["rope_scaling"]
    dim, theta = shape["qk_rope_head_dim"], shape["rope_theta"]
    base = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]

    def c(rotations):
        return dim * math.log(
            rope["original_max_position_embeddings"]
            / (2 * math.pi * rotations)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(dim // 2)]
    freqs = [(1 - r) * f + r * f / rope["factor"] for r, f in zip(ramp, base)]
    return jnp.asarray(freqs, jnp.float32), (
        yarn_mscale(rope["factor"], rope["mscale"])
        / yarn_mscale(rope["factor"], rope["mscale_all_dim"]))


def score_scale(shape: dict) -> float:
    rope = shape["rope_scaling"]
    m = yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    return m * m / math.sqrt(
        shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"])


def rope(x, freqs, factor):
    """x (T, heads, rotary): x cos + rotate_half(x) sin by position."""
    t, _, rotary = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., rotary // 2:], x[..., :rotary // 2]], -1)
    return x * (jnp.cos(angle) * factor) + half * (jnp.sin(angle) * factor)


def rms_norm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def sinkhorn(m, iters: int, eps: float):
    """ASSUMED order and place of eps. m (T, n, n), [to, from]: exp, then
    `iters` times rows over (their sums + eps), columns over (theirs + eps)."""
    h = jnp.exp(m)
    for _ in range(iters):
        h = h / (jnp.sum(h, axis=2, keepdims=True) + eps)
        h = h / (jnp.sum(h, axis=1, keepdims=True) + eps)
    return h


def stream_maps(p, which: str, x, shape: dict, dtype=None):
    """x (T, n, C) -> (H_pre (T, n), H_post (T, n), H_res (T, n, n))."""
    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    normed = flat * lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + shape["hc_eps"])
    a = _dot(normed, p[which + "_phi"], dtype)
    b, alpha = p[which + "_b"], p[which + "_alpha"]
    pre = jax.nn.sigmoid(alpha[0] * a[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[:, n:2 * n] + b[n:2 * n])
    exponent = jnp.clip(
        alpha[2] * a[:, 2 * n:].reshape(t, n, n) + b[2 * n:].reshape(n, n),
        shape["mhc_h_res_clamp_min"], shape["mhc_h_res_clamp_max"])
    return pre, post, sinkhorn(
        exponent, shape["hc_sinkhorn_iters"], shape["hc_eps"])


def sub_layer(p, which: str, x, shape: dict, fn, dtype=None):
    """x (T, n, C) -> x' through the sub-layer `fn` of the normed read."""
    pre, post, res = stream_maps(p, which, x, shape, dtype)
    u = jnp.einsum("tn,tnc->tc", pre, x, precision=HI)
    y = fn(rms_norm(u, p[which + "_norm"], shape["rms_norm_eps"]))
    return jnp.einsum("tij,tjc->tic", res, x, precision=HI) \
        + post[:, :, None] * y[:, None, :]


def attention(q, k, v, scale: float, dtype=None):
    """q, k (T, H, D), v (T, H, Dv) of one sequence -> (T, H, Dv). Dense:
    every query against every key, under the causal mask written out."""
    t, h, d = q.shape
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(qb, start):
        i = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", _stored(qb, dtype), _stored(k, dtype),
                       precision=HI) * scale
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
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
        ).reshape(whole * QUERY_BLOCK, h, v.shape[-1]))
    if t % QUERY_BLOCK:
        out.append(block(q[whole * QUERY_BLOCK:], whole * QUERY_BLOCK))
    return jnp.concatenate(out, axis=0)


def latent_attention(p, u, shape: dict, dtype=None):
    """u (T, hidden), the normed read -> (T, hidden)."""
    t = u.shape[0]
    h = shape["num_attention_heads"]
    dn, dr = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"]
    dv, rank = shape["v_head_dim"], shape["kv_lora_rank"]
    eps = shape["rms_norm_eps"]
    freqs, factor = inv_freq(shape)
    c_q = rms_norm(_dot(u, p["w_dq"], dtype), p["q_norm"], eps)
    q = _dot(c_q, p["w_uq"], dtype).reshape(t, h, dn + dr)
    latent = _dot(u, p["w_dkv"], dtype)
    c_kv, k_r = latent[:, :rank], latent[:, rank:]
    kv = _dot(rms_norm(c_kv, p["kv_norm"], eps), p["w_ukv"], dtype).reshape(
        t, h, dn + dv)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], freqs, factor)], axis=-1)
    k_r = rope(k_r[:, None, :], freqs, factor)  # one for all the heads
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (t, h, dr))], axis=-1)
    o = attention(q, k, kv[..., dn:], score_scale(shape), dtype)
    return _dot(o.reshape(t, h * dv), p["wo"], dtype)


def route(u, router, bias, top_k: int, scaling: float):
    """ASSUMED `noaux_tc`: s = sigmoid(u W_r) over all experts; the `top_k`
    largest of s + bias are chosen; each weighs its s over the sum of the
    chosen s, times `scaling`. (indices (T, k), weights (T, k)). Float32 as
    stored: the control leaves the router alone, as the configuration's
    precision states a float32 router."""
    scores = jax.nn.sigmoid(jnp.dot(u, router, precision=HI))
    _, idx = lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) * scaling


def swiglu(u, w_gate, w_up, w_down, dtype=None):
    gate, up = _dot(u, w_gate, dtype), _dot(u, w_up, dtype)
    return _dot(jax.nn.silu(gate) * up, w_down, dtype)


def shared_expert(p, u, dtype=None):
    """What every chip of the group computes alike, for every token."""
    return swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"], dtype)


def routed_experts(p, u, shape: dict, first: int, dtype=None):
    """Held routed experts' part of the block for tokens u (T, hidden):
    experts first .. first + count - 1, one after the other, each over all
    tokens with the weight the router gave it (zero where it was not
    chosen)."""
    idx, w = route(u, p["router"], p["router_bias"],
                   shape["num_experts_per_tok"],
                   shape["routed_scaling_factor"])

    @jax.checkpoint
    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(u, w_gate, w_up, w_down, dtype), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(u), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def sparse_block(p, u, shape: dict, first: int, dtype=None):
    return shared_expert(p, u, dtype) + routed_experts(
        p, u, shape, first, dtype)


def attention_sub_layer(p, x, shape: dict, dtype=None):
    return sub_layer(
        p, "attn", x, shape,
        lambda u: latent_attention(p, u, shape, dtype), dtype)


def mlp_sub_layer(p, x, index: int, shape: dict, first: int, dtype=None):
    if index < shape["first_k_dense_replace"]:
        def fn(u):
            return swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"], dtype)
    else:
        def fn(u):
            return sparse_block(p, u, shape, first, dtype)
    return sub_layer(p, "mlp", x, shape, fn, dtype)


def _tree(params: dict) -> dict:
    """{"layer_1/w_dq": a, ...} -> {"layer_1": {"w_dq": a}, ...} in float32."""
    tree: dict = {}
    for key, value in params.items():
        group, name = key.split("/")
        tree.setdefault(group, {})[name] = jnp.asarray(value, jnp.float32)
    return tree


def hidden_states(tree, x, shape: dict, first: int, dtype=None):
    """Final-norm output (T, hidden) of one sequence x (T,) of token ids
    through the layers the parameters hold, under their published indices."""
    emb = tree["embed"]["embedding"][x]
    # ASSUMED: n copies in
    h = jnp.broadcast_to(emb[:, None, :], (x.shape[0], shape["hc_mult"],
                                           emb.shape[-1]))
    for index in sorted(
            int(k.split("_")[1]) for k in tree if k.startswith("layer_")):
        p = tree[f"layer_{index}"]
        h = jax.checkpoint(functools.partial(
            attention_sub_layer, shape=shape, dtype=dtype))(p, h)
        h = jax.checkpoint(functools.partial(
            mlp_sub_layer, index=index, shape=shape, first=first,
            dtype=dtype))(p, h)
    # ASSUMED: the sum of the streams out
    return rms_norm(
        jnp.sum(h, axis=1), tree["out"]["norm"], shape["rms_norm_eps"])


def logits(params: dict, x, *, shape=None, first=None, dtype=None):
    """x (T,) token ids of ONE sequence -> (T, held vocabulary) float32."""
    shape = SHAPE if shape is None else shape
    first = SHARE["first_expert"] if first is None else first
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, first, dtype)
    return _dot(h, tree["out"]["head"], dtype)


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
    beside the parameters (759 M parameters are 3.0 GB in float32)."""
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
    the optimizer gets it; the selection bias's is zero, the choice carries
    none). Sequences have one length, so the mean of their means is the mean
    over tokens, whatever `shards` devices the rows were dealt to. No
    dropout, so `seed` draws nothing. `dtype` (a name, e.g. "float8_e4m3fn")
    computes the control: every product's operands except the router's
    rounded to it first."""
    del seed, shards
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, sumsq = _loss_and_grad_sumsq(
            params, jnp.asarray(x), jnp.asarray(y), dtype)
    return {"loss": float(loss), "grad_norm": float(sumsq) ** 0.5}


def _held_layers() -> range:
    return range(SHARE["first_layer"], SHARE["first_layer"] + SHARE["layers"])


def mhc_macs(t: int) -> int:
    """Multiply-accumulates of ONE sub-layer's stream arithmetic over t
    tokens, forward: x~ phi (nC x (n^2 + 2n)), the read (n C) and the write
    back (n^2 C + n C). The norm's squares, the gates and the Sinkhorn
    iterations (a few hundred operations a token) are not counted."""
    n, c = SHAPE["hc_mult"], SHAPE["hidden_size"]
    return t * (n * c * (n * n + 2 * n) + n * c + n * n * c + n * c)


def forward_macs(shape=(8192,), vocab: int = 16384) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers and routed experts held; `vocab`: the vocabulary
    held). `shape` is (sequence length,). Counted per layer: the five latent
    attention projections; the score products over the causal triangle at
    width 192 and the value products at width 128; both sub-layers' stream
    arithmetic (`mhc_macs`); the dense MLP, or the router over all 64
    experts, the shared expert ONCE a token and the EXPECTED routed work, 4 x
    held / 64 evaluations a token (0.5 at 8 of 64: under uniform routing);
    and the held head. Not counted: the embedding lookup, norms, rotary
    embedding, softmax, sigmoids and Sinkhorn iterations, the recomputation
    the program's checkpoints add, the optimizer."""
    (t,) = shape
    s = SHAPE
    d, h = s["hidden_size"], s["num_attention_heads"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    evaluations = s["num_experts_per_tok"] * SHARE["experts"] \
        / s["n_routed_experts"]
    macs = 0
    for index in _held_layers():
        macs += t * (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
                     + rkv * h * (dn + dv) + h * dv * d)
        macs += t * (t + 1) // 2 * h * (dn + dr + dv)
        macs += 2 * mhc_macs(t)
        if index < s["first_k_dense_replace"]:
            macs += t * 3 * d * s["intermediate_size"]
        else:
            f = s["moe_intermediate_size"]
            macs += t * (
                d * s["n_routed_experts"]
                + 3 * d * f * s["n_shared_experts"]
                + evaluations * 3 * d * f)
    return int(macs + t * d * vocab)


def mhc_flops_and_bytes(t: int = 8192, bytes_per_element: int = 2) -> dict:
    """What the scopes `mhc_map` + `mhc_mix` of ONE training step of the share
    need at the least, for the by-hand roofline share: FLOPs (2 x MACs,
    forward once and backward twice, recomputation not counted) and bytes
    over HBM with the streams stored at `bytes_per_element`. Bytes, a
    sub-layer and token, each pass counted once at its least: forward the
    mappings read the streams (n C), the read reads them and writes u (n C +
    C), the write-back reads them and y and writes them (2 n C + C): 4 n C +
    2 C elements; backward reads the streams, the write-back's cotangent and
    writes the streams' and y's (3 n C + C), and the read's and the
    mappings' add into the streams' cotangent from u's (C read): 3 n C + 2 C.
    The final sum of the streams (n C + C) and its backward (n C + C) once a
    step. This is bandwidth's bill: at 2 FLOPs a byte the products are far
    under the chip's 240 FLOPs a byte."""
    n, c = SHAPE["hc_mult"], SHAPE["hidden_size"]
    sub_layers = 2 * SHARE["layers"]
    elements = sub_layers * t * (7 * n * c + 4 * c) + 2 * t * (n * c + c)
    return {
        "flops": 2 * 3 * sub_layers * mhc_macs(t),
        "bytes": elements * bytes_per_element,
    }


def mla_core_flops_and_bytes(t: int = 8192, bytes_per_element: int = 2) -> dict:
    """What the scope `attn_full` of ONE training step of the share needs at
    the least: FLOPs of the causal triangle, forward (scores at 192, values
    at 128) and backward (the probabilities' two cotangent products and the
    three operand gradients: 2 x 192 + 2 x 128 more, the recomputed scores not
    counted), and bytes: q and k (192 a head) and v, the output and its
    cotangent (128 a head) read or written once forward and the operands and
    their gradients once more backward."""
    s = SHAPE
    h = s["num_attention_heads"]
    dqk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    dv = s["v_head_dim"]
    pairs = t * (t + 1) // 2
    macs = SHARE["layers"] * pairs * h * (dqk + dv) * 3
    elements = SHARE["layers"] * t * h * (
        (2 * dqk + 2 * dv) + (2 * dqk + 2 * dv) + (2 * dqk + dv + dv))
    return {"flops": 2 * macs, "bytes": elements * bytes_per_element}
