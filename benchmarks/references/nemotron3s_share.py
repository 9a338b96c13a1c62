"""Plain reference: one chip's share of NVIDIA-Nemotron-3-Super-120B-A12B,
forward pass, loss and gradient in float32.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json
(`model_type` nemotron_h; SHAPE below copies its keys), the equations as a
`transformers`-style `modeling_nemotron_h` computes them. Straight
`jax.numpy`, every product at `highest` precision, the scan the literal
recurrence, the attention a softmax written out under the causal mask, the
experts a plain loop over the held ones. It imports nothing of `mgwfbp_tpu`;
it is handed the program's initial parameters as a flat `{"a/b/c": array}`
dict (random draws from the seed, nothing the program computed).

x is the residual stream of one sequence (T, 4,096); RMSNorm(u) = g u /
sqrt(mean(u^2) + 1e-5); no bias but the convolution's. A layer is ONE norm
and ONE mixer, its kind the layer's letter in `hybrid_override_pattern`:

    x = E[ids];   layer l:  x = x + mixer(RMSNorm_l(x))
    M:  [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC) + b): depthwise,
        causal, width 4 (position t sees t - 3 .. t; w[3] multiplies t)
        [xs (heads x 64) | B (groups x 128) | C (groups x 128)] = xBC
        head h reads group h // (mamba_num_heads / n_groups) = h // 16
        dt = softplus(dt + dt_bias);  A = -exp(A_log), one per head
        per head, S in R^(64 x 128), S_0 = 0, ONE POSITION AFTER ANOTHER:
            S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T;  y_t = S_t C_t + D xs_t
        v = y silu(z); per GROUP of 1,024 channels v_g = w_g v_g /
        sqrt(mean_g(v_g^2) + 1e-5);  out = v W_out
    *:  32 query heads of 128 over 2 key-value heads (each serves 16
        consecutive query heads); NO rotary, no position term; causal
        softmax of (q . k) / sqrt(128);  out = a W_o
    E:  s = sigmoid(u W_r) over all 512; the 22 largest of s + bias CHOSEN;
        w = s[chosen] / (sum of the chosen s + 1e-20) x 5
        l = u W_down (1,024);  r = sum over chosen e of w_e W2_e relu(W1_e l)^2
        out = r W_up + S2 relu(S1 u)^2
    logits = RMSNorm_f(x) W_head; the loss is the mean over tokens of
    -log softmax(logits)[next token].

**Assumed** (config.json does not settle them; each in one function here):
`gated_group_norm`: by group of inner / n_groups channels, gate before norm
(the other reading: over all inner channels at once); `attention`: no
rotary term (`rope_theta`, `partial_rotary_factor` unused); `mamba_mixer`:
no clamp on dt; `route`: DeepSeek-V3's `noaux_tc`, the bias in the choice
alone. The multi-token-prediction module is not here: it lies on the last
pipeline stage.

**The share.** The parameters hold the layers `layer_<i>` under their
published indices i; of each `E` layer the routed experts the stacked
leaves' leading dimension counts, starting at SHARE["first_expert"]; of each
layer the HEADS their leaves' widths say (Mamba heads with their B/C groups,
query heads with their key-value heads, the shared expert's columns: one
member of SHARE["tensor"][1] chips' share); and the embedding's and head's
rows of the held vocabulary. The router scores all 512 and normalises over
all 22 chosen; only the held experts' terms are added. Router, selection
bias, latent projections and norms are whole. What the absent experts and
heads would have added is left out here exactly as in the program: the
partial result goes on to the next layer.

Departures from a textbook forward, for memory only: a sequence at a time, a
layer at a time, an expert at a time (`jax.checkpoint`; the loops over query
blocks and experts are `lax.map` / `lax.scan`), the recurrence in blocks of
TIME_BLOCK positions (a checkpointed scan over blocks of an inner scan), the
attention QUERY_BLOCK queries at a time against ALL keys under the
written-out mask, the head and loss LOSS_BLOCK tokens at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

MAMBA, ATTENTION, MOE = "M", "*", "E"
SHAPE = {
    "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    "mamba_num_heads": 128,
    "mamba_head_dim": 64,
    "ssm_state_size": 128,
    "n_groups": 8,
    "conv_kernel": 4,
    "chunk_size": 128,  # `forward_macs` only: nothing here is chunked
    "num_attention_heads": 32,
    "num_key_value_heads": 2,
    "head_dim": 128,
    "n_routed_experts": 512,
    "num_experts_per_tok": 22,
    "moe_intermediate_size": 2688,
    "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "routed_scaling_factor": 5.0,
    "layer_norm_epsilon": 1e-5,
}
# what `forward_macs` takes for the share where the parameters cannot say
# it: layers 26 to 36 of 88, experts 0..7 of 512, member 0 of 8 chips that
# share each layer's heads
SHARE = {"first_layer": 26, "layers": 11, "first_expert": 0, "experts": 8,
         "tensor": (0, 8)}
TIME_BLOCK = 128
QUERY_BLOCK = 256
LOSS_BLOCK = 2048
HI = lax.Precision.HIGHEST


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _dot(a, b, dtype=None):
    return jnp.dot(_stored(a, dtype), _stored(b, dtype), precision=HI)


def rms_norm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w, bias):
    """x (T, C), w (K, C), bias (C,): out_t = bias + sum_k w_k x_{t-K+1+k}."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return bias + sum(w[i] * padded[i:i + t] for i in range(k))


def recurrence(xs, dt, a, b, c, dtype=None):
    """S_t = exp(dt_t a) S_{t-1} + dt_t xs_t B_t^T, y_t = S_t C_t for the
    heads of ONE group over one sequence, position by position. xs (T, H,
    P), dt (T, H), a (H,), b, c (T, N) -> y (T, H, P). `dtype`: the control
    rounds the operands of the two products (dt_t xs_t and B_t; S_t and
    C_t); the state carried from position to position stays float32."""
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
    return jnp.concatenate(out, axis=0)


def gated_group_norm(y, z, g, groups: int, eps: float):
    """ASSUMED: v = y silu(z), then each of the `groups` stretches of
    channels normed over its own mean square, under its stretch of g."""
    t, inner = y.shape
    v = (y * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    return rms_norm(v, g.reshape(groups, inner // groups), eps).reshape(
        t, inner)


def mamba_mixer(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (T, hidden), over the heads and the B/C
    groups the leaves hold (heads per group and sizes as published)."""
    t = u.shape[0]
    hd, n = shape["mamba_head_dim"], shape["ssm_state_size"]
    per = shape["mamba_num_heads"] // shape["n_groups"]  # heads a group
    groups = p["conv_b"].shape[0] // (per * hd + 2 * n)
    inner = groups * per * hd
    zxbcdt = _dot(u, p["in_proj"], dtype)
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * groups * n]
    dt = zxbcdt[:, 2 * inner + 2 * groups * n:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[:, :inner].reshape(t, groups * per, hd)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # ASSUMED: no clamp
    a = -jnp.exp(p["a_log"])
    y = jnp.concatenate([
        recurrence(
            xs[:, g * per:(g + 1) * per], dt[:, g * per:(g + 1) * per],
            a[g * per:(g + 1) * per],
            xbc[:, inner + g * n:inner + (g + 1) * n],
            xbc[:, inner + (groups + g) * n:inner + (groups + g + 1) * n],
            dtype)
        for g in range(groups)], axis=1)
    y = (y + p["d"][:, None] * xs).reshape(t, inner)
    y = gated_group_norm(
        y, z, p["gate_norm"], groups, shape["layer_norm_epsilon"])
    return _dot(y, p["out_proj"], dtype)


def attention(p, u, shape, dtype=None):
    """u (T, hidden), normed -> (T, hidden), over the query heads the leaves
    hold and their key-value heads. Dense: every query against every key
    under the causal mask written out. ASSUMED: no position term."""
    t = u.shape[0]
    hd = shape["head_dim"]
    q = _dot(u, p["wq"], dtype).reshape(t, -1, hd)
    k = _dot(u, p["wk"], dtype).reshape(t, -1, hd)
    v = _dot(u, p["wv"], dtype).reshape(t, -1, hd)
    h = q.shape[1]
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(qb, start):
        i = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", _stored(qb, dtype), _stored(k, dtype),
                       precision=HI) * hd ** -0.5
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
    return _dot(jnp.concatenate(out, axis=0).reshape(t, h * hd), p["wo"],
                dtype)


def route(u, router, bias, top_k: int, scaling: float):
    """ASSUMED `noaux_tc`: s = sigmoid(u W_r) over all experts; the `top_k`
    largest of s + bias are chosen; each weighs its s over (the sum of the
    chosen s + 1e-20), times `scaling`. (indices (T, k), weights (T, k)).
    Float32 as stored: the control leaves the router alone, as the
    configuration's precision states a float32 router."""
    scores = jax.nn.sigmoid(jnp.dot(u, router, precision=HI))
    _, idx = lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling


def relu2_mlp(u, w_up, w_down, dtype=None):
    return _dot(jnp.square(jax.nn.relu(_dot(u, w_up, dtype))), w_down, dtype)


def shared_expert(p, u, dtype=None):
    """The shared expert's held columns: independent hidden units whose
    outputs add."""
    return relu2_mlp(u, p["shared_up"], p["shared_down"], dtype)


def routed_experts(p, u, shape: dict, first: int, dtype=None):
    """Held routed experts' part of the block for tokens u (T, hidden),
    through the latent: experts first .. first + count - 1, one after the
    other, each over all tokens' latents with the weight the router gave it
    (zero where it was not chosen), then back up."""
    idx, w = route(u, p["router"], p["router_bias"],
                   shape["num_experts_per_tok"],
                   shape["routed_scaling_factor"])
    latent = _dot(u, p["latent_down"], dtype)

    @jax.checkpoint
    def add_expert(r, expert):
        e, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return r + w_e[:, None] * relu2_mlp(latent, w_up, w_down, dtype), None

    r, _ = lax.scan(add_expert, jnp.zeros_like(latent), (
        jnp.arange(p["w_up"].shape[0]), p["w_up"], p["w_down"]))
    return _dot(r, p["latent_up"], dtype)


def latent_moe(p, u, shape: dict, first: int, dtype=None):
    return routed_experts(p, u, shape, first, dtype) \
        + shared_expert(p, u, dtype)


def layer(p, x, kind: str, shape: dict, first: int, dtype=None):
    u = rms_norm(x, p["norm"], shape["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + mamba_mixer(p, u, shape, dtype)
    if kind == ATTENTION:
        return x + attention(p, u, shape, dtype)
    return x + latent_moe(p, u, shape, first, dtype)


def _tree(params: dict) -> dict:
    """{"layer_26/in_proj": a, ...} -> {"layer_26": {"in_proj": a}, ...} in
    float32."""
    tree: dict = {}
    for key, value in params.items():
        group, name = key.split("/")
        tree.setdefault(group, {})[name] = jnp.asarray(value, jnp.float32)
    return tree


def hidden_states(tree, x, shape: dict, first: int, dtype=None):
    """Final-norm output (T, hidden) of one sequence x (T,) of token ids
    through the layers the parameters hold, under their published indices."""
    h = tree["embed"]["embedding"][x]
    for index in sorted(
            int(k.split("_")[1]) for k in tree if k.startswith("layer_")):
        h = jax.checkpoint(functools.partial(
            layer, kind=shape["hybrid_override_pattern"][index], shape=shape,
            first=first, dtype=dtype))(tree[f"layer_{index}"], h)
    return rms_norm(h, tree["out"]["norm"], shape["layer_norm_epsilon"])


def logits(params: dict, x, *, shape=None, first=None, dtype=None):
    """x (T,) token ids of ONE sequence -> (T, held vocabulary) float32."""
    shape = SHAPE if shape is None else shape
    first = SHARE["first_expert"] if first is None else first
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, first, dtype)
    return _dot(h, tree["out"]["head"], dtype)


def sequence_loss(params: dict, x, y, *, shape=None, first=None, dtype=None):
    """Mean over the sequence's tokens of -log softmax(logits)[y], the head
    LOSS_BLOCK tokens at a time."""
    shape = SHAPE if shape is None else shape
    first = SHARE["first_expert"] if first is None else first
    tree = _tree(params)
    h = hidden_states(tree, jnp.asarray(x), shape, first, dtype)
    head = tree["out"]["head"]

    @jax.checkpoint
    def block_sum(hb, yb):
        lg = _dot(hb, head, dtype)
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
    scan whose body is recomputed in the backward pass), so one sequence's
    float32 activations and ONE gradient tree are all the device holds
    beside the parameters (508 M parameters are 2.0 GB in float32)."""
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
    computes the control: every product's operands except the router's, the
    recurrence's two among them, rounded to it first."""
    del seed, shards
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, sumsq = _loss_and_grad_sumsq(
            params, jnp.asarray(x), jnp.asarray(y), dtype)
    return {"loss": float(loss), "grad_norm": float(sumsq) ** 0.5}


def held() -> dict:
    """The share's sizes from SHAPE and SHARE: what `forward_macs` and the
    `*_flops_and_bytes` functions count."""
    s = SHAPE
    of = SHARE["tensor"][1]
    return {
        "mamba_heads": s["mamba_num_heads"] // of,
        "groups": s["n_groups"] // of,
        "heads": s["num_attention_heads"] // of,
        "kv_heads": max(s["num_key_value_heads"] // of, 1),
        "shared_columns": s["moe_shared_expert_intermediate_size"] // of,
        # a routed expert's evaluations a token at even routing
        "evaluations": s["num_experts_per_tok"] * SHARE["experts"]
        / s["n_routed_experts"],
    }


def causal_pairs(t: int) -> int:
    """The (query, key) pairs the causal mask lets through."""
    return t * (t + 1) // 2


def scan_macs(t: int) -> int:
    """Multiply-accumulates of one Mamba layer's scan over a sequence of t
    in the CHUNKED form at the published chunk, over the share's heads and
    groups (the form every implementation trains with). Per chunk of Q and
    group: the pairs s <= t the mask lets through, Q (Q + 1) / 2 of them,
    each the score C_t . B_s (state size, once for the group's heads) and
    the mixing of xs_s into y_t (the group's heads x head size); the chunk's
    contribution to the state and the carried state's to the output, heads
    x head size x state a token each."""
    s, h = SHAPE, held()
    q = min(s["chunk_size"], t)
    inner = h["mamba_heads"] * s["mamba_head_dim"]
    whole, rest = divmod(t, q)
    pairs = whole * causal_pairs(q) + causal_pairs(rest)
    return pairs * (h["groups"] * s["ssm_state_size"] + inner) \
        + 2 * t * inner * s["ssm_state_size"]


def forward_macs(shape=(8192,), vocab: int = 16384) -> int:
    """Multiply-accumulates of one SEQUENCE's forward pass through the share
    (SHARE: the layers, routed experts and heads held; `vocab`: the
    vocabulary held). `shape` is (sequence length,). Counted: on a Mamba
    layer the input and output projections over the held heads, the
    convolution (4 a channel) and the scan in its chunked form
    (`scan_macs`); on the attention layer the four projections over the held
    heads and the score and value products over the causal pairs; on an `E`
    layer the router over all 512 experts, both latent projections, the
    shared expert's held columns ONCE a token and the EXPECTED routed work,
    22 x held / 512 evaluations a token (0.34 at 8 of 512: each held expert
    at its even load of 352 rows of 8,192 tokens); the held head. Not
    counted: the embedding lookup, norms, softplus, exponentials, gates,
    softmax, sigmoids, the recomputation the program's checkpoints add, the
    optimizer."""
    (t,) = shape
    s, h = SHAPE, held()
    d, hd, n = s["hidden_size"], s["head_dim"], s["ssm_state_size"]
    inner = h["mamba_heads"] * s["mamba_head_dim"]
    channels = inner + 2 * h["groups"] * n
    f, latent = s["moe_intermediate_size"], s["moe_latent_size"]
    first = SHARE["first_layer"]
    macs = 0
    for kind in s["hybrid_override_pattern"][first:first + SHARE["layers"]]:
        if kind == MAMBA:
            macs += t * d * (inner + channels + h["mamba_heads"])
            macs += t * s["conv_kernel"] * channels
            macs += scan_macs(t) + t * inner * d
        elif kind == ATTENTION:
            macs += t * d * hd * (2 * h["heads"] + 2 * h["kv_heads"])
            macs += causal_pairs(t) * h["heads"] * hd * 2
        else:
            macs += t * (d * s["n_routed_experts"] + 2 * d * latent
                         + 2 * d * h["shared_columns"]
                         + h["evaluations"] * 2 * latent * f)
    return int(macs + t * d * vocab)


def scan_flops_and_bytes(t: int = 8192, batch: int = 1,
                         bytes_per_element: int = 2) -> dict:
    """What ONE Mamba layer's scan of the share needs for `batch` sequences
    of t, forward and backward passes together, for its roofline share
    (device time under the scope `ssm_scan`; PERF.md).

    flops: 2 x `scan_macs` forward, twice that again backward (each product
    has two transposes); recomputation is not counted. bytes: the least
    traffic to memory, every operand read and every result written once:
    forward reads xs, B, C (elements of `bytes_per_element`) and dt
    (float32) and writes y; backward reads those and y's cotangent and
    writes the four cotangents. The (chunk, chunk) matrices and the states
    are temporaries a fused kernel keeps on the chip."""
    s, h = SHAPE, held()
    inner = h["mamba_heads"] * s["mamba_head_dim"]
    inputs = t * (inner + 2 * h["groups"] * s["ssm_state_size"]) \
        * bytes_per_element + t * h["mamba_heads"] * 4
    y = t * inner * bytes_per_element
    return {
        "flops": batch * 3 * 2 * scan_macs(t),
        "bytes": batch * ((inputs + y) + (inputs + y + inputs)),
    }


def latent_moe_flops_and_bytes(t: int = 8192,
                               bytes_per_element: int = 2) -> dict:
    """What the scope `moe_experts` of ONE `E` layer of the share needs at
    the least for a sequence of t, forward and backward together: the held
    experts' two products over the rows routed to them at even routing
    (t x 22 x held / 512 rows: 2,816 at t 8,192) and the gather and
    weighted combine of those rows. flops: 2 x (rows x 2 x latent x width)
    forward, twice that again backward. bytes: both weights of every held
    expert read once forward and once backward and their gradients written
    once (float32 accumulation rounded to `bytes_per_element`), the rows'
    latents read and results written forward (latent wide) and the hidden
    units written and read once (a kernel that fused the two products would
    not), the same again backward. The N x k-row buffers the program's
    grouped arrays have (180,224 rows, 1.56% of them in a group) are NOT in
    this bill: they are the waste the share measures."""
    s = SHAPE
    rows = int(t * held()["evaluations"])
    f, latent = s["moe_intermediate_size"], s["moe_latent_size"]
    weights = SHARE["experts"] * 2 * latent * f
    acts = rows * (2 * latent + 2 * f)
    return {
        "flops": 3 * 2 * rows * 2 * latent * f,
        "bytes": (3 * weights + 3 * acts) * bytes_per_element,
    }


def attention_core_flops_and_bytes(t: int = 8192,
                                   bytes_per_element: int = 2) -> dict:
    """What the scope `attn_full` of the share's ONE attention layer needs
    at the least: FLOPs of the causal triangle over the held query heads,
    forward (scores and values) and backward (the probabilities' two
    cotangent products and the three operand gradients; the recomputed
    scores not counted), and bytes: q, the output and its cotangent and d q
    a query head, k, v and their gradients a key-value head, read or written
    once forward and once more backward."""
    s, h = SHAPE, held()
    hd = s["head_dim"]
    macs = causal_pairs(t) * h["heads"] * hd * 2 * 3
    elements = t * hd * (
        (2 * h["heads"] + 2 * h["kv_heads"])
        + (4 * h["heads"] + 4 * h["kv_heads"]))
    return {"flops": 2 * macs, "bytes": elements * bytes_per_element}
