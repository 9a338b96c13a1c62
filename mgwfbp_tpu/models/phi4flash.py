"""Phi-4-mini-flash-reasoning (microsoft, `model_type` phi4flash): a
decoder-hybrid-decoder. The first half alternates Mamba-1 layers with
window-512 differential attention; one full-attention layer follows it; the
second half owns no token mixing of its own: its even layers gate the LAST
Mamba layer's memory (gated memory units) and its odd layers attend to the
full layer's keys and values (cross layers). A gated MLP in every layer, a
tied embedding, no position term.

Source: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
(config.json), the equations as the published `modeling_phi4flash.py`
computes them. x is the residual stream, LN = LayerNorm with weight and bias,
eps 1e-5. Layer l of n = 32:

    every layer:  x = x + mixer_l(LN1(x));  [g | u] = LN2(x) W_1 (no bias)
                  x = x + (silu(g) u) W_2 (no bias)
    out:          logits = LN_f(x) E^T, the same E as the embedding, no bias
    kind of l:    l even -> Mamba-1 if l <= n / 2, else GMU
                  l odd  -> attention: window 512 if l < n / 2, full if
                            l = n / 2 + 1 (it publishes its K, V), cross if
                            l >= n / 2 + 3 (reads layer n / 2 + 1's K, V)
                  layer n / 2, the last Mamba, publishes its memory m.
                  So: 0..15 = 8 x [Mamba, SWA]; 16 = Mamba*; 17 = Full*;
                  18..31 = 7 x [GMU, Cross]

    Mamba-1 (d_inner 5,120, d_state 16, d_conv 4, dt_rank 160; in / out /
    x_proj no bias):
                  [xs | z] = u W_in;  xs = silu(conv1d_causal_depthwise(xs, 4) + b)
                  [dl | B_t | C_t] = xs W_x;  dt = softplus(dl W_dt + b_dt)
                  A = -exp(A_log);  h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t xs_t) (x) B_t
                  m_t = h_t C_t + D . xs_t;   out = (m . silu(z)) W_out
    GMU:          out = (m . silu(u W_g)) W_o, m the memory of layer n / 2
    attention (no position term; W_qkv and W_o WITH bias; scale 1 / 8):
                  q (40 x 64), k, v (20 x 64 each) = u W_qkv;  q1 = even, q2 =
                  odd query heads, k1, k2, v1, v2 likewise (a key head serves
                  2 query heads)
                  a1 = [Att(q1,k1,v1) | Att(q1,k1,v2)],  a2 = [Att(q2,k2,v1) | Att(q2,k2,v2)]
                  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
                  lam0 = 0.8 - 0.6 exp(-0.3 l)
                  a = RMSNorm_128(a1 - lam a2) (1 - lam0);  out = a W_o
                  Att is causal softmax attention, banded to the last 512
                  keys on window layers
    cross:        q = u W_q (bias) only; k1, k2, v1, v2 are layer n / 2 + 1's;
                  the rest as attention, full causal

**The cores.** The four softmax products of a layer are ONE call of
`ops/blockattn.blockwise_attention` at the head size 64 its kernel has tiles
for: the query heads stacked [q1, q1, q2, q2] over the key heads [k1, k1, k2,
k2] and the values [v1, v2, v1, v2]. Each pair's scores are so computed
twice; a core that takes values of 128 beside keys of 64 would compute them
once.

**The scan** is `ops/selscan.py`'s: its two kernels with the state in VMEM
where the step is traced for a TPU and the shape fits, else its chunked form
(which `scan_chunk` and `scan_block` size); the gated MLP, the causal
convolution and its initialisers are `models/granite.py`'s, the head and loss
`models/mellum.py`'s.

**A chip's share.** `layers_held = (first, count)`, a pipeline stage anywhere
in the model, and `vocab_size` (the rows of the tied embedding that live
here). A held layer keeps its published index: its kind and its lam0 follow
from it, and its leaves are named by it (`layer_14` .. `layer_19`). A stage
that holds a GMU without layer n / 2, or a cross layer without layer
n / 2 + 1, has nothing to read and is refused by name.

**Memory.** Every layer is under `jax.checkpoint`. What the forward pass
keeps, beside the residual stream at each layer's input, is the memory and
the keys and values that leave their layers (T x 5,120 and T x 2 x 1,280 in
the compute dtype: 126 MB at 8,192 tokens in bf16), once; they are inputs of
the later, recomputed layers and collect a cotangent from every reader before
their own layer's backward runs. Inside a layer the scan recomputes its
blocks, the cores keep no scores and the loss recomputes its token blocks.

**Counters.** With `targets` the model returns, beside the per-token loss,
`health/sel_scan_state` (the root mean square of each Mamba layer's state
after the last position), `health/gmu_gate` (per GMU, the root mean square of
m . silu(u W_g): 0 where the memory is not wired) and `health/diff_lambda`
(per attention or cross layer, its lam); `step_counters` turns them into the
`step` record's `sel_scan_state_rms`, `gmu_gate_rms`, `diff_lambda_mean`.

Assumed (config.json names none): `d_state` 16, `d_conv` 4, `expand` 2,
`dt_rank` 160 = ceil(2,560 / 16); biases on W_qkv, W_q, W_o and the
convolution, none elsewhere; the heads paired even / odd; lam0's formula; the
sub-norm's eps 1e-5 and its weight at one; the lambdas normal(0, 0.1); `A_log`
= log(1..16) per state; `dt` bias the inverse softplus of a log-uniform step
in [1e-3, 1e-1]; the convolution's weight and bias uniform in +-1 / sqrt(4)
(models/granite.py's reason holds: at normal(0, 0.02) the scan's inputs are
fifty times smaller than the `D` skip beside them); `D` and the norms' weights
at one, every other bias at zero, normal(0, 0.02) elsewhere; no dropout
(`resid_pdrop` and `embd_pdrop` are 0); sequences of one length, no document
mask, the state never reset inside a sequence and zero at its start.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mgwfbp_tpu.models.lm_parts import (
    ATTENTION,
    SCOPES,
    STATE_SPACE,
    _Leaves,
    _conv_init,
    _dt_bias_init,
    gated_mlp,
    rms_norm,
    token_losses,
)
from mgwfbp_tpu.ops import selscan, shortconv
from mgwfbp_tpu.ops.blockattn import blockwise_attention
from mgwfbp_tpu.ops.programs import counted

MAMBA, GMU = "mamba", "gmu"
WINDOW, FULL, CROSS = "sliding_attention", "full_attention", "cross_attention"
# the step's metrics carry these under HEALTH_PREFIX of train/step.py
SEL_SCAN_STATE_KEY = "health/sel_scan_state"
GMU_GATE_KEY = "health/gmu_gate"
DIFF_LAMBDA_KEY = "health/diff_lambda"


@dataclasses.dataclass(frozen=True)
class Phi4FlashShape:
    """The published sizes (config.json) and, from `mamba_state` on, the
    assumed ones; a test builds a smaller one."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    num_layers: int = 32
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    sub_norm_eps: float = 1e-5
    scan_chunk: int = 64  # ours: positions a chunk of ops/selscan.py

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def memory_layer(self) -> int:  # the last Mamba: publishes its memory
        return self.num_layers // 2

    @property
    def keys_layer(self) -> int:  # the full layer: publishes its K, V
        return self.num_layers // 2 + 1

    def kind(self, index: int) -> str:
        """The kind of published layer `index`."""
        if index % self.mb_per_layer == 0:
            return MAMBA if index <= self.memory_layer else GMU
        if index < self.memory_layer:
            return WINDOW
        return FULL if index == self.keys_layer else CROSS

    def lambda_init(self, index: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * index)


PHI4FLASH = Phi4FlashShape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# phi4flash_share_tiny.py states the same numbers independently): by the
# publisher's rule its 8 layers are [Mamba, SWA, Mamba, SWA, Mamba*, Full*,
# GMU, Cross]
PHI4FLASH_TINY = Phi4FlashShape(
    vocab_size=256, hidden_size=32, intermediate_size=48, num_heads=4,
    num_kv_heads=2, head_dim=8, num_layers=8, sliding_window=16,
    mamba_state=4, mamba_dt_rank=2, scan_chunk=8,
)


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(1..N) along the states of every channel (S4D-real)."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = centred * lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rms(a: jax.Array) -> jax.Array:
    return lax.stop_gradient(jnp.sqrt(jnp.mean(jnp.square(a))))


def mamba_mixer(p: dict, u: jax.Array, s: Phi4FlashShape, scan_block: int):
    """The Mamba-1 mixer on the normed input u (B, T, hidden): (out (B, T,
    hidden), the memory m (B, T, d_inner) in u's dtype, root mean square of
    the final state)."""
    inner, n, rank = s.mamba_inner, s.mamba_state, s.mamba_dt_rank
    with jax.named_scope("ssm_in_proj"):
        xz = u @ p["in_proj"]
        xs, z = xz[..., :inner], xz[..., inner:]
    with jax.named_scope("ssm_conv"):
        xs = shortconv.causal_conv_silu(xs, p["conv_w"], p["conv_b"])
    with jax.named_scope("ssm_dt_proj"):
        dbc = xs @ p["x_proj"]
        dt = jax.nn.softplus(
            (dbc[..., :rank] @ p["dt_proj"]).astype(jnp.float32)
            + p["dt_bias"].astype(jnp.float32))
    with jax.named_scope("ssm_sel_scan"):
        y, state = selscan.selective_scan(
            xs, dt, -jnp.exp(p["a_log"].astype(jnp.float32)),
            dbc[..., rank:rank + n], dbc[..., rank + n:],
            chunk=s.scan_chunk, block=scan_block)
        m = y + p["d"].astype(jnp.float32) * xs.astype(jnp.float32)
    with jax.named_scope("ssm_out_proj"):
        gated = (m * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
        return gated @ p["out_proj"], m.astype(u.dtype), _rms(state)


def gated_memory(p: dict, u: jax.Array, m: jax.Array):
    """The gated memory unit on the normed input u and the memory m of the
    last Mamba layer: (out, root mean square of the gated memory)."""
    with jax.named_scope("gmu"):
        gated = m.astype(jnp.float32) * jax.nn.silu(
            (u @ p["w_g"]).astype(jnp.float32))
        return gated.astype(u.dtype) @ p["w_o"], _rms(gated)


def project_qkv(p: dict, u: jax.Array, s: Phi4FlashShape):
    """(q (B, T, H, D), k, v (B, T, Hkv, D)) of an attention layer."""
    b, t, _ = u.shape
    dq, dkv = s.num_heads * s.head_dim, s.num_kv_heads * s.head_dim
    with jax.named_scope("attn_proj"):
        qkv = u @ p["wqkv"] + p["bqkv"]
        return (qkv[..., :dq].reshape(b, t, s.num_heads, s.head_dim),
                qkv[..., dq:dq + dkv].reshape(b, t, s.num_kv_heads, s.head_dim),
                qkv[..., dq + dkv:].reshape(b, t, s.num_kv_heads, s.head_dim))


def differential_lambda(p: dict, lam0: float) -> jax.Array:
    """lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0 of one layer, float32."""
    lq1, lk1, lq2, lk2 = (
        p[name].astype(jnp.float32)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
    return jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0


def differential_attention(p: dict, q, k, v, s: Phi4FlashShape, kind: str,
                           index: int, block: int):
    """Differential attention of layer `index` from its queries and the keys
    and values it attends to (its own, or the full layer's on a cross
    layer), the output projection included: (out (B, T, hidden), lam)."""
    b, t, h, hd = q.shape
    window = s.sliding_window if kind == WINDOW else None
    if window is not None:
        # a quarter of the window a plain block (models/mellum.py)
        block = min(block, max(window // 4, 1))
    with jax.named_scope({
            WINDOW: "attn_window", FULL: "attn_full", CROSS: "attn_cross",
    }[kind]):
        q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
        k1, k2 = k[:, :, 0::2], k[:, :, 1::2]
        v1, v2 = v[:, :, 0::2], v[:, :, 1::2]
        o = blockwise_attention(
            jnp.concatenate([q1, q1, q2, q2], axis=2),
            jnp.concatenate([k1, k1, k2, k2], axis=2),
            jnp.concatenate([v1, v2, v1, v2], axis=2),
            window=window, block=block, scale=hd ** -0.5)
    with jax.named_scope("attn_diff"):
        o = o.reshape(b, t, 4, h // 2, hd).astype(jnp.float32)
        a1 = jnp.concatenate([o[:, :, 0], o[:, :, 1]], axis=-1)
        a2 = jnp.concatenate([o[:, :, 2], o[:, :, 3]], axis=-1)
        lam0 = s.lambda_init(index)
        lam = differential_lambda(p, lam0)
        a = rms_norm(a1 - lam * a2, p["sub_norm"], s.sub_norm_eps)
        a = (a * (1.0 - lam0)).astype(q.dtype).reshape(b, t, h * hd)
    with jax.named_scope("attn_proj"):
        return a @ p["wo"] + p["bo"], lax.stop_gradient(lam)


def layer(p: dict, x: jax.Array, read, index: int, s: Phi4FlashShape,
          attn_block: int, scan_block: int):
    """Published layer `index` on the residual stream. `read` is what the
    layer takes from an earlier one: the memory on a GMU, (keys, values) on a
    cross layer, else None. Returns (x', what later layers read of this one
    (the memory of layer n / 2, (keys, values) of layer n / 2 + 1, else
    None), the layer's counter: the state's rms | the gated memory's | lam)."""
    kind = s.kind(index)
    u = layer_norm(x, p["norm"], p["norm_b"], s.layer_norm_eps)
    published = None
    if kind == MAMBA:
        y, m, counter = mamba_mixer(p, u, s, scan_block)
        if index == s.memory_layer:
            published = m
    elif kind == GMU:
        y, counter = gated_memory(p, u, read)
    elif kind == CROSS:
        b, t, _ = u.shape
        with jax.named_scope("attn_proj"):
            q = (u @ p["wq"] + p["bq"]).reshape(
                b, t, s.num_heads, s.head_dim)
        y, counter = differential_attention(
            p, q, *read, s, kind, index, attn_block)
    else:
        q, k, v = project_qkv(p, u, s)
        y, counter = differential_attention(
            p, q, k, v, s, kind, index, attn_block)
        if index == s.keys_layer:
            published = (k, v)
    x = x + y
    x = x + gated_mlp(
        p, layer_norm(x, p["mlp_norm"], p["mlp_norm_b"], s.layer_norm_eps), s)
    return x, published, counter


def layer_leaves(kind: str, s: Phi4FlashShape) -> tuple:
    d, f, hd = s.hidden_size, s.intermediate_size, s.head_dim
    zeros = nn.initializers.zeros
    shared = (
        ("norm", (d,), True), ("norm_b", (d,), zeros),
        ("mlp_norm", (d,), True), ("mlp_norm_b", (d,), zeros),
        ("w1", (d, 2 * f), False), ("w2", (f, d), False))
    inner = s.mamba_inner
    if kind == MAMBA:
        n, rank = s.mamba_state, s.mamba_dt_rank
        return (
            ("in_proj", (d, 2 * inner), False),
            ("conv_w", (s.mamba_conv, inner), _conv_init),
            ("conv_b", (inner,), _conv_init),
            ("x_proj", (inner, rank + 2 * n), False),
            ("dt_proj", (rank, inner), False),
            ("dt_bias", (inner,), _dt_bias_init),
            ("a_log", (inner, n), _a_log_init), ("d", (inner,), True),
            ("out_proj", (inner, d), False), *shared)
    if kind == GMU:
        return (("w_g", (d, inner), False), ("w_o", (inner, d), False),
                *shared)
    dq, dkv = s.num_heads * hd, s.num_kv_heads * hd
    lambdas = tuple(
        (name, (hd,), nn.initializers.normal(0.1))
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
    queries = (("wq", (d, dq), False), ("bq", (dq,), zeros)) \
        if kind == CROSS else (
            ("wqkv", (d, dq + 2 * dkv), False), ("bqkv", (dq + 2 * dkv,), zeros))
    return (*queries, ("wo", (dq, d), False), ("bo", (d,), zeros), *lambdas,
            ("sub_norm", (2 * hd,), True), *shared)


class Phi4FlashLM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, the counters) without ever
    holding the logits of more than `loss_block` tokens: the path the train
    and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = PHI4FLASH.vocab_size
    shape: Phi4FlashShape = PHI4FLASH
    layers_held: Optional[tuple[int, int]] = None  # (first, count)
    attn_block: int = 512
    loss_block: int = 2048
    scan_block: int = 16  # chunks of the scan recomputed together
    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        "ssm_in_proj": STATE_SPACE, "ssm_conv": STATE_SPACE,
        "ssm_dt_proj": STATE_SPACE, "ssm_sel_scan": STATE_SPACE,
        "ssm_out_proj": STATE_SPACE, "gmu": STATE_SPACE,
        "attn_proj": ATTENTION, "attn_window": ATTENTION,
        "attn_full": ATTENTION, "attn_cross": ATTENTION,
        "attn_diff": ATTENTION, **SCOPES["gated_mlp"],
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (SEL_SCAN_STATE_KEY, GMU_GATE_KEY, DIFF_LAMBDA_KEY)

    def layer_indices(self) -> tuple[int, ...]:
        """The published indices of the layers held, checked: they are among
        the model's, and every layer that reads another's tensors has it."""
        s = self.shape
        first, count = self.layers_held or (0, s.num_layers)
        if not (0 <= first and count >= 1 and first + count <= s.num_layers):
            raise ValueError(
                f"layers held {first}:{count} are not among the model's "
                f"{s.num_layers}")
        held = tuple(range(first, first + count))
        for index in held:
            needs = {GMU: s.memory_layer, CROSS: s.keys_layer}.get(
                s.kind(index))
            if needs is not None and needs not in held:
                what = "memory" if s.kind(index) == GMU else "keys and values"
                raise ValueError(
                    f"layers held {first}:{count}: layer {index} "
                    f"({s.kind(index)}) reads the {what} of layer {needs}, "
                    "which is not held: the stage has nothing to read")
        return held

    @nn.compact
    def __call__(self, x: jax.Array, targets: Optional[jax.Array] = None,
                 train: bool = False):
        s = self.shape
        d = s.hidden_size
        held = self.layer_indices()
        # ONE leaf, used by the lookup and by the head
        embed = _Leaves(
            (("embedding", (self.vocab_size, d), False),), name="embed",
        )()["embedding"]
        layers = [
            _Leaves(layer_leaves(s.kind(i), s), name=f"layer_{i}")()
            for i in held
        ]
        out = _Leaves(
            (("norm", (d,), True), ("norm_b", (d,), nn.initializers.zeros)),
            name="out")()
        if self.is_initializing():
            # the declarations above and no forward pass (models/mellum.py)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        h = embed[x]
        reads = {GMU: None, CROSS: None}
        counters: dict = {}  # a layer's counter under its kind's key
        for p, index in zip(layers, held):
            kind = s.kind(index)
            # what the layer traces is counted where its trace is a cached
            # one too
            h, published, counter = counted(jax.checkpoint(
                layer, static_argnums=(3, 4, 5, 6)))(
                    p, h, reads.get(kind), index, s, self.attn_block,
                    self.scan_block)
            if published is not None:
                reads[GMU if kind == MAMBA else CROSS] = published
            counters.setdefault(
                {MAMBA: SEL_SCAN_STATE_KEY, GMU: GMU_GATE_KEY}.get(
                    kind, DIFF_LAMBDA_KEY), []).append(counter)
        h = layer_norm(h, out["norm"], out["norm_b"], s.layer_norm_eps)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, embed.T)
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), embed.T, targets.reshape(b * t),
            self.loss_block)
        return losses.reshape(b, t), {
            key: jnp.stack(values) for key, values in counters.items()}

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's counters from one step's statistics as host
        arrays: each the mean over the layers of its kind that are held."""
        del tokens
        return {
            name: float(np.mean(stats[key])) for name, key in (
                ("sel_scan_state_rms", SEL_SCAN_STATE_KEY),
                ("gmu_gate_rms", GMU_GATE_KEY),
                ("diff_lambda_mean", DIFF_LAMBDA_KEY)) if key in stats
        }
