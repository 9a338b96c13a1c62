"""Qwen3-Next (Qwen `Qwen3-Next-80B-A3B-Instruct`, `model_type` qwen3_next):
a pre-norm decoder of three Gated DeltaNet layers (a linear attention whose
state is corrected by the gated delta rule) to one gated full-attention layer
(16 query heads over 2 key heads of 256), and in EVERY layer 512 softmax-routed
SwiGLU experts of width 512 (10 a token, renormalised) beside one shared expert
of width 512 under a sigmoid gate of one scalar a token.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json,
the equations as the published `modeling_qwen3_next.py` computes them. x is the
residual stream; RMS0(x; w) = x / rms(x) . (1 + w), eps 1e-6, w starting at
ZERO (the model's own norm: residual, final, q and k norms). Layer l of 48 is
full attention where (l + 1) % 4 == 0 and Gated DeltaNet elsewhere; no bias
anywhere:

    every layer:  x = x + mixer_l(RMS0(x));  x = x + moe(RMS0(x))
    out:          logits = RMS0(x) W_head          (untied)

    Gated DeltaNet (16 key heads, 32 value heads of 128):
        [q | k | v | z] = u W_qkvz   (stored grouped by key head: a key
                          head's [q 128 | k 128 | v 2 x 128 | z 2 x 128])
        [b | a] = u W_ba             (32 + 32)
        [q | k | v] = silu(conv1d_causal_depthwise([q | k | v], 4 taps))
        beta_t = sigmoid(b_t);  g_t = -exp(A_log) . softplus(a_t + dt_bias)
        q, k = q / ||q||, k / ||k|| over a head's 128;  q = q / sqrt(128)
        a key head's q and k serve 2 adjacent value heads
        per value head, S in R^(128 x 128), S_0 = 0:
            S' = exp(g_t) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
            o_t = S_t^T q_t
        out = (RMSNorm_128(o_t; w_n starting at ONE) . silu(z_t)) W_out
    full attention (16 query heads, 2 key heads of 256, scale 1 / 16):
        [q | gate] = u W_q  (a head's [q 256 | gate 256])
        k = u W_k, v = u W_v
        q = RMS0_256(q; w_q), k = RMS0_256(k; w_k) a head
        rotary on the FIRST 64 of the 256 (theta 1e7, half-split), the rest
        passed;  a = causal softmax attention, a key head serving 8 query heads
        out = (a . sigmoid(gate)) W_o            (a gate a CHANNEL)
    moe:  p = softmax(u W_r) over all 512 (float32), the 10 largest, their p
          over the sum of the ten;  y = sum_j w_j E_{i_j}(u)
          + sigmoid(u w_s) . E_shared(u);  E(u) = (silu(u W_g) . u W_u) W_d

The delta rule is `ops/deltarule.py`'s (chunks of 64: its kernels on a TPU,
its plain chunked form elsewhere); the attention core `ops/blockattn.py`'s; the router Mellum 2's (`mellum.route`:
the same softmax, top k, renormalised); the routed experts
`mellum.held_experts`; the rotary embedding `laguna.partial_rope`; the
convolution with its SiLU `ops/shortconv.py`'s; the loss `mellum.token_losses`.

**A chip's share**, as models/laguna.py takes it: `layers_held` (the first n
layers), `experts_held = (first, count)` of every layer and `vocab_size`. The
router keeps its 512 outputs, its 10 a token and its normaliser over all ten
chosen; only the terms whose expert is held are added. The mixers, the router,
the shared expert and its gate are whole on every chip of the group: of the
sparse block a share holds the shared expert entire (counted ONCE when shares
are added up) and the routed sum in part. Nothing stands in for the absent
chips or their exchange.

**Memory.** Every layer is under `jax.checkpoint`, its mixer and its sparse
block each under one of their own: the forward pass keeps the residual stream
before each half and the backward pass recomputes a half before it
differentiates it, so the mixer's and the experts' intermediates are never
alive together (the loss and gradient of two sequences compile for a v5e
at 7.20 GiB of temporaries with one checkpoint round the whole layer, which
does not fit beside 9.3 GiB of state, and at 3.73 with the two halves; PERF.md,
PR 40). Inside, the delta rule recomputes its blocks of positions, the attention
core keeps no scores, the routed experts keep nothing but their inputs, and
the loss recomputes its token blocks.

**Counters.** With `targets` the model returns, beside the per-token loss,
Mellum 2's routing counts and three of its own: the root mean square of each
Gated DeltaNet layer's state after the last position (`health/delta_state`),
the mean of beta per such layer (`health/delta_beta`: 0 or 1 says the write
gate is not wired) and the mean of the shared expert's gate per layer
(`health/shared_gate`). `step_counters` turns them into the `step` record's
`delta_state_rms`, `delta_beta_mean`, `shared_gate_mean` and the `moe_*`
counters every `held_experts` model has.

Assumed (config.json names none of them):
  * `A_log` the log of a uniform draw in (0, 16) a value head (drawn from
    [1e-3, 16) so that no log is of 0); `dt_bias` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1] (Granite's draw; the publisher's file
    starts it at one);
  * the convolution's weight uniform in +-0.5 (Granite's reason: at normal(0,
    0.02) the rule's inputs vanish and a wrong delta rule moves no number a
    seeded comparison reads); no convolution bias;
  * the zero-centred norms at zero and the gated norm at one; normal(0, 0.02)
    everywhere else;
  * the L2 norm as x * rsqrt(sum(x^2) + 1e-6);
  * W_ba's columns as [b of the 32 value heads | a of the 32];
  * the chunk of 64 positions (ours, not the model's);
  * the multi-token-prediction module (`described_as`: "MTP 1"; config.json
    declares no such layer) is LEFT OUT;
  * sequences of one length, no document mask, the state zero at a
    sequence's start and never reset inside it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mgwfbp_tpu.models.lm_parts import (
    ATTENTION,
    EXPERTS,
    LINEAR_ATTENTION,
    MOE_DROPPED_KEY,
    MOE_TOKENS_KEY,
    SCOPES,
    _Leaves,
    _conv_init,
    _dt_bias_init,
    held_experts,
    partial_rope,
    plain_inv_freq,
    rms_norm,
    route,
    routing_counters,
    swiglu,
    token_losses,
)
from mgwfbp_tpu.ops import blockattn, deltarule, shortconv
from mgwfbp_tpu.ops.programs import counted

GDN, FULL = "linear_attention", "full_attention"
# the step's metrics carry these under HEALTH_PREFIX of train/step.py
DELTA_STATE_KEY = "health/delta_state"
DELTA_BETA_KEY = "health/delta_beta"
SHARED_GATE_KEY = "health/shared_gate"


@dataclasses.dataclass(frozen=True)
class Qwen3NextShape:
    """The published sizes (config.json); a test builds a smaller one."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    linear_conv: int = 4
    num_experts: int = 512
    experts_per_token: int = 10
    expert_width: int = 512
    shared_expert_width: int = 512
    rms_norm_eps: float = 1e-6
    l2_norm_eps: float = 1e-6  # assumed
    delta_chunk: int = 64  # ours: positions a chunk of ops/deltarule.py

    def kind(self, index: int) -> str:
        """The kind of published layer `index`."""
        full = (index + 1) % self.full_attention_interval == 0
        return FULL if full else GDN

    @property
    def key_dim(self) -> int:
        return self.linear_key_heads * self.linear_key_dim

    @property
    def value_dim(self) -> int:
        return self.linear_value_heads * self.linear_value_dim


QWEN3NEXT = Qwen3NextShape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# qwen3next_share_tiny.py states the same numbers independently): two periods
QWEN3NEXT_TINY = Qwen3NextShape(
    vocab_size=256, hidden_size=32, num_layers=8, num_heads=4, num_kv_heads=1,
    head_dim=16, linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
    linear_value_dim=8, num_experts=16, experts_per_token=3, expert_width=16,
    shared_expert_width=16, delta_chunk=16,
)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def rms_norm0(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """The model's zero-centred norm over the last dimension: x / rms(x)
    times (1 + weight), float32 inside, x's dtype out."""
    return rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


def l2_norm(x: jax.Array, eps: float, scale: float = 1.0) -> jax.Array:
    """x over its last dimension's length, times `scale`, float32 inside."""
    x32 = x.astype(jnp.float32)
    return (x32 * (scale * lax.rsqrt(
        jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps))
    ).astype(x.dtype)


def _mean(a: jax.Array) -> jax.Array:
    return lax.stop_gradient(jnp.mean(a.astype(jnp.float32)))


def delta_mixer(p: dict, u: jax.Array, s: Qwen3NextShape, delta_block: int):
    """The Gated DeltaNet mixer on the normed input u (B, T, hidden): (out
    (B, T, hidden), root mean square of the final state, mean of beta)."""
    b, t, _ = u.shape
    hk, hv = s.linear_key_heads, s.linear_value_heads
    dk, dv, r = s.linear_key_dim, s.linear_value_dim, hv // hk
    with jax.named_scope("gdn_in_proj"):
        # a key head's columns: [q dk | k dk | v r x dv | z r x dv]
        qkvz = (u @ p["w_qkvz"]).reshape(b, t, hk, 2 * dk + 2 * r * dv)
        q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
        v = qkvz[..., 2 * dk:2 * dk + r * dv]
        z = qkvz[..., 2 * dk + r * dv:].reshape(b, t, hv, dv)
        ba = (u @ p["w_ba"]).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(jnp.float32))
    with jax.named_scope("gdn_conv"):
        qkv = jnp.concatenate([
            q.reshape(b, t, s.key_dim), k.reshape(b, t, s.key_dim),
            v.reshape(b, t, s.value_dim)], axis=-1)
        qkv = shortconv.causal_conv_silu(qkv, p["conv_w"])
    with jax.named_scope("gdn_delta"):
        q = l2_norm(qkv[..., :s.key_dim].reshape(b, t, hk, dk),
                    s.l2_norm_eps, dk ** -0.5)
        k = l2_norm(qkv[..., s.key_dim:2 * s.key_dim].reshape(b, t, hk, dk),
                    s.l2_norm_eps)
        v = qkv[..., 2 * s.key_dim:].reshape(b, t, hv, dv)
        o, state = deltarule.gated_delta_rule(
            q, k, v, g, beta, chunk=s.delta_chunk, block=delta_block)
        state_rms = lax.stop_gradient(jnp.sqrt(jnp.mean(jnp.square(state))))
    with jax.named_scope("gdn_gate_norm"):
        o32 = o.astype(jnp.float32)
        o32 = o32 * lax.rsqrt(
            jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + s.rms_norm_eps)
        o = (o32 * p["gate_norm"].astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
    with jax.named_scope("gdn_out_proj"):
        return (o.reshape(b, t, s.value_dim) @ p["w_out"], state_rms,
                _mean(beta))


def output_gate(gate: jax.Array) -> jax.Array:
    """What multiplies the core's output: sigmoid of W_q's second half, one
    a channel, float32."""
    return jax.nn.sigmoid(gate.astype(jnp.float32))


def attention(p: dict, u: jax.Array, s: Qwen3NextShape, block: int):
    """The gated full-attention mixer on the normed input u (B, T, hidden)."""
    b, t, _ = u.shape
    h, hkv, hd = s.num_heads, s.num_kv_heads, s.head_dim
    with jax.named_scope("attn_proj"):
        qg = (u @ p["wq"]).reshape(b, t, h, 2 * hd)  # a head's [q | gate]
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (u @ p["wk"]).reshape(b, t, hkv, hd)
        v = (u @ p["wv"]).reshape(b, t, hkv, hd)
        q = rms_norm0(q, p["q_norm"], s.rms_norm_eps)
        k = rms_norm0(k, p["k_norm"], s.rms_norm_eps)
        inv_freq = plain_inv_freq(
            int(hd * s.partial_rotary_factor), s.rope_theta)
        # 1 / sqrt(D) rides on q's rotation (models/mellum.attention)
        q = partial_rope(q, inv_freq, 1.0, hd ** -0.5)
        k = partial_rope(k, inv_freq, 1.0)
    with jax.named_scope("attn_full"):
        a = blockattn.blockwise_attention(q, k, v, block=block, scale=1.0)
    with jax.named_scope("attn_gate"):
        a = (a.astype(jnp.float32) * output_gate(gate)).astype(a.dtype)
    with jax.named_scope("attn_proj"):
        return a.reshape(b, t, h * hd) @ p["wo"]


def shared_gate(u: jax.Array, w: jax.Array) -> jax.Array:
    """sigmoid(u w_s): one scalar a token, float32. u (N, hidden), w
    (hidden,)."""
    return jax.nn.sigmoid(
        jnp.dot(u, w, preferred_element_type=jnp.float32))


def sparse_block(p: dict, x: jax.Array, s: Qwen3NextShape, first: int):
    """The sparse block on the normed input x (B, T, hidden): the shared
    expert under its gate, whole, plus the held routed experts' part. (y,
    tokens per held expert (E,) float32, dropped float32, mean gate)."""
    b, t, d = x.shape
    u = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        idx, weights = route(u, p["router"], s.experts_per_token)
    with jax.named_scope("moe_shared"):
        gate = shared_gate(u, p["shared_gate_w"])
        shared = swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
        shared = (gate[:, None] * shared.astype(jnp.float32)).astype(u.dtype)
    with jax.named_scope("moe_experts"):
        y, sizes, dropped = counted(
            jax.checkpoint(held_experts, static_argnums=6))(
                u, idx, weights, p["w_gate"], p["w_up"], p["w_down"], first)
    return ((shared + y).reshape(b, t, d), sizes.astype(jnp.float32),
            dropped.astype(jnp.float32), _mean(gate))


def mixer_half(p: dict, x: jax.Array, kind: str, s: Qwen3NextShape,
               attn_block: int, delta_block: int):
    """A decoder layer's first half on the residual stream: (x + mixer, (state
    rms, mean beta) of a Gated DeltaNet layer or None)."""
    u = rms_norm0(x, p["attn_norm"], s.rms_norm_eps)
    if kind == GDN:
        y, *delta = delta_mixer(p, u, s, delta_block)
    else:
        y, delta = attention(p, u, s, attn_block), None
    return x + y, delta


def sparse_half(p: dict, x: jax.Array, s: Qwen3NextShape, first: int):
    """A decoder layer's second half on the residual stream: (x + moe, the
    sparse block's (tokens per held expert, dropped, mean shared gate))."""
    y, *routing = sparse_block(
        p, rms_norm0(x, p["moe_norm"], s.rms_norm_eps), s, first)
    return x + y, routing


def layer_leaves(kind: str, count: int, s: Qwen3NextShape) -> tuple:
    d, f, fs = s.hidden_size, s.expert_width, s.shared_expert_width
    zeros = nn.initializers.zeros
    sparse = (
        ("moe_norm", (d,), zeros), ("router", (d, s.num_experts), False),
        ("shared_gate", (d, fs), False), ("shared_up", (d, fs), False),
        ("shared_down", (fs, d), False), ("shared_gate_w", (d,), False),
        ("w_gate", (count, d, f), False), ("w_up", (count, d, f), False),
        ("w_down", (count, f, d), False))
    if kind == FULL:
        dq, dkv = s.num_heads * s.head_dim, s.num_kv_heads * s.head_dim
        return (
            ("attn_norm", (d,), zeros), ("wq", (d, 2 * dq), False),
            ("wk", (d, dkv), False), ("wv", (d, dkv), False),
            ("q_norm", (s.head_dim,), zeros), ("k_norm", (s.head_dim,), zeros),
            ("wo", (dq, d), False), *sparse)
    hv = s.linear_value_heads
    return (
        ("attn_norm", (d,), zeros),
        ("w_qkvz", (d, 2 * s.key_dim + 2 * s.value_dim), False),
        ("w_ba", (d, 2 * hv), False),
        ("conv_w", (s.linear_conv, 2 * s.key_dim + s.value_dim), _conv_init),
        ("dt_bias", (hv,), _dt_bias_init), ("a_log", (hv,), _a_log_init),
        ("gate_norm", (s.linear_value_dim,), True),
        ("w_out", (s.value_dim, d), False), *sparse)


class Qwen3NextLM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, the counters) without ever
    holding the logits of more than `loss_block` tokens: the path the train
    and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = QWEN3NEXT.vocab_size
    shape: Qwen3NextShape = QWEN3NEXT
    layers_held: Optional[int] = None  # the first n layers
    experts_held: tuple[int, int] = (0, QWEN3NEXT.num_experts)
    attn_block: int = 512
    loss_block: int = 2048
    delta_block: int = 8  # chunks of the delta rule recomputed together
    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        "gdn_in_proj": LINEAR_ATTENTION, "gdn_conv": LINEAR_ATTENTION,
        "gdn_delta": LINEAR_ATTENTION, "gdn_gate_norm": LINEAR_ATTENTION,
        "gdn_out_proj": LINEAR_ATTENTION, "attn_proj": ATTENTION,
        "attn_full": ATTENTION, "attn_gate": ATTENTION,
        "moe_route": EXPERTS, "moe_shared": EXPERTS, "moe_experts": EXPERTS,
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (
        MOE_TOKENS_KEY, MOE_DROPPED_KEY, DELTA_STATE_KEY, DELTA_BETA_KEY,
        SHARED_GATE_KEY)

    def layer_kinds(self) -> tuple[str, ...]:
        s = self.shape
        held = s.num_layers if self.layers_held is None else self.layers_held
        return tuple(s.kind(i) for i in range(held))

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's counters from one step's statistics as host
        arrays; `tokens` one device's tokens a (micro-)step. The routing
        counts as `Mellum2LM.step_counters` gives them; the delta rule's
        over the Gated DeltaNet layers held (none: no such counter)."""
        out = {
            **routing_counters(stats, tokens * self.shape.experts_per_token),
            "shared_gate_mean": float(np.mean(stats[SHARED_GATE_KEY])),
        }
        if DELTA_STATE_KEY in stats:
            out.update(
                delta_state_rms=float(np.mean(stats[DELTA_STATE_KEY])),
                delta_beta_mean=float(np.mean(stats[DELTA_BETA_KEY])))
        return out

    @nn.compact
    def __call__(self, x: jax.Array, targets: Optional[jax.Array] = None,
                 train: bool = False):
        s = self.shape
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= s.num_experts):
            raise ValueError(
                f"experts held {first}:{count} are not among the model's "
                f"{s.num_experts}")
        d = s.hidden_size
        embed = _Leaves(
            (("embedding", (self.vocab_size, d), False),), name="embed",
        )()["embedding"]
        kinds = self.layer_kinds()
        layers = [
            _Leaves(layer_leaves(kind, count, s), name=f"layer_{i}")()
            for i, kind in enumerate(kinds)
        ]
        out = _Leaves(
            (("norm", (d,), nn.initializers.zeros),
             ("head", (d, self.vocab_size), False)), name="out",
        )()
        if self.is_initializing():
            # the declarations above and no forward pass (models/mellum.py)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        # equal halves share ONE cached trace under `jax.checkpoint`: what a
        # trace counted (grouped products and permutations, delta rules,
        # convolutions, attention cores) is counted again where it is replayed
        mixer = counted(
            jax.checkpoint(mixer_half, static_argnums=(2, 3, 4, 5)))
        sparse = counted(jax.checkpoint(sparse_half, static_argnums=(2, 3)))
        h = embed[x]
        deltas, routing = [], []
        for p, kind in zip(layers, kinds):
            h, delta = mixer(
                p, h, kind, s, self.attn_block, self.delta_block)
            h, layer_routing = sparse(p, h, s, first)
            if delta is not None:
                deltas.append(delta)
            routing.append(layer_routing)
        h = rms_norm0(h, out["norm"], s.rms_norm_eps)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, out["head"])
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), out["head"], targets.reshape(b * t),
            self.loss_block)
        tokens, dropped, gates = zip(*routing)
        stats = {
            # (layers held, experts held): tokens each held expert took
            MOE_TOKENS_KEY: jnp.stack(tokens),
            MOE_DROPPED_KEY: jnp.sum(jnp.stack(dropped)),
            SHARED_GATE_KEY: jnp.stack(gates),
        }
        if deltas:  # the Gated DeltaNet layers held
            state_rms, beta = zip(*deltas)
            stats.update({
                DELTA_STATE_KEY: jnp.stack(state_rms),
                DELTA_BETA_KEY: jnp.stack(beta)})
        return losses.reshape(b, t), stats
