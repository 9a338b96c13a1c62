"""Laguna-XS.2 (poolside, `model_type` laguna, 33.4B-A3B): a pre-norm decoder
whose layers differ in kind. Grouped-query attention with 8 key/value heads of
128 and a QUERY-HEAD COUNT BY LAYER (48 on full layers, 64 on window layers),
one full layer to three window layers (window 512), a headwise sigmoid gate on
the attention output, rotary embedding over HALF of each head on full layers
and the whole head on window layers; layer 0's MLP dense (8,192), every later
layer 256 routed SwiGLU experts of width 512 (8 a token, sigmoid scores,
scaled by 2.5) BESIDE one shared expert of width 512 that every token takes.

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json.
Per layer l, x the residual stream, n_l its head count:

    u = RMSNorm(x);  q = rope_l(W_q u) (n_l x 128), k = rope_l(W_k u),
                     v = W_v u (8 x 128);  g = sigmoid(u W_g) (n_l)
    h = x + W_o [ g_head * Attn_l(q, k, v) ]      causal, 1 / sqrt(128),
                                                  window 512 on window layers
    v' = RMSNorm(h)
    l = 0:  x' = h + W_down (silu(W_gate v') * W_up v')          (width 8,192)
    l > 0:  x' = h + Shared(v')
                   + 2.5 sum_{k in top 8, e_k held here} w_k Expert_{e_k}(v')

then a final RMSNorm and an untied head. No bias, no dropout. rope_l rotates
dimensions 0 to 63 of a head on a full layer (half-split inside those 64, YaRN:
theta 500,000, factor 64 over 4,096 positions, beta_fast 64, cos and sin times
1.4158883083359672; the other 64 pass through) and all 128 on a window layer
(plain, theta 10,000). Router: s = sigmoid(v' W_r) in float32 over all 256,
the 8 largest, w_k = s_k / (sum of the 8 chosen).

**A chip's share**, as models/mellum.py takes it: `layers_held` (the first n
of the per-layer lists, so the dense layer stays first), `experts_held =
(first, count)` of every sparse layer and `vocab_size`. The router keeps its
256 outputs, its 8 a token and its normaliser over all 8 chosen; only the
terms whose expert is held are added. The shared expert, the router, the
attention and the dense MLP are whole on every chip of the group: of the
sparse block, a share holds the shared expert entire and the routed sum in
part (`held_experts`, `_dispatch`, `_unsort` and `token_losses` are Mellum
2's, unchanged). Nothing stands in for the absent chips or their exchange.

**Memory.** As models/mellum.py: the attention core keeps no scores
(ops/blockattn.py: the fused kernel saves its output and a row log-sum-exp),
the routed experts recompute their sorted rows and products, the loss its
blocks of `loss_block` tokens; what is saved per layer is the residual stream
and the products' inputs. The layers themselves are NOT recomputed: the step
at the cell's size compiles for a v5e at 12.88 GiB (5.15 of temporaries beside
7.73 of state), and at 10.87 with every layer under `jax.checkpoint`, which
would run each forward pass twice for memory the chip has.

**Counters.** With `targets` the model returns Mellum 2's routing counts
(`health/moe_tokens`, `health/moe_dropped`) and two of its own: the mean of g
per layer (`health/attn_gate`) and, per sparse layer, the mean over tokens of
the sum of the 8 chosen scores (`health/moe_score_sum`, the normaliser's
denominator). `step_counters` turns them into the `step` record's `moe_here`,
`moe_load_max`, `moe_load_mean`, `moe_dropped` (Mellum 2's names and
meanings), `attn_gate_mean` and `moe_score_sum`.

Assumed, each in ONE place here (and one in the plain reference), because
config.json does not settle it:
  * `gating: true` is a headwise sigmoid gate on the attention output, one
    scalar a head and token, before W_o (arXiv:2505.06708, headwise form):
    `attention_gate`. The published size decides: with the key's numbers the
    model has 33.44 B parameters without gate weights and with one scalar a
    head (3.01 / 3.02 B active), 34.07 B with one gate a channel; it is
    described as 33.4B-A3B. Other readings: per channel (excluded by the
    count), a scalar gate on the shared expert.
  * the router's score is DeepSeek-V3's (arXiv:2412.19437 eq. 12 to 15)
    without the selection bias: sigmoid, top 8, normalised over the chosen,
    times `moe_routed_scaling_factor`: `route`. Other reading: softmax before
    the top 8.
No query/key normalization, no router bias, no load-balancing loss (config.json
names none); initial weights normal(0, 0.02), norms at one.
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
    FULL,
    MLP,
    MOE_DROPPED_KEY,
    MOE_TOKENS_KEY,
    SCOPES,
    SLIDING,
    _Leaves,
    held_experts,
    partial_rope,
    plain_inv_freq,
    rms_norm,
    routing_counters,
    swiglu,
    token_losses,
    yarn_inv_freq,
)
from mgwfbp_tpu.ops.blockattn import blockwise_attention
from mgwfbp_tpu.ops.programs import counted

DENSE, SPARSE = "dense", "sparse"
# the step's metrics carry these under HEALTH_PREFIX of train/step.py
ATTN_GATE_KEY = "health/attn_gate"
MOE_SCORE_SUM_KEY = "health/moe_score_sum"


@dataclasses.dataclass(frozen=True)
class LagunaShape:
    """The published sizes (config.json); a test builds a smaller one."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192  # the dense layer's MLP
    heads_per_layer: tuple[int, ...] = (48, 64, 64, 64) * 10
    num_kv_heads: int = 8
    head_dim: int = 128
    layer_types: tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 10
    mlp_layer_types: tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    sliding_window: int = 512
    num_experts: int = 256
    experts_per_token: int = 8
    expert_width: int = 512
    shared_expert_width: int = 512
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    # rotary embedding by kind (`rope_parameters`)
    full_rotary_factor: float = 0.5  # the share of a head that rotates
    full_rope_theta: float = 500000.0
    yarn_factor: float = 64.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    sliding_rotary_factor: float = 1.0
    sliding_rope_theta: float = 10000.0


LAGUNA_XS2 = LagunaShape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# laguna_xs2_share_tiny.py states the same numbers independently): one period
# and the layer after it, a group of THREE query heads a key head on the full
# layers (no power of two, as the published six) and of four on the window's
LAGUNA_XS2_TINY = LagunaShape(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    heads_per_layer=(6, 8, 8, 8, 6), num_kv_heads=2, head_dim=16,
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE, SPARSE),
    sliding_window=16, num_experts=16, experts_per_token=2, expert_width=32,
    shared_expert_width=24, yarn_original_len=32, yarn_beta_fast=4.0,
)


def rope_inv_freq(shape: LagunaShape, kind: str) -> tuple[jax.Array, float]:
    """(inverse frequencies over the dimensions of a head that rotate on a
    layer of `kind`, half as many as rotate; factor on cos and sin)."""
    if kind == SLIDING:
        dim = int(shape.head_dim * shape.sliding_rotary_factor)
        return plain_inv_freq(dim, shape.sliding_rope_theta), 1.0
    dim = int(shape.head_dim * shape.full_rotary_factor)
    return yarn_inv_freq(
        dim, shape.full_rope_theta, shape.yarn_factor, shape.yarn_original_len,
        shape.yarn_beta_fast, shape.yarn_beta_slow,
    ), shape.yarn_attention_factor


def attention_gate(u: jax.Array, w_gate: jax.Array) -> jax.Array:
    """What `gating: true` is taken to be: g = sigmoid(u W_g), one scalar a
    head and token, float32. u (B, T, hidden), w_gate (hidden, heads)."""
    return jax.nn.sigmoid(
        jnp.dot(u, w_gate, preferred_element_type=jnp.float32))


def attention(p: dict, u: jax.Array, shape: LagunaShape, kind: str,
              heads: int, block: int):
    """The attention sublayer on the normed input u (B, T, hidden) of a layer
    with `heads` query heads: (W_o [g * Attn], mean of g)."""
    b, t, _ = u.shape
    hd = shape.head_dim
    with jax.named_scope("attn_proj"):
        q = (u @ p["wq"]).reshape(b, t, heads, hd)
        k = (u @ p["wk"]).reshape(b, t, shape.num_kv_heads, hd)
        v = (u @ p["wv"]).reshape(b, t, shape.num_kv_heads, hd)
        inv_freq, factor = rope_inv_freq(shape, kind)
        # 1 / sqrt(D) rides on q's rotation, float32 inside, so q is rounded
        # to the compute dtype once (models/mellum.attention)
        q = partial_rope(q, inv_freq, factor, hd ** -0.5)
        k = partial_rope(k, inv_freq, factor)
    with jax.named_scope("attn_gate"):
        g = attention_gate(u, p["wg"])  # (B, T, heads) float32
    window = shape.sliding_window if kind == SLIDING else None
    if window is not None:
        block = min(block, max(window // 4, 1))  # models/mellum.attention
    with jax.named_scope("attn_window" if kind == SLIDING else "attn_full"):
        a = blockwise_attention(q, k, v, window=window, block=block, scale=1.0)
    with jax.named_scope("attn_gate"):
        a = (a.astype(jnp.float32) * g[..., None]).astype(a.dtype)
    with jax.named_scope("attn_proj"):
        return (a.reshape(b, t, heads * hd) @ p["wo"],
                lax.stop_gradient(jnp.mean(g)))


def route(u: jax.Array, router: jax.Array, top_k: int, scaling: float):
    """What the router's score is taken to be: s = sigmoid(u W_r) over ALL
    experts in float32 (operands as stored, product at `highest`), the
    `top_k` largest, each over the sum of the chosen, times `scaling`.
    (indices (N, k), weights (N, k), sum of the chosen scores (N,))."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ))
    top, idx = lax.top_k(scores, top_k)
    total = jnp.sum(top, axis=-1, keepdims=True)
    return idx, top / total * scaling, total[:, 0]


def sparse_block(p: dict, x: jax.Array, shape: LagunaShape, first: int):
    """The sparse block on the normed input x (B, T, hidden): the shared
    expert whole plus the held routed experts' part. (y, tokens per held
    expert (E,) float32, dropped float32, mean sum of the chosen scores)."""
    b, t, d = x.shape
    u = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        idx, weights, score_sum = route(
            u, p["router"], shape.experts_per_token,
            shape.routed_scaling_factor)
    with jax.named_scope("moe_shared"):
        shared = swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    with jax.named_scope("moe_experts"):
        y, sizes, dropped = counted(
            jax.checkpoint(held_experts, static_argnums=6))(
                u, idx, weights, p["w_gate"], p["w_up"], p["w_down"], first)
    return (
        (shared + y).reshape(b, t, d), sizes.astype(jnp.float32),
        dropped.astype(jnp.float32), lax.stop_gradient(jnp.mean(score_sum)),
    )


def layer(p: dict, x: jax.Array, kind: str, mlp_kind: str, heads: int,
          shape: LagunaShape, first: int, attn_block: int):
    """One decoder layer on the residual stream: (x', mean gate, the sparse
    block's (tokens per held expert, dropped, mean score sum) or None on the
    dense layer)."""
    a, gate = attention(
        p, rms_norm(x, p["attn_norm"], shape.rms_norm_eps), shape, kind,
        heads, attn_block)
    h = x + a
    v = rms_norm(h, p["mlp_norm"], shape.rms_norm_eps)
    if mlp_kind == DENSE:
        with jax.named_scope("mlp"):
            return h + swiglu(
                v, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), gate, None
    y, *routing = sparse_block(p, v, shape, first)
    return h + y, gate, routing


def layer_leaves(mlp_kind: str, heads: int, count: int,
                 s: LagunaShape) -> tuple:
    d = s.hidden_size
    dq, dkv = heads * s.head_dim, s.num_kv_heads * s.head_dim
    attn = (
        ("attn_norm", (d,), True), ("wq", (d, dq), False),
        ("wk", (d, dkv), False), ("wv", (d, dkv), False),
        ("wg", (d, heads), False), ("wo", (dq, d), False),
        ("mlp_norm", (d,), True))
    if mlp_kind == DENSE:
        f = s.intermediate_size
        return (*attn, ("mlp_gate", (d, f), False), ("mlp_up", (d, f), False),
                ("mlp_down", (f, d), False))
    f, fs = s.expert_width, s.shared_expert_width
    return (
        *attn, ("router", (d, s.num_experts), False),
        ("shared_gate", (d, fs), False), ("shared_up", (d, fs), False),
        ("shared_down", (fs, d), False),
        ("w_gate", (count, d, f), False), ("w_up", (count, d, f), False),
        ("w_down", (count, f, d), False))


class LagunaLM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, the counters) without ever
    holding the logits of more than `loss_block` tokens: the path the train
    and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = LAGUNA_XS2.vocab_size
    shape: LagunaShape = LAGUNA_XS2
    layers_held: Optional[int] = None  # the first n of the per-layer lists
    experts_held: tuple[int, int] = (0, LAGUNA_XS2.num_experts)
    attn_block: int = 512  # queries a block; a window layer takes fewer
    loss_block: int = 2048
    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        "attn_proj": ATTENTION, "attn_gate": ATTENTION,
        "attn_window": ATTENTION, "attn_full": ATTENTION, "mlp": MLP,
        "moe_route": EXPERTS, "moe_shared": EXPERTS, "moe_experts": EXPERTS,
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (
        MOE_TOKENS_KEY, MOE_DROPPED_KEY, ATTN_GATE_KEY, MOE_SCORE_SUM_KEY)

    def layer_kinds(self) -> tuple[tuple[str, str, int], ...]:
        """(attention kind, MLP kind, query heads) of every layer held."""
        s = self.shape
        kinds = tuple(
            zip(s.layer_types, s.mlp_layer_types, s.heads_per_layer))
        return kinds if self.layers_held is None else kinds[: self.layers_held]

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's counters from one step's statistics as host
        arrays; `tokens` one device's tokens a (micro-)step. The routing
        counts as `Mellum2LM.step_counters` gives them, over the SPARSE
        layers held; none of them where only the dense layer is held."""
        out = {"attn_gate_mean": float(np.mean(stats[ATTN_GATE_KEY]))}
        if MOE_TOKENS_KEY in stats:
            out.update(
                routing_counters(
                    stats, tokens * self.shape.experts_per_token),
                moe_score_sum=float(np.mean(stats[MOE_SCORE_SUM_KEY])))
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
            _Leaves(layer_leaves(mlp_kind, heads, count, s),
                    name=f"layer_{i}")()
            for i, (_, mlp_kind, heads) in enumerate(kinds)
        ]
        out = _Leaves(
            (("norm", (d,), True), ("head", (d, self.vocab_size), False)),
            name="out",
        )()
        if self.is_initializing():
            # the declarations above and no forward pass (models/mellum.py)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        h = embed[x]
        gates, routing = [], []
        for p, (kind, mlp_kind, heads) in zip(layers, kinds):
            h, gate, layer_routing = layer(
                p, h, kind, mlp_kind, heads, s, first, self.attn_block)
            gates.append(gate)
            if layer_routing is not None:
                routing.append(layer_routing)
        h = rms_norm(h, out["norm"], s.rms_norm_eps)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, out["head"])
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), out["head"], targets.reshape(b * t),
            self.loss_block)
        stats = {ATTN_GATE_KEY: jnp.stack(gates)}  # (layers held,)
        if routing:  # the sparse layers held: none under `layers_held` 1
            tokens, dropped, score_sums = zip(*routing)
            stats.update({
                # (sparse layers held, experts held): tokens each took
                MOE_TOKENS_KEY: jnp.stack(tokens),
                MOE_DROPPED_KEY: jnp.sum(jnp.stack(dropped)),
                MOE_SCORE_SUM_KEY: jnp.stack(score_sums),
            })
        return losses.reshape(b, t), stats
