"""NVIDIA Nemotron 3 Super 120B-A12B (nvidia, `model_type` nemotron_h): a
pre-norm decoder of 88 layers of which each is ONE mixer behind ONE norm:
40 Mamba-2 layers (`M`), 40 LatentMoE layers (`E`: 512 ungated relu^2
experts of width 2,688, 22 a token, computed in a latent of 1,024 beside a
shared expert of 5,376 at the full 4,096) and 8 grouped-query attention
layers (`*`: 32 query heads over 2 key-value heads of 128), in the order the
published pattern string gives; untied head.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json,
the equations as a `transformers`-style `modeling_nemotron_h` computes them.
x is the residual stream (B, T, 4,096), RMSNorm(u) = g u / sqrt(mean(u^2) +
1e-5), no bias but the convolution's:

    x = E[ids]                                   (no multiplier)
    layer l of kind k:  x = x + mixer_k(RMSNorm_l(x))
    M:  [z | xBC | dt] = u W_in     widths 8,192 | 8,192 + 2 x 8 x 128 | 128
        xBC = silu(conv1d_causal_depthwise(xBC, 4) + b);  [xs | B | C] = xBC
        xs: 128 heads x 64;  B, C: 8 groups x 128, head h reads group h // 16
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T;  y_t = S_t C_t + D xs_t
        v = y silu(z);  per GROUP g of 1,024 channels:
            v_g = w_g v_g / sqrt(mean_g(v_g^2) + 1e-5);   out = v W_out
    *:  q = u W_q (32 x 128), k = u W_k, v = u W_v (2 x 128 each); causal
        softmax(q k^T / sqrt(128)) v; out = a W_o; no position term
    E:  s = sigmoid(u W_r) in float32 (512); chosen = the 22 largest of s + b
        w = s[chosen] / (sum of s[chosen] + 1e-20) x 5.0
        l = u W_down (4,096 -> 1,024)
        r = sum over chosen e of w_e W2_e relu(W1_e l)^2   (1,024 -> 2,688 ->
            1,024: two products an expert, no gate)
        out = r W_up (1,024 -> 4,096) + S2 relu(S1 u)^2    (4,096 -> 5,376 ->
            4,096)
    logits = RMSNorm_f(x) W_head

The Mamba-2 mixer is `lm_parts.mamba2_mixer` (Granite 4.0-H's body, with the
groups of B and C and the gated norm by group), the scan `ops/ssd.py`'s at
the published chunk of 128, the router `lm_parts.sigmoid_bias_route`
(Xing4.0's), the experts `lm_parts.held_relu2_experts`, the attention core
`ops/blockattn.py`'s.

**A chip's share.** Three cuts, as the model-configs guide's section 4 has
them, each a constructor argument:
  * `layers_held = (first, count)`: a pipeline stage under the published
    layer numbers (`layer_26` .. `layer_36`, the first whole period
    `EMEMEMEMEM*`);
  * `experts_held = (first, count)` of every `E` layer's 512 routed experts.
    The router keeps its 512 outputs, its 22 a token and its normaliser over
    all 22; only the terms whose expert is held are added;
  * `tensor_share = (index, of)`: member `index` of `of` chips that share
    each layer's HEADS (`held`): Mamba heads index x 128/of .. with the B/C
    groups they read (`of` divides the 8 groups; at of = 8 a share is 16
    heads over ONE group and a gated norm over its own 1,024 channels),
    query heads index x 32/of .. with key-value head index x 2 // of (held
    whole where of > 2), columns index x 5,376/of .. of S1 and the same rows
    of S2. Router, selection bias, both latent projections and every norm
    are whole on every chip. The leaves are declared at the share's sizes
    (`share_leaves` cuts a whole layer's to them: what the shares-add-up
    test and a checkpoint's loader need); no product's contraction or output
    width is cut.
On one chip a layer runs without its all-reduce and its all-to-all: the
partial result goes on to the next layer. Nothing stands in for the absent
chips, and the multi-token-prediction module (the model's last part, on the
last stage; how it joins the next token's embedding is in no key) is not
built.

**Memory.** A layer IS a sub-layer here: every layer under its own
`jax.checkpoint`, so the forward pass keeps the residual stream once a layer
(64 MiB in bf16 at T 8,192) and the backward pass recomputes a layer before
it differentiates it. The large temporaries are `held_relu2_experts`': its
grouped arrays have a row for each assignment that CAN be held, N x min(22,
experts held) of them (M = 65,536 at T 8,192 and 8 of 512 held, where N x 22
would be 180,224; 4.3% of the M are in a held group at even routing), (M,
1,024) and (M, 2,688) in the compute dtype (128 and 336 MiB in bf16), alive
inside one `E` layer's backward pass.

**Counters.** With `targets` the model returns, beside the per-token loss:
the scan's `health/ssm_state` and `health/ssm_log_decay_min` (as Granite's),
the routing counts (as Mellum 2's), the selection bias's swap share (as
Xing4.0's), and per `E` layer the root mean square of the latent the experts
read and the share of the held experts' hidden units, over the rows in a
group, that relu left above zero; `step_counters` turns the last two into
`moe_latent_rms` and `moe_relu2_active`.

Assumed, each in ONE place here (and one in the plain reference), because
config.json does not settle it:
  * the gated norm by GROUP of inner / n_groups channels, gate before norm
    (the publisher's `group_size`; over all 8,192 at once the tensor shares
    would not add up): `lm_parts.mamba2_mixer`;
  * no rotary term in the attention layers (`rope_theta` and
    `partial_rotary_factor` are in the file and unused by the publisher's
    attention): `attention`;
  * no clamp on dt (`time_step_min` / `max` / `floor` draw `dt_bias`):
    `lm_parts.mamba2_mixer`;
  * the selection bias a held leaf no gradient reaches and the optimizer
    leaves where it is: `latent_moe`;
  * the initial draws: normal(0, 0.02); the Mamba layers' `out_proj` over
    sqrt(88) (`rescale_prenorm_residual`, which in the publisher's code
    names that leaf alone; the other reading rescales every projection into
    the residual stream); convolution, `A_log`, `dt_bias`, `D` as `lm_parts`
    draws them for Granite; the selection bias uniform
    +-`selection_bias_init`, wide enough to change some of the 22 choices;
  * sequences of one length, no document mask, the state zero at a
    sequence's start.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mgwfbp_tpu.models.lm_parts import (
    ATTENTION as ATTENTION_LAYER,
    EXPERTS,
    MOE_DROPPED_KEY,
    MOE_TOKENS_KEY,
    SCOPES,
    _bias_init,
    _Leaves,
    held_relu2_experts,
    mamba2_leaves,
    mamba2_mixer,
    rms_norm,
    routing_counters,
    sigmoid_bias_route,
    token_losses,
)
from mgwfbp_tpu.ops.blockattn import blockwise_attention
from mgwfbp_tpu.ops.programs import counted

MAMBA, ATTENTION, MOE = "M", "*", "E"  # the pattern string's letters
# the step's metrics carry these under HEALTH_PREFIX of train/step.py
SSM_STATE_KEY = "health/ssm_state"
SSM_LOG_DECAY_KEY = "health/ssm_log_decay_min"
MOE_SWAP_KEY = "health/moe_bias_swap"
MOE_LATENT_KEY = "health/moe_latent_rms"
MOE_ACTIVE_KEY = "health/moe_relu2_active"


@dataclasses.dataclass(frozen=True)
class NemotronHShape:
    """The published sizes (config.json); a test builds a smaller one."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    # `hybrid_override_pattern`: a letter a layer
    pattern: str = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_state: int = 128  # `ssm_state_size`
    mamba_groups: int = 8  # `n_groups`
    mamba_chunk: int = 128  # `chunk_size`
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 512  # `n_routed_experts`
    experts_per_token: int = 22
    expert_width: int = 2688  # `moe_intermediate_size`
    latent_size: int = 1024  # `moe_latent_size`
    shared_expert_width: int = 5376  # `moe_shared_expert_intermediate_size`
    routed_scaling_factor: float = 5.0
    rms_norm_eps: float = 1e-5
    # half the width of the selection bias's seeded draw (assumed): against
    # sigmoid scores whose 22nd and 23rd largest of 512 lie a few thousandths
    # apart
    selection_bias_init: float = 0.02

    @property
    def num_layers(self) -> int:
        return len(self.pattern)


NEMOTRON3S = NemotronHShape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# nemotron3s_share_tiny.py states the same numbers independently): every
# kind of layer, 4 B/C groups of 2 Mamba heads, 8 query heads over 2
# key-value heads, so that 4 tensor shares exercise the group norm and the
# key-value mapping
NEMOTRON3S_TINY = NemotronHShape(
    vocab_size=256, hidden_size=32, pattern="MEM*EME", mamba_heads=8,
    mamba_head_dim=8, mamba_state=8, mamba_groups=4, mamba_chunk=16,
    num_heads=8, num_kv_heads=2, head_dim=8, num_experts=8,
    experts_per_token=3, expert_width=24, latent_size=16,
    shared_expert_width=48,
)


class Held(NamedTuple):
    """What one of `of` chips that share a layer's heads holds of it."""

    mamba_heads: int
    mamba_groups: int
    num_heads: int
    num_kv_heads: int
    shared_columns: int


def held(s: NemotronHShape, tensor_share: tuple[int, int]) -> Held:
    """The sizes of member `index` of `of` (which member changes no size)."""
    index, of = tensor_share
    if not (of >= 1 and 0 <= index < of):
        raise ValueError(
            f"tensor share {index}:{of} names no member of a group of {of}")
    for said, size in (("B/C groups", s.mamba_groups),
                       ("query heads", s.num_heads),
                       ("shared expert's columns", s.shared_expert_width)):
        if size % of:
            raise ValueError(
                f"tensor share {index}:{of}: {of} chips do not divide the "
                f"model's {size} {said}")
    if of % s.num_kv_heads and s.num_kv_heads % of:
        raise ValueError(
            f"tensor share {index}:{of}: {of} chips neither divide nor are "
            f"divided by the model's {s.num_kv_heads} key-value heads")
    return Held(
        s.mamba_heads // of, s.mamba_groups // of, s.num_heads // of,
        max(s.num_kv_heads // of, 1), s.shared_expert_width // of)


def share_leaves(p: dict, kind: str, s: NemotronHShape,
                 tensor_share: tuple[int, int]) -> dict:
    """The leaves of a WHOLE layer `p` of `kind` that member `index` of `of`
    holds: the columns of its heads (and of the B/C groups, key-value head
    and shared columns they go with) out of every projection, what every
    chip holds alike (norm, router, selection bias, latent projections,
    routed experts) as it is."""
    index, of = tensor_share
    h = held(s, tensor_share)

    def part(size: int, width: int, member: int = index) -> np.ndarray:
        """Columns member x size .. of a stretch of `size`-wide units."""
        return np.arange(member * size * width, (member + 1) * size * width)

    out = dict(p)
    if kind == MAMBA:
        inner = s.mamba_heads * s.mamba_head_dim
        bc = s.mamba_groups * s.mamba_state
        own = part(h.mamba_heads, s.mamba_head_dim)
        groups = part(h.mamba_groups, s.mamba_state)
        heads = part(h.mamba_heads, 1)
        channels = np.concatenate([own, inner + groups, inner + bc + groups])
        out.update(
            in_proj=p["in_proj"][:, np.concatenate(
                [own, inner + channels, 2 * inner + 2 * bc + heads])],
            conv_w=p["conv_w"][:, channels], conv_b=p["conv_b"][channels],
            dt_bias=p["dt_bias"][heads], a_log=p["a_log"][heads],
            d=p["d"][heads], gate_norm=p["gate_norm"][own],
            out_proj=p["out_proj"][own])
    elif kind == ATTENTION:
        q = part(h.num_heads, s.head_dim)
        kv = part(h.num_kv_heads, s.head_dim, index * s.num_kv_heads // of)
        out.update(wq=p["wq"][:, q], wk=p["wk"][:, kv], wv=p["wv"][:, kv],
                   wo=p["wo"][q])
    else:
        columns = part(h.shared_columns, 1)
        out.update(shared_up=p["shared_up"][:, columns],
                   shared_down=p["shared_down"][columns])
    return out


def _scaled_normal(scale: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) * scale

    return init


def attention(p: dict, u: jax.Array, s: NemotronHShape, block: int):
    """Grouped-query attention without a position term on the normed input
    u (B, T, hidden), over the heads the leaves hold."""
    b, t, _ = u.shape
    hd = s.head_dim
    with jax.named_scope("attn_proj"):
        q = (u @ p["wq"]).reshape(b, t, -1, hd)
        k = (u @ p["wk"]).reshape(b, t, -1, hd)
        v = (u @ p["wv"]).reshape(b, t, -1, hd)
    with jax.named_scope("attn_full"):
        a = blockwise_attention(q, k, v, block=block, scale=hd ** -0.5)
    with jax.named_scope("attn_proj"):
        return a.reshape(b, t, -1) @ p["wo"]


def relu2_mlp(v: jax.Array, w_up, w_down) -> jax.Array:
    mid = jnp.square(jax.nn.relu((v @ w_up).astype(jnp.float32)))
    return mid.astype(v.dtype) @ w_down


def latent_moe(p: dict, x: jax.Array, s: NemotronHShape, first: int):
    """The LatentMoE block on the normed input x (B, T, hidden): the held
    routed experts' part through the latent plus the shared expert's held
    columns. (y, tokens per held expert (E,) float32, dropped, swapped
    share, the latent's rms, the share of hidden units relu left on)."""
    b, t, d = x.shape
    u = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        idx, weights, swapped = sigmoid_bias_route(
            u, p["router"], p["router_bias"], s.experts_per_token,
            s.routed_scaling_factor, eps=1e-20)
    with jax.named_scope("moe_latent_down"):
        latent = u @ p["latent_down"]
        latent_rms = lax.stop_gradient(jnp.sqrt(jnp.mean(
            jnp.square(latent.astype(jnp.float32)))))
    with jax.named_scope("moe_experts"):
        r, sizes, dropped, active = held_relu2_experts(
            latent, idx, weights, p["w_up"], p["w_down"], first)
    with jax.named_scope("moe_latent_up"):
        y = r @ p["latent_up"]
    with jax.named_scope("moe_shared"):
        y = y + relu2_mlp(u, p["shared_up"], p["shared_down"])
    return (y.reshape(b, t, d), sizes.astype(jnp.float32),
            dropped.astype(jnp.float32), swapped, latent_rms, active)


def layer(p: dict, x: jax.Array, kind: str, s: NemotronHShape, h: Held,
          first: int, attn_block: int, scan_block: int):
    """One layer on the residual stream: (x', the mixer's counters: a Mamba
    layer's (state rms, most negative chunk log-decay), an `E` layer's
    (tokens per held expert, dropped, swapped, latent rms, active share), an
    attention layer's ())."""
    u = rms_norm(x, p["norm"], s.rms_norm_eps)
    if kind == MAMBA:
        y, *counters = mamba2_mixer(
            p, u, heads=h.mamba_heads, head_dim=s.mamba_head_dim,
            state=s.mamba_state, groups=h.mamba_groups, chunk=s.mamba_chunk,
            eps=s.rms_norm_eps, scan_block=scan_block)
    elif kind == ATTENTION:
        y, counters = attention(p, u, s, attn_block), []
    else:
        y, *counters = latent_moe(p, u, s, first)
    return x + y, tuple(counters)


def layer_leaves(kind: str, count: int, s: NemotronHShape, h: Held) -> tuple:
    """A layer's leaves at the share's sizes: `count` routed experts, `h`'s
    heads and columns."""
    d = s.hidden_size
    norm = ("norm", (d,), True)
    if kind == MAMBA:
        # `rescale_prenorm_residual`: over the root of the PUBLISHED depth
        return (norm, *mamba2_leaves(
            d, h.mamba_heads, s.mamba_head_dim, s.mamba_state, h.mamba_groups,
            _scaled_normal(0.02 / math.sqrt(s.num_layers))))
    if kind == ATTENTION:
        dq, dkv = h.num_heads * s.head_dim, h.num_kv_heads * s.head_dim
        return (norm, ("wq", (d, dq), False), ("wk", (d, dkv), False),
                ("wv", (d, dkv), False), ("wo", (dq, d), False))
    f, latent = s.expert_width, s.latent_size
    return (
        norm, ("router", (d, s.num_experts), False),
        ("router_bias", (s.num_experts,), _bias_init(s.selection_bias_init)),
        ("latent_down", (d, latent), False), ("latent_up", (latent, d), False),
        ("shared_up", (d, h.shared_columns), False),
        ("shared_down", (h.shared_columns, d), False),
        ("w_up", (count, latent, f), False),
        ("w_down", (count, f, latent), False))


class NemotronHLM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, the counters) without ever
    holding the logits of more than `loss_block` tokens: the path the train
    and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = NEMOTRON3S.vocab_size
    shape: NemotronHShape = NEMOTRON3S
    layers_held: Optional[tuple[int, int]] = None  # (first, count)
    experts_held: tuple[int, int] = (0, NEMOTRON3S.num_experts)
    tensor_share: tuple[int, int] = (0, 1)  # (index, of)
    attn_block: int = 512  # queries a block of the plain blocks
    loss_block: int = 2048
    scan_block: int = 8  # chunks of the scan recomputed together
    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        **SCOPES["mamba2_mixer"], "attn_proj": ATTENTION_LAYER,
        "attn_full": ATTENTION_LAYER, "moe_route": EXPERTS,
        "moe_latent_down": EXPERTS, "moe_experts": EXPERTS,
        "moe_latent_up": EXPERTS, "moe_shared": EXPERTS,
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (
        SSM_STATE_KEY, SSM_LOG_DECAY_KEY, MOE_TOKENS_KEY, MOE_DROPPED_KEY,
        MOE_SWAP_KEY, MOE_LATENT_KEY, MOE_ACTIVE_KEY)

    def layer_indices(self) -> tuple[int, ...]:
        """The published indices of the layers held, checked."""
        s = self.shape
        first, count = self.layers_held or (0, s.num_layers)
        if not (0 <= first and count >= 1 and first + count <= s.num_layers):
            raise ValueError(
                f"layers held {first}:{count} are not among the model's "
                f"{s.num_layers}")
        return tuple(range(first, first + count))

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's counters from one step's statistics as host
        arrays; `tokens` one device's tokens a (micro-)step. The scan's over
        the Mamba layers held (as `Granite4HLM.step_counters`), the routing
        counts over the `E` layers held (as `Mellum2LM.step_counters`) with
        the means of their other three; a stage without a kind has none of
        its counters."""
        out = {}
        if SSM_STATE_KEY in stats:
            out.update(
                ssm_state_rms=float(np.mean(stats[SSM_STATE_KEY])),
                ssm_log_decay_min=float(np.min(stats[SSM_LOG_DECAY_KEY])))
        if MOE_TOKENS_KEY in stats:
            out.update(
                routing_counters(
                    stats, tokens * self.shape.experts_per_token),
                moe_bias_swap_share=float(np.mean(stats[MOE_SWAP_KEY])),
                moe_latent_rms=float(np.mean(stats[MOE_LATENT_KEY])),
                moe_relu2_active=float(np.mean(stats[MOE_ACTIVE_KEY])))
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
        share = held(s, self.tensor_share)
        d = s.hidden_size
        indices = self.layer_indices()
        kinds = [s.pattern[i] for i in indices]
        embed = _Leaves(
            (("embedding", (self.vocab_size, d), False),), name="embed",
        )()["embedding"]
        layers = [
            _Leaves(layer_leaves(kind, count, s, share), name=f"layer_{i}")()
            for i, kind in zip(indices, kinds)
        ]
        out = _Leaves(
            (("norm", (d,), True), ("head", (d, self.vocab_size), False)),
            name="out",
        )()
        if self.is_initializing():
            # the declarations above and no forward pass (models/mellum.py)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        # equal layers share ONE cached trace under `jax.checkpoint`: what a
        # trace counted (scans, convolutions, attention cores, grouped
        # products, row permutations) is counted again where it is replayed
        one = counted(jax.checkpoint(
            layer, static_argnums=(2, 3, 4, 5, 6, 7)))
        h = embed[x]
        scans, routing = [], []
        for p, kind in zip(layers, kinds):
            h, counters = one(p, h, kind, s, share, first, self.attn_block,
                              self.scan_block)
            if kind == MAMBA:
                scans.append(counters)
            elif kind == MOE:
                routing.append(counters)
        h = rms_norm(h, out["norm"], s.rms_norm_eps)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, out["head"])
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), out["head"], targets.reshape(b * t),
            self.loss_block)
        stats = {}
        if scans:  # the Mamba layers held
            state_rms, low = zip(*scans)
            stats.update({SSM_STATE_KEY: jnp.stack(state_rms),
                          SSM_LOG_DECAY_KEY: jnp.stack(low)})
        if routing:  # the `E` layers held
            tokens, dropped, swapped, latent_rms, active = zip(*routing)
            stats.update({
                # (`E` layers held, experts held): tokens each took
                MOE_TOKENS_KEY: jnp.stack(tokens),
                MOE_DROPPED_KEY: jnp.sum(jnp.stack(dropped)),
                MOE_SWAP_KEY: jnp.stack(swapped),
                MOE_LATENT_KEY: jnp.stack(latent_rms),
                MOE_ACTIVE_KEY: jnp.stack(active),
            })
        return losses.reshape(b, t), stats
