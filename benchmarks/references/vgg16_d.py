"""Plain reference: VGG-16 (configuration D) forward pass and loss, float32.

Simonyan & Zisserman, "Very Deep Convolutional Networks for Large-Scale Image
Recognition" (arXiv:1409.1556), table 1 column D: thirteen 3x3 convolutions
with bias and ReLU, five 2x2 max-pools, fc 4096-4096-classes with dropout 0.5
after the first two. Straight `jax.numpy` / `lax.conv_general_dilated`, every
product at `highest` precision. It imports nothing of `mgwfbp_tpu`; it is handed
the program's initial parameters as a flat `{"a/b/c": array}` dict (random
draws from the seed, nothing the program computed).

Departure from the paper, the program's own: NHWC layout, so the flatten before
fc6 runs H, W, C. Dropout is part of the training loss the program reports, so
the reference applies the same masks: `dropout_key` below re-derives the key
each device uses from the seed, the way `train/step.py` and flax document it
(split, fold in step, device, micro-step, then flax's SHA-1 of the module
path). A program that derives its masks differently fails this check and needs
a benchmark PR; `tests/benchmark` holds the derivation against the zoo model.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
from jax import lax

CFG = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
       512, 512, 512, "M", 512, 512, 512, "M")
FC = (4096, 4096)
DROPOUT = 0.5


def _path_hash(*path) -> int:
    m = hashlib.sha1()
    for part in path:
        m.update(
            part.encode("utf-8") if isinstance(part, str)
            else part.to_bytes((part.bit_length() + 7) // 8, "big")
        )
    return int.from_bytes(m.digest()[:4], "big")


def dropout_key(seed: int, step: int, shard: int, layer: int) -> jax.Array:
    """Key of dropout layer `layer` (0, 1) on data-parallel device `shard`
    in training step `step` (0-based), micro-step 0."""
    key = jax.random.split(jax.random.PRNGKey(seed))[1]
    for fold in (step, shard, 0):
        key = jax.random.fold_in(key, fold)
    return jax.random.fold_in(
        key, jnp.uint32(_path_hash(f"Dropout_{layer}", 1))
    )


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _conv_relu(p, x, index, dtype):
    x = lax.conv_general_dilated(
        _stored(x, dtype), _stored(p[f"Conv_{index}/kernel"], dtype),
        (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    ) + p[f"Conv_{index}/bias"]
    return jnp.maximum(x, 0.0)


def logits(params: dict, x: jax.Array, keys=None, dtype=None) -> jax.Array:
    """x: (N, H, W, 3) float32. `keys`: two dropout keys, or None for the
    deterministic (evaluation) forward. Returns (N, classes) float32.
    `dtype`: the lower-precision control, every convolution's and dense
    layer's operands rounded to it first (products still float32)."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = jnp.asarray(x, jnp.float32)
    conv = 0
    for item in CFG:
        if item == "M":
            x = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
            continue
        x = _conv_relu(p, x, conv, dtype)
        conv += 1
    x = x.reshape(x.shape[0], -1)
    for i in range(len(FC) + 1):
        x = jnp.dot(
            _stored(x, dtype), _stored(p[f"Dense_{i}/kernel"], dtype),
            precision=lax.Precision.HIGHEST,
        ) + p[f"Dense_{i}/bias"]
        if i < len(FC):
            x = jnp.maximum(x, 0.0)
            if keys is not None:
                keep = jax.random.bernoulli(keys[i], 1.0 - DROPOUT, x.shape)
                x = jnp.where(keep, x / (1.0 - DROPOUT), 0.0)
    return x


def cross_entropy(lg: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean over rows of -log softmax(logits)[label], float32."""
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def first_step(
    params: dict, x, y, *, seed: int, shards: int, dtype=None,
) -> dict:
    """What training step 1 on batch (x, y) at `params` computes: `loss`, and
    `grad_norm`, the L2 norm over all parameters of the loss's gradient (no
    weight decay: the gradient as the optimizer gets it). Both are the means
    over the `shards` devices, each over its rows with its own masks, one
    device's rows at a time so that the float32 activations of the whole
    batch never sit on the device together. `dtype` (a name, e.g.
    "float8_e4m3fn") computes the control instead."""
    rows = x.shape[0] // shards
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(shards):
            keys = jnp.stack([dropout_key(seed, 0, i, j) for j in (0, 1)])
            sl = slice(i * rows, (i + 1) * rows)
            part, g = _loss_and_grad(params, x[sl], y[sl], keys, dtype)
            loss += float(part) / shards
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        sumsq = sum(
            float(jnp.sum(jnp.square(g / shards))) for g in grads.values())
    return {"loss": loss, "grad_norm": sumsq ** 0.5}


@functools.partial(jax.jit, static_argnums=4)
def _loss_and_grad(params, x, y, keys, dtype):
    dtype = None if dtype is None else jnp.dtype(dtype)
    return jax.value_and_grad(
        lambda p: cross_entropy(logits(p, x, keys, dtype), y))(params)


def forward_macs(image_hw=(224, 224), num_classes: int = 1000) -> int:
    """Multiply-accumulates of one image's forward pass: convolutions and the
    three fully connected layers (15.47e9 at 224x224, 1000 classes)."""
    h, w = image_hw
    cin, macs = 3, 0
    for item in CFG:
        if item == "M":
            h, w = h // 2, w // 2
        else:
            macs += h * w * 9 * cin * item
            cin = item
    width = h * w * cin
    for out in (*FC, num_classes):
        macs += width * out
        width = out
    return macs
