"""Plain reference: ResNet-50 v1.5 forward pass and loss, float32.

He et al., "Deep Residual Learning for Image Recognition" (arXiv:1512.03385),
bottleneck variant with the stride on the 3x3 convolution ("v1.5", as
torchvision's resnet50). Straight `jax.numpy` / `lax.conv_general_dilated`,
every product at `highest` precision (on a TPU a float32 convolution otherwise
runs in one bfloat16 pass). It imports nothing of `mgwfbp_tpu`; it is handed the
program's initial parameters as a flat `{"a/b/c": array}` dict, which are random
draws from the seed and nothing the program computed.

Departures from the paper, both the program's own: NHWC layout, and `SAME`
padding (the stride-2 convolutions and the max-pool pad 2+3 / 0+1 where
torchvision pads symmetrically). BatchNorm is in training mode: statistics of
the batch at hand over N, H, W, biased variance, eps 1e-5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))
EXPANSION = 4
BN_EPS = 1e-5


def _stored(a, dtype):
    """`a` as it reads back from storage in `dtype` (None: float32 as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _conv(x, kernel, stride, dtype=None):
    return lax.conv_general_dilated(
        _stored(x, dtype), _stored(kernel, dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )


def _conv_bn(p, prefix, x, stride=1, relu=True, dtype=None):
    x = _conv(x, p[f"{prefix}/Conv_0/kernel"], stride, dtype)
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    x = (x - mean) * lax.rsqrt(var + BN_EPS)
    x = x * p[f"{prefix}/BatchNorm_0/scale"] + p[f"{prefix}/BatchNorm_0/bias"]
    return jnp.maximum(x, 0.0) if relu else x


def _block(p, x, name, stride, project, dtype):
    y = _conv_bn(p, f"{name}/ConvBN_0", x, dtype=dtype)
    y = _conv_bn(p, f"{name}/ConvBN_1", y, stride=stride, dtype=dtype)
    y = _conv_bn(p, f"{name}/ConvBN_2", y, relu=False, dtype=dtype)
    if project:  # the only blocks whose input and output shapes differ
        x = _conv_bn(p, f"{name}/shortcut", x, stride=stride, relu=False,
                     dtype=dtype)
    return jnp.maximum(y + x, 0.0)


# a block's activations are recomputed in the backward pass, so that the
# float32 gradient of a 128-image batch fits beside nothing else on a chip
_block_remat = jax.checkpoint(_block, static_argnums=(2, 3, 4, 5))


def logits(params: dict, x: jax.Array, dtype=None) -> jax.Array:
    """x: (N, H, W, 3) float32, normalized. Returns (N, classes) float32.
    `dtype`: the lower-precision control, every convolution's and the
    classifier's operands rounded to it first (products still float32)."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = _conv_bn(
        p, "ConvBN_0", jnp.asarray(x, jnp.float32), stride=2, dtype=dtype)
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    block = 0
    for stage, (width, nblocks) in enumerate(STAGES):
        for i in range(nblocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            x = _block_remat(
                p, x, f"Bottleneck_{block}", stride, i == 0, dtype)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(
        _stored(x, dtype), _stored(p["fc/kernel"], dtype),
        precision=lax.Precision.HIGHEST,
    ) + p["fc/bias"]


def cross_entropy(lg: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean over rows of -log softmax(logits)[label], float32."""
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def first_step(
    params: dict, x, y, *, seed: int, shards: int, dtype=None,
) -> dict:
    """What training step 1 on batch (x, y) at `params` computes: `loss`, and
    `grad_norm`, the L2 norm over all parameters of the loss's gradient (no
    weight decay: the gradient as the optimizer gets it).

    `shards` is the number of data-parallel devices the program split the
    batch over: BatchNorm there sees each device's rows only, and the step's
    loss and gradient are the means of the devices'. `seed` is unused (no
    dropout). `dtype` (a name, e.g. "float8_e4m3fn") computes the control."""
    del seed
    rows = x.shape[0] // shards
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(shards):
            sl = slice(i * rows, (i + 1) * rows)
            part, g = _loss_and_grad(params, x[sl], y[sl], dtype)
            loss += float(part) / shards
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        sumsq = sum(
            float(jnp.sum(jnp.square(g / shards))) for g in grads.values())
    return {"loss": loss, "grad_norm": sumsq ** 0.5}


@functools.partial(jax.jit, static_argnums=3)
def _loss_and_grad(params, x, y, dtype):
    dtype = None if dtype is None else jnp.dtype(dtype)
    return jax.value_and_grad(
        lambda p: cross_entropy(logits(p, x, dtype), y))(params)


def forward_macs(image_hw=(224, 224), num_classes: int = 1000) -> int:
    """Multiply-accumulates of one image's forward pass: convolutions and the
    classifier, from the layer shapes alone (4.09e9 at 224x224, 1000 classes).
    BatchNorm, ReLU, pooling and the residual adds are not counted."""
    def out(n, stride):  # SAME padding
        return -(-n // stride)

    h, w = image_hw
    h, w = out(h, 2), out(w, 2)
    macs = h * w * 7 * 7 * 3 * 64
    h, w = out(h, 2), out(w, 2)  # max-pool
    cin = 64
    for stage, (width, nblocks) in enumerate(STAGES):
        for i in range(nblocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            cout = width * EXPANSION
            macs += h * w * cin * width  # 1x1
            ho, wo = out(h, stride), out(w, stride)
            macs += ho * wo * 9 * width * width  # 3x3, strided
            macs += ho * wo * width * cout  # 1x1
            if i == 0:
                macs += ho * wo * cin * cout  # projection shortcut
            h, w, cin = ho, wo, cout
    return macs + cin * num_classes
