"""ops/blockattn.py. Its `scale`: the default is 1 / sqrt(D), and a model's
own multiplier (granite-4.0-h: 1 / 64 at head size 64, a group of 4 query
heads a key head) replaces it, value and gradients against dense attention.
Its fused kernel (the installed splash_attention, here in `interpret` mode on
the CPU) against its plain blocks at the call shapes of both language cells;
and the test of platform and shape that chooses between the two. (The count
of both that a step program leaves on the telemetry is held where a Trainer
runs each preset anyway: tests/test_<family>_trainer.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from mgwfbp_tpu.ops import blockattn, programs
from mgwfbp_tpu.ops.blockattn import blockwise_attention


def dense_attention(q, k, v, scale):
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(q.shape[1])[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("scale,want_scale", [
    (None, 0.125), (1.0 / 64, 1.0 / 64)], ids=["default", "published-1/64"])
def test_scale_value_and_gradient_against_dense(scale, want_scale):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    t = 40  # no multiple of the block
    q = 4.0 * jax.random.normal(keys[0], (2, t, 8, 64))
    k = jax.random.normal(keys[1], (2, t, 2, 64))
    v = jax.random.normal(keys[2], (2, t, 2, 64))
    w = jax.random.normal(keys[3], (2, t, 8, 64))

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))

    got, got_grads = through(lambda q, k, v: blockwise_attention(
        q, k, v, block=16, scale=scale))(q, k, v)
    want, want_grads = through(
        lambda q, k, v: dense_attention(q, k, v, want_scale))(q, k, v)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert float(jnp.linalg.norm(g - wg) / jnp.linalg.norm(wg)) < 1e-5
    # the two scales are different functions of these inputs
    other, _ = through(lambda q, k, v: dense_attention(
        q, k, v, 0.125 if scale else 1.0 / 64))(q, k, v)
    assert abs(float(other) - float(want)) > 1e-3 * abs(float(want))


def test_head_size_256_value_and_gradients_against_the_plain_softmax():
    """Qwen3-Next's core: 16 query heads over 2 key heads of 256, scores over
    16. The entry point (the plain blocks on this CPU) and the fused kernel in
    `interpret` mode at the tiles' shape, each against the softmax written
    out, float32."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    t = 256
    q = 4.0 * jax.random.normal(keys[0], (1, t, 16, 256))
    k = jax.random.normal(keys[1], (1, t, 2, 256))
    v = jax.random.normal(keys[2], (1, t, 2, 256))
    w = jax.random.normal(keys[3], (1, t, 16, 256))

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))

    want, want_grads = through(
        lambda q, k, v: dense_attention(q, k, v, 1.0 / 16))(q, k, v)
    tiles = blockattn._kernel_tiles(t, 256, None)
    assert tiles is not None and tiles.block_q == t
    for fn in (lambda q, k, v: blockwise_attention(q, k, v, block=96),
               lambda q, k, v: blockattn._fused(
                   q, k, v, None, 1.0 / 16, tiles, interpret=True)):
        got, got_grads = through(fn)(q, k, v)
        assert abs(float(got) - float(want)) < 2e-5 * abs(float(want))
        for g, wg in zip(got_grads, want_grads):
            assert float(jnp.linalg.norm(g - wg) / jnp.linalg.norm(wg)) < 1e-5


def tiles_of(size, **more):
    return blockattn._splash().BlockSizes(
        block_q=size, block_kv=size, block_kv_compute=size, block_q_dkv=size,
        block_kv_dkv=size, block_kv_dkv_compute=size, **(more or dict(
            block_q_dq=size, block_kv_dq=size)))


@pytest.mark.parametrize("b,h,hkv,d,window,scale,tiles", [
    (1, 8, 2, 64, None, 1.0 / 64, tiles_of(128)),
    (1, 8, 1, 128, None, None, tiles_of(128)),
    (1, 8, 1, 128, 128, None, tiles_of(128)),
    (2, 4, 2, 128, None, None, tiles_of(256)),
    (2, 4, 2, 64, 128, None, tiles_of(128, use_fused_bwd_kernel=True)),
    (1, 12, 2, 128, None, 1.0, tiles_of(256, use_fused_bwd_kernel=True)),
    (1, 8, 1, 128, 512, None, tiles_of(512)),
    (1, 6, 1, 64, 256, None, tiles_of(256)),
    (1, 8, 1, 256, None, 1.0 / 16, tiles_of(256, use_fused_bwd_kernel=True)),
], ids=["d64-full-scale-1/64-groups-of-4", "d128-full", "d128-window-4-long",
        "batch-2", "batch-2-window-fused-backward",
        "laguna-full-groups-of-6-scale-in-q", "laguna-window-512-one-tile",
        "groups-of-6-window-a-tile", "qwen3next-d256-groups-of-8"])
def test_fused_kernel_value_and_gradients_against_the_plain_blocks(
        b, h, hkv, d, window, scale, tiles):
    """Float32, so the two differ by rounding order alone; T 512 is four
    windows and four tiles long. The batch rides in the kernel's heads.
    Laguna-XS.2's shapes: a group of SIX query heads a key head (the first
    that is no power of two) and a window of 512 under the 512 tile the
    chip's sweep kept for it (the band is one tile wide: at T 512 every tile
    the kernel visits is on the diagonal)."""
    t = 512
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = 2.0 * jax.random.normal(keys[0], (b, t, h, d))
    k = jax.random.normal(keys[1], (b, t, hkv, d))
    v = jax.random.normal(keys[2], (b, t, hkv, d))
    w = jax.random.normal(keys[3], (b, t, h, d))
    scale = d ** -0.5 if scale is None else scale

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)) * w),
            argnums=(0, 1, 2)))

    got, got_grads = through(lambda q, k, v: blockattn._fused(
        q, k, v, window, scale, tiles, interpret=True))(q, k, v)
    want, want_grads = through(lambda q, k, v: blockattn._blocks(
        q, k, v, window, 128, scale))(q, k, v)
    assert abs(float(got) - float(want)) < 2e-5 * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert float(jnp.linalg.norm(g - wg) / jnp.linalg.norm(wg)) < 5e-6


def test_the_kernel_object_is_built_once_a_shape_and_outside_the_trace():
    """Its mask information is host work: the second trace of a shape finds
    the first one's object, whose arrays are no tracers of the first."""
    tiles = tiles_of(128)
    blockattn._splash_kernel.cache_clear()
    q = jnp.ones((1, 256, 2, 64))
    for _ in range(2):
        jax.make_jaxpr(lambda q: blockattn._fused(
            q, q[:, :, :1], q[:, :, :1], None, 1.0, tiles, interpret=True))(q)
    info = blockattn._splash_kernel.cache_info()
    assert (info.misses, info.hits) == (1, 1)
    kernel = blockattn._splash_kernel(256, 2, None, tiles, True)
    assert all(isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(kernel))


@pytest.mark.parametrize("d,dv,way", [
    (192, 128, "blocks"), (192, 128, "kernel"), (24, 16, "blocks"),
    (128, 128, "blocks"), (128, 128, "kernel"),
], ids=["latent-192-128-blocks", "latent-192-128-kernel", "tiny-24-16-blocks",
        "equal-128-blocks", "equal-128-kernel"])
def test_a_value_width_other_than_the_score_width_against_the_plain_softmax(
        d, dv, way):
    """Latent attention scores over 128 + 64 rotary and sums values of 128:
    the entry point's plain blocks (the last block shorter) and the fused
    kernel in `interpret` mode, value and the three gradients against the
    softmax written out, float32; the output has the VALUES' width. At equal
    widths the same calls are what they were."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    t = 256 if way == "kernel" else 200
    q = 2.0 * jax.random.normal(keys[0], (1, t, 4, d))
    k = jax.random.normal(keys[1], (1, t, 4, d))
    v = jax.random.normal(keys[2], (1, t, 4, dv))
    w = jax.random.normal(keys[3], (1, t, 4, dv))
    scale = 0.1446796 if d == 192 else d ** -0.5

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)) * w),
            argnums=(0, 1, 2)))

    if way == "kernel":
        tiles = blockattn._kernel_tiles(t, d, None, dv)
        assert tiles is not None and tiles.block_q == t

        def fn(q, k, v):
            return blockattn._fused(q, k, v, None, scale, tiles,
                                    interpret=True)
    else:
        def fn(q, k, v):
            return blockwise_attention(q, k, v, block=96, scale=scale)
    assert jax.eval_shape(fn, q, k, v).shape == (1, t, 4, dv)
    got, got_grads = through(fn)(q, k, v)
    want, want_grads = through(
        lambda q, k, v: dense_attention(q, k, v, scale))(q, k, v)
    assert abs(float(got) - float(want)) < 2e-5 * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert g.shape == wg.shape
        assert float(jnp.linalg.norm(g - wg) / jnp.linalg.norm(wg)) < 1e-5


def test_at_equal_widths_the_traced_program_is_the_one_width_programs():
    """The plain blocks at D = Dv = 64 trace to the text they traced to when
    the core took one head size: no pad, no slice of the values, and the same
    equations as a copy of that one-width block written out here."""
    q = jnp.ones((1, 160, 4, 64))
    kv = jnp.ones((1, 160, 2, 64))

    def one_width_block(q, k, v, q_start, k_start, scale):
        b, hkv, g, tq, d = q.shape
        s = jnp.einsum(
            "bhmd,bhkd->bhmk", q.reshape(b, hkv, g * tq, d), k,
            preferred_element_type=jnp.float32) * scale
        qi = q_start + jnp.arange(tq)[:, None]
        kj = k_start + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(jnp.tile(kj <= qi, (g, 1)), s, blockattn._NEG_INF)
        m = jax.lax.optimization_barrier(
            jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
        e = jnp.exp(s - m)
        out = jnp.einsum(
            "bhmk,bhkd->bhmd", e.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ) / jnp.sum(e, axis=-1, keepdims=True)
        return out.astype(v.dtype).reshape(b, hkv, g, tq, d)

    got = str(jax.make_jaxpr(lambda q, k, v: blockattn._one_block(
        q, k, v, 64, 0, None, 0.125))(
            jnp.ones((1, 2, 2, 96, 64)), kv.transpose(0, 2, 1, 3),
            kv.transpose(0, 2, 1, 3)))
    want = str(jax.make_jaxpr(lambda q, k, v: one_width_block(
        q, k, v, 64, 0, 0.125))(
            jnp.ones((1, 2, 2, 96, 64)), kv.transpose(0, 2, 1, 3),
            kv.transpose(0, 2, 1, 3)))
    assert got == want
    whole = str(jax.make_jaxpr(
        lambda q, k, v: blockwise_attention(q, k, v, block=96))(q, kv, kv))
    assert "pad" not in whole and "f32[1,160,4,64]" in whole


@pytest.mark.parametrize("t,d,window,fits", [
    (8192, 128, None, True), (8192, 64, None, True), (8192, 128, 1024, True),
    (8192, 128, 512, True),
    (512, 64, None, True), (8192 + 512, 128, None, False),
    (640, 64, None, False), (40, 64, None, False), (8192, 96, None, False),
    (8192, 256, None, True), (8192, 512, None, False),
    (8192, (192, 128), None, True), (8192, (192, 96), None, False),
    (8192, (320, 128), None, False),
], ids=["mellum2-full", "granite", "mellum2-window", "laguna-window-512",
        "short", "tiles-overhang",
        "five-lane-tiles", "no-lane-tile", "head-96", "qwen3next-head-256",
        "head-512", "xing4-scores-192-values-128", "values-96",
        "scores-320"])
def test_shape_test_of_the_kernel(t, d, window, fits):
    d, dv = d if isinstance(d, tuple) else (d, None)
    tiles = blockattn._kernel_tiles(t, d, window, dv)
    assert (tiles is not None) == fits
    if fits:
        sizes = [size for name, size in dataclasses.asdict(tiles).items()
                 if name.startswith("block_") and size is not None]
        assert all(t % size == 0 and size <= t for size in sizes)
        # a window layer keeps the dq kernel of its own
        assert (tiles.block_q_dq is None) == (window is None)
        assert tiles.use_fused_bwd_kernel == (window is None)
        # the tiles the chip's sweeps kept (PR 31; PR 32 for a window of 512)
        assert tiles.block_q == tiles.block_kv == min(
            t, 1024 if window is None else 512)
        assert tiles.block_kv_compute == min(t, 512)


def traced_ways(fn, *args):
    before = programs.LOWERED.copy()
    # a fresh function each time: a cached trace calls nothing and counts none
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    return (programs.lowered_since(before)["attention"],
            "pallas_call" in str(jaxpr))


def test_falls_back_to_the_blocks_off_the_tpu_and_on_a_shape_that_misfits(
        monkeypatch):
    """The choice is read off the platform traced for and the shape: on this
    CPU every call takes the blocks; traced as for a TPU, a shape the tiles
    divide takes the kernel and one they do not divide the blocks."""
    fits, misfits = jnp.ones((1, 256, 2, 64)), jnp.ones((1, 200, 2, 64))

    def attend(q):
        return blockwise_attention(q, q[:, :, :1], q[:, :, :1])

    assert not programs.traced_for_tpu()
    assert traced_ways(attend, fits) == ({"kernel": 0, "blocks": 1}, False)
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    assert traced_ways(attend, fits) == ({"kernel": 1, "blocks": 0}, True)
    assert traced_ways(attend, misfits) == ({"kernel": 0, "blocks": 1}, False)


@pytest.mark.parametrize("model,want", [
    ("mellum2", {"kernel": 4, "blocks": 0}),
    ("granite4h", {"kernel": 1, "blocks": 0}),
    ("laguna_xs2", {"kernel": 5, "blocks": 0}),
    ("xing4", {"kernel": 3, "blocks": 0}),
])
def test_traced_as_for_a_tpu_the_models_cores_go_where_the_shape_test_sends_them(
        monkeypatch, model, want):
    """The tiny models at a head size the kernel has tiles for, T 256: the
    count a step program would record on the chip."""
    from mgwfbp_tpu.models import granite, laguna, mellum, xing4

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    if model == "laguna_xs2":
        # 6 | 8 query heads over 2 key heads: every one of the five layers'
        # cores is counted, the three window layers alike among them
        module = laguna.LagunaLM(
            vocab_size=256, experts_held=(0, 2), shape=dataclasses.replace(
                laguna.LAGUNA_XS2_TINY, head_dim=64, sliding_window=128))
    elif model == "xing4":
        # scores over 128 + 64, values of 128; three layers' cores, each
        # sub-layer's cached trace counted again where it is replayed
        module = xing4.Xing4LM(
            vocab_size=256, layers_held=(1, 3), experts_held=(0, 2),
            shape=dataclasses.replace(
                xing4.XING4_TINY, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128))
    elif model == "mellum2":
        module = mellum.Mellum2LM(
            vocab_size=256, experts_held=(0, 2), shape=dataclasses.replace(
                mellum.MELLUM2_TINY, head_dim=64, sliding_window=128))
    else:
        module = granite.Granite4HLM(
            vocab_size=256, layers_held=3, shape=dataclasses.replace(
                granite.GRANITE4H_TINY, head_dim=64))
    x = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    ways, kernel = traced_ways(
        lambda p: jax.grad(lambda p: jnp.sum(
            module.apply(p, x, targets=x)[0]))(p), params)
    assert ways == want and kernel
