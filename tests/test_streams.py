"""ops/streams.py: the passes over n residual streams. The four kernels run
here under Pallas `interpret=True` (off the chip there is no other way down
them) against the plain form, values and every pull-back; the rule sends
what the kernels do not take down the plain form; and on the CPU the tiny
Xing4.0 preset lowers to the text it lowered to when these passes were
models/xing4.py's own (the plain form moved, it was not rewritten). That the
kernels compile for a v5e is asked in tests/test_tpu_compile_streams.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mgwfbp_tpu.models import xing4
from mgwfbp_tpu.ops import programs, streams

N, B, T, ROWS = 4, 1, 256, 128  # two blocks of tokens
M = N * N + 2 * N
EPS = 1e-6
F32, BF16 = jnp.float32, jnp.bfloat16
SHAPES = [(256, F32), (384, F32), (256, BF16), (384, BF16)]
IDS = ["c256-float32", "c384-float32", "c256-bfloat16", "c384-bfloat16"]


def drawn(c, dtype, seed=0):
    """Streams, a sub-layer's mapping leaves as `models/xing4.py` draws them
    (b off its zeros, so that every gate moves) and a mixer's weight."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (N, B, T, c), F32).astype(dtype)
    phi = xing4._phi_init(keys[1], (N * c, M)).astype(dtype)
    b = (xing4._b_init(None, (M,))
         + 0.1 * jax.random.normal(keys[2], (M,))).astype(dtype)
    alpha = jnp.asarray([0.5, 0.4, 0.6]).astype(dtype)
    w = (jax.random.normal(keys[3], (c, c), F32) * c ** -0.5).astype(dtype)
    return x, phi, b, alpha, w


def close(got, want, dtype, what, units=1):
    """Float32: to float32's rounding of the largest value. bfloat16: to one
    unit (2^-7 of the larger of the two) on rounded outputs, beside that
    float32 rounding where a token's terms cancel; to one unit of the
    largest value on sums of rounded terms (`units` of them where two
    sub-layers' roundings add up)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    top = np.abs(want).max()
    if dtype == F32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * top,
                                   err_msg=what)
    elif what in ("u", "x'"):
        unit = 2.0 ** -7 * np.maximum(np.abs(got), np.abs(want))
        assert (np.abs(got - want) <= unit + 1e-5 * top).all(), what
    else:
        np.testing.assert_allclose(
            got, want, rtol=0, atol=units * 2.0 ** -7 * top, err_msg=what)


@pytest.mark.parametrize("c,dtype", SHAPES, ids=IDS)
def test_the_mapping_and_the_read_against_the_plain_form(c, dtype):
    """`streams_map_read` and its pull-back: u, a and the streams handed on,
    and d x, d phi, d alpha, d b through a scalar loss over all three (the
    loss weighs every column of a, and the streams handed on as the
    write-back's d x reaches them)."""
    x, phi, b, alpha, _ = drawn(c, dtype)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    wu = jax.random.normal(keys[0], (B, T, c), F32)
    wa = jax.random.normal(keys[1], (M, B, T), F32)
    wx = jax.random.normal(keys[2], x.shape, F32)

    def plain(phi, b, alpha, x):
        a = streams.plain_maps(phi, x, EPS)
        pre = jax.nn.sigmoid(
            alpha.astype(F32)[0] * a[:N] + b.astype(F32)[:N, None, None])
        return a, *streams.read_streams(x, pre, None)

    def kernels(phi, b, alpha, x):
        a, read = streams._maps_by_kernels(
            phi, b, alpha, x, EPS, ROWS, interpret=True)
        return a, *streams.read_streams(x, None, read)

    def loss(fn):
        def of(*args):
            a, u, handed = fn(*args)
            return (jnp.sum(u.astype(F32) * wu) + jnp.sum(a * wa)
                    + jnp.sum(handed.astype(F32) * wx)), (a, u, handed)
        return jax.jit(jax.value_and_grad(of, (0, 1, 2, 3), has_aux=True))

    (_, (a0, u0, x0)), want = loss(plain)(phi, b, alpha, x)
    (_, (a1, u1, x1)), got = loss(kernels)(phi, b, alpha, x)
    close(u1, u0, dtype, "u")
    close(a1, a0, F32, "a")
    np.testing.assert_array_equal(np.asarray(x1, np.float32),
                                  np.asarray(x0, np.float32))
    for what, g, w in zip(("d phi", "d b", "d alpha", "d x"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        # d b and d alpha are sums over every token: autodiff of the plain
        # form rounds to bfloat16 on the way, the kernel sums in float32
        close(g, w, dtype, what, units=4 if what in ("d b", "d alpha") else 1)
    # b and alpha past the read's gates move nothing here
    assert not np.asarray(got[1], np.float32)[N:].any()
    assert not np.asarray(got[2], np.float32)[1:].any()


@pytest.mark.parametrize("c,dtype", SHAPES, ids=IDS)
def test_the_write_back_against_the_plain_form(c, dtype):
    """`streams_write` and its pull-back: x', and d x, d y, d H_res, d H_post
    through a scalar loss."""
    x, *_ = drawn(c, dtype)
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    y = jax.random.normal(keys[0], (B, T, c), F32).astype(dtype)
    res = jax.nn.softmax(jax.random.normal(keys[1], (N, N, B, T), F32), 1)
    post = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[2], (N, B, T), F32))
    wo = jax.random.normal(keys[3], x.shape, F32)

    def loss(fn):
        def of(*args):
            out = fn(*args)
            return jnp.sum(out.astype(F32) * wo), out
        return jax.jit(jax.value_and_grad(of, (0, 1, 2, 3), has_aux=True))

    (_, out0), want = loss(streams.plain_write)(x, res, post, y)
    (_, out1), got = loss(lambda *v: streams._write_by_kernels(
        *v, ROWS, interpret=True))(x, res, post, y)
    close(out1, out0, dtype, "x'")
    for what, g, w in zip(("d x", "d H_res", "d H_post", "d y"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        close(g, w, dtype, what)


def sub_layer(by_kernels: bool):
    """A sub-layer as models/xing4.py has it, under its `jax.checkpoint`:
    the mapping, the read, a mixer, the write-back."""
    s = xing4.XING4_TINY

    @jax.checkpoint
    def layer(phi, b, alpha, x, w):
        if by_kernels:
            a, read = streams._maps_by_kernels(
                phi, b, alpha, x, s.hc_eps, ROWS, interpret=True)
        else:
            a, read = streams.plain_maps(phi, x, s.hc_eps), None
        b32 = b.astype(F32)[:, None, None]
        al = alpha.astype(F32)
        pre = jax.nn.sigmoid(al[0] * a[:N] + b32[:N])
        post = 2.0 * jax.nn.sigmoid(al[1] * a[N:2 * N] + b32[N:2 * N])
        res = xing4.sinkhorn(
            jnp.clip(al[2] * a[2 * N:] + b32[2 * N:], -30.0, 30.0).reshape(
                N, N, *a.shape[1:]), s.hc_sinkhorn_iters, s.hc_eps)
        u, x = streams.read_streams(x, pre, read)
        y = jnp.tanh(u @ w)
        if by_kernels:
            return streams._write_by_kernels(x, res, post, y, ROWS, True)
        return streams.plain_write(x, res, post, y)

    return layer


@pytest.mark.parametrize("c,dtype", [(256, F32), (384, BF16)],
                         ids=["c256-float32", "c384-bfloat16"])
def test_two_sub_layers_under_their_checkpoints(c, dtype):
    """Two sub-layers in a row, each under a `jax.checkpoint` with the
    Sinkhorn iterations between the kernels: the loss and every leaf's
    gradient are the plain form's, the write-back's d x having reached the
    mapping's backward kernel through the streams handed on."""
    x, phi, b, alpha, w = drawn(c, dtype)
    target = jax.random.normal(jax.random.PRNGKey(3), x.shape, F32)

    def loss(by_kernels):
        layer = sub_layer(by_kernels)

        def of(phi, b, alpha, x, w):
            out = layer(phi, b, alpha, layer(phi, b, alpha, x, w), w)
            return jnp.sum(out.astype(F32) * target) / out.size
        return jax.jit(jax.value_and_grad(of, (0, 1, 2, 3, 4)))

    want_loss, want = loss(False)(phi, b, alpha, x, w)
    got_loss, got = loss(True)(phi, b, alpha, x, w)
    np.testing.assert_allclose(
        got_loss, want_loss, rtol=1e-5 if dtype == F32 else 2e-2)
    for what, g, v in zip(
            ("d phi", "d b", "d alpha", "d x", "d w"), got, want):
        close(g, v, dtype, what, units=2)


REFUSED = [
    ((4, 1, 512, 200), BF16, BF16),  # C no whole number of lane tiles
    ((4, 1, 500, 256), BF16, BF16),  # T no multiple of a block
    ((2, 1, 1, 1), F32, F32),  # the reference's test of the write-back
    ((9, 1, 512, 128), BF16, BF16),  # more streams than the kernels take
    ((4, 1, 512, 256), jnp.float16, jnp.float16),
    ((4, 1, 512, 256), BF16, F32),  # phi and y not of x's dtype
    ((4, 1, 512, 32768), F32, F32),  # no block of it fits VMEM
]


@pytest.mark.parametrize("shape,dtype,other", REFUSED, ids=[
    "c-200", "t-500", "n-2-at-c-1", "n-9", "float16", "mixed-dtypes",
    "c-32768-float32"])
def test_what_the_rule_refuses_goes_down_the_plain_form(
        monkeypatch, shape, dtype, other):
    """Traced as for a TPU, both entry points note `plain` and call no
    kernel for a shape or a dtype outside the rule."""
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    n, bsz, t, c = shape
    m = n * n + 2 * n
    x = jax.ShapeDtypeStruct(shape, dtype)
    before = programs.LOWERED.copy()
    jaxpr = jax.make_jaxpr(
        lambda phi, b, alpha, x: streams.map_streams(phi, b, alpha, x, EPS)[0]
    )(jax.ShapeDtypeStruct((n * c, m), other),
      jax.ShapeDtypeStruct((m,), other), jax.ShapeDtypeStruct((3,), other),
      x)
    assert programs.lowered_since(before)["streams"] == {
        "kernel": 0, "plain": 1, "programs": 0}
    assert "pallas_call" not in str(jaxpr)
    jaxpr = jax.make_jaxpr(lambda *v: streams.write_streams(*v))(
        x, jax.ShapeDtypeStruct((n, n, bsz, t), F32),
        jax.ShapeDtypeStruct((n, bsz, t), F32),
        jax.ShapeDtypeStruct((bsz, t, c), other))
    assert programs.lowered_since(before)["streams"] == {
        "kernel": 0, "plain": 2, "programs": 0}
    assert "pallas_call" not in str(jaxpr)


@pytest.mark.parametrize("t,rows", [(8192, 256), (384, 128), (192, None)])
def test_the_block_is_the_largest_whole_lane_tiles_that_divide_t(t, rows):
    x = jax.ShapeDtypeStruct((4, 1, t, 3584), BF16)
    assert streams._kernel_rows(x) == rows


def test_a_sub_layer_traced_for_a_tpu_notes_two_passes_and_four_programs(
        monkeypatch):
    """Both entry points down the kernels: 2 + 0 a sub-layer and, however
    many sub-layers of one shape, four kernel programs; `read_streams` is
    the first pass's and notes nothing."""
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    s = xing4.XING4_TINY

    def two(phi, b, alpha, x, y):
        for _ in range(2):
            _, _, res, read = xing4.stream_maps(phi, b, alpha, x, s)
            _, x = streams.read_streams(x, None, read)
            x = xing4.write_streams(x, res, res[0], y)
        return x

    before = programs.LOWERED.copy()
    jaxpr = jax.make_jaxpr(two)(
        jax.ShapeDtypeStruct((N * 128, M), BF16),
        jax.ShapeDtypeStruct((M,), BF16), jax.ShapeDtypeStruct((3,), BF16),
        jax.ShapeDtypeStruct((N, B, 128, 128), BF16),
        jax.ShapeDtypeStruct((B, 128, 128), BF16))
    assert programs.lowered_since(before)["streams"] == {
        "kernel": 4, "plain": 0, "programs": 4}
    # the two forward programs, each printed once for both sub-layers
    assert str(jaxpr).count("pallas_call") == 2


# models/xing4.py's passes over the streams as they stood before they moved
# to ops/streams.py (PR 44's, verbatim): what the tiny preset lowered to


def _parent_stream_maps(phi, b, alpha, x, s):
    n, _, _, c = x.shape
    f32 = jnp.float32
    phi = phi.reshape(n, c, s.map_width)
    a = sum(
        jnp.dot(x[i], phi[i], preferred_element_type=f32) for i in range(n))
    a = jnp.moveaxis(a * _parent_inverse_rms(x, s.hc_eps)[..., None], -1, 0)
    b = b.astype(f32)[:, None, None]
    alpha = alpha.astype(f32)
    pre = jax.nn.sigmoid(alpha[0] * a[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + b[n:2 * n])
    lo, hi = s.hc_res_clamp
    res = xing4.sinkhorn(
        jnp.clip(alpha[2] * a[2 * n:] + b[2 * n:], lo, hi).reshape(
            n, n, *a.shape[1:]),
        s.hc_sinkhorn_iters, s.hc_eps)
    return pre, post, res


@jax.checkpoint
def _parent_inverse_rms(x, eps):
    n, _, _, c = x.shape
    return lax.rsqrt(
        jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(0, 3)) / (n * c)
        + eps)


@jax.checkpoint
def _parent_read_streams(x, pre):
    return jnp.sum(
        pre[..., None] * x.astype(jnp.float32), axis=0).astype(x.dtype)


@jax.checkpoint
def _parent_write_streams(x, res, post, y):
    n = x.shape[0]
    y32 = y.astype(jnp.float32)
    return jnp.stack([
        (post[i][..., None] * y32 + sum(
            res[i, j][..., None] * x[j].astype(jnp.float32)
            for j in range(n))).astype(x.dtype)
        for i in range(n)])


def _parent_sub_layer(p, x, which, s, fn):
    x = lax.optimization_barrier(x)
    with jax.named_scope("mhc_map"):
        pre, post, res = _parent_stream_maps(
            p[which + "_phi"], p[which + "_b"], p[which + "_alpha"], x, s)
        counters = xing4.res_counters(res)
    with jax.named_scope("mhc_mix"):
        u = _parent_read_streams(x, pre)
    y, *rest = fn(xing4.rms_norm(u, p[which + "_norm"], s.rms_norm_eps))
    with jax.named_scope("mhc_mix"):
        return _parent_write_streams(x, res, post, y), counters, rest


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_on_the_cpu_the_tiny_preset_lowers_to_the_text_it_was(
        monkeypatch, dtype):
    """The tiny preset's loss and gradients (a dense and a sparse layer,
    every sub-layer under its checkpoint) lowered with the passes as they
    are, and with the parent's sub-layer put back in their place: the same
    text, character for character (whole steps under the Trainer, tree
    against tree: sha256 18c615b463d9... both; CHANGES.md, PR 45)."""
    from mgwfbp_tpu.models import create_model

    model, _ = create_model(
        "xing4_tiny", num_classes=256, layers_held="1:2", experts_held=(2, 4))
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens,
                           train=False))["params"]
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), params)

    def loss(params, tokens):
        losses, _ = model.apply({"params": params}, tokens, targets=tokens)
        return jnp.mean(losses)

    def lowered():
        jax.clear_caches()  # the sub-layers' traces are cached
        return jax.jit(jax.value_and_grad(loss)).lower(
            params, tokens).as_text()

    now = lowered()
    monkeypatch.setattr(xing4, "_sub_layer", _parent_sub_layer)
    was = lowered()
    jax.clear_caches()
    assert "sinkhorn" not in now and len(now) > 100000
    assert now == was
