"""ops/ssd.py against the literal recurrence S_t = a_t S_{t-1} + dt_t x_t
B_t^T, y_t = S_t C_t, one position at a time: outputs, final state and every
input's gradient; T a multiple of the chunk and not; float32 tight, bfloat16
inputs (float32 decays and state inside) within a stated tolerance; and a
decay so strong that exp(L_t) * exp(-L_s) overflows. Then the kernels
(Pallas `interpret=True`: off the chip they run no other way) against the
plain form at shapes `_kernel_dims` admits, what it refuses, and the tiny
preset's lowered text against the mixer as it stood before the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.models import granite
from mgwfbp_tpu.ops import programs, shortconv, ssd
from mgwfbp_tpu.ops.ssd import ssd_scan

HI = jax.lax.Precision.HIGHEST


def literal(x, dt, a, b, c, s0=None):
    """(y (B, T, H, P), final state (B, H, P, N)) by the recurrence."""
    bsz, t, h, p = x.shape
    s0 = jnp.zeros((bsz, h, p, b.shape[-1])) if s0 is None else s0

    def step(s, inp):
        xt, dtt, bt, ct = inp  # (B, H, P), (B, H), (B, N), (B, N)
        s = jnp.exp(dtt * a)[..., None, None] * s + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt, precision=HI)
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=HI)

    s, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), s


def draws(seed, t, bsz=2, h=3, p=4, n=5, decay=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (bsz, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) - 1.0)
    a = -decay * jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.0))
    b = jax.random.normal(k[3], (bsz, t, n))
    c = jax.random.normal(k[4], (bsz, t, n))
    return x, dt, a, b, c


def weighted(fn, args, seed=9):
    """Scalar of fn's outputs under fixed random weights, so that one
    gradient exercises y and the final state together."""
    y, s = fn(*args)[:2]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jnp.sum(y.astype(jnp.float32) * jax.random.normal(k1, y.shape))
            + jnp.sum(s * jax.random.normal(k2, s.shape)))


@pytest.mark.parametrize("t,chunk,block", [
    (32, 8, 2),   # whole chunks, two blocks of two
    (29, 8, 8),   # a short last chunk, one block
    (40, 16, 2),  # three chunks: the block shrinks to one that divides
    (7, 16, 4),   # shorter than one chunk
])
def test_float32_matches_the_recurrence_forward_and_gradients(t, chunk, block):
    args = draws(t, t)
    with jax.default_matmul_precision("highest"):
        y, s, low = ssd_scan(*args, chunk=chunk, block=block)
        want_y, want_s = literal(*args)
        np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *v: weighted(
            lambda *w: ssd_scan(*w, chunk=chunk, block=block), v),
            argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *v: weighted(literal, v),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    # the counter: the most negative whole-chunk sum of dt * a
    x, dt, a, _, _ = args
    pad = -t % chunk
    sums = jnp.pad(dt * a, ((0, 0), (0, pad), (0, 0))).reshape(
        2, -1, chunk, 3).sum(axis=2)
    assert float(low) == pytest.approx(float(sums.min()), rel=1e-5)


def test_bfloat16_inputs_keep_float32_decays_and_state():
    """bf16 x, B, C (dt and A stay float32, as the mixer hands them over)
    against the float32 recurrence on the same rounded inputs: the products'
    operands are rounded to bf16 (2^-9 relative each), accumulation and the
    decays are float32, so outputs agree to about 1% of their scale."""
    x, dt, a, b, c = draws(11, 48)
    lo = [v.astype(jnp.bfloat16) for v in (x, b, c)]
    y, s, _ = ssd_scan(lo[0], dt, a, lo[1], lo[2], chunk=16, block=2)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want_y, want_s = literal(
            lo[0].astype(jnp.float32), dt, a, lo[1].astype(jnp.float32),
            lo[2].astype(jnp.float32))
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y.astype(jnp.float32) - want_y).max()) < 0.02 * scale
    assert float(jnp.abs(s - want_s).max()) < 0.02 * float(jnp.abs(want_s).max())
    g = jax.grad(lambda v: jnp.sum(ssd_scan(
        v, dt, a, lo[1], lo[2], chunk=16, block=2)[0].astype(jnp.float32)))(lo[0])
    assert g.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(g)))


def test_a_decay_that_overflows_the_naive_form_stays_finite_and_right():
    """dt * a near -4 a step: over a chunk of 64 the sum passes -250, so
    exp(-L_s) is inf in float32 and exp(L_t) * exp(-L_s) is nan. The
    difference form is exact to rounding, forward and backward."""
    x, dt, a, b, c = draws(5, 128, decay=8.0)
    cum = jnp.cumsum((dt * a).reshape(2, 2, 64, 3), axis=2)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))  # the naive factor
    with jax.default_matmul_precision("highest"):
        y, s, low = ssd_scan(x, dt, a, b, c, chunk=64, block=1)
        want_y, want_s = literal(x, dt, a, b, c)
        grads = jax.grad(lambda *v: weighted(
            lambda *w: ssd_scan(*w, chunk=64, block=1), v),
            argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
        want = jax.grad(lambda *v: weighted(literal, v),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    assert float(low) < -88.0
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)
    for g, w in zip(grads, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


# --- the kernels, interpreted ------------------------------------------------


def kernels(chunk):
    """The scan with the mixer's skip on it down the kernels' way,
    interpreted: x, B and C side by side as the mixer's convolution leaves
    them -> (y + d x float32, the final state, the chunks' lowest sum)."""

    def scan(x, dt, a, b, c, d):
        bsz, t, h, p = x.shape
        dims = ssd._kernel_dims(
            t, h, p, b.shape[-1], chunk, (x.dtype, b.dtype, c.dtype))
        assert dims is not None
        return ssd._kernel_scan(jnp.concatenate(
            [x.reshape(bsz, t, h * p), b, c], axis=-1), dt, a, d, dims, True)

    return scan


def plain(chunk):
    """The same of the plain form, as `ssd_scan_in_place` adds the skip."""

    def scan(x, dt, a, b, c, d):
        y, s, low = ssd._plain_scan(x, dt, a, b, c, chunk, 2)
        return y.astype(jnp.float32) + d[:, None] * x.astype(jnp.float32), \
            s, low

    return scan


def lane_draws(seed, bsz, t, p, dtype, h=4, n=128):
    x, dt, a, b, c = draws(seed, t, bsz=bsz, h=h, p=p, n=n)
    d = 1.0 + jax.random.normal(jax.random.PRNGKey(seed + 100), (h,))
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d


def close(got, want, share):
    """Within `share` of the largest entry wanted, and finite."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert np.all(np.isfinite(got))
    assert np.abs(got - want).max() <= share * np.abs(want).max()


@pytest.mark.parametrize("bsz,chunks,p,chunk,dtype", [
    (1, 2, 64, 128, jnp.float32),
    (2, 4, 128, 128, jnp.float32),
    (1, 2, 64, 256, jnp.float32),
    (2, 2, 64, 128, jnp.bfloat16),
    (1, 4, 128, 256, jnp.bfloat16),
    (1, 2, 64, 256, jnp.bfloat16),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_kernels_are_the_plain_form_forward_and_pulled_back(
        bsz, chunks, p, chunk, dtype):
    """y with the skip on it, the final state, the third result and every
    pull-back (the skip's weight's too) through a scalar that also reads the
    final state, to the plain form's own rounding: float32 to float32's (the sums run in another order), bfloat16
    to a bfloat16 digit of the largest entry (the kernels keep d `mixed` and
    d `scores` float32 where autodiff rounds them to x's dtype)."""
    args = lane_draws(chunks, bsz, chunks * chunk, p, dtype)
    y, s, low = jax.jit(kernels(chunk))(*args)
    want_y, want_s, want_low = jax.jit(plain(chunk))(*args)
    assert y.dtype == s.dtype == jnp.float32
    share = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    close(y, want_y, share)
    close(s, want_s, 2e-5)
    assert float(low) == float(want_low)
    got = jax.jit(jax.grad(lambda *v: weighted(kernels(chunk), v),
                           argnums=(0, 1, 2, 3, 4, 5)))(*args)
    want = jax.jit(jax.grad(lambda *v: weighted(plain(chunk), v),
                            argnums=(0, 1, 2, 3, 4, 5)))(*args)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        close(g, w, 2e-5 if dtype == jnp.float32 else 2.0 ** -6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_decay_from_differences_masked_before_the_exponential(
        dtype):
    """The Range note's case: a head with a = -16 and dt = 0.1 over a whole
    chunk (its sum -204.8 at 128 positions: exp(-L_s) is inf in float32)
    beside a mild one. No inf, no nan, forward or backward, and the plain
    form's numbers."""
    x, dt, a, b, c, d = lane_draws(3, 1, 256, 64, dtype, h=2)
    dt = dt.at[:, :, 0].set(0.1)
    a = a.at[0].set(-16.0)
    args = (x, dt, a, b, c, d)
    y, s, low = jax.jit(kernels(128))(*args)
    assert float(low) == pytest.approx(-204.8, rel=1e-5)
    want_y, want_s, _ = jax.jit(plain(128))(*args)
    share = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    close(y, want_y, share)
    close(s, want_s, 2e-5)
    got = jax.jit(jax.grad(lambda *v: weighted(kernels(128), v),
                           argnums=(0, 1, 2, 3, 4, 5)))(*args)
    want = jax.jit(jax.grad(lambda *v: weighted(plain(128), v),
                            argnums=(0, 1, 2, 3, 4, 5)))(*args)
    for g, w in zip(got, want):
        close(g, w, 2e-5 if dtype == jnp.float32 else 2.0 ** -6)


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("t,h,p,n,chunk,dtypes,admitted", [
    (8192, 64, 64, 128, 256, (BF16,) * 3, True),  # the Granite cell
    (256, 4, 128, 256, 128, (F32,) * 3, True),
    (64, 4, 16, 8, 16, (F32,) * 3, False),  # GRANITE4H_TINY
    (256, 4, 16, 128, 128, (BF16,) * 3, False),  # P 16
    (256, 4, 64, 8, 128, (BF16,) * 3, False),  # N 8
    (200, 4, 64, 128, 128, (BF16,) * 3, False),  # a padded T
    (256, 4, 64, 128, 128, (BF16, F32, BF16), False),  # mixed dtypes
    (256, 3, 64, 128, 128, (BF16,) * 3, False),  # half a lane tile left
    (256, 4, 64, 128, 64, (BF16,) * 3, False),  # a chunk under a lane tile
], ids=lambda v: str(v) if isinstance(v, (int, bool)) else "dtypes")
def test_the_rule_sends_to_the_plain_form_what_the_kernels_do_not_take(
        monkeypatch, t, h, p, n, chunk, dtypes, admitted):
    """Traced as for a TPU (said so by the test: this process's default
    backend is the CPU; nothing runs), both entry points go the way
    `_kernel_dims` says and `ssd_program` says which."""
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    assert (ssd._kernel_dims(t, h, p, n, chunk, dtypes) is not None) \
        == admitted

    def shape(*dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype)

    x, b, c = (shape(1, t, *dims, dtype=dtype) for dims, dtype in zip(
        ((h, p), (n,), (n,)), dtypes))
    calls = [(lambda *v: ssd_scan(*v, chunk=chunk),
              (x, shape(1, t, h), shape(h), b, c))]
    if len(set(dtypes)) == 1:  # side by side they have one dtype
        calls.append((
            lambda xbc, dt, a, d: ssd.ssd_scan_in_place(
                xbc, xbc[..., :h * p].reshape(1, t, h, p), dt, a, d,
                chunk=chunk),
            (shape(1, t, h * p + 2 * n, dtype=dtypes[0]), shape(1, t, h),
             shape(h), shape(h))))
    for fn, args in calls:
        before = programs.LOWERED.copy()
        jaxpr = jax.make_jaxpr(fn)(*args)
        assert programs.lowered_since(before)["ssd"] == {
            "kernel": int(admitted), "plain": int(not admitted),
            "programs": 2 * admitted}
        assert ("pallas_call" in str(jaxpr)) == admitted
        assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [
            (1, t, h, p), (1, h, p, n), ()]


def _parent_mamba_mixer(p, u, shape, scan_block):
    """models/granite.mamba_mixer as it stood before ops/ssd.py had kernels
    (PR 45's, verbatim): what the tiny preset lowered to."""
    b, t, _ = u.shape
    inner, n = shape.mamba_inner, shape.mamba_state
    heads, hd = shape.mamba_heads, shape.mamba_head_dim
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = u @ p["in_proj"]
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + shape.conv_channels]
        dt = zxbcdt[..., inner + shape.conv_channels:]
    with jax.named_scope("ssm_conv"):
        xbc = shortconv.causal_conv_silu(
            xbc, p["conv_w"], p["conv_b"])
    with jax.named_scope("ssm_scan"):
        xs = xbc[..., :inner].reshape(b, t, heads, hd)
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        y, state, low = ssd_scan(
            xs, dt, a, xbc[..., inner:inner + n], xbc[..., inner + n:],
            chunk=shape.mamba_chunk, block=scan_block)
        y = y.astype(jnp.float32) + (
            p["d"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32))
        state_rms = jnp.sqrt(jnp.mean(jnp.square(state)))
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(b, t, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = granite.rms_norm(
            y, p["gate_norm"], shape.rms_norm_eps).astype(u.dtype)
    with jax.named_scope("ssm_out_proj"):
        return y @ p["out_proj"], jax.lax.stop_gradient(state_rms), low


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_on_the_cpu_the_tiny_preset_lowers_to_the_text_it_was(
        monkeypatch, dtype):
    """The tiny preset's loss and gradients (mamba, mamba, attention, mamba,
    every layer under its checkpoint) lowered with the mixer as it is, and
    with the parent's mixer put back in its place: the same text, character
    for character."""
    from mgwfbp_tpu.models import create_model

    model, _ = create_model("granite4h_tiny", num_classes=256)
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
        jax.clear_caches()  # the layers' traces are cached
        return jax.jit(jax.value_and_grad(loss)).lower(
            params, tokens).as_text()

    now = lowered()
    monkeypatch.setattr(granite, "mamba_mixer", _parent_mamba_mixer)
    was = lowered()
    jax.clear_caches()
    assert "cumsum" in now and len(now) > 100000
    assert now == was
