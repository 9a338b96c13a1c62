"""Knock-outs of the Xing4.0 program (models/xing4.py) against its plain
reference (benchmarks/references/xing4_share.py) at the tiny size: each case
PATCHES THE PROGRAM in one place and must fail the comparison that
tests/benchmark/test_xing4_reference.py passes (same tolerance, same helpers'
shapes; a file of its own so that neither passes two minutes alone). Held
against layers 2 and 3 (sparse: both mappings, latent attention, the routed
experts under their selection bias; two layers because the streams enter the
first as four copies, which no H_res can tell apart), all 8 experts, float32 on
the CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench_paths import load
from test_xing4_reference import (
    RTOL,
    SHAPE,
    flat,
    gaps,
    loss_and_grads,
    program,
    reference_loss_and_grads,
)

from mgwfbp_tpu.models import xing4


@pytest.fixture(scope="module")
def ref():
    return load("references/xing4_share_tiny.py").full


@pytest.fixture(scope="module")
def seeded(ref):
    """Seed 0's draws, layers 2 and 3 (sparse) with all 8 experts held, and
    the reference's loss and gradient on them."""
    model, params, x, y = program(layers_held=(2, 2))
    host = flat(params)
    return model, params, x, y, host, reference_loss_and_grads(
        ref, host, x, y, 0)


def test_the_unpatched_program_passes(seeded):
    model, params, x, y, _, (want_loss, want_grads) = seeded
    (loss, _), grads = loss_and_grads(model, params, x, y)
    assert max(gaps(loss, grads, want_loss, want_grads).values()) < RTOL


KNOCK_OUTS = [
    "sinkhorn-one-iteration", "h-res-the-identity", "alphas-at-zero",
    "selection-bias-dropped", "bias-also-weighting", "k-r-a-head-not-shared",
    "score-m-squared-left-out", "value-width-read-as-192"]


@pytest.mark.parametrize("part", KNOCK_OUTS)
def test_each_part_knocked_out_of_the_program_fails_the_comparison(
        seeded, monkeypatch, part):
    """The PROGRAM with one thing changed is another model, and its loss or a
    gradient leaf leaves the tolerance by a wide margin: the Sinkhorn
    iterations cut to one; H_res the identity (the plain residual); the three
    alphas at zero (the mappings static); the selection bias dropped from the
    choice; the bias also in the weights; the rotary key kept for head 0 alone
    instead of shared by all; the score's m^2 left out; the values read over
    the score's width (the core handed [v | the key's rotary columns], its 8
    extra output columns folded onto the first 8, as a core with ONE head size
    would force)."""
    model, params, x, y, _, (want_loss, want_grads) = seeded
    jax.clear_caches()  # the sub-layers' traces are cached by their function
    s = SHAPE
    if part == "sinkhorn-one-iteration":
        model = model.clone(shape=dataclasses.replace(s, hc_sinkhorn_iters=1))
    elif part == "h-res-the-identity":
        monkeypatch.setattr(
            xing4, "sinkhorn", lambda m, iters, eps: 0.0 * m + jnp.eye(
                m.shape[0], dtype=m.dtype).reshape(
                    m.shape[0], m.shape[0], *(1,) * (m.ndim - 2)))
    elif part == "alphas-at-zero":
        real = xing4.stream_maps
        monkeypatch.setattr(
            xing4, "stream_maps",
            lambda phi, b, alpha, x, s: real(phi, b, 0.0 * alpha, x, s))
    elif part == "selection-bias-dropped":
        real_route = xing4.route
        monkeypatch.setattr(
            xing4, "route",
            lambda u, r, bias, k, c: real_route(u, r, 0.0 * bias, k, c))
    elif part == "bias-also-weighting":
        def route(u, router, bias, top_k, scaling):
            scores = jax.nn.sigmoid(u @ router) + bias
            top, idx = jax.lax.top_k(scores, top_k)
            return (idx, top / jnp.sum(top, -1, keepdims=True) * scaling,
                    jnp.zeros((), jnp.float32))
        monkeypatch.setattr(xing4, "route", route)
    elif part == "score-m-squared-left-out":
        model = model.clone(shape=dataclasses.replace(
            s, yarn_mscale=0.0, yarn_mscale_all_dim=0.0))
        assert model.shape.score_scale == pytest.approx(24 ** -0.5)
        assert model.shape.rope_factor == 1.0
    else:
        real_core = xing4.blockwise_attention
        dn = s.qk_nope_head_dim

        def core(q, k, v, **kw):
            if part == "k-r-a-head-not-shared":
                k = k.at[:, :, 1:, dn:].set(0.0)
                return real_core(q, k, v, **kw)
            out = real_core(q, k, jnp.concatenate([v, k[..., dn:]], -1), **kw)
            extra = out.shape[-1] - v.shape[-1]
            return out[..., :v.shape[-1]].at[..., :extra].add(
                out[..., v.shape[-1]:])
        monkeypatch.setattr(xing4, "blockwise_attention", core)
    (loss, _), grads = loss_and_grads(model, params, x, y)
    got = gaps(loss, grads, want_loss, want_grads)
    jax.clear_caches()
    assert max(got.values()) > 100 * RTOL, max(got.values())
