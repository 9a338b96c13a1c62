"""MFU roofline counterfactuals for resnet50 (VERDICT r4 #6).

Whether ResNet-50's MFU on one chip is HBM-bound is an empirical question
(ROADMAP S2); this tool MEASURES the counterfactual rows a bandwidth
argument only reasons about:

  * batch 64 / 128 / 256 — per-sample HBM traffic is ~batch-invariant, so
    throughput should be flat if the HBM diagnosis is right;
  * uint8 input + on-device normalize — cuts the input-read traffic 4x
    (and models the H2D-lean production input path);
  * bf16 batch statistics (MGWFBP_BN_DTYPE=bfloat16) — runs the BN
    reduce/broadcast passes in bf16.

Each row: bench-protocol timing (AOT-compiled donated step, >=30 timed
iters, ONE host sync after the last chained step) + XLA cost analysis
(flops, bytes_accessed). Writes profiles/mfu_ablation_resnet50_tpu.json.
Never run on a chip yet: its rows are not measured until it has been.

Run on the TPU chip, as the only process on it:  python tools/mfu_ablation.py
CPU smoke:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    python tools/mfu_ablation.py --iters 3 --no-save
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "profiles", "mfu_ablation_resnet50_tpu.json",
)


def _build(batch, uint8_input=False):
    """Bench-protocol setup for one row: returns (timed_fn, state, batch,
    flops, bytes_accessed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.optim import make_optimizer
    from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh
    from mgwfbp_tpu.train import create_train_state, make_train_step

    mesh = make_mesh(MeshSpec(data=1))
    model, meta = zoo.create_model("resnet50")
    input_dtype = meta.input_dtype

    if uint8_input:

        class Uint8Normalize(nn.Module):
            """uint8 NHWC in; dequantize+normalize on device in bf16.
            Models the H2D-lean input path (the data loader ships raw
            bytes; normalization constants baked into the graph). The
            wrapped model is a FIELD so flax binds it as a submodule."""

            inner: nn.Module

            @nn.compact
            def __call__(self, x, train=True):
                x = x.astype(jnp.bfloat16) * jnp.bfloat16(1.0 / 255.0)
                x = (x - jnp.bfloat16(0.45)) * jnp.bfloat16(1.0 / 0.225)
                return self.inner(x, train=train)

        model = Uint8Normalize(inner=model)
        input_dtype = jnp.uint8

    tx, _ = make_optimizer(
        0.01, momentum=0.9, weight_decay=1e-4, lr_schedule="const",
        dataset="imagenet", num_batches_per_epoch=1,
    )
    init_x = (
        jnp.zeros((1, 224, 224, 3), input_dtype)
    )
    state = create_train_state(jax.random.PRNGKey(0), model, init_x, tx)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16,
        donate=True,
    )
    rs = np.random.RandomState(0)
    if uint8_input:
        x = jnp.asarray(
            rs.randint(0, 256, (1, batch, 224, 224, 3)), jnp.uint8
        )
    else:
        x = jnp.asarray(rs.randn(1, batch, 224, 224, 3), jnp.float32)
    bd = {
        "x": x,
        "y": jnp.asarray(rs.randint(0, 1000, (1, batch)), jnp.int32),
    }
    compiled = step.lower(state, bd).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    return compiled, state, bd, flops, nbytes


def _time_row(compiled, state, bd, iters):
    for _ in range(5):
        state, metrics = compiled(state, bd)
    float(metrics["loss"])  # sync anchor
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = compiled(state, bd)
    loss = float(metrics["loss"])  # ONE end sync brackets the chain
    dt = (time.perf_counter() - t0) / iters
    import math

    assert math.isfinite(loss), f"non-finite loss {loss}"
    return dt


def run_rows(iters):
    import jax

    # device-kind-keyed peak (shared package table) instead of a
    # hardcoded v5e constant: mfu on any other device would be wrong
    from mgwfbp_tpu.utils.platform import peak_flops

    peak = peak_flops(jax.devices()[0].device_kind)
    rows = {}

    def measure(name, batch, uint8_input=False, bn_bf16=False):
        prior = os.environ.get("MGWFBP_BN_DTYPE")
        if bn_bf16:
            os.environ["MGWFBP_BN_DTYPE"] = "bfloat16"
        else:
            # rows labeled baseline must BE the baseline even if the
            # caller exported the knob globally
            os.environ.pop("MGWFBP_BN_DTYPE", None)
        try:
            compiled, state, bd, flops, nbytes = _build(
                batch, uint8_input=uint8_input
            )
            dt = _time_row(compiled, state, bd, iters)
        finally:
            if prior is None:
                os.environ.pop("MGWFBP_BN_DTYPE", None)
            else:
                os.environ["MGWFBP_BN_DTYPE"] = prior
        del compiled, state, bd
        rows[name] = {
            "batch": batch,
            "sec_per_iter": round(dt, 6),
            "images_per_sec": round(batch / dt, 1),
            "mfu": round(flops / dt / peak, 4) if peak else None,
            "flops_per_step": flops,
            "xla_bytes_accessed_GB": round(nbytes / 1e9, 3),
            "achieved_GBps_on_xla_bytes": round(nbytes / dt / 1e9, 1),
        }
        print(name, json.dumps(rows[name]), flush=True)

    measure("baseline_b128", 128)
    measure("batch_64", 64)
    measure("batch_256", 256)
    measure("uint8_input_b128", 128, uint8_input=True)
    measure("bf16_batchstats_b128", 128, bn_bf16=True)

    base = rows["baseline_b128"]
    for r in rows.values():
        r["throughput_vs_baseline"] = round(
            r["images_per_sec"] / base["images_per_sec"], 4
        )
    return {
        "protocol": (
            "AOT-compiled donated step, 5 warmup + "
            f"{iters} timed iters, ONE host sync after the last chained "
            "step; XLA cost analysis for flops/bytes"
        ),
        "platform": jax.devices()[0].platform,
        "device": jax.devices()[0].device_kind,
        "rows": rows,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--no-save", action="store_true")
    args = ap.parse_args(argv)
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()
    report = run_rows(args.iters)
    base = report["rows"]["baseline_b128"]
    verdicts = []
    for name in ("batch_64", "batch_256", "uint8_input_b128",
                 "bf16_batchstats_b128"):
        r = report["rows"][name]
        gain = r["images_per_sec"] / base["images_per_sec"] - 1.0
        verdicts.append(f"{name}: {gain:+.1%} img/s vs baseline")
    report["conclusion"] = verdicts
    print(json.dumps(report, indent=2))
    if not args.no_save:
        if report["platform"] == "cpu":
            raise SystemExit(
                "a CPU run is never saved under a *_tpu artifact; "
                "pass --no-save for a CPU smoke"
            )
        with open(ARTIFACT, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {ARTIFACT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
