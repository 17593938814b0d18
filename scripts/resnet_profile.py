#!/usr/bin/env python
"""ResNet-50 MFU component profile (VERDICT r3 item 3 / weak 3).

Round-3 record: 15.8% MFU best-case (2524 img/s, b256, bf16 BN) with
"conv input/filter gradients identified as the remaining slow path" —
analysis done, optimization not. This script measures the pieces so the
optimization is aimed, one JSON line per experiment:

  1. train step    — the bench tier (b256, bf16 BN): the reference point
  2. forward only  — inference pass: how much of the step is backward
  3. batch sweep   — 128 / 512: does conv-backward efficiency scale
  4. conv micro    — fwd / input-grad / filter-grad TFLOP/s for the
                     three canonical ResNet conv shapes (7x7s2 stem,
                     3x3 mid, 1x1 wide), bf16 vs f32: WHERE the
                     backward cliff is, layout NHWC (XLA-native)

Timing is value-fetch based (np.asarray). Run from /root/repo on a
healthy TPU:  python scripts/resnet_profile.py   (--smoke for a tiny
CPU wiring check). Results append to
docs/evidence/RESNET_PROFILE_r5.jsonl as they complete.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "evidence", "RESNET_PROFILE_r5.jsonl",
)
SMOKE = "--smoke" in sys.argv
# Every row carries the platform so a --smoke wiring check appended to
# the same evidence file can never be mistaken for hardware numbers.
_TAGS: dict = {}


def emit(row: dict) -> None:
    row = {"t": round(time.time(), 1), **_TAGS, **row}
    print(json.dumps(row), flush=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")


def _forward_only(jax, jnp, np, batch, img, classes,
                  flops_per_image, peak):
    """Kf serialized forwards inside one jit, ONE scalar fetched (the
    v1 single-call number was ~70 ms round-trip + 0.5 MB logits
    transfer on top of the actual forward; see the conv-micro
    methodology note in main)."""
    import time

    from tpufw.models import resnet50

    model = resnet50(classes, norm_dtype=jnp.bfloat16)
    x = jnp.ones((batch, img, img, 3), jnp.bfloat16)
    variables = jax.jit(
        lambda k, x: model.init(k, x, train=False)
    )(jax.random.key(0), x)
    Kf = 2 if SMOKE else 8

    def fwd_chain(v, x):
        acc = jnp.float32(0.0)
        for _ in range(Kf):
            s = jnp.sum(
                model.apply(v, x, train=False).astype(jnp.float32)
            )
            acc = acc + s
            x = x + (s * jnp.float32(1e-38)).astype(x.dtype)
        return acc

    fwd = jax.jit(fwd_chain)
    np.asarray(fwd(variables, x))  # compile+warm
    t0 = time.perf_counter()
    np.asarray(fwd(variables, x))
    dt = (time.perf_counter() - t0) / Kf
    emit({
        "case": "forward_only", "batch": batch,
        "img_per_s": round(batch / dt, 1),
        # Forward is ~1/3 of train FLOPs.
        "mfu_fwd": round(
            (flops_per_image / 3.0) * batch / dt / peak, 4
        ),
    })


def main() -> int:
    if SMOKE:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if SMOKE:
        jax.config.update("jax_platforms", "cpu")

    from tpufw.mesh import MeshConfig
    from tpufw.models import ResNetConfig, resnet50
    from tpufw.train import (
        VisionTrainer,
        VisionTrainerConfig,
        synthetic_images,
    )

    devices = jax.devices()
    _TAGS.update(platform=devices[0].platform, smoke=SMOKE)
    emit({"event": "start", "kind": devices[0].device_kind})

    img = 64 if SMOKE else 224
    classes = 10 if SMOKE else 1000
    flops_per_image = ResNetConfig().flops_per_image(img)
    peak = 197e12 if not SMOKE else 1e12  # v5e bf16

    # TPUFW_RESNET_MICRO_ONLY=1: skip the train/forward sections (e.g.
    # re-running only a fixed conv-micro methodology on banked tiers).
    from tpufw.workloads.env import env_bool

    micro_only = env_bool("resnet_micro_only", False)

    # 1 + 3. Train step at batch sweep through the bench path.
    for batch in ([] if micro_only else [8] if SMOKE else
                  [128, 256, 512]):
        try:
            vt = VisionTrainer(
                resnet50(classes, norm_dtype=jnp.bfloat16),
                VisionTrainerConfig(
                    batch_size=batch, image_size=img,
                    total_steps=9, sync_every=4,
                ),
                MeshConfig(),
            )
            vt.init_state()
            hist = vt.run(
                synthetic_images(batch, img, classes, on_device=True),
                flops_per_image=flops_per_image,
            )
            steady = [m for m in hist if m.step > 1]
            import statistics

            emit({
                "case": f"train_b{batch}",
                "img_per_s": round(statistics.median(
                    m.tokens_per_sec_per_chip for m in steady
                ), 1),
                "mfu": round(statistics.median(
                    m.mfu for m in steady
                ), 4),
            })
            del vt
        except Exception as e:  # noqa: BLE001
            emit({"case": f"train_b{batch}",
                  "error": f"{type(e).__name__}: {e}"[:300]})

    # 2. Forward only (same model/batch as the b256 tier).
    batch = 8 if SMOKE else 256
    if not micro_only:
        _forward_only(jax, jnp, np, batch, img, classes,
                      flops_per_image, peak)

    # 4. Conv microbench: canonical shapes, fwd + both grads.
    #
    # Methodology v3. v1 (single dispatch + np.asarray of the raw conv
    # output) measured the host link, not the chip: big outputs (stem
    # fwd, 411 MB) were transfer-bound (72 s!) and tiny outputs sat at the
    # dispatch+fetch round trip (~70 ms) regardless of shape. v2
    # (K=16 Python-unrolled serial iterations, scalar fetch, null
    # subtraction) fixed the transfer but not the VARIANCE: the round
    # trip swings 26-107 ms between calls, so fast cases measured
    # d - null <= 0 and one stem row read an impossible 349 TFLOP/s
    # (> the 197 peak). v3: a lax.fori_loop chain (constant compile
    # cost) with a FLOP-targeted per-case K, sized so device time
    # >= ~300 ms at 25% efficiency — round-trip noise becomes < 15%.
    # bf16 only (the production dtype). Each iteration's scalar
    # perturbs the next iteration's input by scalar*1e-38 (numerically
    # a no-op at these magnitudes, but data-dependent, so the compiler
    # cannot CSE or reorder the K convs).
    target_flops = 2e10 if SMOKE else 8e12

    def conv(x, w, stride):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def timed_chain(step_fn, arr, k_iters):
        """Wall seconds for k_iters serial evaluations of
        step_fn(arr) inside one jit. Each iteration's scalar perturbs
        ONE element of the next iteration's input — data-dependent, so
        the compiler can neither CSE nor loop-hoist the k_iters
        evaluations, and O(1) bytes, so the perturbation itself is
        unmeasurable (v3 added the scalar to the FULL tensor, up to
        ~100 MB of extra HBM traffic per iteration on the big
        activations — tens of percent of bias on the fast convs)."""

        def body(_, carry):
            a, acc = carry
            s = step_fn(a)
            return (
                a.at[(0,) * a.ndim].add(
                    (s * jnp.float32(1e-38)).astype(a.dtype)
                ),
                acc + s,
            )

        def chain(a):
            _, acc = jax.lax.fori_loop(
                0, k_iters, body, (a, jnp.float32(0.0))
            )
            return acc

        fn = jax.jit(chain)
        np.asarray(fn(arr))  # compile+warm
        t0 = time.perf_counter()
        np.asarray(fn(arr))
        return time.perf_counter() - t0

    shapes = [
        # (name, H, Cin, Cout, k, stride) at the profile batch
        ("stem7x7s2", img, 3, 64, 7, 2),
        # Space-to-depth stem equivalent (MLPerf-style): s2d(2) folds
        # 224x224x3 -> 112x112x12 on the host/data side; the stem
        # becomes a stride-1 4x4x12 conv at the SAME output shape and
        # ~same FLOPs, but with 4x the MXU lane occupancy (Cin 12 vs 3).
        ("stem_s2d2_4x4", img // 2, 12, 64, 4, 1),
        ("mid3x3", img // 8, 128, 128, 3, 1),
        ("wide1x1", img // 16, 1024, 256, 1, 1),
    ]
    dtype, dt_name = jnp.bfloat16, "bf16"
    for name, h, cin, cout, k, stride in shapes:
        x = jnp.ones((batch, h, h, cin), dtype)
        w = jnp.ones((k, k, cin, cout), dtype)
        flops = 2.0 * batch * (h / stride) ** 2 * cin * cout * k * k
        k_iters = max(8, min(2048, int(target_flops / flops)))

        def fwd_step(x, w=w, stride=stride):
            return jnp.sum(conv(x, w, stride).astype(jnp.float32))

        # dx: the cotangent of a LINEAR op is x-independent, so a
        # grad-of-sum formulation is loop-invariant no matter how x is
        # perturbed (v3's dx cells were hoistable — review finding).
        # Take ONE vjp outside the loop and time the transposed conv
        # applied to a perturbed cotangent instead.
        y, conv_vjp = jax.vjp(
            lambda x, w=w, stride=stride: conv(x, w, stride), x
        )
        ct0 = jnp.ones_like(y)

        def dx_step(ct, conv_vjp=conv_vjp):
            return jnp.sum(conv_vjp(ct)[0].astype(jnp.float32))

        def dw_step(x, w=w, stride=stride):
            def loss(w):
                return jnp.sum(
                    conv(x, w, stride).astype(jnp.float32)
                )

            return jnp.sum(jax.grad(loss)(w).astype(jnp.float32))

        for kind, step_fn, arr in (
            ("fwd", fwd_step, x),
            ("dx", dx_step, ct0),
            ("dw", dw_step, x),
        ):
            try:
                d = timed_chain(step_fn, arr, k_iters)
                emit({
                    "case": f"conv_{name}_{kind}_{dt_name}",
                    "k_iters": k_iters,
                    "tflop_per_s": round(
                        k_iters * flops / d / 1e12, 2
                    ),
                    "ms_per_call": round(d / k_iters * 1e3, 3),
                    "raw_ms": round(d * 1e3, 2),
                })
            except Exception as e:  # noqa: BLE001
                emit({
                    "case": f"conv_{name}_{kind}_{dt_name}",
                    "error": f"{type(e).__name__}: {e}"[:200],
                })
    emit({"event": "done"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
