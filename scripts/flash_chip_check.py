"""Does the Pallas flash kernel compile under Mosaic, and agree with XLA?

Run on the chip (one process, one chip):

    python scripts/flash_chip_check.py

For each case it compiles ``flash_attention`` forward and both backward
kernels (through ``jax.grad``) and compares output, dq, dk and dv with
``xla_attention`` at the same shape on the same device. Inputs are bf16;
the two paths round differently (XLA casts the probabilities to bf16
before the PV matmul, the kernel keeps them in fp32), so agreement is
``max|flash - xla| <= 2e-2 * max|xla|`` per tensor — a few bf16 ulps of
the largest element. A wrong mask or a dropped block is off by O(1).

Cases: the chip smoke's attention shape (12 q / 6 kv heads, head_dim 128,
T = 2047 padded to 2048, 512-blocks) and T = 8192, each with and without
``segment_ids``. Then a compile-and-run probe at longer T with no
reference (XLA's [T, T] logits do not fit), to find where the kernel's
whole-sequence VMEM slabs stop compiling.

One JSON line per case; exit 1 if any case failed. ``--cpu-tiny`` runs
small shapes through the Pallas interpreter to debug the script itself.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

REL_TOL = 2e-2


def _inputs(b, t, h, kh, d, seg, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, kh, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, kh, d), jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, t, h, d), jnp.float32)
    seg_ids = None
    if seg:
        # Packed rows: documents of uneven length, ids 1..n, and a tail of
        # padding (segment 0) — what tpufw.train.native_data emits.
        rng = np.random.default_rng(seed)
        rows = []
        for _ in range(b):
            cuts = np.sort(rng.choice(np.arange(1, t - t // 8), 5, False))
            ids = np.searchsorted(cuts, np.arange(t), side="right") + 1
            ids[t - t // 16:] = 0
            rows.append(ids)
        seg_ids = jnp.asarray(np.stack(rows), jnp.int32)
    return q, k, v, w, seg_ids


def _value_and_grads(attn, q, k, v, w, seg_ids):
    import jax
    import jax.numpy as jnp

    def f(q, k, v):
        out = attn(q, k, v, causal=True, segment_ids=seg_ids)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out), grads = jax.jit(
        jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    return jax.block_until_ready((out, *grads))


def _compare(name, got, want, seg_ids):
    import numpy as np

    errs = {}
    ok = True
    for label, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if label in ("out", "dq") and seg_ids is not None:
            # Padding queries (segment 0) attend to padding keys only;
            # both paths define them, neither result is used. Compare
            # real positions.
            keep = np.asarray(seg_ids) > 0
            a, b = a[keep], b[keep]
        scale = float(np.max(np.abs(b)))
        err = float(np.max(np.abs(a - b)))
        errs[label] = {"max_abs_err": err, "max_abs_ref": scale}
        ok = ok and np.isfinite(a).all() and err <= REL_TOL * scale
    return ok, errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-tiny", action="store_true")
    ap.add_argument(
        "--probe", default="16384,32768,65536,131072",
        help="comma-separated T for the no-reference compile probe",
    )
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from tpufw.ops.attention import xla_attention
    from tpufw.ops.flash import flash_attention

    dev = jax.devices()[0]
    print(
        json.dumps(
            {"platform": dev.platform, "device_kind": dev.device_kind}
        ),
        flush=True,
    )
    if dev.platform != "tpu" and not args.cpu_tiny:
        print("flash_chip_check: no TPU", file=sys.stderr)
        return 2
    if args.cpu_tiny:
        cases = [("tiny", 1, 255, 4, 2), ("tiny_b", 1, 384, 2, 2)]
        probes = []
    else:
        cases = [("smoke_shape", 2, 2047, 12, 6), ("t8192", 1, 8192, 4, 2)]
        probes = [int(t) for t in args.probe.split(",") if t]
    failed = 0
    for name, b, t, h, kh in cases:
        for seg in (False, True):
            rec = {"case": name, "B": b, "T": t, "H": h, "K": kh, "seg": seg}
            t0 = time.time()
            try:
                q, k, v, w, seg_ids = _inputs(b, t, h, kh, 128, seg)
                got = _value_and_grads(flash_attention, q, k, v, w, seg_ids)
                rec["flash_s"] = round(time.time() - t0, 1)
                want = _value_and_grads(xla_attention, q, k, v, w, seg_ids)
                ok, errs = _compare(name, got, want, seg_ids)
                rec.update(ok=ok, rel_tol=REL_TOL, **errs)
            except Exception as e:  # noqa: BLE001 — report every case
                rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:1500])
            failed += not rec["ok"]
            print(json.dumps(rec), flush=True)
    for t in probes:
        rec = {"case": "probe", "B": 1, "T": t, "H": 2, "K": 1, "seg": False}
        t0 = time.time()
        try:
            q, k, v, w, _ = _inputs(1, t, 2, 1, 128, False)
            got = _value_and_grads(flash_attention, q, k, v, w, None)
            rec["ok"] = bool(
                all(jnp.isfinite(x.astype(jnp.float32)).all() for x in got)
            )
            rec["s"] = round(time.time() - t0, 1)
        except Exception as e:  # noqa: BLE001 — the probe LOOKS for this
            rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:1500])
        # A probe that fails is a finding, not a failure of the check.
        print(json.dumps(rec), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
