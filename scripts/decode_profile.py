#!/usr/bin/env python
"""Decode-throughput component profile (VERDICT r3 item 2 / weak 4).

The round-3 record: 626 tok/s/chip bf16 at batch 8 on the 596M bench
model, vs a ~5,400 tok/s weight-stream roofline (1.19 GB bf16 weights,
819 GB/s v5e HBM) — 12.8 ms/step where weights account for ~1.5 ms.
Nobody has measured WHERE the other 11 ms goes. This script isolates
the components, one JSON line per experiment:

  1. baseline      — the exact bench decode tier (prefill 128 + 128 new)
  2. decode_only   — max_new only, 1-token prompt (prefill cost out)
  3. batch sweep   — B in {1, 8, 32}: flat per-step = bandwidth-bound,
                     linear = compute/overhead-bound
  4. newtok sweep  — 64 vs 256 new tokens: per-step slope vs fixed cost
  5. no_head       — hidden-states only (lm head + sampling cost out)
  6. unscanned     — scan_layers=False (layer-scan slice overhead out)
  7. small_cache   — max_seq_len exactly prompt+new vs 2048 (cache
                     update / attention slot traffic)
  8. int8          — weight-only quant (the serving lever; r3: 1.124x,
                     should be ~1.7x if truly bandwidth-bound)

Timing is value-fetch based (np.asarray): the fetch is the barrier.
Run from the repo root on the chip:  python scripts/decode_profile.py
Results land in docs/evidence/DECODE_PROFILE_r5.jsonl as they complete.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "evidence", "DECODE_PROFILE_r5.jsonl",
)
# Every row carries the platform so a --smoke wiring check appended to
# the same evidence file can never be mistaken for hardware numbers.
_TAGS: dict = {}


def emit(row: dict) -> None:
    row = {"t": round(time.time(), 1), **_TAGS, **row}
    print(json.dumps(row), flush=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")


def main() -> int:
    smoke = "--smoke" in sys.argv
    if smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpufw.configs import bench_model_config
    from tpufw.infer import SamplingConfig, cast_decode_params, generate
    from tpufw.models import Llama

    if smoke:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    _TAGS.update(platform=devices[0].platform, smoke=smoke)
    emit({"event": "start", "kind": devices[0].device_kind})

    base_cfg = bench_model_config()
    if smoke:
        from tpufw.models import LLAMA_CONFIGS

        base_cfg = LLAMA_CONFIGS["llama3_tiny"]
    hbm_bw = 819e9  # v5e

    def weight_bytes(cfg, quant):
        """Per-CASE decode-streamed weight bytes: the embedding table is
        a [B]-row gather (excluded), the lm head streams fully; int8
        stores projections at 1 byte (+~1% scales, ignored)."""
        streamed = cfg.n_params() - cfg.vocab_size * cfg.d_model
        return streamed * (1 if quant else 2)

    def run_case(name, cfg, b, prompt_len, n_new, quant=False,
                 return_hidden=False):
        """Compile+warm one generate, then time a second full call.
        Returns per-step ms and roofline fraction."""
        import gc

        gc.collect()
        # Params always init from the UNquantized twin; int8 cases
        # quantize that tree and run it through the quantized model
        # (bench.py's decode-tier discipline).
        fp_cfg = (
            dataclasses.replace(cfg, quantized_weights=False)
            if quant else cfg
        )
        model = Llama(cfg)
        prompts = jax.random.randint(
            jax.random.key(0), (b, prompt_len), 0, cfg.vocab_size
        )
        pads = jnp.zeros((b,), jnp.int32)
        params = cast_decode_params(
            jax.jit(Llama(fp_cfg).init)(
                jax.random.key(1), prompts
            )["params"]
        )
        if quant:
            from tpufw.ops.quant import quantize_params

            params = quantize_params(params)

        def gen():
            return generate(
                model, params, prompts, pads, jax.random.key(2),
                max_new_tokens=n_new, sampling=SamplingConfig(),
            )

        t0 = time.perf_counter()
        np.asarray(gen())
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(gen())
        dt = time.perf_counter() - t0
        step_ms = dt / n_new * 1e3
        wb = weight_bytes(cfg, quant)
        row = {
            "case": name, "batch": b, "prompt": prompt_len,
            "new": n_new, "total_s": round(dt, 4),
            "step_ms": round(step_ms, 3),
            "tok_per_s": round(b * n_new / dt, 1),
            "roofline_frac": round((wb / hbm_bw) / (dt / n_new), 4),
            "compile_s": round(compile_s, 1),
        }
        emit(row)
        del params
        return row

    dec = lambda **kw: dataclasses.replace(  # noqa: E731
        base_cfg.decode_config(), **kw
    )

    # 1. The exact bench decode tier.
    run_case("baseline", dec(max_seq_len=256), 8, 128, 128)
    # 2. Prefill out of the picture.
    run_case("decode_only", dec(max_seq_len=257), 8, 1, 256)
    # 3. Batch sweep: bandwidth-bound decode is ~flat in step_ms.
    for b in (1, 32):
        run_case(f"batch{b}", dec(max_seq_len=256), b, 128, 128)
    # 4. New-token sweep at MATCHED cache size (256 slots, same as
    # baseline — cache length alone moved step_ms ~10x in the smoke
    # run, so it must not vary here): half the steps amortizing the
    # same 128-token prefill. step_ms(new64) - step_ms(baseline)
    # ~= prefill_cost/64; equal step_ms means per-step cost dominates.
    run_case("new64", dec(max_seq_len=256), 8, 128, 64)
    # 5. Head + sampling out: hidden-only decode loop. (Approximated by
    #    a model with a tiny vocab: head matmul+sample shrink ~256x.)
    run_case(
        "tiny_vocab", dec(max_seq_len=256, vocab_size=128), 8, 128, 128
    )
    # 6. Layer scan out (per-layer weight slicing overhead).
    run_case(
        "unscanned", dec(max_seq_len=256, scan_layers=False),
        8, 128, 128,
    )
    # 7. Oversized cache: slot traffic scaling (2048 slots vs 256).
    run_case("cache2048", dec(max_seq_len=2048), 8, 128, 128)
    # 8. int8 weight-only.
    run_case(
        "int8", dec(max_seq_len=256, quantized_weights=True),
        8, 128, 128, quant=True,
    )
    emit({"event": "done"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
