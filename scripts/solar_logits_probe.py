"""Logits with logits, outside the benchmark's harness: the solar_open2
configuration at its published widths through the serving pool (a prompt
prefilled in chunks, then decode steps, bfloat16 as served) against the
family's plain float32 reference's full forward pass, once with the
recurrent state in float32 (the configuration's) and once kept in
bfloat16, which the stated tolerance has to tell apart.

    python scripts/solar_logits_probe.py [--cpu-tiny] [--prompt 2048] [--steps 64] [--compute float32]

Prints, per run, the root-mean-square and the largest difference of the
next-token logits over the decode positions (and at the prompt's end),
and writes them to ``chiprun_out/solar_logits_probe.<compute>.json``. On the chip
it needs the whole device: run it alone.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cpu-tiny", action="store_true", help="rehearse at the tiny widths on the CPU")
    ap.add_argument("--prompt", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--chunk-pages", type=int, default=32)
    ap.add_argument("--seed", type=int, default=2147481028)
    ap.add_argument("--compute", choices=("bfloat16", "float32"), default="bfloat16",
                    help="the program's activation type: as served, or float32 at highest matmul precision, "
                         "where rounding no longer hides what the state's type does")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness
    from benchmarks.reference import common
    from benchmarks.weights import make_weights
    from tpufw.infer import SamplingConfig, pages
    from tpufw.infer.generate import _model_apply
    from tpufw.models import solar_open2

    if args.cpu_tiny:
        keys = harness.model_keys(harness.load_json("benchmarks/configs/rehearse/solar_open2.json"))
        keys["max_position_embeddings"] = 4096
    else:
        if jax.devices()[0].platform != "tpu":
            print("probe: no TPU; use --cpu-tiny to rehearse", file=sys.stderr)
            return 3
        from tpufw.utils.profiling import enable_compile_cache

        enable_compile_cache()
        keys = harness.model_keys(harness.load_json("benchmarks/configs/solar-open2-250b-4l-ep8.json"))
    ref, adapter = harness.family_modules("solar_open2")
    t0 = time.time()
    weights = make_weights(ref.weight_specs(keys), args.seed)
    cls, pc = adapter.program_model(keys, {"moe_dispatch": "sorted"})
    precision = None
    if args.compute == "float32":
        pc, precision = dataclasses.replace(pc, dtype=jnp.float32), "highest"
    params = adapter.to_program(weights, keys)
    jax.block_until_ready(params)
    print(f"probe: device {jax.devices()[0].device_kind}; weights in {time.time() - t0:.1f} s", flush=True)
    page, n_slots, positions = 16, 2, keys["max_position_embeddings"]
    prompt = np.random.default_rng(args.seed).integers(1, keys["vocab_size"], size=args.prompt).tolist()
    fwd = jax.jit(lambda w, toks, at: ref.logits(w, keys, toks, at)[0])

    def reference(seq, at):
        pad = -len(seq) % common.QUERY_BLOCK if len(seq) > common.QUERY_BLOCK else 0
        at = at + [0] * (-len(at) % 8)
        return np.asarray(fwd(weights, jnp.asarray(seq + [0] * pad, jnp.int32), jnp.asarray(at, jnp.int32)))

    out = {"device": jax.devices()[0].device_kind, "prompt": args.prompt, "steps": args.steps,
           "chunks": -(-args.prompt // (args.chunk_pages * page)), "seed": args.seed, "compute": args.compute,
           "runs": {}}
    for name, state_dtype in (("float32_state", jnp.float32), ("bfloat16_state", jnp.bfloat16)):
        # The state's type is a constant of the program; the probe rebinds it.
        solar_open2.KDA_STATE_DTYPE = state_dtype
        cfg = dataclasses.replace(pc.decode_config(), max_seq_len=positions)
        paged = dataclasses.replace(cfg, kv_page=page, kv_pages=n_slots * (positions // page) + 1)
        pool = pages.PagedSlotPool.create_paged(
            cls(paged), cls(cfg), params, n_slots, sampling=SamplingConfig(temperature=0.0), eos_id=None)

        @jax.jit
        def peek(p, cache, token, pos):
            apply = _model_apply(pool.model, p)
            return apply(cache, token[:, None], pos[:, None], jnp.ones((n_slots, 1), jnp.int32))[0][:, -1]

        t0 = time.time()
        matmuls = jax.default_matmul_precision(precision)
        matmuls.__enter__()
        cp = pool.start_chunked(prompt, args.prompt + args.steps + 1, jax.random.key(0), args.chunk_pages)
        try:
            while pool.chunk_step(cp) != "done":
                pass
            pool.finalize_chunked(1, cp, args.steps + 1)
        except BaseException:
            pool.abandon_chunked(cp)
            raise
        got, toks = [], [cp.first_int]
        for i in range(args.steps + 1):
            got.append(np.asarray(peek(pool.params, pool.cache, pool.token, pool.pos))[1])
            if i < args.steps:
                toks.append(int(np.asarray(pool.decode_steps(jax.random.split(jax.random.key(i), 1)))[1, 0]))
        matmuls.__exit__(None, None, None)
        seq = prompt + toks
        at = [args.prompt - 1] + list(range(args.prompt, args.prompt + args.steps + 1))
        t1 = time.time()
        want = reference(seq[:args.prompt + args.steps + 1], at)[: len(at)]
        first_ok = int(np.argmax(want[0])) == cp.first_int
        diff = np.stack(got) - want[1:]
        rms = np.sqrt((diff ** 2).mean(axis=-1))
        top2 = np.sort(want[1:], axis=-1)[:, -2:]
        moved = int(sum(int(np.argmax(w)) != t for w, t in zip(want[1:], toks[1:])))
        out["runs"][name] = {
            "chunks_run": cp.n_chunks, "first_token_is_the_references": first_ok,
            "rms_mean": float(rms.mean()), "rms_max": float(rms.max()), "rms_first": float(rms[0]),
            "rms_last": float(rms[-1]), "abs_max": float(np.abs(diff).max()),
            "reference_logit_std": float(want[1:].std()),
            "reference_top2_gap_min": float((top2[:, 1] - top2[:, 0]).min()),
            "served_tokens_not_the_references_first": moved,
            "program_s": t1 - t0, "reference_s": time.time() - t1,
        }
        print(f"probe: {name} " + json.dumps(out["runs"][name]), flush=True)
        del pool
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"solar_logits_probe.{args.compute}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
