"""Where do the paged and the contiguous serving postures part, and by how much?

    python scripts/paged_parity_probe.py          # on the chip

Both postures decode the same greedy prompt through the real scheduler
(``tpufw.workloads.serve._SlotScheduler``): contiguous pool + monolithic
prefill, then ``page=16, prefill_chunk_pages=2``. ``sample_token`` is
wrapped so every call also reports its top-4 logits to the host; the
probe prints, per posture, the ids, and at the first step where the ids
differ the two top-4 lists — enough to tell a bf16 near-tie between two
differently compiled programs (the same candidates, logits a rounding
step apart, the top two closer than that) from a bug (different
candidates, or logits far apart).

The prompt is the chip smoke's request 1 (41 tokens, 32 new).
``--cpu-tiny`` rehearses at llama3_tiny on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

RECORDS: list = []


def _install_spy():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpufw.infer import pages, sampling, slots

    # ``tpufw.infer.generate`` the attribute is the function; the module
    # whose global the prefill jit reads is in sys.modules.
    generate = sys.modules["tpufw.infer.generate"]
    orig = sampling.sample_token

    def record(vals, idx):
        RECORDS.append((np.asarray(vals), np.asarray(idx)))

    def sample_token(logits, cfg, rng, seen=None):
        vals, idx = jax.lax.top_k(logits.astype(jnp.float32), 4)
        jax.debug.callback(record, vals, idx, ordered=True)
        return orig(logits, cfg, rng, seen)

    for mod in (generate, pages, slots):
        mod.sample_token = sample_token


def _run(model, params, prompt, max_new, **posture):
    from tpufw.workloads.serve import _SlotScheduler

    RECORDS.clear()
    sched = _SlotScheduler(model, params, **posture)
    outs, _ = sched.submit([prompt], max_new)
    import jax

    jax.effects_barrier()
    # B=1 records come from prefill programs (chunked prefill samples once
    # per chunk and keeps the LAST draw); the rest are decode steps, whose
    # row 0 is slot 0 — the only occupied slot. The decode chunks run past
    # the row's budget; the first max_new - 1 steps produced the ids.
    prefill = [r for r in RECORDS if r[0].shape[0] == 1]
    decode = [r for r in RECORDS if r[0].shape[0] != 1]
    steps = [
        {
            "top_ids": idx[0].tolist(),
            "top_logits": [float(v) for v in vals[0]],
        }
        for vals, idx in [prefill[-1], *decode[: max_new - 1]]
    ]
    return outs[0], steps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.update(TPUFW_MODEL="llama3_tiny", TPUFW_MAX_SEQ_LEN="512")
    import jax
    import numpy as np

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}))
    if dev.platform != "tpu" and not args.cpu_tiny:
        print("paged_parity_probe: no TPU", file=sys.stderr)
        return 2
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()
    _install_spy()
    from tpufw.workloads.serve import build_generator

    model, params, cfg, _ = build_generator()
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=n).tolist() for n in (20, 41, 64)
    ]
    prompt, max_new = prompts[1], 32
    ids_c, steps_c = _run(model, params, prompt, max_new, page=0)
    print(json.dumps({"posture": "contiguous", "ids": ids_c}))
    # The paged pool alone, then with chunked prefill on top: which of the
    # two moves the numbers.
    for name, posture in (
        ("paged", dict(page=16, prefill_chunk_pages=0)),
        ("paged+chunked", dict(page=16, prefill_chunk_pages=2)),
    ):
        ids_p, steps_p = _run(model, params, prompt, max_new, **posture)
        print(json.dumps({"posture": name, "ids": ids_p}))
        for ids, steps in ((ids_c, steps_c), (ids_p, steps_p)):
            # The record IS the program's argmax: the spy saw what sampled.
            assert [s["top_ids"][0] for s in steps] == ids, name
        first = next(
            (i for i in range(max_new) if ids_c[i] != ids_p[i]), None
        )
        upto = max_new if first is None else first
        print(
            json.dumps(
                {
                    "posture": name,
                    "first_difference_at": first,
                    # Step 0 is the prefill's draw, the rest are decode.
                    "top1_logit_drift_per_step": [
                        abs(c["top_logits"][0] - p["top_logits"][0])
                        for c, p in zip(steps_c[:upto], steps_p[:upto])
                    ],
                    "contiguous_top2_gap_per_step": [
                        c["top_logits"][0] - c["top_logits"][1]
                        for c in steps_c[:upto]
                    ],
                }
            )
        )
        if first is not None:
            print(json.dumps({"step": first, "contiguous": steps_c[first]}))
            print(json.dumps({"step": first, name: steps_p[first]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
