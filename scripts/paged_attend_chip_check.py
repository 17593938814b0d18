"""Does the paged decode kernel compile under Mosaic, agree with the
store's ladder read, and what does a call cost beside it?

Run on the chip (one process, one chip):

    python scripts/paged_attend_chip_check.py

For each case (a cell's pool at its published widths: slots, query / kv
/ stored heads, context, live rows and their lengths as the cell's
traced run shows them) it builds random arenas, a shuffled page table
and segment ids with a left pad in each row, runs
``tpufw.ops.paged_attend.paged_attention`` and the read it replaces (the
gather of every slot's whole row through the table, then
``xla_attention``: the top rung of both ladders), compares the live
rows (``max|kernel - xla| <= 2e-2 * max|xla|``: both round the
probabilities to bfloat16, in another order), checks that rows not live
come back exact zeros, and times both: a jitted loop of 20 dependent
calls, the best of three. ``gbps`` is the live rows' own pages, K and V,
over the kernel's time: its share of the 819 GB/s the chip reads.

One JSON line per case; exit 1 if any case failed. ``--cpu-tiny`` runs
small shapes through the Pallas interpreter to debug the script itself;
``--blocks a,b`` times other block sizes than the kernel's own.
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
LOOP = 20

# name: slots, q heads, kv heads, stored heads, head dim, page, context,
# live rows' lengths
CASES = {
    "olmoh-reason-pool": (16, 30, 30, 32, 128, 16, 4096,
                          (2901, 2200, 1710, 1260, 820, 391)),
    "olmoh-full": (16, 30, 30, 32, 128, 16, 4096, (4096,) * 16),
    "falconh1-instruct-burst": (32, 20, 4, 4, 128, 16, 2048,
                                (1100, 870, 640, 520, 470, 390, 300, 210, 90)),
    "mixtral-prefill-heavy": (16, 32, 8, 8, 128, 16, 8192, (5300, 2100)),
    "laguna-repo-context": (8, 48, 8, 8, 128, 16, 16384, (15300, 4100)),
    "solar2-longdoc-answers": (8, 64, 8, 8, 128, 16, 8192, (6200, 2300)),
}
TINY = {
    "tiny-mha-padded": (4, 6, 6, 8, 32, 4, 64, (64, 33, 5)),
    "tiny-gqa": (4, 10, 2, 2, 32, 8, 128, (128, 70)),
}


def _inputs(case, seed=0):
    import jax.numpy as jnp
    import numpy as np

    b, h, kvh, stored, hd, page, s, live = case
    rng = np.random.default_rng(seed)
    per_row = s // page
    n_pages = b * per_row + 1
    shape = (n_pages, page, stored, hd)
    k_arena = jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
    v_arena = jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
    table = 1 + rng.permutation(n_pages - 1)[: b * per_row].reshape(b, per_row)
    lens = np.zeros((b,), np.int32)
    rows = rng.permutation(b)[: len(live)]
    lens[rows] = live
    # A left pad of up to a page in each row: segment 0 inside the row.
    same = np.arange(s)[None, :] >= rng.integers(0, page, (b, 1))
    q = jnp.asarray(rng.standard_normal((b, h, hd), np.float32), jnp.bfloat16)
    return (q, k_arena, v_arena, jnp.asarray(table, jnp.int32),
            jnp.asarray(lens), jnp.asarray(same))


def _ladder(q, k_arena, v_arena, table, lens, same, *, kv_heads):
    """The read the kernel replaces, at the top rung of both ladders."""
    import jax.numpy as jnp

    from tpufw.ops.attention import xla_attention

    b, s = same.shape
    view = lambda a: a[table].reshape((b, s) + a.shape[2:])[:, :, :kv_heads]
    return xla_attention(
        q[:, None], view(k_arena), view(v_arena), causal=True,
        segment_ids=jnp.ones((b, 1), jnp.int32),
        kv_segment_ids=same.astype(jnp.int32),
        q_positions=jnp.maximum(lens - 1, 0)[:, None],
    )[:, 0]


def _time(fn, args):
    """Seconds a call of ``fn``, from a jitted loop of dependent calls."""
    import jax

    def loop(q, *rest):
        def body(_, q):
            return q + 0 * fn(q, *rest)

        return jax.lax.fori_loop(0, LOOP, body, q)

    run = jax.jit(loop)
    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t)
    return best / LOOP


def check(name, case, interpret, blocks):
    import functools

    import jax
    import numpy as np

    from tpufw.ops import paged_attend

    b, h, kvh, stored, hd, page, s, live = case
    args = _inputs(case)
    lens = np.asarray(args[4])
    out = {"case": name, "slots": b, "heads": [h, kvh, stored],
           "context": s, "live": list(live)}
    ok = True
    ref = functools.partial(_ladder, kv_heads=kvh)
    want = np.asarray(jax.jit(ref)(*args), np.float32)
    for block_rows in blocks:
        kernel = functools.partial(
            paged_attend.paged_attention, kv_heads=kvh,
            interpret=interpret, block_rows=block_rows,
        )
        got = np.asarray(jax.jit(kernel)(*args), np.float32)
        err = float(np.abs(got - want)[lens > 0].max())
        scale = float(np.abs(want[lens > 0]).max())
        zeros = bool((got[lens == 0] == 0).all())
        key = f"block_{block_rows}"
        out[key] = {"max_err": err, "ref_max": scale, "dead_rows_zero": zeros}
        ok &= zeros and err <= REL_TOL * scale
        if not interpret:
            sec = _time(kernel, args)
            pages = sum(-(-n // page) for n in live)
            out[key]["kernel_us"] = round(sec * 1e6, 1)
            out[key]["gbps"] = round(
                pages * page * stored * hd * 2 * 2 / sec / 1e9, 1
            )
    if not interpret:
        out["ladder_us"] = round(_time(ref, args) * 1e6, 1)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-tiny", action="store_true")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--cases", default="")
    args = ap.parse_args()

    import jax

    from tpufw.ops import paged_attend

    if not args.cpu_tiny and jax.default_backend() != "tpu":
        print("no TPU: run on the chip, or --cpu-tiny", file=sys.stderr)
        return 2
    cases = TINY if args.cpu_tiny else CASES
    if args.cases:
        cases = {n: cases[n] for n in args.cases.split(",")}
    blocks = [int(x) for x in args.blocks.split(",") if x] or [
        256 if args.cpu_tiny else paged_attend.BLOCK_ROWS
    ]
    ok = True
    for name, case in cases.items():
        ok &= check(name, case, args.cpu_tiny, blocks)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
