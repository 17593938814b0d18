"""What do a pool's decode step's expert matmuls cost today, what is that
cost made of, and what does the live-assignment kernel cost beside them?

Run on the chip (one process, one chip):

    python scripts/moe_live_chip_check.py

For each case (a benchmark cell's routed experts at their published
widths: experts held, ``d_model``, expert width, experts a token, the
pool's slots) and each count of live rows (1, 2, 4, 8, R and R + 1 where
the pool holds them, R = ``tpufw.ops.moe_live.live_rows``) it draws each
live row's k distinct experts from the seed and times, as a jitted loop
of 50 dependent calls, the best of three:

- ``ragged_today``: ``jax.lax.ragged_dot`` over all ``k x B`` rows with
  ``group_sizes`` as ``route_topk_sorted`` makes them, the dead rows'
  assignments riding in group E-1 (the gate / up call, ``[k x B, d] x
  [E, d, f]``; ``ragged_down_today`` the down call);
- ``ragged_no_dead``: the same rows, the dead ones in no group (E-1
  holds its live assignments alone);
- ``ragged_live_rows``: the rows cut to the ``k x R`` the kernel is
  handed, the dead ones in no group;
- ``kernel``, ``kernel_gate_up``, ``kernel_down``:
  ``tpufw.ops.moe_live.live_experts`` with one stack, with gate and up
  fused, and at the down call's shape.

``need_mb`` is the bytes of the distinct experts the live rows name, one
stack; each ``*_gbps`` is those bytes (two stacks for the fused call) over
the call's time: its share of the 819 GB/s the chip reads. The kernel's
result is compared with ``ragged_dot``'s on the live assignments
(``max|kernel - ragged| <= 2e-2 * max|ragged|``) and its rows past the
live count must be exact zeros. ``layer_us`` sums a layer's calls: three
``ragged_dot``s today, the fused call and the down call after.

One JSON line per case and live count; exit 1 if any failed.
``--cpu-tiny`` runs small shapes through the Pallas interpreter to debug
the script itself; ``--blocks a,b`` times other block sizes (bytes) than
the kernel's own; ``--cases`` picks cases.
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
LOOP = 50

# name: experts held, d_model, expert width, experts a token, pool slots
CASES = {
    "dsv2l-decode-long": (64, 2048, 1408, 6, 64),
    "mixtral-prefill-heavy": (8, 4096, 14336, 2, 16),
    "solar2-longdoc-answers": (40, 4096, 1280, 8, 8),
    "laguna-repo-context": (32, 3072, 1024, 10, 8),
}
TINY = {
    "tiny-16-slots": (8, 128, 256, 2, 16),
    "tiny-8-slots": (4, 256, 128, 3, 8),
}


def _assignments(case, live, seed):
    """(sorted expert ids of the live rows' assignments, group sizes as
    a step makes them today, group sizes with the dead rows in none)."""
    import numpy as np

    e, _, _, k, b = case
    rng = np.random.default_rng(seed)
    eid = np.sort(
        np.concatenate([rng.permutation(e)[:k] for _ in range(live)])
    ).astype(np.int32)
    alone = np.bincount(eid, minlength=e).astype(np.int32)
    today = alone.copy()
    today[e - 1] += k * (b - live)
    return eid, today, alone


def _timer(fn):
    """``time(x, *rest)``: seconds a call of ``fn(x, *rest)``, from a
    jitted loop of dependent calls, compiled once a shape."""
    import jax

    @jax.jit
    def run(x, *rest):
        def body(_, x):
            return x + (0 * fn(x, *rest)[:, :1]).astype(x.dtype)

        return jax.lax.fori_loop(0, LOOP, body, x)

    def timed(x, *rest):
        jax.block_until_ready(run(x, *rest))
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            jax.block_until_ready(run(x, *rest))
            best = min(best, time.perf_counter() - t)
        return best / LOOP

    return timed


def check(name, case, interpret, blocks, seed=0):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpufw.ops import moe_live

    e, d, f, k, b = case
    r = moe_live.live_rows(b)
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    stack = lambda *shape: jnp.asarray(
        rng.standard_normal(shape, np.float32) / np.sqrt(shape[1]), bf
    )
    w_gate, w_up, w_down = stack(e, d, f), stack(e, d, f), stack(e, f, d)
    rows = lambda n, width: jnp.asarray(
        rng.standard_normal((n, width), np.float32), bf
    )
    x_all, h_all = rows(k * b, d), rows(k * b, f)
    a = k * r
    ragged = jax.lax.ragged_dot
    time_ragged = _timer(ragged)
    kernels = {
        bb: functools.partial(
            moe_live.live_experts, interpret=interpret, block_bytes=bb
        )
        for bb in blocks
    }
    time_kernel = {bb: _timer(fn) for bb, fn in kernels.items()}
    ok_all = True
    for live in sorted({n for n in (1, 2, 4, 8, r, r + 1) if 0 < n <= b}):
        eid, today, alone = _assignments(case, live, seed + live)
        n = len(eid)
        distinct = len(set(eid.tolist()))
        need = distinct * d * f * 2
        out = {"case": name, "experts": e, "d": d, "f": f, "k": k,
               "slots": b, "R": r, "live": live, "assignments": n,
               "distinct_experts": distinct,
               "need_mb": round(need / 1e6, 2)}
        ok = True
        gs_today, gs_alone = jnp.asarray(today), jnp.asarray(alone)
        us = lambda sec: round(sec * 1e6, 1)
        gbps = lambda sec, stacks=1: round(stacks * need / sec / 1e9, 1)
        if not interpret:
            t = {
                "ragged_today": time_ragged(x_all, w_gate, gs_today),
                "ragged_no_dead": time_ragged(x_all, w_gate, gs_alone),
                "ragged_down_today": time_ragged(h_all, w_down, gs_today),
            }
            out["layer_us_today"] = us(
                2 * t["ragged_today"] + t["ragged_down_today"]
            )
        if live <= r:
            # What the kernel is handed: the live assignments first,
            # padded to A.
            xs, hs = x_all[:a], h_all[:a]
            ids = jnp.asarray(np.pad(eid, (0, a - n), constant_values=e))
            want = np.asarray(ragged(xs, w_gate, gs_alone), np.float32)[:n]
            for bb, kernel in kernels.items():
                got = np.asarray(kernel(xs, ids, n, w_gate), np.float32)
                err = float(np.abs(got[:n] - want).max())
                scale = float(np.abs(want).max())
                zeros = bool((got[n:] == 0).all())
                key = f"block_{bb}"
                out[key] = {"max_err": err, "ref_max": scale,
                            "past_n_zero": zeros}
                ok &= zeros and err <= REL_TOL * scale
                if not interpret:
                    timed = time_kernel[bb]
                    one = timed(xs, ids, n, w_gate)
                    both = timed(xs, ids, n, w_gate, w_up)
                    down = timed(hs, ids, n, w_down)
                    out[key].update(
                        kernel_us=us(one), kernel_gbps=gbps(one),
                        kernel_gate_up_us=us(both),
                        kernel_gate_up_gbps=gbps(both, 2),
                        kernel_down_us=us(down),
                        kernel_down_gbps=gbps(down),
                        layer_us=us(both + down),
                    )
            if not interpret:
                t["ragged_live_rows"] = time_ragged(xs, w_gate, gs_alone)
        if not interpret:
            for key, sec in t.items():
                out[key + "_us"] = us(sec)
                out[key + "_gbps"] = gbps(sec)
        out["ok"] = ok
        ok_all &= ok
        print(json.dumps(out), flush=True)
    return ok_all


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-tiny", action="store_true")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--cases", default="")
    args = ap.parse_args()

    import jax

    from tpufw.ops import moe_live

    if not args.cpu_tiny and jax.default_backend() != "tpu":
        print("no TPU: run on the chip, or --cpu-tiny", file=sys.stderr)
        return 2
    cases = TINY if args.cpu_tiny else CASES
    if args.cases:
        cases = {n: cases[n] for n in args.cases.split(",")}
    blocks = [int(x) for x in args.blocks.split(",") if x] or [
        128 * 128 * 2 if args.cpu_tiny else moe_live.BLOCK_BYTES
    ]
    ok = True
    for name, case in cases.items():
        ok &= check(name, case, args.cpu_tiny, blocks)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
