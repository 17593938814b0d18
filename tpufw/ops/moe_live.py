"""A pool's decode step over its live rows' expert assignments (Pallas TPU).

``tpufw.models.mixtral.MoEMLP._sorted_experts`` hands ``jax.lax.ragged_dot``
all ``k x B`` assignments of a pool's ``B`` rows and all E groups, however
few rows are live: the dead rows' sort last under a sentinel and ride in
group E-1 with a zero gate, so expert E-1 is streamed for nothing, and the
call pays for its groups and row tiles whatever the live rows need
(measured, PERF.md section 5: 270 us a call in DeepSeek-V2-Lite's 64-slot
pool with 2-5 rows live, where the bytes those rows need take 90). This
kernel is handed the assignments of the LIVE rows alone, sorted by expert
and padded to a static length A, and their count n: it fetches an expert's
weights only where an assignment names it and computes nothing past n. It
serves the call a pool's decode step makes (one token a row, some rows
dead) while no more than ``live_rows`` of them are live; above that, and
in every other call, ``ragged_dot`` runs as it did. The mathematics and the
precision are ``ragged_dot``'s: operands in the activations' dtype, float32
accumulation, the result in the activations' dtype.

HOW AN EXPERT IS CONTRACTED. Grid ``(out tiles, in tiles, A)``, the
assignments innermost. The weight block of step a is ``W[eid[a]]``'s tile,
its expert id read from SMEM in the ``index_map`` (scalar prefetch): equal
experts are consecutive, and a block whose index did not change is not
fetched again, so each touched expert's tile is read once; the ids at and
past n are padded with the last live one, so those steps fetch nothing.
The stacks go in as they are stored, ``[E, in, out]``. At the FIRST step
of a run of equal ids the kernel contracts ALL A rows with that expert's
tile on the MXU and adds the rows that are this expert's into a float32
accumulator ``[A, out tile]`` (the others' products are dropped, not
multiplied by zero: what an expert holds reaches its own rows alone); the
other steps of the run, and every step past n, do nothing. The MXU does A
times the multiplies a row needs and is idle in a decode step anyway. Gate
and up share the rows and the ids: given both stacks, one call keeps two
accumulators and writes ``silu(gate) * up``, each rounded to the
activations' dtype first, as two ``ragged_dot``s' results are.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpufw.ops.kv_store import row_ladder

#: Bytes of one weight block (two are in flight an operand). A whole
#: expert of DeepSeek-V2-Lite is 5.77 MB and goes as one block; a wider
#: one is cut along the contraction (contiguous rows) or, where that
#: leaves a shorter block, along the output. Measured on the chip (PR 46,
#: scripts/moe_live_chip_check.py, a layer's two calls at the most live
#: rows each pool hands over, us, blocks of 3.7 / 6 / 12 MB):
#: DeepSeek-V2-Lite's 1,013 / 999 / 1,000, Mixtral's 1,911 / 1,919 /
#: 1,919, Solar-Open2's 378 / 381 / 383, Laguna's 294 / 298 / 295.
BLOCK_BYTES = 6 * 1024 * 1024


def live_rows(n_rows: int) -> int:
    """R: a pool's step of ``n_rows`` rows runs the kernel while at most
    R of them are live. The rung of ``kv_store.row_ladder`` under the
    whole pool (B/8), a function of the pool's width alone; 0 where the
    ladder has none (the step then keeps ``ragged_dot``, as ``generate``'s
    few rows do). ONE rule for the program (``MoEMLP``) and for the host,
    which counts the steps that took it (tpufw.workloads.serve)."""
    rungs = row_ladder(n_rows)
    return rungs[0] if len(rungs) > 1 else 0


def takes(rows: int, live):
    """Whether a step with ``live`` live rows runs the kernel in a pool
    whose ``pool_rows`` is ``rows``. ``live`` is a Python int (the host)
    or a traced scalar (the program): one rule for both."""
    return rows > 0 and live <= rows


def serves(d_in: int, d_out: int, dtype) -> bool:
    """Whether the kernel is built for stacks ``[E, d_in, d_out]`` in
    ``dtype``: on the TPU, float operands, both widths whole 128-lane
    vectors. Off the chip (the rule ``paged_attend.serves`` follows)
    ``ragged_dot`` runs: it is the kernel's reference in the tests."""
    return (
        jax.default_backend() == "tpu"
        and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
        and d_in % 128 == 0
        and d_out % 128 == 0
    )


def pool_rows(cfg, n_rows: int, d_in: int, d_ff: int) -> int:
    """``live_rows`` of a pool's step through ``cfg``'s routed experts
    ``[E, d_in, d_ff]``, or 0 where that step never runs the kernel: by
    what the layer can observe and nothing a caller sets. Sorted
    dispatch over float stacks held whole (not a scan's slice, which a
    custom call would copy), no LoRA beside them, at the kernel's widths
    on the chip."""
    if (
        getattr(cfg, "moe_dispatch", "einsum") != "sorted"
        or getattr(cfg, "quantized_weights", False)
        or getattr(cfg, "lora_rank", 0)
        or getattr(cfg, "scan_layers", False)
        or not serves(d_in, d_ff, cfg.dtype)
    ):
        return 0
    return live_rows(n_rows)


def expert_widths(params) -> Optional[tuple]:
    """``(d_in, d_ff)`` of the routed experts' stacks in ``params`` (the
    ``w_gate`` leaves ``MoEMLP`` declares), None for a model without
    any: what the host reads the program's rule from."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        # A boxed leaf's path ends in the box's own key.
        if any(getattr(k, "key", None) == "w_gate" for k in path):
            return int(leaf.shape[-2]), int(leaf.shape[-1])
    return None


def _tiles(d_in: int, d_out: int, itemsize: int, block_bytes: int):
    """(tk, tn) of a weight block: the whole expert where it fits, else
    the cut (whole 128-lane vectors that divide the width) that leaves
    the larger block: rows of the contraction, contiguous in HBM, before
    columns of the output; both where neither alone fits."""
    def cut(width, other):
        return max(
            (
                t for t in range(128, width + 1, 128)
                if width % t == 0 and t * other * itemsize <= block_bytes
            ),
            default=0,
        )

    if d_in * d_out * itemsize <= block_bytes:
        return d_in, d_out
    tk, tn = cut(d_in, d_out), cut(d_out, d_in)
    if not (tk or tn):
        return 128, cut(d_out, 128) or 128
    return (tk, d_out) if tk * d_out >= d_in * tn else (d_in, tn)


def _kernel(eid_ref, n_ref, x_ref, ids_ref, *refs, fused: bool):
    if fused:
        w_ref, u_ref, o_ref, acc, acc_u = refs
    else:
        (w_ref, o_ref, acc), u_ref, acc_u = refs, None, None
    kk, a = pl.program_id(1), pl.program_id(2)
    n = n_ref[0]

    @pl.when((kk == 0) & (a == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)
        if fused:
            acc_u[...] = jnp.zeros_like(acc_u)

    e = eid_ref[a]
    first = (a < n) & ((a == 0) | (e != eid_ref[jnp.maximum(a - 1, 0)]))

    @pl.when(first)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, ids_ref.shape, 0)
        mine = (ids_ref[...] == e) & (rows < n)  # [A, 1]
        x = x_ref[...]
        for w, into in ((w_ref, acc), (u_ref, acc_u))[: 1 + fused]:
            into[...] += jnp.where(
                mine,
                jnp.dot(x, w[0], preferred_element_type=jnp.float32),
                0.0,
            )

    @pl.when((kk == pl.num_programs(1) - 1) & (a == pl.num_programs(2) - 1))
    def _():
        out = acc[...].astype(o_ref.dtype)
        if fused:
            up = acc_u[...].astype(o_ref.dtype).astype(jnp.float32)
            out = (jax.nn.silu(out.astype(jnp.float32)) * up).astype(
                o_ref.dtype
            )
        o_ref[...] = out


# Jitted of its own, as ``paged_attend.paged_attention`` is: a model's
# layers and a pool's decode programs share ONE trace a shape.
@functools.partial(
    jax.jit, static_argnames=("interpret", "block_bytes")
)
def live_experts(
    xs: jax.Array,
    eid: jax.Array,
    n: jax.Array,
    w: jax.Array,
    w_up: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
    block_bytes: int = BLOCK_BYTES,
) -> jax.Array:
    """``y[a] = xs[a] @ w[eid[a]]`` for ``a < n``; rows at and past n
    are not computed and come back exact zeros.

    ``xs`` [A, d_in], the assignments' rows; ``eid`` [A] int32, their
    expert ids in ``[0, E)``, ascending over the first ``n`` (equal ids
    consecutive; what lies at and past n is not read); ``n`` an int32
    scalar; ``w`` [E, d_in, d_out] as stored. With ``w_up`` (the same
    shape) the result is ``silu(xs @ w[eid]) * (xs @ w_up[eid])``, both
    products rounded to ``xs.dtype`` first. Returns [A, d_out] in
    ``xs.dtype``. No expert that ``eid[:n]`` does not name is read (n = 0
    fetches expert 0's tiles and computes nothing)."""
    a, d_in = xs.shape
    e, _, d_out = w.shape
    fused = w_up is not None
    # Whole sublane tiles of rows (16 of bfloat16, 8 of float32).
    tile = 8 * 4 // jnp.dtype(xs.dtype).itemsize
    a_pad = -(-a // tile) * tile
    tk, tn = _tiles(d_in, d_out, jnp.dtype(w.dtype).itemsize, block_bytes)
    n = jnp.asarray(n, jnp.int32).reshape(1)
    at = jnp.arange(a, dtype=jnp.int32)
    eid = eid.astype(jnp.int32)
    # Steps at and past n name the last live block: nothing is fetched.
    eid = jnp.clip(
        jnp.where(at < n, eid, eid[jnp.maximum(n[0] - 1, 0)]), 0, e - 1
    )
    weight = pl.BlockSpec(
        (1, tk, tn), lambda j, kk, i, eid_ref, n_ref: (eid_ref[i], kk, j)
    )
    stacks = (w, w_up) if fused else (w,)
    need = (
        2 * len(stacks) * tk * tn * jnp.dtype(w.dtype).itemsize
        + len(stacks) * a_pad * tn * 4
        + 2 * a_pad * (tk + tn) * jnp.dtype(xs.dtype).itemsize
    )
    out = pl.pallas_call(
        functools.partial(_kernel, fused=fused),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(d_out // tn, d_in // tk, a),
            in_specs=[
                pl.BlockSpec((a_pad, tk), lambda j, kk, i, *_: (0, kk)),
                pl.BlockSpec((a_pad, 1), lambda j, kk, i, *_: (0, 0)),
            ] + [weight] * len(stacks),
            out_specs=pl.BlockSpec((a_pad, tn), lambda j, kk, i, *_: (0, j)),
            scratch_shapes=[pltpu.VMEM((a_pad, tn), jnp.float32)]
            * len(stacks),
        ),
        out_shape=jax.ShapeDtypeStruct((a_pad, d_out), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=int(need * 1.25) + (4 << 20),
        ),
        interpret=interpret,
        name="moe_live_gate_up" if fused else "moe_live",
    )(
        eid, n,
        jnp.pad(xs, ((0, a_pad - a), (0, 0))),
        jnp.pad(eid, (0, a_pad - a))[:, None],
        *stacks,
    )
    return out[:a]
