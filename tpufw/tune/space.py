"""Search space for the train-step autotuner: candidates + validity.

Every performance-critical knob the bench sweeps hand-picked per machine
(docs/PERF.md: remat policy, batch/grad-accum split, CE chunk, flash
block sizes, sync window) becomes one axis of a small Cartesian space.
Two filters keep compile-and-measure tractable:

- **validity**: divisibility constraints the trainer itself enforces
  (grad_accum over the data x fsdp row sharding, flash blocks over the
  padded sequence) are checked here so invalid candidates never reach a
  compile;
- **HBM pre-pruning**: the analytic per-device estimate
  (tpufw.tools.estimate_memory.estimate_train) runs first, and any
  candidate predicted past the chip's usable HBM is pruned without
  compiling — a compile costs up to minutes, and the OOM ladder
  already showed which knobs drive the footprint.

The estimate is first-order, so pruning keeps a headroom margin and the
runner still quarantines the occasional surviving OOM (tpufw.tune.runner).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

from tpufw.tools.estimate_memory import estimate_train


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point in the search space — the knobs a winner carries.

    ``flash_bq``/``flash_bkv`` of None keep the kernel's size heuristic
    (tpufw.ops.flash._block_sizes); ``loss_chunk_size`` of None keeps
    full logits."""

    remat_policy: str = "dots"
    grad_accum: int = 1
    loss_chunk_size: Optional[int] = None
    flash_bq: Optional[int] = None
    flash_bkv: Optional[int] = None
    sync_every: int = 1
    # Pipeline schedule dimension (PipelineTrainer workloads only).
    # None = not searched / keep the trainer's own schedule — the
    # default old cache entries deserialize to, so pre-existing
    # winners stay valid. pipeline_vstages is the interleaved
    # schedule's v and meaningful only with
    # pipeline_schedule="interleaved".
    pipeline_schedule: Optional[str] = None
    pipeline_vstages: int = 1

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Candidate":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


@dataclasses.dataclass(frozen=True)
class SearchSpace:
    """Axes of the Cartesian candidate space. The defaults cover the
    knob ranges the round-2/3 hardware sweeps actually explored; tests
    and budget-tight runs pass smaller spaces."""

    remat_policies: tuple = ("dots", "attn_out", "nothing")
    grad_accums: tuple = (1, 2)
    loss_chunk_sizes: tuple = (None, 512)
    # (bq, bkv) pairs; None = the kernel's divisor heuristic.
    flash_blocks: tuple = (None, (256, 256), (512, 512))
    sync_everys: tuple = (1, 4)
    # (schedule, vstages) pairs; the lone None default keeps the axis
    # inert for non-pipeline workloads. Pipeline searches pass e.g.
    # (None, ("1f1b", 1), ("interleaved", 2), ("zb1", 1)).
    pipeline_schedules: tuple = (None,)


DEFAULT_SPACE = SearchSpace()

# Headroom on the analytic estimate: XLA fusion/padding/temp buffers add
# real variance (estimate_memory docstring), so pruning at 100% of HBM
# would compile candidates that OOM anyway.
HBM_FRACTION = 0.9


def _pad128(n: int) -> int:
    return n + (-n) % 128


def candidate_order(c: Candidate) -> tuple:
    """Deterministic measurement order: baseline-ish candidates first so
    a tight wall-clock budget always measures something runnable before
    the exotic corners."""
    return (
        c.grad_accum,
        c.sync_every,
        c.flash_bq or 0,
        c.flash_bkv or 0,
        c.remat_policy,
        c.loss_chunk_size or 0,
        c.pipeline_schedule or "",
        c.pipeline_vstages,
    )


def enumerate_candidates(
    model_cfg,
    batch_size: int,
    seq_len: int,
    space: SearchSpace | None = None,
    dp_shards: int = 1,
    n_shards: int = 1,
    hbm_bytes: Optional[float] = None,
    hbm_fraction: float = HBM_FRACTION,
    pipe_stages: int = 0,
    pipe_microbatches: int = 0,
) -> tuple[list[Candidate], list[tuple[Candidate, str]]]:
    """The space, filtered. Returns (valid, pruned-with-reason).

    ``dp_shards`` is the data x fsdp product the batch rows shard over
    (the trainer's grad_accum divisibility check); ``n_shards`` the
    param sharding degree fed to the HBM estimate. ``hbm_bytes`` of
    None disables HBM pruning (pure-validity mode, used by tests and
    CPU runs where the static chip table is meaningless).
    ``pipe_stages``/``pipe_microbatches`` describe the pipeline
    workload shape (0 = not a pipeline trainer — every non-None
    ``pipeline_schedules`` entry then prunes); they gate the schedule
    axis with the same divisibility rules PipelineConfig.validate
    enforces, so invalid schedules never reach a compile.
    """
    space = space or DEFAULT_SPACE
    # The trainer feeds tokens[:, :-1] to the model, padded to 128
    # inside the kernel — flash blocks must divide THAT length.
    t_pad = _pad128(seq_len - 1)
    uses_flash = getattr(model_cfg, "attention_backend", "") == "flash"
    uses_remat = getattr(model_cfg, "remat", False)
    policies = space.remat_policies if uses_remat else (
        getattr(model_cfg, "remat_policy", "dots"),
    )
    blocks = space.flash_blocks if uses_flash else (None,)

    valid: list[Candidate] = []
    pruned: list[tuple[Candidate, str]] = []
    seen: set = set()
    n_layers = getattr(model_cfg, "n_layers", 0)
    for policy, accum, chunk, blk, sync, sched in itertools.product(
        policies, space.grad_accums, space.loss_chunk_sizes, blocks,
        space.sync_everys, space.pipeline_schedules,
    ):
        bq, bkv = blk if blk is not None else (None, None)
        ps, pv = sched if sched is not None else (None, 1)
        cand = Candidate(
            remat_policy=policy,
            grad_accum=accum,
            loss_chunk_size=chunk,
            flash_bq=bq,
            flash_bkv=bkv,
            sync_every=sync,
            pipeline_schedule=ps,
            pipeline_vstages=pv,
        )
        if cand in seen:
            continue
        seen.add(cand)
        if ps is not None:
            if pipe_stages < 2:
                pruned.append(
                    (cand, f"pipeline schedule {ps!r} needs a pipeline "
                     "trainer (pipe_stages >= 2)")
                )
                continue
            if ps == "interleaved":
                if pv < 2:
                    pruned.append(
                        (cand, "interleaved needs pipeline_vstages "
                         ">= 2")
                    )
                    continue
                if n_layers % (pv * pipe_stages):
                    pruned.append(
                        (cand, f"n_layers={n_layers} not divisible "
                         f"into {pv}x{pipe_stages} virtual chunks")
                    )
                    continue
                if pipe_microbatches % pipe_stages:
                    pruned.append(
                        (cand, f"microbatches {pipe_microbatches} not "
                         f"divisible by {pipe_stages} stages")
                    )
                    continue
            elif pv != 1:
                pruned.append(
                    (cand, f"pipeline_vstages={pv} only applies to "
                     "the interleaved schedule")
                )
                continue
        if accum < 1 or batch_size % accum:
            pruned.append(
                (cand, f"grad_accum {accum} does not divide batch "
                 f"{batch_size}")
            )
            continue
        if (batch_size // accum) % max(dp_shards, 1):
            pruned.append(
                (cand, f"microbatch rows {batch_size // accum} do not "
                 f"divide over data x fsdp = {dp_shards}")
            )
            continue
        if chunk is not None and chunk < 1:
            pruned.append((cand, f"loss_chunk_size {chunk} < 1"))
            continue
        bad_block = next(
            (
                b for b in (bq, bkv)
                if b is not None and (b % 128 or t_pad % b)
            ),
            None,
        )
        if bad_block is not None:
            pruned.append(
                (cand, f"flash block {bad_block} is not a 128-multiple "
                 f"divisor of padded seq {t_pad}")
            )
            continue
        if hbm_bytes:
            est = estimate_train(
                model_cfg,
                batch_size,
                seq_len,
                n_shards=max(n_shards, 1),
                remat_policy=policy,
                loss_chunk_size=chunk,
                grad_accum=accum,
            )
            if est.total() > hbm_bytes * hbm_fraction:
                pruned.append(
                    (cand, f"estimated {est.total() / 2**30:.2f} GiB > "
                     f"{hbm_fraction:.0%} of "
                     f"{hbm_bytes / 2**30:.2f} GiB HBM")
                )
                continue
        valid.append(cand)
    valid.sort(key=candidate_order)
    return valid, pruned
