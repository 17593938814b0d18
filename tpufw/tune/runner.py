"""Compile-and-measure autotuning: time real train steps per candidate.

The search is bench.py-shaped: each surviving candidate from
tpufw.tune.space builds a real Trainer, compiles the real jitted step,
runs a few timed steps, and reports the median. Selection is purely
empirical — no cost model picks the winner, the clock does. Two rules
keep a search from ever being worse than not searching:

- **quarantine, never abort**: a candidate that fails to compile or
  OOMs (the analytic pre-prune is first-order) is recorded and skipped;
  the search continues with what remains;
- **wall-clock budget**: once the budget is spent, remaining candidates
  are marked skipped — but the first candidate always runs, so a
  too-tight budget degrades to "measure the baseline", not "crash".

Winners persist via tpufw.tune.cache; ``apply_autotune`` is the
Trainer-facing entry consulted from ``Trainer.run`` when
``TrainerConfig.autotune != "off"``.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import time
from typing import Callable, Optional

from tpufw.tune import cache as tune_cache
from tpufw.tune.space import (
    Candidate,
    SearchSpace,
    enumerate_candidates,
)

_FLASH_ENV = ("TPUFW_FLASH_BQ", "TPUFW_FLASH_BKV")


@dataclasses.dataclass
class Trial:
    candidate: Candidate
    status: str  # "ok" | "quarantined" | "skipped_budget"
    median_step_s: Optional[float] = None
    error: Optional[str] = None


@dataclasses.dataclass
class TuneResult:
    best: Optional[Candidate]
    best_step_s: Optional[float]
    trials: list
    pruned: list
    tune_s: float
    cache_hit: bool = False
    cache_key: Optional[str] = None
    mode: str = "search"

    def summary(self) -> dict:
        """The JSON-able record bench.py and train logs echo."""
        return {
            "mode": self.mode,
            "cache_hit": self.cache_hit,
            "cache_key": self.cache_key,
            "tune_s": round(self.tune_s, 3),
            "config": self.best.as_dict() if self.best else None,
            "best_step_s": self.best_step_s,
            "n_measured": sum(1 for t in self.trials if t.status == "ok"),
            "n_quarantined": sum(
                1 for t in self.trials if t.status == "quarantined"
            ),
            "n_pruned": len(self.pruned),
        }


def search(
    candidates: list[Candidate],
    measure_fn: Callable[[Candidate], float],
    budget_s: float = 120.0,
    pruned: Optional[list] = None,
    events=None,
) -> TuneResult:
    """Measure candidates under a wall-clock budget; best = min median.

    ``measure_fn(candidate) -> median_step_seconds`` does all the real
    work (tests inject a fake); any exception it raises quarantines that
    candidate only. The first candidate is always measured even if the
    budget is already blown, so the result is never empty-by-budget.
    ``events`` (tpufw.obs event log) gets one ``tune_trial`` line per
    candidate as it resolves — a hung measure is then localizable to
    the exact candidate from the event stream.
    """
    if events is None:
        from tpufw.obs import events as events_mod

        events = events_mod.NULL
    t0 = time.perf_counter()
    trials: list[Trial] = []
    measured_any = False

    def log_trial(t: Trial) -> None:
        trials.append(t)
        events.emit(
            "tune_trial",
            trial=len(trials) - 1,
            status=t.status,
            candidate=t.candidate.as_dict(),
            median_step_s=t.median_step_s,
            error=t.error,
        )

    for cand in candidates:
        if measured_any and time.perf_counter() - t0 > budget_s:
            log_trial(Trial(cand, "skipped_budget"))
            continue
        try:
            med = float(measure_fn(cand))
        except Exception as e:  # noqa: BLE001 — quarantine, never abort
            log_trial(
                Trial(cand, "quarantined", error=f"{type(e).__name__}: {e}")
            )
            continue
        log_trial(Trial(cand, "ok", median_step_s=med))
        measured_any = True
    ok = [t for t in trials if t.status == "ok"]
    best = min(ok, key=lambda t: t.median_step_s, default=None)
    return TuneResult(
        best=best.candidate if best else None,
        best_step_s=best.median_step_s if best else None,
        trials=trials,
        pruned=list(pruned or []),
        tune_s=time.perf_counter() - t0,
    )


def _set_flash_env(bq: Optional[int], bkv: Optional[int]) -> dict:
    """Point the kernel's env override at the candidate's blocks (None
    pops, restoring the size heuristic). Returns the previous values so
    measurement can restore them."""
    prev = {k: os.environ.get(k) for k in _FLASH_ENV}
    for k, v in zip(_FLASH_ENV, (bq, bkv)):
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    return prev


def _restore_env(prev: dict) -> None:
    for k, v in prev.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _candidate_model(model, cand: Candidate):
    """The model to measure/run with: same module unless the remat
    policy differs (apply_fn bakes the policy in, so that needs a
    rebuilt module)."""
    mcfg = getattr(model, "cfg", None)
    if (
        mcfg is None
        or not getattr(mcfg, "remat", False)
        or getattr(mcfg, "remat_policy", None) == cand.remat_policy
    ):
        return model
    return type(model)(
        dataclasses.replace(mcfg, remat_policy=cand.remat_policy)
    )


def candidate_program_name(cand: Candidate) -> str:
    """Stable perf-observatory program name for one tune candidate —
    the key the measured trial's cost/MFU lands under in
    ``programs.json`` (tpufw.obs.perf), so "did the autotuner win"
    reads as a utilization comparison, not just step wall."""
    parts = [
        f"tune:{cand.remat_policy}",
        f"ga{cand.grad_accum}",
        f"lc{cand.loss_chunk_size}",
    ]
    if cand.flash_bq or cand.flash_bkv:
        parts.append(f"fb{cand.flash_bq}x{cand.flash_bkv}")
    if cand.pipeline_schedule:
        parts.append(
            f"{cand.pipeline_schedule}v{cand.pipeline_vstages}"
        )
    return "-".join(parts)


def make_measure_fn(
    model,
    trainer_cfg,
    mesh,
    tx=None,
    n_steps: int = 3,
    warmup_steps: int = 1,
    seed: int = 0,
    perf=None,
) -> Callable[[Candidate], float]:
    """A measure_fn that builds a REAL Trainer per candidate and times
    the REAL jitted step on synthetic tokens. Each candidate gets a
    fresh state (fresh params + optimizer): steps/candidate is tiny, so
    init cost dominates fairness concerns less than sharing donated
    state across incompatible compiled steps would."""
    import jax
    import numpy as np

    from tpufw.train.trainer import Trainer

    vocab = getattr(getattr(model, "cfg", None), "vocab_size", 32000)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, vocab, (trainer_cfg.batch_size, trainer_cfg.seq_len),
        dtype=np.int32,
    )

    def measure(cand: Candidate) -> float:
        cfg = dataclasses.replace(
            trainer_cfg,
            grad_accum=cand.grad_accum,
            loss_chunk_size=cand.loss_chunk_size,
            sync_every=1,
            checkpoint_dir=None,
            profile_dir=None,
            eval_every=0,
            handle_preemption=False,
            autotune="off",
        )
        prev = _set_flash_env(cand.flash_bq, cand.flash_bkv)
        try:
            trainer = Trainer(_candidate_model(model, cand), cfg,
                              mesh=mesh, tx=tx)
            trainer.init_state(seed=seed)
            batch = {"tokens": tokens}
            from tpufw.parallel.context import use_mesh

            with use_mesh(mesh):
                step = trainer.compiled_step(batch)
                state = trainer.state
                if perf is not None:
                    perf.observe_jit(
                        candidate_program_name(cand), step, (state, batch)
                    )
                for _ in range(max(warmup_steps, 1)):
                    state, m = step(state, batch)
                    jax.block_until_ready(m["loss"])
                times = []
                for _ in range(max(n_steps, 1)):
                    t0 = time.perf_counter()
                    state, m = step(state, batch)
                    jax.block_until_ready(m["loss"])
                    times.append(time.perf_counter() - t0)
            med = statistics.median(times)
            if perf is not None:
                perf.record_wall(candidate_program_name(cand), med)
            return med
        finally:
            _restore_env(prev)

    return measure


def make_pipeline_measure_fn(
    model_cfg,
    pipe,
    trainer_cfg,
    mesh_cfg,
    tx=None,
    n_steps: int = 3,
    warmup_steps: int = 1,
    seed: int = 0,
    perf=None,
) -> Callable[[Candidate], float]:
    """make_measure_fn's PipelineTrainer twin: a fresh trainer per
    candidate so each schedule's shard_map step compiles against its
    own stage layout. The candidate's schedule rides in via the
    TrainerConfig knob (the ctor's single override point), so the
    measured step is exactly the one apply_candidate would install."""
    import jax
    import numpy as np

    from tpufw.train.pipeline_trainer import PipelineTrainer

    vocab = getattr(model_cfg, "vocab_size", 32000)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, vocab, (trainer_cfg.batch_size, trainer_cfg.seq_len),
        dtype=np.int32,
    )

    def measure(cand: Candidate) -> float:
        sched = {}
        if cand.pipeline_schedule:
            sched = dict(
                pipeline_schedule=cand.pipeline_schedule,
                pipeline_vstages=cand.pipeline_vstages,
            )
        cfg = dataclasses.replace(
            trainer_cfg,
            # n_microbatches IS the accumulation on this trainer (the
            # ctor rejects grad_accum != 1), so that axis is pinned.
            grad_accum=1,
            loss_chunk_size=cand.loss_chunk_size,
            sync_every=1,
            checkpoint_dir=None,
            profile_dir=None,
            eval_every=0,
            handle_preemption=False,
            autotune="off",
            **sched,
        )
        mc = model_cfg
        if (
            getattr(model_cfg, "remat", False)
            and getattr(model_cfg, "remat_policy", None)
            != cand.remat_policy
        ):
            mc = dataclasses.replace(
                model_cfg, remat_policy=cand.remat_policy
            )
        prev = _set_flash_env(cand.flash_bq, cand.flash_bkv)
        try:
            trainer = PipelineTrainer(mc, pipe, cfg, mesh_cfg, tx=tx)
            trainer.init_state(seed=seed)
            batch = {"tokens": tokens}
            step = trainer._compiled_step(batch)
            state = trainer.state
            if perf is not None:
                perf.observe_jit(
                    candidate_program_name(cand), step, (state, batch)
                )
            for _ in range(max(warmup_steps, 1)):
                state, m = step(state, batch)
                jax.block_until_ready(m["loss"])
            times = []
            for _ in range(max(n_steps, 1)):
                t0 = time.perf_counter()
                state, m = step(state, batch)
                jax.block_until_ready(m["loss"])
                times.append(time.perf_counter() - t0)
            med = statistics.median(times)
            if perf is not None:
                perf.record_wall(candidate_program_name(cand), med)
            return med
        finally:
            _restore_env(prev)

    return measure


def _trainer_model_cfg(trainer):
    """The model config for either trainer kind: the flax Trainer
    wraps a module (``trainer.model.cfg``), the PipelineTrainer holds
    the config directly (``trainer.model_cfg``)."""
    model = getattr(trainer, "model", None)
    mcfg = getattr(model, "cfg", None)
    if mcfg is None:
        mcfg = getattr(trainer, "model_cfg", None)
    return mcfg


def _trainer_cache_key(trainer) -> str:
    mcfg = _trainer_model_cfg(trainer)
    mesh_shape = tuple(trainer.mesh.shape.values())
    pipe = getattr(trainer, "pipe", None)
    return tune_cache.cache_key(
        mcfg
        if mcfg is not None
        else {"model": type(trainer.model).__name__},
        trainer.cfg.batch_size,
        trainer.cfg.seq_len,
        mesh_shape,
        # Stage/microbatch counts change the step being tuned (and
        # which schedules are valid) without changing the model config
        # — a pp2xM4 winner must not apply to pp4xM8. The SCHEDULE is
        # deliberately not in the key: it is the searched dimension.
        extra=(
            f"pp{pipe.n_stages}x{pipe.n_microbatches}"
            if pipe is not None
            else None
        ),
    )


def _relayout_pipe_state(state, old_pipe, new_pipe):
    """Convert a live PipeTrainState between the canonical [S, ...]
    and interleaved [v, S, ...] stage layouts — pure reshapes, applied
    to the stage stacks and (by shape match, the same trick
    PipelineTrainer._state_shardings uses) their optimizer moments."""
    import jax

    from tpufw.parallel.pipeline import (
        to_canonical_stages,
        to_virtual_stages,
    )

    if new_pipe.virtual_layout:
        conv = lambda t: to_virtual_stages(  # noqa: E731
            t, new_pipe.n_virtual, new_pipe.n_stages
        )
    else:
        conv = lambda t: to_canonical_stages(  # noqa: E731
            t, new_pipe.n_stages
        )
    old_shapes = {
        tuple(x.shape) for x in jax.tree.leaves(state.params["stages"])
    }

    def conv_if_stage(leaf):
        if (
            hasattr(leaf, "shape")
            and tuple(leaf.shape) in old_shapes
        ):
            return conv(leaf)
        return leaf

    params = dict(state.params)
    params["stages"] = conv(state.params["stages"])
    return state.replace(
        params=params,
        opt_state=jax.tree.map(conv_if_stage, state.opt_state),
    )


def _apply_pipeline_candidate(trainer, cand: Candidate) -> None:
    """Install a winner on a live PipelineTrainer. Schedule changes
    re-layout the state in place (reshapes + a re-shard) so a tuned
    run keeps its step counter and optimizer moments; grad_accum is
    not a pipeline knob (n_microbatches IS the accumulation) and is
    left alone."""
    import dataclasses as _dc

    import jax

    trainer.cfg.loss_chunk_size = cand.loss_chunk_size
    trainer.cfg.sync_every = cand.sync_every
    _set_flash_env(cand.flash_bq, cand.flash_bkv)
    if cand.pipeline_schedule:
        old = trainer.pipe
        new = _dc.replace(
            old,
            schedule=cand.pipeline_schedule,
            n_virtual=(
                cand.pipeline_vstages
                if cand.pipeline_schedule == "interleaved"
                else 1
            ),
        )
        if new != old:
            new.validate(trainer.model_cfg, trainer.cfg.batch_size)
            trainer.pipe = new
            if (
                trainer.state is not None
                and new.virtual_layout != old.virtual_layout
            ):
                trainer.state = _relayout_pipe_state(
                    trainer.state, old, new
                )
            trainer._shardings = trainer._state_shardings(
                trainer._abstract_state()
            )
            if trainer.state is not None:
                trainer.state = jax.device_put(
                    trainer.state, trainer._shardings
                )
    trainer._step_fn = None
    trainer._eval_fn = None


def apply_candidate(trainer, cand: Candidate) -> None:
    """Install a winner on a live Trainer: config knobs, a rebuilt model
    when the remat policy changed (re-pointing state.apply_fn if state
    already exists), and the flash env override. Compiled steps are
    dropped — they baked in the old knobs. PipelineTrainers take the
    pipeline branch (schedule swap + state re-layout)."""
    if hasattr(trainer, "pipe"):
        _apply_pipeline_candidate(trainer, cand)
        return
    trainer.cfg.grad_accum = cand.grad_accum
    trainer.cfg.loss_chunk_size = cand.loss_chunk_size
    trainer.cfg.sync_every = cand.sync_every
    new_model = _candidate_model(trainer.model, cand)
    if new_model is not trainer.model:
        trainer.model = new_model
        if trainer.state is not None:
            trainer.state = trainer.state.replace(apply_fn=new_model.apply)
    _set_flash_env(cand.flash_bq, cand.flash_bkv)
    trainer._compiled.clear()


def apply_autotune(
    trainer,
    space: Optional[SearchSpace] = None,
    events=None,
    perf=None,
) -> Optional[TuneResult]:
    """The Trainer.run entry: resolve TrainerConfig.autotune.

    - ``"cached"``: apply the persisted winner if one exists, else no-op.
    - ``"search"``: cache hit applies instantly; miss runs the budgeted
      compile-and-measure search, persists the winner, applies it.

    Returns the TuneResult (also stashed as ``trainer.last_tune``) or
    None when mode is "off"/unknown. ``events`` (tpufw.obs event log)
    gets per-candidate ``tune_trial`` lines and one ``tune_result``;
    ``perf`` (tpufw.obs.perf observatory) gets each measured trial's
    compiled cost + MFU under its ``candidate_program_name``.
    """
    if events is None:
        from tpufw.obs import events as events_mod

        events = events_mod.NULL
    mode = getattr(trainer.cfg, "autotune", "off")
    if mode not in ("cached", "search"):
        return None
    key = _trainer_cache_key(trainer)
    cached = tune_cache.load_candidate(key)
    if cached is not None:
        apply_candidate(trainer, cached)
        result = TuneResult(
            best=cached, best_step_s=None, trials=[], pruned=[],
            tune_s=0.0, cache_hit=True, cache_key=key, mode=mode,
        )
        trainer.last_tune = result
        events.emit("tune_result", **result.summary())
        return result
    if mode == "cached":
        result = TuneResult(
            best=None, best_step_s=None, trials=[], pruned=[],
            tune_s=0.0, cache_hit=False, cache_key=key, mode=mode,
        )
        trainer.last_tune = result
        events.emit("tune_result", **result.summary())
        return result

    import jax

    from tpufw.utils.hardware import detect_chip

    on_tpu = jax.devices()[0].platform == "tpu"
    # HBM pruning only means something against a real chip's HBM.
    hbm = detect_chip().hbm_bytes if on_tpu else None
    mcfg = _trainer_model_cfg(trainer)
    dp = trainer.mesh.shape["data"] * trainer.mesh.shape["fsdp"]
    pipe = getattr(trainer, "pipe", None)
    if pipe is not None and space is None:
        # Default pipeline space: the schedule axis IS the search (the
        # flax knobs that don't exist here — grad_accum, remat swaps —
        # are pinned), interleaved at the cheapest valid v.
        space = SearchSpace(
            grad_accums=(1,),
            remat_policies=(getattr(mcfg, "remat_policy", "dots"),),
            pipeline_schedules=(
                None, ("1f1b", 1), ("interleaved", 2), ("zb1", 1),
            ),
        )
    candidates, pruned = enumerate_candidates(
        mcfg,
        trainer.cfg.batch_size,
        trainer.cfg.seq_len,
        space=space,
        dp_shards=dp,
        n_shards=dp,
        hbm_bytes=hbm,
        pipe_stages=pipe.n_stages if pipe is not None else 0,
        pipe_microbatches=(
            pipe.n_microbatches if pipe is not None else 0
        ),
    )
    if pipe is not None:
        from tpufw.mesh import MeshConfig

        shape = dict(trainer.mesh.shape)
        measure = make_pipeline_measure_fn(
            trainer.model_cfg,
            pipe,
            trainer.cfg,
            MeshConfig(
                data=shape.get("data", 1),
                pipe=shape.get("pipe", 1),
                fsdp=shape.get("fsdp", 1),
                tensor=shape.get("tensor", 1),
                expert=shape.get("expert", 1),
            ),
            tx=trainer.tx,
            n_steps=getattr(trainer.cfg, "autotune_steps", 3),
            perf=perf if perf is not None and perf.enabled else None,
        )
    else:
        measure = make_measure_fn(
            trainer.model, trainer.cfg, trainer.mesh, tx=trainer.tx,
            n_steps=getattr(trainer.cfg, "autotune_steps", 3),
            perf=perf if perf is not None and perf.enabled else None,
        )
    result = search(
        candidates,
        measure,
        budget_s=getattr(trainer.cfg, "autotune_budget_s", 120.0),
        pruned=pruned,
        events=events,
    )
    result.cache_key = key
    result.mode = mode
    if result.best is not None:
        tune_cache.store(
            key,
            result.best,
            median_step_s=result.best_step_s,
            tune_s=result.tune_s,
        )
        apply_candidate(trainer, result.best)
    trainer.last_tune = result
    events.emit("tune_result", **result.summary())
    return result
