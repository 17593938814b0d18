"""Pipeline-parallel LM training workload over the ``pipe`` axis.

The deploy-facing entry for tpufw.train.PipelineTrainer: same JSON-lines
metrics channel as train_llama (``kubectl logs`` is the telemetry
surface, the reference's verification pattern upgraded —
reference README.md:331-335), driven by TPUFW_* env:

  TPUFW_PIPE_STAGES (required, >1)   pipeline stages == mesh pipe size
  TPUFW_PIPE_MICROBATCHES (default 2*stages)
  TPUFW_PIPELINE_SCHEDULE            gpipe (default) | 1f1b |
                                     interleaved | zb1
  TPUFW_PIPELINE_VSTAGES             virtual stages v for interleaved
  TPUFW_PIPE_SCHEDULE                older spelling of the schedule
                                     knob (gpipe | 1f1b); the
                                     TPUFW_PIPELINE_* form wins
  TPUFW_MODEL / TPUFW_BATCH_SIZE / TPUFW_SEQ_LEN / ... (as train_llama)
  TPUFW_MESH_DATA / TPUFW_MESH_FSDP  data-parallel axes alongside pipe
  TPUFW_MESH_TENSOR / TPUFW_MESH_EXPERT  in-stage Megatron split /
                                     pipelined-MoE expert sharding

Data: synthetic batches; TPUFW_EVAL_EVERY > 0 adds the in-loop
held-out eval (forward-only pipeline, token-weighted loss/ppl JSON
lines). Packed batches (segment_ids + loss_mask) are supported — the
masks ride the pipe ring with their microbatch.
"""

from __future__ import annotations

import json
import time

from tpufw.workloads.env import (
    env_bool,
    env_float,
    env_int,
    env_opt_int,
    env_str,
)

_T0 = time.time()


def build_trainer():
    """(PipelineTrainer, model_cfg) from TPUFW_* env; import-light."""
    from tpufw.configs import bench_model_config
    from tpufw.mesh import MeshConfig
    from tpufw.models import GEMMA_CONFIGS, LLAMA_CONFIGS
    from tpufw.parallel.pipeline import PipelineConfig
    from tpufw.train import PipelineTrainer, TrainerConfig

    stages = env_int("pipe_stages", 0)
    if stages < 2:
        raise ValueError(
            f"TPUFW_PIPE_STAGES={stages}: pipeline training needs >= 2 "
            "stages (use tpufw.workloads.train_llama for pipe=1)"
        )
    from tpufw.models import MIXTRAL_CONFIGS

    name = env_str("model", "llama3_600m_bench")
    if name == "llama3_600m_bench":
        model_cfg = bench_model_config()
    elif name in LLAMA_CONFIGS:
        model_cfg = LLAMA_CONFIGS[name]
    elif name in GEMMA_CONFIGS:
        model_cfg = GEMMA_CONFIGS[name]
    elif name in MIXTRAL_CONFIGS:
        # Pipelined MoE: expert stacks shard over `expert` inside the
        # GPipe stages (pp x ep — tpufw.parallel.pipeline._moe_mlp).
        model_cfg = MIXTRAL_CONFIGS[name]
    else:
        raise ValueError(
            f"unknown TPUFW_MODEL={name!r} for pipeline training; choose "
            f"from {['llama3_600m_bench', *LLAMA_CONFIGS, *GEMMA_CONFIGS, *MIXTRAL_CONFIGS]}"
        )
    pipe = PipelineConfig(
        n_stages=stages,
        n_microbatches=env_int("pipe_microbatches", 2 * stages),
        # TPUFW_PIPELINE_SCHEDULE (full set: gpipe | 1f1b |
        # interleaved | zb1) wins over the older TPUFW_PIPE_SCHEDULE
        # spelling, which stays honored so existing manifests keep
        # working.
        schedule=env_str("pipeline_schedule", "")
        or env_str("pipe_schedule", "gpipe"),
        n_virtual=env_int("pipeline_vstages", 1),
    )
    trainer_cfg = TrainerConfig(
        batch_size=env_int("batch_size", 8),
        seq_len=env_int("seq_len", model_cfg.max_seq_len),
        total_steps=env_int("total_steps", 100),
        lr=env_float("lr", 3e-4),
        warmup_steps=env_int("warmup_steps", 10),
        log_every=env_int("log_every", 10),
        checkpoint_dir=env_str("checkpoint_dir", "") or None,
        checkpoint_every=env_int("checkpoint_every", 100),
        adam_mu_dtype=env_str("adam_mu_dtype", "") or None,
        # grad_accum is still READ so PipelineTrainer's loud
        # NotImplementedError fires on a configured-but-ignored knob
        # (microbatching IS the schedule; size n_microbatches instead).
        grad_accum=env_int("grad_accum", 1),
        loss_chunk_size=env_int("loss_chunk_size", 0) or None,
        loss_chunk_dtype=env_str("loss_chunk_dtype", "bfloat16"),
        profile_dir=env_str("profile_dir", "") or None,
        profile_start=env_int("profile_start", 3),
        profile_stop=env_int("profile_stop", 6),
        eval_every=env_int("eval_every", 0),
        eval_batches=env_int("eval_batches", 8),
        # Same SIGTERM-to-forced-checkpoint contract as train_llama.
        handle_preemption=env_bool("handle_preemption", True),
        preemption_sync_every=env_int("preemption_sync_every", 1),
        sync_every=env_int("sync_every", 1),
        # Unified telemetry (tpufw.obs) — same knobs as train_llama.
        telemetry_dir=env_str("telemetry_dir", "") or None,
        metrics_port=env_opt_int("metrics_port"),
        straggler_factor=env_float("straggler_factor", 2.0),
    )
    mesh_cfg = MeshConfig(
        data=env_int("mesh_data", 1),
        pipe=stages,
        fsdp=env_int("mesh_fsdp", -1),
        tensor=env_int("mesh_tensor", 1),
        expert=env_int("mesh_expert", 1),
    )
    return PipelineTrainer(model_cfg, pipe, trainer_cfg, mesh_cfg), model_cfg


def main() -> int:
    from tpufw.cluster import initialize_cluster
    from tpufw.utils.profiling import enable_compile_cache

    cache = enable_compile_cache()
    cluster = initialize_cluster()

    import jax

    from tpufw.train import synthetic_batches

    trainer, model_cfg = build_trainer()
    print(
        f"tpufw train_pipeline: process {cluster.process_id}/"
        f"{cluster.num_processes} devices={len(jax.devices())} "
        f"mesh={dict(trainer.mesh.shape)} "
        f"stages={trainer.pipe.n_stages} "
        f"microbatches={trainer.pipe.n_microbatches} "
        f"bubble={trainer.pipe.bubble_fraction():.1%} "
        f"params={model_cfg.n_params():,}"
        f" compile_cache={cache}"
    )

    resumed = trainer.maybe_restore()
    if resumed:
        print(f"resumed from checkpoint at step {int(trainer.state.step)}")
    else:
        trainer.init_state(seed=env_int("seed", 0))
    from tpufw.workloads._common import (
        check_global_batch,
        metrics_printer,
        print_summary,
        resume_data_seed,
    )

    # Fresh data permutation on resume (no replayed batches) — the
    # same contract as train_llama; see resume_data_seed. The EVAL
    # stream keeps the BASE seed: the held-out set must keep its
    # identity across restarts or eval_loss jumps spuriously.
    data_seed = resume_data_seed(
        env_int("data_seed", 0), int(trainer.state.step)
    )

    cfg = trainer.cfg
    local_bs = check_global_batch(cfg.batch_size, cluster.num_processes)
    # Held-out eval stream (TPUFW_EVAL_EVERY > 0 enables) — same disjoint
    # odd-seed space convention as train_llama.
    eval_data = None
    if cfg.eval_every:

        def eval_data():
            return synthetic_batches(
                local_bs, cfg.seq_len, model_cfg.vocab_size,
                # BASE seed: the held-out set keeps its identity
                # across restarts (only the TRAIN stream re-seeds).
                seed=env_int("data_seed", 0) * 2000
                + 2 * cluster.process_id + 1,
            )

    history = trainer.run(
        synthetic_batches(
            local_bs,
            cfg.seq_len,
            model_cfg.vocab_size,
            seed=data_seed * 2000 + 2 * cluster.process_id,
        ),
        model_flops_per_token=model_cfg.flops_per_token(cfg.seq_len - 1),
        on_metrics=metrics_printer(_T0, cache),
        eval_data=eval_data,
        on_eval=lambda ev: print(json.dumps(ev), flush=True),
    )
    from tpufw.workloads._common import (
        report_preemption,
        report_telemetry,
    )

    report_preemption(trainer)
    report_telemetry(trainer)
    print_summary(history)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
