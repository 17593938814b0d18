"""LM training workload (BASELINE configs 3-5): Llama-3 / Mixtral over a mesh.

One entry point covers single-chip through multi-host: the cluster bootstrap
no-ops when not distributed, the mesh axes come from TPUFW_MESH_* env vars,
and checkpoint-resume makes a JobSet gang restart transparent. Structured
step metrics (loss, tokens/sec/chip, MFU) stream to stdout as JSON lines —
``kubectl logs`` is the metrics channel, the reference's verification
pattern (README.md:331-335) upgraded from a device table to training
telemetry.
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

# Import time ~= process start: the anchor for cold-start→first-step
# (BASELINE.md metric 2 — the reference's analog is its unmeasured
# Steps 1-9 wall clock, reference README.md:70-74).
_T0 = time.time()


def build_trainer():
    """Construct (trainer, model_cfg) from config layers. Import-light so
    tests can exercise config resolution without touching a backend.

    Precedence (lowest first): ``TPUFW_CONFIG`` YAML of record
    (tpufw.configs.loader, SURVEY.md §5) < ``TPUFW_*`` env vars — so a
    manifest points at the YAML and overrides only deployment-specifics.
    """
    import dataclasses

    from tpufw.configs import bench_model_config
    from tpufw.mesh import MeshConfig
    from tpufw.models import (
        DEEPSEEK_CONFIGS,
        Deepseek,
        GEMMA_CONFIGS,
        Gemma,
        LLAMA_CONFIGS,
        Llama,
        MIXTRAL_CONFIGS,
        Mixtral,
    )
    from tpufw.train import Trainer, TrainerConfig

    run = None
    cfg_path = env_str("config", "")
    if cfg_path:
        from tpufw.configs.loader import load_run_config

        run = load_run_config(cfg_path)
        if not isinstance(run.trainer, TrainerConfig):
            raise ValueError(
                f"{cfg_path}: preset {run.model_preset!r} is not an LM "
                "config; use tpufw.workloads.train_resnet for vision runs"
            )
    base_t = run.trainer if run else TrainerConfig()
    base_m = run.mesh if run else MeshConfig()

    name = env_str("model", run.model_preset if run else "llama3_600m_bench")
    def model_for(model_cfg):
        tname = type(model_cfg).__name__
        if "Mixtral" in tname:
            return Mixtral(model_cfg)
        if "Gemma" in tname:
            return Gemma(model_cfg)
        if "Deepseek" in tname:
            return Deepseek(model_cfg)
        return None  # Llama built after the backend override below

    if run and name == run.model_preset:
        model_cfg = run.model_cfg  # keeps the YAML's model.overrides
        model = model_for(model_cfg)
    elif name == "llama3_600m_bench":
        model_cfg, model = bench_model_config(), None
    elif name in LLAMA_CONFIGS:
        model_cfg, model = LLAMA_CONFIGS[name], None
    elif name in MIXTRAL_CONFIGS:
        model_cfg = MIXTRAL_CONFIGS[name]
        model = Mixtral(model_cfg)
    elif name in GEMMA_CONFIGS:
        model_cfg = GEMMA_CONFIGS[name]
        model = Gemma(model_cfg)
    elif name in DEEPSEEK_CONFIGS:
        model_cfg = DEEPSEEK_CONFIGS[name]
        model = Deepseek(model_cfg)
    else:
        raise ValueError(
            f"unknown TPUFW_MODEL={name!r}; choose from "
            f"{['llama3_600m_bench', *LLAMA_CONFIGS, *MIXTRAL_CONFIGS, *GEMMA_CONFIGS, *DEEPSEEK_CONFIGS]}"
        )
    backend = env_str("attention", "")
    if backend:
        model_cfg = dataclasses.replace(model_cfg, attention_backend=backend)
        model = None if model is None else type(model)(model_cfg)
    # TPUFW_MOE_DISPATCH=sorted: grouped ragged_dot expert matmuls
    # (2.26x the einsum dispatch on one v5e chip, docs/PERF.md) for
    # MoE configs training without expert-axis sharding; "einsum"
    # (default) is the EP-shardable path. Ignored by dense configs.
    moe_dispatch = env_str("moe_dispatch", "")
    if moe_dispatch and hasattr(model_cfg, "moe_dispatch"):
        model_cfg = dataclasses.replace(
            model_cfg, moe_dispatch=moe_dispatch
        )
        model = None if model is None else type(model)(model_cfg)
    # LoRA fine-tune: TPUFW_LORA_RANK > 0 adds adapters and freezes the
    # base (pairs with TPUFW_INIT_FROM pointing at a bare-params
    # checkpoint, e.g. an import_hf conversion).
    lora_rank = env_int("lora_rank", getattr(model_cfg, "lora_rank", 0))
    lora_alpha = env_float(
        "lora_alpha", getattr(model_cfg, "lora_alpha", 16.0)
    )
    if lora_rank and not hasattr(model_cfg, "lora_rank"):
        raise NotImplementedError(
            f"TPUFW_LORA_RANK: {type(model_cfg).__name__} does not "
            "implement LoRA adapters (the MLA family is full-fine-tune "
            "only today)"
        )
    if (lora_rank, lora_alpha) != (
        getattr(model_cfg, "lora_rank", 0),
        getattr(model_cfg, "lora_alpha", 16.0),
    ):
        model_cfg = dataclasses.replace(
            model_cfg, lora_rank=lora_rank, lora_alpha=lora_alpha
        )
        model = None if model is None else type(model)(model_cfg)
    if model is None:
        model = Llama(model_cfg)

    trainer_cfg = TrainerConfig(
        batch_size=env_int("batch_size", base_t.batch_size),
        seq_len=env_int(
            "seq_len",
            base_t.seq_len if run else model_cfg.max_seq_len,
        ),
        total_steps=env_int("total_steps", base_t.total_steps),
        lr=env_float("lr", base_t.lr if run else 3e-4),
        warmup_steps=env_int("warmup_steps", base_t.warmup_steps),
        log_every=env_int("log_every", base_t.log_every),
        checkpoint_dir=env_str("checkpoint_dir", base_t.checkpoint_dir or "")
        or None,
        checkpoint_every=env_int(
            "checkpoint_every", base_t.checkpoint_every if run else 100
        ),
        # 0/unset = full logits; >0 enables chunked-vocab CE.
        loss_chunk_size=env_int(
            "loss_chunk_size",
            (base_t.loss_chunk_size or 0) if run else 512,
        )
        or None,
        # "float32" restores exact full-logits numerics (slower head).
        loss_chunk_dtype=env_str("loss_chunk_dtype", base_t.loss_chunk_dtype),
        profile_dir=env_str("profile_dir", base_t.profile_dir or "") or None,
        profile_start=env_int("profile_start", base_t.profile_start),
        profile_stop=env_int("profile_stop", base_t.profile_stop),
        eval_every=env_int("eval_every", base_t.eval_every),
        eval_batches=env_int("eval_batches", base_t.eval_batches),
        grad_accum=env_int("grad_accum", base_t.grad_accum),
        adam_mu_dtype=env_str(
            "adam_mu_dtype", base_t.adam_mu_dtype or ""
        )
        or None,
        # Deployed pods handle SIGTERM by default: k8s termination →
        # forced final checkpoint → clean exit → JobSet restart resumes.
        handle_preemption=env_bool(
            "handle_preemption", base_t.handle_preemption
        ),
        preemption_sync_every=env_int(
            "preemption_sync_every", base_t.preemption_sync_every
        ),
        sync_every=env_int("sync_every", base_t.sync_every),
        # MFU autotuning (tpufw.tune): "cached" applies a persisted
        # winner, "search" measures candidates before the first step.
        autotune=env_str("autotune", base_t.autotune),
        autotune_budget_s=env_float(
            "autotune_budget_s", base_t.autotune_budget_s
        ),
        autotune_steps=env_int("autotune_steps", base_t.autotune_steps),
        # Unified telemetry (tpufw.obs): TPUFW_TELEMETRY_DIR writes
        # events.jsonl + trace.json per host; TPUFW_METRICS_PORT
        # serves Prometheus /metrics (unset = off, 0 = ephemeral).
        telemetry_dir=env_str(
            "telemetry_dir", base_t.telemetry_dir or ""
        ) or None,
        metrics_port=env_opt_int("metrics_port", base_t.metrics_port),
        straggler_factor=env_float(
            "straggler_factor", base_t.straggler_factor
        ),
    )
    if trainer_cfg.autotune not in ("off", "cached", "search"):
        raise ValueError(
            f"TPUFW_AUTOTUNE={trainer_cfg.autotune!r}: expected "
            "off | cached | search"
        )
    mesh_cfg = MeshConfig(
        data=env_int("mesh_data", base_m.data),
        fsdp=env_int("mesh_fsdp", base_m.fsdp),
        expert=env_int("mesh_expert", base_m.expert),
        sequence=env_int("mesh_sequence", base_m.sequence),
        tensor=env_int("mesh_tensor", base_m.tensor),
        # >1 = multi-slice: data parallelism across slices over DCN.
        dcn_data=env_int("mesh_dcn_data", base_m.dcn_data),
    )
    if (
        getattr(model_cfg, "moe_dispatch", "einsum") == "sorted"
        and mesh_cfg.expert not in (0, 1)
    ):
        # Silently defeating EP would be worse than refusing: the
        # sorted path's whole expert stacks would be all-gathered to
        # every device each layer under an expert-sharded mesh.
        raise ValueError(
            "moe_dispatch='sorted' keeps expert weight stacks whole "
            f"and cannot shard the expert mesh axis (got expert="
            f"{mesh_cfg.expert}); use the default einsum dispatch for "
            "expert parallelism"
        )
    # Objective selection: TPUFW_DPO_DATA switches to preference pairs
    # (DPOTrainer), TPUFW_DISTILL_TEACHER to teacher-student KL
    # (DistillTrainer); default is the LM objective. Mutually exclusive
    # — each replaces the loss, not the data alone.
    dpo_path = env_str("dpo_data", "")
    teacher_name = env_str("distill_teacher", "")
    if dpo_path and teacher_name:
        raise ValueError(
            "TPUFW_DPO_DATA and TPUFW_DISTILL_TEACHER are mutually "
            "exclusive objectives"
        )
    if dpo_path:
        from tpufw.train import DPOConfig, DPOTrainer

        trainer = DPOTrainer(
            model, trainer_cfg, mesh_cfg,
            dpo=DPOConfig(
                beta=env_float("dpo_beta", 0.1),
                label_smoothing=env_float("dpo_label_smoothing", 0.0),
            ),
        )
    elif teacher_name:
        from tpufw.train import DistillConfig, DistillTrainer

        trainer = DistillTrainer(
            model, trainer_cfg, mesh_cfg,
            distill=DistillConfig(
                temperature=env_float("distill_temperature", 2.0),
                alpha=env_float("distill_alpha", 0.5),
            ),
        )
    else:
        trainer = Trainer(model, trainer_cfg, mesh_cfg)
    return trainer, model_cfg


def main() -> int:
    from tpufw.cluster import initialize_cluster
    from tpufw.utils.profiling import enable_compile_cache

    # Before any compile: persistent XLA cache makes pod-restart recompiles
    # near-free (cold-start -> first-step, the BASELINE metric).
    cache = enable_compile_cache()
    cluster = initialize_cluster()

    import jax

    from tpufw.train import synthetic_batches

    trainer, model_cfg = build_trainer()
    dev = jax.devices()[0]
    print(
        f"tpufw train_llama: process {cluster.process_id}/"
        f"{cluster.num_processes} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} devices={len(jax.devices())} "
        f"mesh={dict(trainer.mesh.shape)} params={model_cfg.n_params():,} "
        f"attention={getattr(model_cfg, 'attention_backend', None)} "
        f"compile_cache={cache}"
    )

    from tpufw.train import DPOTrainer as _DPOT

    init_from = env_str("init_from", "")
    if isinstance(trainer, _DPOT) and init_from:
        # DPO resume safety (mirrors rl.py's ordering): anchor the
        # reference snapshot to the ORIGINAL base weights BEFORE
        # restoring — maybe_restore() overwrites only policy/optimizer
        # state, so ref_params keeps the step-0 anchor and a pod
        # restart after the first checkpoint no longer crash-loops.
        trainer.init_from_params(init_from, seed=env_int("seed", 0))
        print(f"initialized params from {init_from}")
    resumed = trainer.maybe_restore()
    if resumed:
        print(f"resumed from checkpoint at step {int(trainer.state.step)}")
    elif trainer.state is None:
        if init_from:
            # Bare-params checkpoint (tpufw.tools.import_hf CLI output):
            # fine-tune from imported weights, fresh optimizer state.
            trainer.init_from_params(init_from, seed=env_int("seed", 0))
            print(f"initialized params from {init_from}")
        else:
            trainer.init_state(seed=env_int("seed", 0))

    from tpufw.workloads._common import (
        check_global_batch,
        metrics_printer,
        print_summary,
        resume_data_seed,
    )

    from tpufw.train.distill import DistillTrainer as _DT

    if isinstance(trainer, _DT):
        # Teacher preset + optional bare-params checkpoint; without a
        # checkpoint the teacher is RANDOM — only good for smoke tests,
        # so say so loudly.
        from tpufw.models import (
            DEEPSEEK_CONFIGS as _DC,
            GEMMA_CONFIGS as _GC,
            LLAMA_CONFIGS as _LC,
            MIXTRAL_CONFIGS as _MC,
            model_for_config,
        )

        t_name = env_str("distill_teacher", "")
        t_cfgs = {**_LC, **_MC, **_GC, **_DC}
        if t_name not in t_cfgs:
            raise ValueError(
                f"unknown TPUFW_DISTILL_TEACHER={t_name!r}; choose "
                f"from {sorted(t_cfgs)}"
            )
        t_cfg = t_cfgs[t_name]
        teacher = model_for_config(t_cfg)
        t_ckpt = env_str("distill_teacher_ckpt", "")
        if t_ckpt:
            trainer.set_teacher_from(teacher, t_ckpt)
            print(f"teacher {t_name} restored from {t_ckpt}")
        else:
            from flax.core import meta as _meta

            import jax.numpy as _jnp

            t_params = _meta.unbox(
                jax.jit(teacher.init)(
                    jax.random.key(env_int("seed", 0) + 1),
                    _jnp.zeros((2, 8), _jnp.int32),
                )["params"]
            )
            trainer.set_teacher(teacher, t_params)
            print(
                f"WARNING: teacher {t_name} is RANDOM-INIT (no "
                "TPUFW_DISTILL_TEACHER_CKPT) — smoke-test only"
            )

    cfg = trainer.cfg
    # Resumed runs get a FRESH data permutation (seed folded with the
    # restored step) instead of replaying consumed batches — see
    # resume_data_seed; the EVAL streams below keep the BASE seed so
    # the held-out set's identity survives restarts.
    data_seed = resume_data_seed(
        env_int("data_seed", 0), int(trainer.state.step)
    )
    flops_per_token = model_cfg.flops_per_token(cfg.seq_len - 1)
    if isinstance(trainer, _DT):
        # Teacher forward = 2N_t per token; flops_per_token is the 6N
        # train convention, so the forward is a third of the TEACHER's
        # own figure — without this, distill MFU undercounts real work
        # (the DPO branch makes the matching 4/3 correction).
        flops_per_token += (
            trainer.teacher_model.cfg.flops_per_token(cfg.seq_len - 1)
            / 3.0
        )
    # cfg.batch_size is GLOBAL; each process loads its local shard.
    n_proc = cluster.num_processes
    local_bs = check_global_batch(cfg.batch_size, n_proc)
    sft_path = env_str("sft_data", "")
    dpo_path = env_str("dpo_data", "")
    data_prefix = env_str("data_prefix", "")
    if dpo_path:
        # Preference pairs (tpufw.train.dpo): local rows = 2 * pairs;
        # interleaved layout keeps multi-process pairing correct.
        from tpufw.train import prefetch_to_device
        from tpufw.train.dpo import dpo_batches
        from tpufw.workloads._common import resolve_encode

        if local_bs % 2:
            raise ValueError(
                f"DPO local batch {local_bs} must be even (2 rows/pair)"
            )
        # The reference forward adds 2N FLOPs to the 6N train
        # convention (DPOTrainer docstring).
        flops_per_token = flops_per_token * 4.0 / 3.0
        data = prefetch_to_device(
            dpo_batches(
                dpo_path,
                local_bs // 2,
                cfg.seq_len,
                resolve_encode(env_str("sft_tokenizer", "bytes")),
                template=env_str("sft_template", "plain"),
                seed=data_seed,
                shard_id=cluster.process_id,
                num_shards=n_proc,
            ),
            trainer.mesh,
        )
    elif sft_path:
        # Supervised fine-tuning: JSONL conversations, chat-template
        # rendered, assistant-masked (tpufw.train.sft). Pairs with
        # TPUFW_INIT_FROM (imported base weights) + TPUFW_LORA_RANK.
        from tpufw.train.sft import sft_batches
        from tpufw.workloads._common import resolve_encode

        encode = resolve_encode(env_str("sft_tokenizer", "bytes"))

        from tpufw.train import prefetch_to_device

        data = prefetch_to_device(
            sft_batches(
                sft_path,
                local_bs,
                cfg.seq_len,
                encode,
                template=env_str("sft_template", "plain"),
                seed=data_seed,
                # Disjoint per-process conversation shards (same
                # contract as the TokenCorpus path below).
                shard_id=cluster.process_id,
                num_shards=n_proc,
            ),
            trainer.mesh,
        )
    elif data_prefix:
        # Real corpus (native/ mmap packer; TPUFW_DATA_PREFIX points at the
        # <prefix>.bin/.idx pair): disjoint per-process doc shards, H2D
        # transfer prefetched off the step path.
        from tpufw.train import TokenCorpus, prefetch_to_device

        data = prefetch_to_device(
            iter(
                TokenCorpus(
                    data_prefix, local_bs, cfg.seq_len,
                    shuffle=True, seed=data_seed,
                    shard_id=cluster.process_id, num_shards=n_proc,
                )
            ),
            trainer.mesh,
        )
    else:
        data = synthetic_batches(
            local_bs, cfg.seq_len, model_cfg.vocab_size,
            # Even seed space; the synthetic eval stream uses odd.
            seed=data_seed * 2000 + 2 * cluster.process_id,
        )
    # Held-out eval stream (TPUFW_EVAL_EVERY > 0 enables): a disjoint
    # corpus prefix when given, else synthetic batches from a disjoint
    # seed space (train seeds are even, eval seeds odd — no collision
    # for any TPUFW_DATA_SEED / process id).
    eval_data = None
    if cfg.eval_every:
        eval_prefix = env_str("eval_data_prefix", "")
        if eval_prefix:
            from tpufw.train import TokenCorpus

            def eval_data():
                return iter(
                    TokenCorpus(
                        eval_prefix, local_bs, cfg.seq_len,
                        shard_id=cluster.process_id, num_shards=n_proc,
                    )
                )
        else:

            def eval_data():
                return synthetic_batches(
                    local_bs, cfg.seq_len, model_cfg.vocab_size,
                    seed=env_int("data_seed", 0) * 2000
                    + 2 * cluster.process_id + 1,
                )

    history = trainer.run(
        data,
        model_flops_per_token=flops_per_token,
        on_metrics=metrics_printer(_T0, cache),
        eval_data=eval_data,
        on_eval=lambda ev: print(json.dumps(ev), flush=True),
    )
    from tpufw.workloads._common import (
        report_preemption,
        report_telemetry,
    )

    if trainer.last_tune is not None:
        # One JSON line, same channel as step metrics: the chosen
        # config and the tuning wall-clock, kubectl-logs greppable.
        print(
            json.dumps({"autotune": trainer.last_tune.summary()}),
            flush=True,
        )
    report_preemption(trainer)
    report_telemetry(trainer)
    print_summary(history)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
