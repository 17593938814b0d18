"""GRPO RL fine-tuning workload: rollout -> reward -> update, deployed.

The same kubectl-apply shape as the other training workloads (reference
README.md:303-335 upgraded from a device table to RL telemetry):
``kubectl logs`` streams one JSON line per step with reward_mean,
clip_frac, kl, and loss.

Env surface (TPUFW_*):
  MODEL / INIT_FROM / SEED       — as train_llama
  PROMPTS_FILE                   — JSONL: {"prompt": <text>} or a bare
                                   token list per line (default: two
                                   built-in demo prompts)
  SFT_TOKENIZER                  — "bytes" (default) or a HF name, for
                                   text prompts
  REWARD                         — "low_token" (demo: fraction of ids
                                   < vocab/2), "length" (completion
                                   length / max_new), or "pkg.mod:fn"
                                   importing a custom
                                   fn(prompts, completions) -> [N]
  GRPO_GROUP / GRPO_CLIP / GRPO_KL_BETA / GRPO_TEMPERATURE /
  GRPO_MAX_NEW / EOS_ID          — GRPOConfig knobs
  BATCH_SIZE / SEQ_LEN / TOTAL_STEPS / LR / ... — TrainerConfig knobs
  MESH_*                         — mesh axes, as train_llama
"""

from __future__ import annotations

import json
import time

from tpufw.workloads.env import env_float, env_int, env_str

_T0 = time.time()

_DEMO_PROMPTS = [[7, 8, 9, 10], [11, 12, 13]]


def load_prompts(path: str, encode) -> list[list[int]]:
    """JSONL prompts: {"prompt": <text>} rows are tokenized; bare lists
    pass through as token ids."""
    prompts: list[list[int]] = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if isinstance(obj, dict) and "prompt" in obj:
                prompts.append(encode(obj["prompt"]))
            elif isinstance(obj, list) and all(
                isinstance(t, int) for t in obj
            ):
                prompts.append(obj)
            else:
                raise ValueError(
                    f"{path}:{ln}: expected {{'prompt': text}} or a "
                    "token-id list"
                )
    if not prompts:
        raise ValueError(f"{path}: no prompts")
    return prompts


def resolve_reward(spec: str, vocab_size: int, max_new: int):
    """Built-in demo rewards or an importable ``pkg.mod:fn``."""
    import numpy as np

    if spec == "low_token":
        half = vocab_size // 2

        def low_token(prompts, completions):
            return np.array([
                np.mean([t < half for t in c]) if c else 0.0
                for c in completions
            ])

        return low_token
    if spec == "length":

        def length(prompts, completions):
            return np.array(
                [len(c) / max_new for c in completions], np.float32
            )

        return length
    if ":" in spec:
        import importlib

        mod_name, fn_name = spec.split(":", 1)
        fn = getattr(importlib.import_module(mod_name), fn_name)
        if not callable(fn):
            raise TypeError(f"{spec} is not callable")
        return fn
    raise ValueError(
        f"TPUFW_REWARD={spec!r}: expected 'low_token', 'length', or an "
        "importable 'pkg.mod:fn'"
    )


def build_trainer():
    """(trainer, model_cfg) for the RL loop; import-light like
    train_llama.build_trainer."""
    from tpufw.mesh import MeshConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.train import TrainerConfig
    from tpufw.train.grpo import GRPOConfig, GRPOTrainer

    name = env_str("model", "llama3_tiny")
    if name not in LLAMA_CONFIGS:
        raise ValueError(
            f"unknown TPUFW_MODEL={name!r}; RL workload presets: "
            f"{sorted(LLAMA_CONFIGS)}"
        )
    model_cfg = LLAMA_CONFIGS[name]
    grpo = GRPOConfig(
        group_size=env_int("grpo_group", 8),
        clip_eps=env_float("grpo_clip", 0.2),
        kl_beta=env_float("grpo_kl_beta", 0.02),
        temperature=env_float("grpo_temperature", 1.0),
        max_new_tokens=env_int("grpo_max_new", 64),
        # -1 sentinel: 0 is a valid EOS id in several vocabularies.
        eos_id=(lambda e: None if e < 0 else e)(env_int("eos_id", -1)),
    )
    trainer_cfg = TrainerConfig(
        batch_size=env_int("batch_size", 16),
        seq_len=env_int("seq_len", min(512, model_cfg.max_seq_len)),
        total_steps=env_int("total_steps", 50),
        lr=env_float("lr", 1e-5),
        warmup_steps=env_int("warmup_steps", 5),
        loss_chunk_size=env_int("loss_chunk_size", 512) or None,
        checkpoint_dir=env_str("checkpoint_dir", "") or None,
        checkpoint_every=env_int("checkpoint_every", 100),
        log_every=1,
    )
    mesh_cfg = MeshConfig(
        data=env_int("mesh_data", 1),
        fsdp=env_int("mesh_fsdp", -1),
        tensor=env_int("mesh_tensor", 1),
    )
    return (
        GRPOTrainer(Llama(model_cfg), trainer_cfg, mesh_cfg, grpo=grpo),
        model_cfg,
    )


def main() -> int:
    from tpufw.cluster import initialize_cluster
    from tpufw.utils.profiling import enable_compile_cache

    cache = enable_compile_cache()
    cluster = initialize_cluster()
    if cluster.num_processes > 1:
        raise NotImplementedError(
            "the RL workload is single-process for now: rollouts are "
            "host-driven; shard prompts across independent Jobs instead"
        )

    import jax

    trainer, model_cfg = build_trainer()
    print(
        f"tpufw rl: devices={len(jax.devices())} "
        f"mesh={dict(trainer.mesh.shape)} params={model_cfg.n_params():,}"
        f" compile_cache={cache}"
    )

    init_from = env_str("init_from", "")
    if init_from:
        # Base init FIRST (snapshots the step-0 KL reference), THEN
        # resume: a JobSet restart mid-RL keeps the correct anchor.
        trainer.init_from_params(init_from, seed=env_int("seed", 0))
        print(f"initialized params from {init_from}")
    else:
        trainer.init_state(seed=env_int("seed", 0))
    if trainer.maybe_restore():
        print(f"resumed from checkpoint at step {int(trainer.state.step)}")

    from tpufw.workloads._common import resolve_encode

    prompts_file = env_str("prompts_file", "")
    if prompts_file:
        encode = resolve_encode(env_str("sft_tokenizer", "bytes"))
        prompts = load_prompts(prompts_file, encode)
    else:
        prompts = _DEMO_PROMPTS
        print("no TPUFW_PROMPTS_FILE: using built-in demo prompts")
    per_step = trainer.cfg.batch_size // trainer.grpo.group_size
    if len(prompts) < per_step:
        raise ValueError(
            f"{len(prompts)} prompts < {per_step} needed per step "
            f"(batch_size {trainer.cfg.batch_size} / group "
            f"{trainer.grpo.group_size})"
        )
    reward_fn = resolve_reward(
        env_str("reward", "low_token"),
        model_cfg.vocab_size,
        trainer.grpo.max_new_tokens,
    )

    first = {}

    def on_metrics(entry: dict) -> None:
        if not first:
            first["t"] = time.time()
            print(
                json.dumps({
                    "cold_start_to_first_step_s": round(
                        first["t"] - _T0, 1
                    ),
                    "compile_cache": cache,
                }),
                flush=True,
            )
        print(json.dumps(entry), flush=True)

    # Rotate through the prompt set: each step uses a contiguous
    # (wrapping) window, so every prompt gets rollouts over a long run.
    def window(i: int):
        return [
            prompts[(i * per_step + j) % len(prompts)]
            for j in range(per_step)
        ]

    history = trainer.run_rl(
        window, reward_fn, seed=env_int("seed", 0),
        on_metrics=on_metrics,
    )

    from tpufw.workloads._common import report_preemption

    report_preemption(trainer)
    if history:
        last = history[-1]
        print(
            f"RL OK: {len(history)} steps, reward_mean "
            f"{last['reward_mean']:.4f}, kl {last['kl']:.4f}"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
