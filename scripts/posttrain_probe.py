"""Post-training suite on the real chip — DPO, GRPO, and contrastive
embeddings at bench scale (596M model) have only ever run on CPU
meshes. One timed case each, JSON rows to
docs/evidence/POSTTRAIN_r5.jsonl. Timing is value-fetch based
(float(loss) is the barrier)."""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "evidence", "POSTTRAIN_r5.jsonl",
)
_TAGS: dict = {}


def emit(row):
    row = {"t": round(time.time(), 1), **_TAGS, **row}
    print(json.dumps(row), flush=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")


def main():
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()

    import jax
    import numpy as np

    from tpufw.configs.presets import bench_model_config
    from tpufw.mesh import MeshConfig
    from tpufw.models import Llama
    from tpufw.train import TrainerConfig

    d = jax.devices()[0]
    _TAGS.update(platform=d.platform)
    emit({"event": "start", "kind": d.device_kind})

    cfg = dataclasses.replace(
        bench_model_config(), remat_policy="attn_out"
    )
    flops_tok = cfg.flops_per_token(2047)
    peak = 197e12

    def timed_steps(step, state, batch, n=3):
        state, m = step(state, batch)  # compile + step 1
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = step(state, batch)
        float(m["loss"])
        return (time.perf_counter() - t0) / n, m

    # 1. DPO: policy fwd+bwd + frozen bf16 reference fwd per step.
    try:
        from tpufw.train.dpo import DPOConfig, DPOTrainer

        rows, seq = 8, 2048
        tr = DPOTrainer(
            Llama(cfg),
            TrainerConfig(
                batch_size=rows, seq_len=seq, total_steps=4, lr=1e-5,
                warmup_steps=1, loss_chunk_size=512,
            ),
            MeshConfig(),
            dpo=DPOConfig(beta=0.1, ref_dtype="bfloat16"),
        )
        tr.init_state()
        rng = np.random.default_rng(0)
        batch = tr.globalize_batch({
            "tokens": rng.integers(
                1, cfg.vocab_size, (rows, seq)
            ).astype(np.int32),
            "loss_mask": np.ones((rows, seq), np.int32),
            "segment_ids": np.ones((rows, seq), np.int32),
        })
        step = tr.compiled_step(batch)
        dt, m = timed_steps(step, tr.state, batch)
        # DPO compute per step ~= policy fwd+bwd (3x fwd) + ref fwd
        # (1x) = 4/3 of an LM train step's FLOPs.
        emit({
            "case": "dpo_step", "rows": rows, "seq": seq,
            "step_ms": round(dt * 1e3, 1),
            "tok_per_s": round(rows * seq / dt, 1),
            "mfu_policy_plus_ref": round(
                (4.0 / 3.0) * flops_tok * rows * seq / dt / peak, 4
            ),
            "loss": round(float(m["loss"]), 4),
        })
        del tr, step, batch
    except Exception as e:  # noqa: BLE001
        emit({"case": "dpo_step",
              "error": f"{type(e).__name__}: {e}"[:300]})
    import gc

    gc.collect()
    jax.clear_caches()

    # 2. GRPO: one full iteration = grouped rollout (decode) + the
    # clipped-ratio policy step.
    try:
        from tpufw.train.grpo import GRPOConfig, GRPOTrainer

        n_prompts, group, new = 2, 8, 128
        seq = 512
        gtr = GRPOTrainer(
            Llama(dataclasses.replace(cfg, max_seq_len=seq)),
            TrainerConfig(
                batch_size=n_prompts * group, seq_len=seq,
                total_steps=4, lr=1e-6, warmup_steps=1,
                loss_chunk_size=512,
            ),
            MeshConfig(),
            grpo=GRPOConfig(
                group_size=group, max_new_tokens=new, temperature=1.0,
            ),
        )
        gtr.init_state()
        prompts = [[7, 8, 9, 10], [11, 12, 13]]

        def reward(ps, completions):
            return np.array(
                [len(c) / float(new) for c in completions]
            )

        def one_iter(key):
            batch, info = gtr.rollout(prompts, reward, key)
            step = gtr.compiled_step(batch)
            gtr.state, m = step(gtr.state, batch)
            float(m["loss"])
            return m

        one_iter(jax.random.key(0))  # compile rollout + step
        t0 = time.perf_counter()
        m = one_iter(jax.random.key(1))
        dt = time.perf_counter() - t0
        emit({
            "case": "grpo_iteration",
            "prompts": n_prompts, "group_size": group,
            "max_new_tokens": new,
            "iter_s": round(dt, 2),
            "completion_tok_per_s": round(
                n_prompts * group * new / dt, 1
            ),
            "loss": round(float(m["loss"]), 4),
        })
        del gtr
    except Exception as e:  # noqa: BLE001
        emit({"case": "grpo_iteration",
              "error": f"{type(e).__name__}: {e}"[:300]})
    gc.collect()
    jax.clear_caches()

    # 3. Contrastive embeddings: bidirectional InfoNCE over in-batch
    # negatives (E5 recipe), bidirectional encoder (causal=False).
    try:
        from tpufw.train.contrastive import (
            ContrastiveConfig,
            EmbeddingTrainer,
        )

        rows, seq = 32, 512
        etr = EmbeddingTrainer(
            Llama(
                dataclasses.replace(
                    cfg, max_seq_len=seq, causal=False
                )
            ),
            TrainerConfig(
                batch_size=rows, seq_len=seq, total_steps=4, lr=1e-5,
                warmup_steps=1,
            ),
            MeshConfig(),
            contrastive=ContrastiveConfig(),
        )
        etr.init_state()
        rng = np.random.default_rng(1)
        batch = etr.globalize_batch({
            "tokens": rng.integers(
                1, cfg.vocab_size, (rows, seq)
            ).astype(np.int32),
            "segment_ids": np.ones((rows, seq), np.int32),
        })
        step = etr.compiled_step(batch)
        dt, m = timed_steps(step, etr.state, batch)
        emit({
            "case": "contrastive_step", "rows": rows, "seq": seq,
            "step_ms": round(dt * 1e3, 1),
            "tok_per_s": round(rows * seq / dt, 1),
            "loss": round(float(m["loss"]), 4),
        })
    except Exception as e:  # noqa: BLE001
        emit({"case": "contrastive_step",
              "error": f"{type(e).__name__}: {e}"[:300]})
    emit({"event": "done"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
