"""Embedding fine-tuning workload: contrastive pairs -> encoder, deployed.

The kubectl-apply shape of the other training workloads (reference
README.md:303-335's log-visible verification, retrieval edition):
``kubectl logs`` streams the InfoNCE loss JSON line per step (the
Trainer metrics channel carries the loss; per-step in-batch accuracy
stays internal), and the run ends with a cosine-similarity retrieval
probe — matched vs mismatched pair similarity, the log-visible proof
the embeddings separate.

Env surface (TPUFW_*):
  MODEL / INIT_FROM / SEED          — as train_llama (Llama-family)
  EMBED_DATA                        — JSONL {"query","positive"} pairs
  SFT_TOKENIZER                     — "bytes" (default) or a HF name
  POOLING                           — "mean" (default) | "last"
  BIDIRECTIONAL                     — 1 = LLM2Vec-style causal=False
                                      (requires sliding_window-free
                                      configs); default 0 (E5-style)
  TEMPERATURE                       — InfoNCE temperature (0.05)
  BATCH_SIZE (rows = 2*pairs) / SEQ_LEN / TOTAL_STEPS / LR / ...
  MESH_*                            — mesh axes, as train_llama
"""

from __future__ import annotations

import dataclasses
import json
import time

from tpufw.workloads.env import env_bool, env_float, env_int, env_str

_T0 = time.time()


def build_trainer():
    from tpufw.mesh import MeshConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.train import TrainerConfig
    from tpufw.train.contrastive import ContrastiveConfig, EmbeddingTrainer

    name = env_str("model", "llama3_tiny")
    if name not in LLAMA_CONFIGS:
        raise ValueError(
            f"unknown TPUFW_MODEL={name!r}; embedding workload "
            f"presets: {sorted(LLAMA_CONFIGS)}"
        )
    model_cfg = LLAMA_CONFIGS[name]
    if env_bool("bidirectional", False):
        model_cfg = dataclasses.replace(
            model_cfg, causal=False, sliding_window=None
        )
    trainer_cfg = TrainerConfig(
        batch_size=env_int("batch_size", 16),
        seq_len=env_int("seq_len", min(512, model_cfg.max_seq_len)),
        total_steps=env_int("total_steps", 100),
        lr=env_float("lr", 2e-5),
        warmup_steps=env_int("warmup_steps", 10),
        checkpoint_dir=env_str("checkpoint_dir", "") or None,
        checkpoint_every=env_int("checkpoint_every", 100),
        log_every=env_int("log_every", 1),
    )
    mesh_cfg = MeshConfig(
        data=env_int("mesh_data", 1),
        fsdp=env_int("mesh_fsdp", -1),
        tensor=env_int("mesh_tensor", 1),
    )
    trainer = EmbeddingTrainer(
        Llama(model_cfg), trainer_cfg, mesh_cfg,
        contrastive=ContrastiveConfig(
            temperature=env_float("temperature", 0.05),
            pooling=env_str("pooling", "mean"),
        ),
    )
    return trainer, model_cfg


def main() -> int:
    from tpufw.cluster import initialize_cluster
    from tpufw.utils.profiling import enable_compile_cache

    cache = enable_compile_cache()
    cluster = initialize_cluster()

    import numpy as np

    import jax

    trainer, model_cfg = build_trainer()
    print(
        f"tpufw embed: process {cluster.process_id}/"
        f"{cluster.num_processes} devices={len(jax.devices())} "
        f"mesh={dict(trainer.mesh.shape)} params={model_cfg.n_params():,}"
        f" pooling={trainer.contrastive.pooling}"
        f" causal={getattr(model_cfg, 'causal', True)}"
        f" compile_cache={cache}"
    )

    resumed = trainer.maybe_restore()
    if resumed:
        print(f"resumed from checkpoint at step {int(trainer.state.step)}")
    else:
        init_from = env_str("init_from", "")
        if init_from:
            trainer.init_from_params(init_from, seed=env_int("seed", 0))
            print(f"initialized params from {init_from}")
        else:
            trainer.init_state(seed=env_int("seed", 0))

    from tpufw.train.contrastive import pair_batches
    from tpufw.workloads._common import (
        check_global_batch,
        metrics_printer,
        report_preemption,
        resolve_encode,
        resume_data_seed,
    )

    cfg = trainer.cfg
    local_bs = check_global_batch(cfg.batch_size, cluster.num_processes)
    if local_bs % 2:
        raise ValueError(
            f"embedding local batch {local_bs} must be even (2 rows/pair)"
        )
    data_path = env_str("embed_data", "")
    if not data_path:
        raise ValueError(
            "TPUFW_EMBED_DATA is required: JSONL "
            '{"query": ..., "positive": ...} pairs'
        )
    encode = resolve_encode(env_str("sft_tokenizer", "bytes"))
    data = pair_batches(
        data_path,
        local_bs // 2,
        cfg.seq_len,
        encode,
        seed=resume_data_seed(
            env_int("data_seed", 0), int(trainer.state.step)
        ),
        shard_id=cluster.process_id,
        num_shards=cluster.num_processes,
    )
    # InfoNCE has no LM head: fwd+bwd over the trunk = 6N minus the
    # head's 6*D*V share. flops_per_token causal-halves the attention
    # score term; a bidirectional encoder attends all keys, so add the
    # halved term once more.
    flops = model_cfg.flops_per_token(
        cfg.seq_len - 1
    ) - 6.0 * model_cfg.d_model * model_cfg.vocab_size
    if not getattr(model_cfg, "causal", True):
        flops += model_cfg._attn_score_flops(cfg.seq_len - 1)
    history = trainer.run(
        data,
        model_flops_per_token=flops,
        on_metrics=metrics_printer(_T0, cache),
    )
    report_preemption(trainer)
    # Log-visible retrieval proof — single-process only: embed() runs
    # an eager forward on host-local arrays, which a multi-host mesh
    # rejects (the training loop above is the multi-process surface).
    if history and cluster.num_processes == 1:
        from tpufw.train.contrastive import _fit, read_pairs

        probe = []
        for i, p in enumerate(read_pairs(data_path)):
            if i >= 4:
                break
            probe.append(p)
        toks = np.zeros((2 * len(probe), cfg.seq_len), np.int32)
        seg = np.zeros_like(toks)
        for i, p in enumerate(probe):
            # _fit: the SAME length-based masking training used (a
            # (tokens != 0) mask would mis-mark a legitimate id-0
            # token under HF tokenizers).
            toks[2 * i], seg[2 * i] = _fit(
                encode(p["query"]), cfg.seq_len
            )
            toks[2 * i + 1], seg[2 * i + 1] = _fit(
                encode(p["positive"]), cfg.seq_len
            )
        emb = trainer.embed(toks, seg)
        sim = emb[0::2] @ emb[1::2].T
        print(json.dumps({
            "probe_sim_matched": round(float(np.diag(sim).mean()), 4),
            "probe_sim_mismatched": round(
                float(
                    (sim.sum() - np.diag(sim).sum())
                    / max(sim.size - len(probe), 1)
                ),
                4,
            ),
        }), flush=True)
    if history:
        print(
            f"EMBED OK: {len(history)} steps, final loss "
            f"{history[-1].loss:.4f}"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
