"""Workload entry-point tests: drive the manifest-invoked mains on the CPU
mesh (conftest forces 8 virtual devices) exactly as a pod would — env in,
logs out."""

from __future__ import annotations

import json

import pytest


def test_smoke_main_prints_device_proof(capsys, monkeypatch):
    monkeypatch.setenv("TPUFW_SMOKE_MATMUL_DIM", "128")
    from tpufw.workloads import smoke

    assert smoke.main() == 0
    out = capsys.readouterr().out
    assert "jax.devices()" in out
    assert "SMOKE OK" in out
    assert "TFLOP/s" in out


def test_train_llama_main_env_config(capsys, monkeypatch):
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "4")
    monkeypatch.setenv("TPUFW_SEQ_LEN", "33")
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "3")
    monkeypatch.setenv("TPUFW_LOG_EVERY", "1")
    monkeypatch.setenv("TPUFW_MESH_TENSOR", "2")
    from tpufw.workloads import train_llama

    assert train_llama.main() == 0
    out = capsys.readouterr().out
    assert "TRAIN OK: 3 steps" in out
    # JSON metric lines are parseable and carry the headline fields.
    lines = [
        json.loads(line) for line in out.splitlines()
        if line.startswith("{")
    ]
    metrics = [m for m in lines if "loss" in m]
    assert len(metrics) == 3
    assert {"loss", "tokens_per_sec_per_chip"} <= metrics[0].keys()
    assert "mfu" not in metrics[0]  # a CPU run has no peak to divide by
    # Cold-start→first-step (BASELINE.md metric 2) precedes the metrics.
    cold = [m for m in lines if "cold_start_to_first_step_s" in m]
    assert len(cold) == 1
    assert cold[0]["cold_start_to_first_step_s"] > 0


def test_train_llama_rejects_unknown_model(monkeypatch):
    monkeypatch.setenv("TPUFW_MODEL", "gpt17_nonexistent")
    from tpufw.workloads import train_llama

    with pytest.raises(ValueError, match="unknown TPUFW_MODEL"):
        train_llama.build_trainer()


def test_train_llama_mixtral_selection(monkeypatch):
    monkeypatch.setenv("TPUFW_MODEL", "mixtral_tiny")
    monkeypatch.setenv("TPUFW_MESH_EXPERT", "2")
    from tpufw.models.mixtral import MixtralConfig
    from tpufw.workloads import train_llama

    trainer, cfg = train_llama.build_trainer()
    assert isinstance(cfg, MixtralConfig)
    assert trainer.mesh.shape["expert"] == 2


def test_train_resnet_main(capsys, monkeypatch):
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "8")
    monkeypatch.setenv("TPUFW_IMAGE_SIZE", "32")
    monkeypatch.setenv("TPUFW_NUM_CLASSES", "10")
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "2")
    from tpufw.workloads import train_resnet

    assert train_resnet.main() == 0
    out = capsys.readouterr().out
    assert "TRAIN OK: 2 steps" in out


def test_train_llama_dpo_objective(capsys, monkeypatch, tmp_path):
    """TPUFW_DPO_DATA switches the workload to DPOTrainer + pair
    batches; the first step's loss is the log-2 anchor (ref == policy)."""
    import math

    path = tmp_path / "pairs.jsonl"
    with open(path, "w") as f:
        for i in range(4):
            f.write(json.dumps({
                "prompt": f"q {i}", "chosen": "good", "rejected": "bad",
            }) + "\n")
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "8")
    monkeypatch.setenv("TPUFW_SEQ_LEN", "32")
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "2")
    monkeypatch.setenv("TPUFW_LOG_EVERY", "1")
    monkeypatch.setenv("TPUFW_LOSS_CHUNK_SIZE", "16")
    monkeypatch.setenv("TPUFW_DPO_DATA", str(path))
    from tpufw.workloads import train_llama

    assert train_llama.main() == 0
    out = capsys.readouterr().out
    metrics = [
        json.loads(line) for line in out.splitlines()
        if line.startswith("{") and "loss" in line
    ]
    assert metrics and abs(
        metrics[0]["loss"] - math.log(2.0)
    ) < 1e-4


def test_train_llama_dpo_resume_after_checkpoint(
    capsys, monkeypatch, tmp_path
):
    """ADVICE r3 (medium): a DPO pod restarting after its first
    checkpoint must RESUME — the reference re-anchored to the ORIGINAL
    base weights via TPUFW_INIT_FROM before restore — not crash-loop.
    train_llama.main orders init_from_params BEFORE maybe_restore for
    the DPO objective (deploy/manifests/10-dpo-v5e4.yaml's shape)."""
    import jax
    import orbax.checkpoint as ocp

    from tpufw.mesh import MeshConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.train import Trainer, TrainerConfig

    # A bare-params checkpoint: the import_hf CLI's output shape.
    base = Trainer(
        Llama(LLAMA_CONFIGS["llama3_tiny"]),
        TrainerConfig(batch_size=8, seq_len=32, total_steps=1),
        MeshConfig(),
    )
    base.init_state(seed=3)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(
            str(tmp_path / "base_params"),
            jax.device_get(base.state.params),
        )

    pairs = tmp_path / "pairs.jsonl"
    with open(pairs, "w") as f:
        for i in range(4):
            f.write(json.dumps({
                "prompt": f"q {i}", "chosen": "good", "rejected": "bad",
            }) + "\n")

    for k, v in {
        "TPUFW_MODEL": "llama3_tiny",
        "TPUFW_BATCH_SIZE": "8",
        "TPUFW_SEQ_LEN": "32",
        "TPUFW_TOTAL_STEPS": "2",
        "TPUFW_LOG_EVERY": "1",
        "TPUFW_LOSS_CHUNK_SIZE": "16",
        "TPUFW_DPO_DATA": str(pairs),
        "TPUFW_INIT_FROM": str(tmp_path / "base_params"),
        "TPUFW_CHECKPOINT_DIR": str(tmp_path / "ckpt"),
        "TPUFW_CHECKPOINT_EVERY": "1",
    }.items():
        monkeypatch.setenv(k, v)
    from tpufw.workloads import train_llama

    assert train_llama.main() == 0
    assert "initialized params from" in capsys.readouterr().out

    # Pod restart, same env: pre-fix this raised RuntimeError ("resumed
    # a DPO run mid-training without a reference snapshot").
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "3")
    assert train_llama.main() == 0
    out = capsys.readouterr().out
    assert "initialized params from" in out
    assert "resumed from checkpoint at step 2" in out


def test_train_llama_distill_objective(capsys, monkeypatch):
    """TPUFW_DISTILL_TEACHER switches to DistillTrainer (random teacher
    warns loudly; real deploys pass TPUFW_DISTILL_TEACHER_CKPT)."""
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "8")
    monkeypatch.setenv("TPUFW_SEQ_LEN", "33")
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "2")
    monkeypatch.setenv("TPUFW_LOG_EVERY", "1")
    monkeypatch.setenv("TPUFW_LOSS_CHUNK_SIZE", "16")
    monkeypatch.setenv("TPUFW_DISTILL_TEACHER", "llama3_tiny")
    from tpufw.workloads import train_llama

    assert train_llama.main() == 0
    out = capsys.readouterr().out
    assert "RANDOM-INIT" in out
    assert "TRAIN OK: 2 steps" in out


def test_train_llama_objectives_mutually_exclusive(monkeypatch):
    monkeypatch.setenv("TPUFW_DPO_DATA", "/tmp/x.jsonl")
    monkeypatch.setenv("TPUFW_DISTILL_TEACHER", "llama3_tiny")
    from tpufw.workloads import train_llama

    with pytest.raises(ValueError, match="mutually exclusive"):
        train_llama.build_trainer()


def test_rl_workload_main(capsys, monkeypatch, tmp_path):
    """The GRPO workload end-to-end: prompts file in, reward telemetry
    JSON lines out."""
    path = tmp_path / "prompts.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"prompt": "say something"}) + "\n")
        f.write(json.dumps([40, 41, 42]) + "\n")
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "8")
    monkeypatch.setenv("TPUFW_SEQ_LEN", "24")
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "2")
    monkeypatch.setenv("TPUFW_LR", "1e-3")
    monkeypatch.setenv("TPUFW_GRPO_GROUP", "4")
    monkeypatch.setenv("TPUFW_GRPO_MAX_NEW", "6")
    monkeypatch.setenv("TPUFW_PROMPTS_FILE", str(path))
    from tpufw.workloads import rl

    assert rl.main() == 0
    out = capsys.readouterr().out
    assert "RL OK: 2 steps" in out
    metrics = [
        json.loads(line) for line in out.splitlines()
        if line.startswith("{") and "reward_mean" in line
    ]
    assert len(metrics) == 2
    assert {"reward_mean", "clip_frac", "kl", "loss"} <= metrics[0].keys()


def test_rl_reward_resolution():
    from tpufw.workloads.rl import resolve_reward

    low = resolve_reward("low_token", 100, 8)
    assert low([], [[10, 80], [60, 70]]).tolist() == [0.5, 0.0]
    length = resolve_reward("length", 100, 8)
    assert length([], [[1, 2], [1, 2, 3, 4]]).tolist() == [0.25, 0.5]
    # Importable spec: any pkg.mod:fn callable.
    fn = resolve_reward("operator:length_hint", 100, 8)
    assert callable(fn)
    with pytest.raises(ValueError, match="TPUFW_REWARD"):
        resolve_reward("nonsense", 100, 8)


def test_resume_data_seed_contract():
    """Resumed runs must not replay consumed data: the seed folds the
    restored step in (fresh permutation), step 0 keeps the base seed."""
    from tpufw.workloads._common import resume_data_seed

    assert resume_data_seed(7, 0) == 7
    a, b = resume_data_seed(7, 100), resume_data_seed(7, 200)
    assert a != 7 and b != 7 and a != b
    # Deterministic given (seed, step) — the gang must agree.
    assert resume_data_seed(7, 100) == a


def test_embed_workload_main(capsys, monkeypatch, tmp_path):
    """The embedding workload end-to-end: pairs in, InfoNCE telemetry
    and a retrieval probe out."""
    path = tmp_path / "pairs.jsonl"
    with open(path, "w") as f:
        for i in range(8):
            f.write(json.dumps({
                "query": f"what is topic {i}",
                "positive": f"topic {i} is item {i} " * 2,
            }) + "\n")
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "8")
    monkeypatch.setenv("TPUFW_SEQ_LEN", "48")
    monkeypatch.setenv("TPUFW_TOTAL_STEPS", "3")
    monkeypatch.setenv("TPUFW_LR", "3e-3")
    monkeypatch.setenv("TPUFW_EMBED_DATA", str(path))
    monkeypatch.setenv("TPUFW_BIDIRECTIONAL", "1")
    from tpufw.workloads import embed

    assert embed.main() == 0
    out = capsys.readouterr().out
    assert "EMBED OK: 3 steps" in out
    assert "causal=False" in out
    probes = [
        json.loads(line) for line in out.splitlines()
        if line.startswith("{") and "probe_sim_matched" in line
    ]
    assert len(probes) == 1
    metrics = [
        json.loads(line) for line in out.splitlines()
        if line.startswith("{") and "loss" in line
    ]
    assert metrics and "step_time_s" in metrics[0]


def test_embed_workload_requires_data(monkeypatch):
    monkeypatch.setenv("TPUFW_MODEL", "llama3_tiny")
    monkeypatch.setenv("TPUFW_BATCH_SIZE", "8")
    from tpufw.workloads import embed

    with pytest.raises(ValueError, match="TPUFW_EMBED_DATA"):
        embed.main()
