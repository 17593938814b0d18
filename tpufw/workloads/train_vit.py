"""ViT training workload — the MXU-native vision counterpart of
tpufw.workloads.train_resnet (same VisionTrainer, JSON step metrics to
pod logs, checkpoint/preemption contract; reference analog is the
log-visible device proof at reference README.md:303-335).

Env knobs (TPUFW_*): MODEL (vit_b16|vit_s16|vit_l16), BATCH_SIZE,
TOTAL_STEPS, plus the shared checkpoint/preemption set.
"""

from __future__ import annotations

import json

from tpufw.workloads.env import env_bool, env_int, env_str


def main() -> int:
    from tpufw.cluster import initialize_cluster
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()
    cluster = initialize_cluster()

    import dataclasses

    import jax

    from tpufw.models import VIT_CONFIGS, ViT
    from tpufw.train import (
        VisionTrainer,
        VisionTrainerConfig,
        synthetic_images,
    )

    name = env_str("model", "vit_b16")
    if name not in VIT_CONFIGS:
        raise SystemExit(
            f"TPUFW_MODEL={name!r} unknown; choose from "
            f"{sorted(VIT_CONFIGS)}"
        )
    mcfg = dataclasses.replace(
        VIT_CONFIGS[name],
        num_classes=env_int("num_classes", 1000),
        # Default to the PRESET's remat (True for the production
        # sizes: without it the layer scan saves every block's f32
        # [B,H,T,T] attention tensor — measured compile-OOM at ViT-B
        # batch 128 on one v5e chip). TPUFW_REMAT=0 overrides.
        remat=env_bool("remat", VIT_CONFIGS[name].remat),
    )
    cfg = VisionTrainerConfig(
        batch_size=env_int("batch_size", 256),
        image_size=mcfg.image_size,
        num_classes=mcfg.num_classes,
        total_steps=env_int("total_steps", 50),
        lr=env_int("lr_milli", 1) / 1000.0,
        checkpoint_dir=env_str("checkpoint_dir", "") or None,
        checkpoint_every=env_int("checkpoint_every", 100),
        handle_preemption=env_bool("handle_preemption", True),
        preemption_sync_every=env_int("preemption_sync_every", 1),
        sync_every=env_int("sync_every", 4),
    )
    print(
        f"tpufw train_vit[{name}]: process {cluster.process_id}/"
        f"{cluster.num_processes} devices={jax.devices()}"
    )
    trainer = VisionTrainer(ViT(mcfg), cfg)
    if trainer.maybe_restore():
        print(f"resumed from checkpoint at step {int(trainer.state.step)}")
    else:
        trainer.init_state(seed=env_int("seed", 0))

    history = trainer.run(
        synthetic_images(
            cfg.batch_size, cfg.image_size, cfg.num_classes,
            on_device=True,
        ),
        flops_per_image=mcfg.flops_per_image(),
        on_metrics=lambda m: print(json.dumps(m.as_dict()), flush=True),
    )
    from tpufw.workloads._common import mfu_suffix, report_preemption

    report_preemption(trainer)
    if history:
        last = history[-1]
        print(
            f"TRAIN OK: {len(history)} windows, final loss "
            f"{last.loss:.4f}, {last.tokens_per_sec_per_chip:.1f} "
            f"images/s/chip" + mfu_suffix(last)
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
