"""Vision training workload (BASELINE config 2): ResNet-50 on one v5e chip.

The first *real* training proof in the recipe ladder: one `kubectl apply`
of deploy/manifests/03-resnet50-v5e1.yaml runs this on a google.com/tpu: 1
pod; images/sec and loss stream to the pod logs.
"""

from __future__ import annotations

import json

from tpufw.workloads.env import env_bool, env_int, env_str


def main() -> int:
    from tpufw.cluster import initialize_cluster
    from tpufw.utils.profiling import enable_compile_cache

    enable_compile_cache()
    cluster = initialize_cluster()

    import jax

    from tpufw.models import ResNetConfig, resnet50
    from tpufw.train import (
        VisionTrainer,
        VisionTrainerConfig,
        synthetic_images,
    )

    # BatchNorm compute dtype (stats stay f32 either way). bfloat16 is
    # the TPU-first default: the early high-resolution stages are
    # HBM-bandwidth-bound and f32 BN doubles their activation traffic
    # (measured on v5e: 1906 -> 2524 img/s at batch 256).
    norm_dtype = env_str("norm_dtype", "bfloat16")
    cfg = VisionTrainerConfig(
        batch_size=env_int("batch_size", 256),
        image_size=env_int("image_size", 224),
        num_classes=env_int("num_classes", 1000),
        total_steps=env_int("total_steps", 50),
        checkpoint_dir=env_str("checkpoint_dir", "") or None,
        checkpoint_every=env_int("checkpoint_every", 100),
        handle_preemption=env_bool("handle_preemption", True),
        preemption_sync_every=env_int("preemption_sync_every", 1),
    )
    print(
        f"tpufw train_resnet: process {cluster.process_id}/"
        f"{cluster.num_processes} devices={jax.devices()}"
    )
    import jax.numpy as jnp

    trainer = VisionTrainer(
        resnet50(cfg.num_classes, norm_dtype=getattr(jnp, norm_dtype)),
        cfg,
    )
    if trainer.maybe_restore():
        print(f"resumed from checkpoint at step {int(trainer.state.step)}")
    else:
        trainer.init_state(seed=env_int("seed", 0))

    flops = ResNetConfig().flops_per_image(cfg.image_size)
    history = trainer.run(
        synthetic_images(cfg.batch_size, cfg.image_size, cfg.num_classes),
        flops_per_image=flops,
        on_metrics=lambda m: print(json.dumps(m.as_dict()), flush=True),
    )
    from tpufw.workloads._common import mfu_suffix, report_preemption

    report_preemption(trainer)
    if history:
        last = history[-1]
        imgs_per_sec = last.tokens_per_sec_per_chip  # tokens == images
        print(
            f"TRAIN OK: {len(history)} steps, final loss {last.loss:.4f}, "
            f"{imgs_per_sec:.1f} images/s/chip" + mfu_suffix(last)
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
