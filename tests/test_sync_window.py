"""sync_every > 1: windowed host syncs across all three trainer loops.

One host sync (block_until_ready on the loss) per window of dispatched
steps — every sync is a host<->device round trip that serializes
against short steps (bench r3: the ResNet tier). The
cadence contract: always sync after the FIRST step (compile boundary,
so cold-start timing survives) and the LAST; metrics entries carry
window averages in ``StepMetrics.window_steps``.
"""

import math

from tpufw.mesh import MeshConfig
from tpufw.models import LLAMA_CONFIGS, Llama
from tpufw.train import (
    Trainer,
    TrainerConfig,
    synthetic_batches,
    synthetic_images,
)

TINY = LLAMA_CONFIGS["llama3_tiny"]


def test_trainer_windowed_sync_cadence():
    trainer = Trainer(
        Llama(TINY),
        TrainerConfig(
            batch_size=8, seq_len=17, total_steps=5, lr=1e-3,
            sync_every=2, log_every=1,
        ),
        MeshConfig(),
    )
    trainer.init_state()
    seen = []
    hist = trainer.run(
        synthetic_batches(8, 17, TINY.vocab_size),
        model_flops_per_token=TINY.flops_per_token(16),
        on_metrics=seen.append,
    )
    # Syncs at step 1 (compile boundary), MULTIPLES of sync_every
    # (2, 4 — so aligned checkpoint_every/eval_every fire), last (5).
    assert [m.step for m in hist] == [1, 2, 4, 5]
    assert [m.window_steps for m in hist] == [1, 1, 2, 1]
    assert len(seen) == 4  # sync_every>1 logs every sync
    assert all(math.isfinite(m.loss) for m in hist)
    assert int(trainer.state.step) == 5  # py_step tracking == device step


def test_trainer_default_sync_is_per_step():
    trainer = Trainer(
        Llama(TINY),
        TrainerConfig(batch_size=8, seq_len=17, total_steps=3, lr=1e-3),
        MeshConfig(),
    )
    trainer.init_state()
    hist = trainer.run(
        synthetic_batches(8, 17, TINY.vocab_size),
        model_flops_per_token=TINY.flops_per_token(16),
    )
    assert [m.step for m in hist] == [1, 2, 3]
    assert all(m.window_steps == 1 for m in hist)


def test_vision_trainer_windowed_sync():
    from tpufw.models.resnet import ResNet, ResNetConfig
    from tpufw.train import VisionTrainer, VisionTrainerConfig

    small = ResNet(
        ResNetConfig(num_classes=10, stage_sizes=(1, 1), width=8)
    )
    vt = VisionTrainer(
        small,
        VisionTrainerConfig(
            batch_size=8, image_size=32, num_classes=10,
            total_steps=5, sync_every=2,
        ),
        MeshConfig(),
    )
    vt.init_state()
    hist = vt.run(
        synthetic_images(8, 32, 10, on_device=True),
        flops_per_image=1e6,
    )
    assert [m.step for m in hist] == [1, 2, 4, 5]
    assert [m.window_steps for m in hist] == [1, 1, 2, 1]
    assert int(vt.state.step) == 5


def test_pipeline_trainer_windowed_sync(devices8):
    import dataclasses

    from tpufw.parallel.pipeline import PipelineConfig
    from tpufw.train import PipelineTrainer

    cfg = dataclasses.replace(TINY, n_layers=4)
    pt = PipelineTrainer(
        cfg,
        PipelineConfig(n_stages=2, n_microbatches=2),
        TrainerConfig(
            batch_size=16, seq_len=17, total_steps=4, lr=1e-3,
            sync_every=3,
        ),
        MeshConfig(data=2, pipe=2, fsdp=2),
    )
    pt.init_state()
    hist = pt.run(
        synthetic_batches(16, 17, cfg.vocab_size),
        model_flops_per_token=cfg.flops_per_token(16),
    )
    # Syncs at step 1, step 3 (multiple of 3), step 4 (last).
    assert [m.step for m in hist] == [1, 3, 4]
    assert [m.window_steps for m in hist] == [1, 2, 1]


def test_exhausted_iterator_flushes_open_window():
    """A finite dataset ending mid-window must still meter and record
    the trailing steps (review r3: they were silently dropped)."""
    import itertools

    trainer = Trainer(
        Llama(TINY),
        TrainerConfig(
            batch_size=8, seq_len=17, total_steps=100, lr=1e-3,
            sync_every=4,
        ),
        MeshConfig(),
    )
    trainer.init_state()
    data = itertools.islice(
        synthetic_batches(8, 17, TINY.vocab_size), 6
    )
    hist = trainer.run(
        data, model_flops_per_token=TINY.flops_per_token(16)
    )
    # Syncs at steps 1 and 4; steps 5-6 flush post-loop.
    assert [m.step for m in hist] == [1, 4, 6]
    assert [m.window_steps for m in hist] == [1, 3, 2]
    assert int(trainer.state.step) == 6


def test_window_data_wait_is_per_step_average():
    """data_wait_s shares step_time_s's per-step units in a window
    entry (review r3: it was the window SUM, inflating boundness by
    sync_every x)."""
    import time as _time

    def slow(it, delay):
        for b in it:
            _time.sleep(delay)
            yield b

    trainer = Trainer(
        Llama(TINY),
        TrainerConfig(
            batch_size=8, seq_len=17, total_steps=4, lr=1e-3,
            sync_every=4,
        ),
        MeshConfig(),
    )
    trainer.init_state()
    hist = trainer.run(
        slow(synthetic_batches(8, 17, TINY.vocab_size), 0.05),
        model_flops_per_token=TINY.flops_per_token(16),
    )
    w = hist[-1]  # steps 2-4 window
    assert w.window_steps == 3
    # Per-step average ~0.05s, never the ~0.15s window sum.
    assert 0.03 < w.data_wait_s < 0.12, w.data_wait_s
