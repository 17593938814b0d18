"""End-to-end sharded training on the 8-device CPU mesh: loss goes down,
metrics are produced, checkpoints round-trip."""

import itertools

import numpy as np
import pytest

from tpufw.mesh import MeshConfig
from tpufw.models import Llama, LLAMA_CONFIGS
from tpufw.train import (
    Trainer,
    TrainerConfig,
    pack_documents,
    synthetic_batches,
)

TINY = LLAMA_CONFIGS["llama3_tiny"]


@pytest.fixture(scope="module")
def trained():
    cfg = TrainerConfig(
        batch_size=8, seq_len=33, total_steps=12, lr=1e-2, warmup_steps=2
    )
    trainer = Trainer(
        Llama(TINY), cfg, MeshConfig(data=2, fsdp=2, tensor=2)
    )
    trainer.init_state()
    # One batch repeated for all steps: per-step loss on FRESH random
    # batches is noisier than 12 steps of learning signal, so the
    # loss-decreases assert would be a coin flip. Overfitting a single
    # batch gives a multi-nat drop that no seed can mask.
    batch = next(synthetic_batches(8, 33, TINY.vocab_size, seed=0))
    history = trainer.run(
        itertools.repeat(batch, 12),
        model_flops_per_token=TINY.flops_per_token(32),
    )
    return trainer, history


def test_loss_decreases(trained):
    _, history = trained
    assert len(history) == 12
    # Synthetic uniform data: loss should fall from ~ln(256) toward entropy.
    assert history[-1].loss < history[0].loss
    assert np.isfinite(history[-1].loss)


def test_metrics_populated(trained):
    _, history = trained
    m = history[-1]
    assert m.tokens_per_sec_per_chip > 0
    # A CPU device has no peak FLOP/s to divide by: no MFU is invented.
    assert m.mfu is None and "mfu" not in m.as_dict()
    assert m.step_time_s > 0


def test_state_is_sharded(trained):
    trainer, _ = trained
    gate = trainer.state.params["layers"]["mlp"]["gate"]["kernel"]
    # Scanned mlp gate kernel: [layers, embed, mlp]; mlp dim sharded on tensor.
    assert gate.shape == (TINY.n_layers, TINY.d_model, TINY.d_ff)
    spec = gate.sharding.spec
    assert "tensor" in str(spec)


def test_checkpoint_roundtrip(tmp_path, trained):
    import jax

    from tpufw.train import CheckpointManager

    trainer, _ = trained
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    step = int(trainer.state.step)
    assert mgr.save(step, trainer.state, force=True)
    mgr.wait()
    assert mgr.latest_step() == step

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        trainer.state,
    )
    restored = mgr.restore(abstract)
    orig_leaf = np.asarray(
        trainer.state.params["layers"]["attn"]["q"]["kernel"]
    )
    rest_leaf = np.asarray(restored.params["layers"]["attn"]["q"]["kernel"])
    np.testing.assert_array_equal(orig_leaf, rest_leaf)
    assert int(restored.step) == step
    mgr.close()


def test_pack_documents_masks_and_shapes():
    docs = [np.arange(1, 20), np.arange(1, 8), np.arange(1, 50)]
    batches = list(pack_documents(iter(docs), batch_size=2, seq_len=16))
    total_real = sum(int(b["loss_mask"].sum()) for b in batches)
    assert total_real == 19 + 7 + 49
    for b in batches:
        assert b["tokens"].shape == (2, 16)
        assert b["segment_ids"].shape == (2, 16)
        # Padding has segment 0 and no loss.
        assert np.all((b["segment_ids"] > 0) == (b["loss_mask"] > 0))


def test_packed_data_through_flash_backend(devices8):
    """End-to-end VERDICT r1 item 2: packed batches (segment_ids +
    loss_mask, the native_data/pack_documents shape) train through the
    segment-aware FLASH kernel, and the loss matches the xla backend
    bit-for-bit-close on the same batch — the production path and the
    measured path are the same math."""
    import dataclasses

    from tpufw.train.data import synthetic_packed_batches

    cfg = LLAMA_CONFIGS["llama3_tiny"]
    batch = next(iter(synthetic_packed_batches(8, 64, cfg.vocab_size)))
    assert (batch["segment_ids"] > 1).any()  # really packed: >1 doc somewhere

    losses = {}
    for backend in ("xla", "flash"):
        bcfg = dataclasses.replace(cfg, attention_backend=backend)
        trainer = Trainer(
            Llama(bcfg),
            TrainerConfig(
                batch_size=8, seq_len=64, total_steps=1, lr=1e-3
            ),
            MeshConfig(),
        )
        trainer.init_state(seed=7)
        history = trainer.run(
            iter([batch]), model_flops_per_token=cfg.flops_per_token(63)
        )
        losses[backend] = history[0].loss
    assert np.isfinite(losses["flash"])
    np.testing.assert_allclose(
        losses["flash"], losses["xla"], rtol=2e-4,
        err_msg="flash-vs-xla packed loss diverged",
    )


@pytest.mark.parametrize(
    "mesh_cfg",
    [MeshConfig(), MeshConfig(fsdp=4, tensor=2)],
    ids=["fsdp8", "fsdp4_tensor2"],
)
def test_flash_train_step_lowers_for_tpu_over_a_mesh(
    devices8, monkeypatch, mesh_cfg
):
    """The blocker PR 21 found by reading, caught from a CPU host.

    Mosaic kernels cannot be partitioned by GSPMD: under the trainer's
    jit over more than one device the flash kernel must sit inside a
    fully-manual ``shard_map``, or lowering raises ``NotImplementedError:
    Mosaic kernels cannot be automatically partitioned``. The Pallas
    interpreter — what every CPU run uses — lowers to plain HLO and never
    meets that rule, so this forces the compiled side (interpret=False)
    and LOWERS (no compile, no run) the train step for the TPU platform
    over the 8-device virtual mesh. Cross-platform lowering runs jax's
    TPU lowering rules, Mosaic's included, without a TPU backend; what
    it cannot reach is the Mosaic compiler itself (chip_smoke.py does)."""
    import dataclasses

    monkeypatch.setattr(
        "tpufw.ops.flash.default_interpret", lambda platform: False
    )
    cfg = dataclasses.replace(
        TINY, attention_backend="flash", head_dim=128, max_seq_len=256
    )
    trainer = Trainer(
        Llama(cfg),
        TrainerConfig(batch_size=8, seq_len=129, loss_chunk_size=64),
        mesh_cfg,
    )
    text = trainer.lower_step(lowering_platforms=("tpu",)).as_text()
    # Forward, dq and dkv kernels, each a Mosaic custom call.
    assert text.count("tpu_custom_call") >= 3


def test_data_wait_is_measured(devices8):
    """data_wait_s reflects host blocking in the data iterator — a
    deliberately slow iterator must show up in the telemetry."""
    import time as _time

    from tpufw.mesh import MeshConfig
    from tpufw.models import LLAMA_CONFIGS, Llama
    from tpufw.train import Trainer, TrainerConfig, synthetic_batches

    tiny = LLAMA_CONFIGS["llama3_tiny"]

    def slow(inner, delay):
        for b in inner:
            _time.sleep(delay)
            yield b

    trainer = Trainer(
        Llama(tiny),
        TrainerConfig(batch_size=8, seq_len=17, total_steps=3, lr=1e-3),
        MeshConfig(data=8),
    )
    trainer.init_state()
    hist = trainer.run(
        slow(synthetic_batches(8, 17, tiny.vocab_size), 0.05),
        model_flops_per_token=tiny.flops_per_token(16),
    )
    assert all(m.data_wait_s >= 0.04 for m in hist), [
        m.data_wait_s for m in hist
    ]
