"""PipelineTrainer: the Trainer surface (metrics, checkpoint/resume)
over the GPipe schedule, on a data x pipe x fsdp mesh."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.mesh import MeshConfig
from tpufw.models import LLAMA_CONFIGS
from tpufw.parallel.pipeline import PipelineConfig
from tpufw.train import PipelineTrainer, TrainerConfig, synthetic_batches

CFG = dataclasses.replace(
    LLAMA_CONFIGS["llama3_tiny"],
    n_layers=4,
    dtype=jnp.float32,
    param_dtype=jnp.float32,
)
PIPE = PipelineConfig(n_stages=2, n_microbatches=4)
MESH = MeshConfig(data=2, pipe=2, fsdp=2)


def _trainer(**over):
    cfg = dict(
        batch_size=16, seq_len=33, total_steps=8, lr=1e-2, warmup_steps=2
    )
    cfg.update(over)
    return PipelineTrainer(CFG, PIPE, TrainerConfig(**cfg), MESH)


def test_trains_and_meters(devices8):
    t = _trainer()
    t.init_state()
    hist = t.run(
        synthetic_batches(16, 33, CFG.vocab_size),
        model_flops_per_token=CFG.flops_per_token(32),
    )
    assert len(hist) == 8
    assert hist[-1].loss < hist[0].loss
    assert hist[-1].tokens_per_sec_per_chip > 0
    assert hist[-1].mfu is None  # CPU mesh: no peak, no MFU


def test_stage_params_sharded_on_pipe(devices8):
    t = _trainer()
    t.init_state()
    wq = t.state.params["stages"]["wq"]
    assert "pipe" in str(wq.sharding.spec)
    # Adam moments mirror the stage sharding.
    import jax

    moment_specs = [
        str(x.sharding.spec)
        for x in jax.tree.leaves(t.state.opt_state)
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == 2
    ]
    assert moment_specs and all("pipe" in s for s in moment_specs)


def test_checkpoint_resume(tmp_path, devices8):
    ckpt = str(tmp_path / "pipe-ckpt")
    t = _trainer(checkpoint_dir=ckpt, checkpoint_every=1, total_steps=3)
    t.init_state()
    t.run(
        synthetic_batches(16, 33, CFG.vocab_size),
        model_flops_per_token=CFG.flops_per_token(32),
    )
    w_before = np.asarray(t.state.params["stages"]["wq"])

    t2 = _trainer(checkpoint_dir=ckpt, checkpoint_every=1, total_steps=5)
    assert t2.maybe_restore()
    assert int(t2.state.step) == 3
    np.testing.assert_array_equal(
        np.asarray(t2.state.params["stages"]["wq"]), w_before
    )
    hist = t2.run(
        synthetic_batches(16, 33, CFG.vocab_size, seed=1),
        model_flops_per_token=CFG.flops_per_token(32),
    )
    # total_steps is a GLOBAL budget: restored at 3, budget 5 -> 2 more.
    assert int(t2.state.step) == 5
    assert len(hist) == 2
    assert np.isfinite(hist[-1].loss)


def test_unsupported_features_are_loud(devices8):
    with pytest.raises(NotImplementedError, match="grad_accum"):
        PipelineTrainer(
            CFG, PIPE,
            TrainerConfig(batch_size=16, seq_len=33, grad_accum=2),
            MESH,
        )


def test_packed_batches_train(devices8):
    """segment_ids + loss_mask flow through the pipe ring with the same
    masking as the flax trainer."""
    from tpufw.train import synthetic_packed_batches

    t = _trainer(total_steps=6)
    t.init_state()
    hist = t.run(
        synthetic_packed_batches(16, 33, CFG.vocab_size, mean_doc_len=8),
        model_flops_per_token=CFG.flops_per_token(32),
    )
    assert len(hist) == 6
    assert np.isfinite(hist[-1].loss)
    assert hist[-1].loss < hist[0].loss


def test_mesh_stage_mismatch_is_loud():
    with pytest.raises(ValueError, match="mesh_cfg.pipe=4"):
        PipelineTrainer(
            CFG,
            PIPE,
            TrainerConfig(batch_size=16, seq_len=33),
            MeshConfig(pipe=4, fsdp=2),
        )


def test_evaluate_token_weighted(devices8):
    """Forward-only pipeline eval: token-weighted loss/ppl with the same
    reporting surface as Trainer.evaluate."""
    t = _trainer(total_steps=2)
    t.init_state()
    t.run(
        synthetic_batches(16, 33, CFG.vocab_size),
        model_flops_per_token=CFG.flops_per_token(32),
    )
    ev = t.evaluate(synthetic_batches(16, 33, CFG.vocab_size, seed=9), 3)
    assert ev["eval_batches"] == 3
    assert ev["eval_tokens"] == 3 * 16 * 32
    assert np.isfinite(ev["eval_loss"])
    assert ev["eval_ppl"] == pytest.approx(
        np.exp(ev["eval_loss"]), rel=1e-6
    )
    # Eval must not touch training state (no donation of params).
    ev2 = t.evaluate(synthetic_batches(16, 33, CFG.vocab_size, seed=9), 3)
    assert ev2["eval_loss"] == pytest.approx(ev["eval_loss"], rel=1e-6)


def test_eval_every_in_run(devices8):
    """cfg.eval_every fires the in-loop eval hook (previously rejected as
    unimplemented)."""
    seen = []
    t = _trainer(total_steps=4, eval_every=2, eval_batches=2)
    t.init_state()
    t.run(
        synthetic_batches(16, 33, CFG.vocab_size),
        model_flops_per_token=CFG.flops_per_token(32),
        eval_data=lambda: synthetic_batches(16, 33, CFG.vocab_size, seed=9),
        on_eval=seen.append,
    )
    assert [ev["step"] for ev in seen] == [2, 4]
    assert all(np.isfinite(ev["eval_loss"]) for ev in seen)


def test_chunked_ce_matches_full_logits(devices8):
    """Pipeline chunked-vocab CE (head inside tpufw.ops.loss, hidden
    states from the pipelined forward) agrees with the full-logits
    objective at fp32."""
    from tpufw.parallel.pipeline import pipeline_eval

    t = _trainer(total_steps=1)
    t.init_state()
    batch = next(synthetic_batches(16, 33, CFG.vocab_size))
    full = pipeline_eval(t.state.params, batch, CFG, PIPE, t.mesh)
    chunked = pipeline_eval(
        t.state.params, batch, CFG, PIPE, t.mesh,
        loss_chunk_size=16, loss_chunk_dtype=jnp.float32,
    )
    np.testing.assert_allclose(
        float(chunked["loss"]), float(full["loss"]), rtol=1e-6
    )
    assert float(chunked["n_tokens"]) == float(full["n_tokens"])


def test_trains_with_chunked_ce_and_profiler(tmp_path, devices8):
    """loss_chunk_size + profile_dir both previously raised; now the
    trainer runs with the chunked objective and writes an XProf trace."""
    prof_dir = str(tmp_path / "prof")
    t = _trainer(
        total_steps=3,
        loss_chunk_size=16,
        profile_dir=prof_dir,
        profile_start=1,
        profile_stop=2,
    )
    t.init_state()
    hist = t.run(
        synthetic_batches(16, 33, CFG.vocab_size),
        model_flops_per_token=CFG.flops_per_token(32),
    )
    assert len(hist) == 3
    assert np.isfinite(hist[-1].loss)
    import os

    assert any(os.scandir(prof_dir)), "no XProf trace written"
