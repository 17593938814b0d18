"""Standalone probe for the block8b seq-8192 compile failure (r5: out
of memory at every batch on the naive attention path). Runs the exact
bench tier config at batch 1 and lets the full compile error reach
stderr."""
import dataclasses
import sys

sys.path.insert(0, "/root/repo")

from tpufw.utils.profiling import enable_compile_cache

enable_compile_cache()

from tpufw.mesh import MeshConfig
from tpufw.models import LLAMA_CONFIGS, Llama
from tpufw.train import Trainer, TrainerConfig, synthetic_batches

cfg = dataclasses.replace(
    LLAMA_CONFIGS["llama3_8b"],
    vocab_size=2048,
    n_layers=1,
    max_seq_len=8192,
    remat_policy="attn_out",
    attention_backend="flash",
)
trainer = Trainer(
    Llama(cfg),
    TrainerConfig(
        batch_size=1, seq_len=8192, total_steps=3, lr=1e-4,
        warmup_steps=2, loss_chunk_size=512, log_every=1, sync_every=2,
    ),
    MeshConfig(),
)
trainer.init_state()
hist = trainer.run(
    synthetic_batches(1, 8192, cfg.vocab_size),
    model_flops_per_token=cfg.flops_per_token(8191),
)
print("OK", [round(m.mfu, 4) for m in hist])
