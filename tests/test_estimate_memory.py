"""Memory estimator: pure arithmetic, no backend, layout-faithful."""

import json
import subprocess
import sys

from tpufw.models import LLAMA_CONFIGS
from tpufw.tools.estimate_memory import estimate_decode, estimate_train

CFG8B = LLAMA_CONFIGS["llama3_8b"]


def test_train_components_scale_with_sharding():
    one = estimate_train(CFG8B, 16, 2048, n_shards=1)
    sixteen = estimate_train(CFG8B, 16, 2048, n_shards=16)
    for field in ("params", "optimizer", "gradients"):
        assert getattr(one, field) == 16 * getattr(sixteen, field)
    # fp32 params + fp32 mu + fp32 nu: optimizer = 2x params.
    assert abs(one.optimizer - 2 * one.params) < 1e-6 * one.params


def test_remat_policy_orders_activation_memory():
    kw = dict(batch_size=8, seq_len=2048, n_shards=1)
    nothing = estimate_train(CFG8B, remat_policy="nothing", **kw)
    dots = estimate_train(CFG8B, remat_policy="dots", **kw)
    everything = estimate_train(CFG8B, remat_policy="everything", **kw)
    assert nothing.activations < dots.activations < everything.activations
    # The r2 sweep's mechanism: "dots" keeps every layer's projection
    # outputs resident, so it is many times "nothing"'s footprint.
    assert dots.activations > 5 * nothing.activations


def test_chunked_ce_caps_logits():
    full = estimate_train(CFG8B, 8, 2048, loss_chunk_size=None)
    chunked = estimate_train(CFG8B, 8, 2048, loss_chunk_size=512)
    assert chunked.logits_ce < full.logits_ce / 3


def test_decode_weights_dtype_halves_params():
    fp32 = estimate_decode(CFG8B, 8, cache_len=2048)
    bf16 = estimate_decode(
        CFG8B, 8, cache_len=2048, weights_dtype="bfloat16"
    )
    assert abs(fp32.params - 2 * bf16.params) < 1e-6 * fp32.params
    assert fp32.kv_cache == bf16.kv_cache  # cache dtype is cfg.dtype
    # The serving reality the cast exists for: 8B fp32 decode cannot
    # fit one v5e (16 GiB) at ANY batch; bf16 fits a short-context one.
    assert fp32.total() > 16 * 2**30
    short = estimate_decode(
        CFG8B, 4, cache_len=512, weights_dtype="bfloat16"
    )
    assert short.total() < 16 * 2**30


def test_decode_cache_len_scales_kv():
    a = estimate_decode(CFG8B, 8, cache_len=256)
    b = estimate_decode(CFG8B, 8, cache_len=2048)
    assert abs(b.kv_cache - 8 * a.kv_cache) < 1e-6 * b.kv_cache


def test_cli_emits_json_without_backend():
    """The CLI answers from the static chip table: no backend is
    asked, so it works where there is no accelerator at all."""
    proc = subprocess.run(
        [
            sys.executable, "-m", "tpufw.tools.estimate_memory",
            "--model", "llama3_8b", "--batch", "16", "--seq", "2048",
            "--fsdp", "16", "--ce-chunk", "512", "--remat", "nothing",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["fits"] is True and out["mode"] == "train"
    assert out["total_gib"] < out["chip_hbm_gib"]


def test_moe_activation_exceeds_dense_equivalent():
    """Mixtral's dispatch/combine tensors (quadratic in the routing
    group) must show up — a dense-MLP model of the same dims would
    green-light batch sizes that OOM (review r3)."""
    from tpufw.models import MIXTRAL_CONFIGS

    moe = MIXTRAL_CONFIGS["mixtral_8x7b"]
    dense_like = LLAMA_CONFIGS["llama3_8b"]
    m = estimate_train(moe, 8, 2048, n_shards=8, remat_policy="dots")
    d = estimate_train(
        dense_like, 8, 2048, n_shards=8, remat_policy="dots"
    )
    assert m.activations > d.activations


def test_decode_sharding_divides_everything():
    one = estimate_decode(CFG8B, 8, cache_len=2048, n_shards=1)
    four = estimate_decode(CFG8B, 8, cache_len=2048, n_shards=4)
    assert abs(one.total() - 4 * four.total()) < 1e-6 * one.total()


def test_bench_preset_is_estimable():
    """The tool's stated purpose is picking the bench's batch point;
    its estimate must reproduce the measured ladder's shape: batch 24
    with full remat ~fits a v5e, batch 32 clearly does not."""
    from tpufw.configs import bench_model_config

    cfg = bench_model_config()
    b24 = estimate_train(
        cfg, 24, 2048, remat_policy="nothing", loss_chunk_size=512
    )
    b32 = estimate_train(
        cfg, 32, 2048, remat_policy="nothing", loss_chunk_size=512
    )
    hbm = 16 * 2**30
    assert b24.total() < 1.1 * hbm  # right at the edge, as measured
    assert b32.total() > 1.15 * hbm


def test_mla_latent_cache_geometry():
    """MLA decode caches the LATENT (kvr + rope dim) per token — far
    smaller than the MHA 2*K*dh formula; train terms include the
    latent + expanded projections."""
    from tpufw.models import DEEPSEEK_CONFIGS, LLAMA_CONFIGS
    from tpufw.tools.estimate_memory import (
        _attn_geometry,
        estimate_decode,
    )

    mla = DEEPSEEK_CONFIGS["deepseek_mla_bench"]
    _, per_tok = _attn_geometry(mla)
    assert per_tok == mla.kv_lora_rank + mla.qk_rope_head_dim  # 576
    llama = LLAMA_CONFIGS["llama3_8b"]
    _, mha_tok = _attn_geometry(llama)
    assert mha_tok == 2 * llama.n_kv_heads * llama.head_dim  # 2048
    # Per layer per token the latent is > 3.5x smaller — the family's
    # headline figure (tpufw.models.deepseek docstring).
    assert mha_tok / per_tok > 3.5
    assert estimate_decode(mla, 8, 2048).kv_cache > 0
