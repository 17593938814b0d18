"""Import HuggingFace Llama checkpoints into tpufw parameter trees.

Interoperability path: users coming from the torch/HF ecosystem load
their existing Llama weights (e.g. Meta-Llama-3-8B) straight into the
tpufw trainer/server. The reference has no model layer to import into
(its workload is ``nvidia-smi``, reference README.md:314); this is part
of the additive ML stack.

The mapping is purely structural (no numerics): HF ``nn.Linear`` stores
``weight`` as [out, in] while flax DenseGeneral kernels are [in, ...out],
so projections transpose; per-layer tensors stack onto the leading
``layers`` axis of the ``nn.scan`` trunk. RoPE conventions already agree
(HF's rotate_half == tpufw.models.llama.apply_rope half-split), which is
what makes logits-level parity possible — pinned by
tests/test_import_hf.py against a real ``transformers`` forward.

Works from an in-memory HF model / state_dict (tests) or a checkpoint
directory with ``*.safetensors`` (production).
"""

from __future__ import annotations

import json
import os
import pathlib
from typing import Any, Mapping

import numpy as np

from tpufw.models.llama import LlamaConfig


def _to_np(t: Any) -> np.ndarray:
    """torch.Tensor / np.ndarray -> float32 numpy (bf16-safe)."""
    if isinstance(t, np.ndarray):
        return t.astype(np.float32)
    # torch tensor (possibly bf16, which numpy can't represent directly).
    return t.detach().to("cpu").float().numpy()


def _rope_scaling_from_hf(rs: Any):
    """HF ``rope_scaling`` dict -> tpufw RopeScaling (or None).

    ``rope_type == "llama3"`` (Llama-3.1/3.3) and ``"linear"``
    (position interpolation, common on long-context Llama-2 fine-tunes)
    import directly. Rejected loudly: "dynamic" (NTK-aware scaling is a
    function of the RUNTIME sequence length, so the frequencies change
    per call — tpufw's static-shape decode caches bake frequencies at
    trace time) and "longrope" (per-dimension learned scaling vectors
    with a short/long context switch; not implemented). A
    silently-dropped transform would import a model whose logits drift
    with position."""
    if not rs:
        return None
    from tpufw.models.llama import RopeScaling

    get = rs.get if isinstance(rs, Mapping) else lambda k, d=None: getattr(
        rs, k, d
    )
    # transformers renamed "type" -> "rope_type"; accept both.
    rtype = get("rope_type") or get("type")
    if rtype == "linear":
        return RopeScaling(
            factor=float(get("factor")), rope_type="linear"
        )
    if rtype != "llama3":
        raise NotImplementedError(
            f"rope_scaling rope_type={rtype!r} is not implemented "
            "('llama3' and 'linear' are; 'dynamic' scales with runtime "
            "sequence length, 'longrope' needs learned per-dim "
            "vectors); importing would silently change rotary "
            "frequencies"
        )
    return RopeScaling(
        factor=float(get("factor")),
        low_freq_factor=float(get("low_freq_factor")),
        high_freq_factor=float(get("high_freq_factor")),
        original_max_position_embeddings=int(
            get("original_max_position_embeddings")
        ),
    )


def config_from_hf(hf_config: Any) -> LlamaConfig:
    """tpufw config from a transformers Llama/Mixtral config (object or
    dict). ``model_type == "mixtral"`` yields a MixtralConfig."""
    get = (
        hf_config.get
        if isinstance(hf_config, Mapping)
        else lambda k, d=None: getattr(hf_config, k, d)
    )
    if get("model_type") == "gemma2":
        return _gemma_config_from_hf(get)
    if get("model_type") == "deepseek_v2":
        return _deepseek_config_from_hf(get)
    is_qwen2 = get("model_type") == "qwen2"
    is_mistral = get("model_type") == "mistral"
    is_mixtral = get("model_type") == "mixtral"
    if is_qwen2 and get("use_sliding_window"):
        raise NotImplementedError(
            "Qwen2 import: use_sliding_window=True (layer-windowed "
            "attention) is not implemented"
        )
    # Reject, loudly, configs whose architecture tpufw doesn't implement —
    # importing them would produce silently wrong logits.
    unsupported = {
        # Qwen2 carries qkv biases by construction; Llama-family configs
        # with attention_bias remain rejected (their bias is on ALL four
        # projections, which the blocks don't implement).
        "attention_bias": lambda v: bool(v) and not is_qwen2,
        "mlp_bias": bool,
        "hidden_act": lambda v: v not in (None, "silu"),
        "sliding_window": lambda v: bool(v)
        and not (is_qwen2 or is_mistral or is_mixtral),
    }
    bad = {
        k: get(k) for k, is_bad in unsupported.items() if is_bad(get(k))
    }
    if bad:
        raise NotImplementedError(
            f"HF config uses features tpufw's Llama/Mixtral don't "
            f"implement: {bad}; importing would silently change the "
            "model's math"
        )
    d_model = get("hidden_size")
    n_heads = get("num_attention_heads")
    common = dict(
        rope_scaling=_rope_scaling_from_hf(get("rope_scaling")),
        vocab_size=get("vocab_size"),
        d_model=d_model,
        n_layers=get("num_hidden_layers"),
        n_heads=n_heads,
        n_kv_heads=get("num_key_value_heads") or n_heads,
        head_dim=get("head_dim") or d_model // n_heads,
        d_ff=get("intermediate_size"),
        rope_theta=float(get("rope_theta") or 10_000.0),
        rms_eps=float(get("rms_norm_eps") or 1e-5),
        max_seq_len=get("max_position_embeddings") or 8192,
        tie_embeddings=bool(get("tie_word_embeddings") or False),
        attention_qkv_bias=bool(is_qwen2),
        # Mistral/Mixtral: one window on every layer (None when the
        # checkpoint disabled it, as Mistral v0.2+ and Mixtral do).
        sliding_window=(
            get("sliding_window")
            if (is_mistral or is_mixtral)
            else None
        ),
    )
    if is_mixtral:
        from tpufw.models.mixtral import MixtralConfig

        return MixtralConfig(
            **common,
            n_experts=get("num_local_experts"),
            experts_per_token=get("num_experts_per_tok"),
            # HF Mixtral routes dropless (dense top-k gather); default
            # imported checkpoints to a capacity that can't drop tokens
            # so served outputs match the checkpoint's semantics. Users
            # fine-tuning at scale can lower this explicitly.
            capacity_factor=float(get("num_local_experts")),
        )
    return LlamaConfig(**common)


def _deepseek_config_from_hf(get):
    """tpufw DeepseekConfig from a transformers DeepseekV2Config.

    Routed experts (DeepSeek MoE FFN), group-limited selection
    (topk_method="group_limited_greedy", n_group/topk_group), and yarn
    rope scaling import directly. Rejects, loudly, what tpufw's MLA
    blocks don't implement or this importer doesn't map: other
    topk_methods, non-softmax scoring (tpufw.ops.moe scores by sigmoid
    with a selection bias too, ``DeepseekConfig.moe_scoring``, but no
    checkpoint key is mapped onto its ``router_bias`` here yet), sparse
    moe_layer_freq, and attention bias — importing them would produce
    silently wrong logits."""
    from tpufw.models.deepseek import DeepseekConfig

    bad = {}
    n_layers = get("num_hidden_layers")
    # Layers >= first_k_dense_replace use the MoE FFN
    # (modeling_deepseek_v2.py DeepseekV2DecoderLayer); all-dense
    # checkpoints set it past the last layer.
    first_moe = get("first_k_dense_replace") or 0
    has_moe = bool(get("n_routed_experts")) and first_moe < n_layers
    group_kwargs = {}
    if has_moe:
        # V2-Lite routes plain greedy-softmax; the 236B/Chat models'
        # group-limited selection imports via n_group/topk_group.
        topk_method = get("topk_method") or "greedy"
        if topk_method == "group_limited_greedy":
            # Validate at the IMPORT boundary like every other gap —
            # a malformed group spec must not surface as a ValueError
            # deep inside the first jit trace.
            ng, tg = get("n_group"), get("topk_group")
            e, k = get("n_routed_experts"), get("num_experts_per_tok")
            ok = (
                ng and tg and e % ng == 0
                and (tg >= ng or k <= tg * (e // ng))
            )
            if ok:
                group_kwargs = dict(n_group=int(ng), topk_group=int(tg))
            else:
                bad["group_limited_greedy"] = {
                    "n_group": ng,
                    "topk_group": tg,
                    "n_routed_experts": e,
                    "num_experts_per_tok": k,
                }
        elif topk_method != "greedy":
            bad["topk_method"] = topk_method
        if (get("scoring_func") or "softmax") != "softmax":
            bad["scoring_func"] = get("scoring_func")
        if (get("moe_layer_freq") or 1) != 1:
            bad["moe_layer_freq"] = get("moe_layer_freq")
    yarn = None
    rs = get("rope_scaling")
    if rs:
        rs_get = rs.get if isinstance(rs, Mapping) else (
            lambda k, d=None: getattr(rs, k, d)
        )
        rtype = rs_get("rope_type") or rs_get("type")
        if rtype != "yarn":
            bad["rope_scaling"] = rs
        else:
            from tpufw.models.deepseek import YarnScaling

            yarn = YarnScaling(
                factor=float(rs_get("factor")),
                original_max_position_embeddings=int(
                    rs_get("original_max_position_embeddings")
                    or get("max_position_embeddings")
                    or 4096
                ),
                beta_fast=float(rs_get("beta_fast") or 32),
                beta_slow=float(rs_get("beta_slow") or 1),
                # Unset stays FALSY: the reference's attention-factor
                # derivation gates on `mscale and mscale_all_dim` — a
                # 1.0 default would flip a mscale_all_dim-only config
                # into the ratio branch (wrong factor).
                mscale=float(rs_get("mscale") or 0.0),
                mscale_all_dim=float(rs_get("mscale_all_dim") or 0.0),
                attention_factor=rs_get("attention_factor"),
                truncate=bool(
                    True if rs_get("truncate") is None
                    else rs_get("truncate")
                ),
            )
    if get("attention_bias"):
        bad["attention_bias"] = get("attention_bias")
    if get("hidden_act") not in (None, "silu"):
        bad["hidden_act"] = get("hidden_act")
    if bad:
        raise NotImplementedError(
            f"DeepseekV2 import: unsupported features {bad}; tpufw's "
            "MLA family implements greedy and group-limited-greedy "
            "softmax MoE and default+yarn rope (importing sigmoid "
            "scoring's selection bias, sparse moe_layer_freq, and "
            "attention bias are the known gaps)"
        )
    moe_kwargs = {}
    if has_moe:
        moe_kwargs = dict(
            n_routed_experts=get("n_routed_experts"),
            experts_per_token=get("num_experts_per_tok"),
            moe_d_ff=get("moe_intermediate_size"),
            n_shared_experts=get("n_shared_experts") or 0,
            first_k_dense=first_moe,
            routed_scaling_factor=float(
                get("routed_scaling_factor") or 1.0
            ),
            # The HF reference STORES norm_topk_prob but never applies
            # it (modeling_deepseek_v2.py MoEGate.forward returns raw
            # softmax topk mass * scaling, no renormalization branch) —
            # parity means matching the executed behavior, not the
            # config flag.
            norm_topk_prob=False,
            # Dropless: HF routes without capacity bounds, so imported
            # checkpoints must not drop tokens (Mixtral convention).
            capacity_factor=float(get("n_routed_experts")),
            # Mixed dense/MoE stacks can't scan (homogeneity).
            scan_layers=first_moe == 0,
            **group_kwargs,
        )
    return DeepseekConfig(
        vocab_size=get("vocab_size"),
        d_model=get("hidden_size"),
        n_layers=get("num_hidden_layers"),
        n_heads=get("num_attention_heads"),
        q_lora_rank=get("q_lora_rank"),
        kv_lora_rank=get("kv_lora_rank"),
        qk_nope_head_dim=get("qk_nope_head_dim"),
        qk_rope_head_dim=get("qk_rope_head_dim"),
        v_head_dim=get("v_head_dim"),
        d_ff=get("intermediate_size"),
        rope_theta=float(get("rope_theta") or 10_000.0),
        rms_eps=float(get("rms_norm_eps") or 1e-6),
        max_seq_len=get("max_position_embeddings") or 4096,
        tie_embeddings=bool(get("tie_word_embeddings") or False),
        rope_scaling=yarn,
        **moe_kwargs,
    )


def _deepseek_from_hf(sd, cfg, dt) -> dict:
    """HF DeepseekV2 state dict -> tpufw Deepseek param tree.

    MLA projections (modeling_deepseek_v2.py DeepseekV2Attention):
    kv_a_proj_with_mqa packs [kv_lora_rank + qk_rope_head_dim, D];
    kv_b_proj packs [H * (qk_nope_head_dim + v_head_dim), kv_lora_rank].
    The rope slices need NO permutation — DeepSeek's rotary is the
    interleaved complex layout, which apply_rope_interleaved matches.
    """
    import jax
    import jax.numpy as jnp

    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def take(key: str, target=None):
        if key not in sd:
            raise KeyError(
                f"HF checkpoint is missing {key!r}; not a DeepseekV2 "
                "state dict?"
            )
        return jnp.asarray(_to_np(sd[key]), target or dt)

    def block(i: int) -> dict:
        pre = f"layers.{i}."
        ap = pre + "self_attn."
        attn: dict = {
            "kv_a": {
                "kernel": take(ap + "kv_a_proj_with_mqa.weight").T
            },
            "kv_a_norm": {
                "scale": take(ap + "kv_a_layernorm.weight", jnp.float32)
            },
            "kv_b_kernel": take(ap + "kv_b_proj.weight")
            .T.reshape(cfg.kv_lora_rank, h, dn + dv),
            "o": {
                "kernel": take(ap + "o_proj.weight").T.reshape(h, dv, d)
            },
        }
        if cfg.q_lora_rank is None:
            attn["q"] = {
                "kernel": take(ap + "q_proj.weight")
                .T.reshape(d, h, dn + dr)
            }
        else:
            attn["q_a"] = {"kernel": take(ap + "q_a_proj.weight").T}
            attn["q_a_norm"] = {
                "scale": take(ap + "q_a_layernorm.weight", jnp.float32)
            }
            attn["q_b"] = {
                "kernel": take(ap + "q_b_proj.weight")
                .T.reshape(cfg.q_lora_rank, h, dn + dr)
            }
        out = {
            "attn_norm": {
                "scale": take(pre + "input_layernorm.weight", jnp.float32)
            },
            "attn": attn,
            "mlp_norm": {
                "scale": take(
                    pre + "post_attention_layernorm.weight", jnp.float32
                )
            },
        }
        if cfg.moe and i >= cfg.first_k_dense:
            mp = pre + "mlp."

            def experts(w: str):
                return jnp.stack(
                    [
                        take(f"{mp}experts.{e}.{w}_proj.weight").T
                        for e in range(cfg.n_routed_experts)
                    ],
                    axis=0,
                )

            moe = {
                "routed": {
                    "router": {"kernel": take(mp + "gate.weight").T},
                    "w_gate": experts("gate"),  # [E, D, F]
                    "w_up": experts("up"),
                    "w_down": experts("down"),  # [E, F, D]
                },
            }
            if cfg.n_shared_experts:
                moe["shared"] = {
                    "gate": {
                        "kernel": take(
                            mp + "shared_experts.gate_proj.weight"
                        ).T
                    },
                    "up": {
                        "kernel": take(
                            mp + "shared_experts.up_proj.weight"
                        ).T
                    },
                    "down": {
                        "kernel": take(
                            mp + "shared_experts.down_proj.weight"
                        ).T
                    },
                }
            out["moe"] = moe
        else:
            out["mlp"] = {
                "gate": {"kernel": take(pre + "mlp.gate_proj.weight").T},
                "up": {"kernel": take(pre + "mlp.up_proj.weight").T},
                "down": {"kernel": take(pre + "mlp.down_proj.weight").T},
            }
        return out

    layers = [block(i) for i in range(cfg.n_layers)]
    params: dict = {
        "embed": {"embedding": take("embed_tokens.weight")},
        "final_norm": {"scale": take("norm.weight", jnp.float32)},
    }
    if cfg.scan_layers:
        params["layers"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *layers
        )
    else:
        for i, lp in enumerate(layers):
            params[f"layer_{i}"] = lp
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": take("lm_head.weight").T}
    return params


def _gemma_config_from_hf(get) -> "GemmaConfig":
    """tpufw GemmaConfig from a transformers Gemma2Config.

    Rejects non-Gemma-2 feature combos loudly (same policy as the
    Llama path): tpufw implements exactly HF Gemma2's architecture —
    gelu_pytorch_tanh GeGLU, sandwich norms, alternating sliding
    window on even layers, logit soft-caps, tied embeddings.
    """
    from tpufw.models.gemma import GemmaConfig

    act = get("hidden_activation") or get("hidden_act")
    if act not in (None, "gelu_pytorch_tanh"):
        raise NotImplementedError(
            f"Gemma2 import supports gelu_pytorch_tanh only, got {act!r}"
        )
    if bool(get("attention_bias")):
        # Same reject-loudly policy as the Llama path: the weight mapper
        # reads only the keys it knows, so bias tensors would be DROPPED
        # silently — wrong logits, not an error.
        raise NotImplementedError(
            "Gemma2 import does not implement attention_bias=True"
        )
    if not (get("tie_word_embeddings") is None or
            bool(get("tie_word_embeddings"))):
        raise NotImplementedError(
            "Gemma2 import assumes tied embeddings (all released "
            "Gemma-2 checkpoints tie them)"
        )
    d_model = get("hidden_size")
    n_heads = get("num_attention_heads")
    return GemmaConfig(
        vocab_size=get("vocab_size"),
        d_model=d_model,
        n_layers=get("num_hidden_layers"),
        n_heads=n_heads,
        n_kv_heads=get("num_key_value_heads") or n_heads,
        head_dim=get("head_dim") or d_model // n_heads,
        d_ff=get("intermediate_size"),
        rope_theta=float(get("rope_theta") or 10_000.0),
        rms_eps=float(get("rms_norm_eps") or 1e-6),
        max_seq_len=get("max_position_embeddings") or 8192,
        tie_embeddings=True,
        attn_logit_soft_cap=get("attn_logit_softcapping"),
        final_logit_soft_cap=get("final_logit_softcapping"),
        sliding_window=get("sliding_window"),
        query_pre_attn_scalar=float(
            get("query_pre_attn_scalar") or
            (get("head_dim") or d_model // n_heads)
        ),
    )


def _gemma_from_hf(sd, cfg, dt) -> dict:
    """HF Gemma2 state dict -> tpufw Gemma param tree (pairs layout).

    HF layer 2p (sliding) -> pair p "local"; layer 2p+1 -> "global".
    Norm weights copy directly: both sides store the offset-from-1.
    """
    import jax
    import jax.numpy as jnp

    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def take(key: str, target=None):
        if key not in sd:
            raise KeyError(
                f"HF checkpoint is missing {key!r}; not a Gemma-2 "
                "state dict?"
            )
        return jnp.asarray(_to_np(sd[key]), target or dt)

    def block(i: int) -> dict:
        pre = f"layers.{i}."
        return {
            "pre_attn_norm": {
                "scale": take(pre + "input_layernorm.weight", jnp.float32)
            },
            "post_attn_norm": {
                "scale": take(
                    pre + "post_attention_layernorm.weight", jnp.float32
                )
            },
            "pre_mlp_norm": {
                "scale": take(
                    pre + "pre_feedforward_layernorm.weight", jnp.float32
                )
            },
            "post_mlp_norm": {
                "scale": take(
                    pre + "post_feedforward_layernorm.weight", jnp.float32
                )
            },
            "attn": {
                "q": {
                    "kernel": take(pre + "self_attn.q_proj.weight")
                    .T.reshape(d, h, dh)
                },
                "k": {
                    "kernel": take(pre + "self_attn.k_proj.weight")
                    .T.reshape(d, kh, dh)
                },
                "v": {
                    "kernel": take(pre + "self_attn.v_proj.weight")
                    .T.reshape(d, kh, dh)
                },
                "o": {
                    "kernel": take(pre + "self_attn.o_proj.weight")
                    .T.reshape(h, dh, d)
                },
            },
            "mlp": {
                "gate": {"kernel": take(pre + "mlp.gate_proj.weight").T},
                "up": {"kernel": take(pre + "mlp.up_proj.weight").T},
                "down": {"kernel": take(pre + "mlp.down_proj.weight").T},
            },
        }

    pairs = [
        {"local": block(2 * p), "global": block(2 * p + 1)}
        for p in range(cfg.n_layers // 2)
    ]
    params: dict = {
        "embed": {"embedding": take("embed_tokens.weight")},
        "final_norm": {"scale": take("norm.weight", jnp.float32)},
    }
    if cfg.scan_layers:
        params["layers"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *pairs
        )
    else:
        for i, lp in enumerate(pairs):
            params[f"layer_{i}"] = lp
    return params


def _load_state_dict(path: str | os.PathLike) -> dict[str, np.ndarray]:
    """Read every ``*.safetensors`` shard in a checkpoint directory."""
    from safetensors import safe_open

    path = pathlib.Path(path)
    shards = sorted(path.glob("*.safetensors"))
    if not shards:
        raise FileNotFoundError(f"no *.safetensors under {path}")
    out: dict[str, np.ndarray] = {}
    for shard in shards:
        with safe_open(str(shard), framework="np") as f:
            for key in f.keys():
                out[key] = f.get_tensor(key)
    return out


def from_hf(
    source: Any,
    cfg: LlamaConfig,
    dtype: Any = None,
) -> dict:
    """Convert HF Llama/Mixtral weights to the tpufw param tree.

    ``source``: a transformers model (has ``.state_dict()``), a state
    dict, or a checkpoint directory path. ``dtype`` defaults to
    ``cfg.param_dtype``. Returns the raw (unboxed) param pytree the
    trainer/apply path consumes; layout matches ``cfg.scan_layers``.
    A MixtralConfig maps the block_sparse_moe experts (w1=gate, w3=up,
    w2=down, gate=router) onto the stacked [E, ...] expert weights.
    """
    import jax.numpy as jnp

    from tpufw.models.gemma import GemmaConfig
    from tpufw.models.mixtral import MixtralConfig

    is_moe = isinstance(cfg, MixtralConfig)

    if isinstance(source, (str, os.PathLike)):
        sd = _load_state_dict(source)
    elif hasattr(source, "state_dict"):
        sd = source.state_dict()
    else:
        sd = dict(source)
    sd = {k.removeprefix("model."): v for k, v in sd.items()}

    dt = jnp.dtype(dtype if dtype is not None else cfg.param_dtype)
    if isinstance(cfg, GemmaConfig):
        return _gemma_from_hf(sd, cfg, dt)
    from tpufw.models.deepseek import DeepseekConfig

    if isinstance(cfg, DeepseekConfig):
        return _deepseek_from_hf(sd, cfg, dt)
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def take(key: str, target=None):
        """One tensor, cast straight to its final dtype — per-tensor
        conversion keeps the host-memory peak at ~one checkpoint copy
        (an 8B bf16 import must not balloon to 3x through fp32
        intermediates). Norm scales default to fp32 (RMSNorm convention).
        """
        if key not in sd:
            raise KeyError(
                f"HF checkpoint is missing {key!r} (have "
                f"{sorted(sd)[:8]}...); not a Llama-family state dict?"
            )
        return jnp.asarray(_to_np(sd[key]), target or dt)

    def layer(i: int) -> dict:
        pre = f"layers.{i}."
        out = {
            "attn_norm": {
                "scale": take(
                    pre + "input_layernorm.weight", jnp.float32
                )
            },
            "attn": {
                "q": {
                    "kernel": take(pre + "self_attn.q_proj.weight")
                    .T.reshape(d, h, dh)
                },
                "k": {
                    "kernel": take(pre + "self_attn.k_proj.weight")
                    .T.reshape(d, kh, dh)
                },
                "v": {
                    "kernel": take(pre + "self_attn.v_proj.weight")
                    .T.reshape(d, kh, dh)
                },
                "o": {
                    "kernel": take(pre + "self_attn.o_proj.weight")
                    .T.reshape(h, dh, d)
                },
            },
        }
        if getattr(cfg, "attention_qkv_bias", False):
            # Qwen2: biases on q/k/v only, stored flat [H*dh] in HF.
            attn_out = out["attn"]
            attn_out["q"]["bias"] = take(
                pre + "self_attn.q_proj.bias", jnp.float32
            ).reshape(h, dh)
            attn_out["k"]["bias"] = take(
                pre + "self_attn.k_proj.bias", jnp.float32
            ).reshape(kh, dh)
            attn_out["v"]["bias"] = take(
                pre + "self_attn.v_proj.bias", jnp.float32
            ).reshape(kh, dh)
        post_norm = take(
            pre + "post_attention_layernorm.weight", jnp.float32
        )
        if is_moe:
            moe_pre = pre + "block_sparse_moe."

            def experts(w: str) -> Any:
                return jnp.stack(
                    [
                        take(f"{moe_pre}experts.{e}.{w}.weight").T
                        for e in range(cfg.n_experts)
                    ],
                    axis=0,
                )

            out["moe_norm"] = {"scale": post_norm}
            out["moe"] = {
                "router": {"kernel": take(moe_pre + "gate.weight").T},
                "w_gate": experts("w1"),  # [E, D, F]
                "w_up": experts("w3"),
                "w_down": experts("w2"),  # [E, F, D]
            }
        else:
            out["mlp_norm"] = {"scale": post_norm}
            out["mlp"] = {
                "gate": {"kernel": take(pre + "mlp.gate_proj.weight").T},
                "up": {"kernel": take(pre + "mlp.up_proj.weight").T},
                "down": {"kernel": take(pre + "mlp.down_proj.weight").T},
            }
        return out

    layers = [layer(i) for i in range(cfg.n_layers)]
    params: dict = {
        "embed": {"embedding": take("embed_tokens.weight")},
        "final_norm": {"scale": take("norm.weight", jnp.float32)},
    }
    if cfg.scan_layers:
        import jax

        params["layers"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *layers
        )
    else:
        for i, lp in enumerate(layers):
            params[f"layer_{i}"] = lp
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": take("lm_head.weight").T}
    return params


#: Back-compat alias (the function now also handles Mixtral).
from_hf_llama = from_hf


# ----------------------------------------------------------------------
# Export: tpufw params -> HF state dict / checkpoint dir
# ----------------------------------------------------------------------


def hf_config_dict(cfg: LlamaConfig) -> dict:
    """The transformers config.json contents for a tpufw config."""
    from tpufw.models.deepseek import DeepseekConfig
    from tpufw.models.mixtral import MixtralConfig

    if isinstance(cfg, DeepseekConfig):
        out = {
            "model_type": "deepseek_v2",
            "architectures": ["DeepseekV2ForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_heads,
            "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            # transformers' rotary sizes itself from head_dim, which
            # for MLA is the ROPE slice.
            "head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "intermediate_size": cfg.d_ff,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_eps,
            "max_position_embeddings": cfg.max_seq_len,
            "tie_word_embeddings": cfg.tie_embeddings,
            "attention_bias": False,
            "hidden_act": "silu",
            "torch_dtype": "float32",
            # All layers below first_k_dense_replace are dense; a
            # dense-FFN export pushes it past the last layer (the
            # routed-expert fields then never construct).
            "first_k_dense_replace": (
                cfg.first_k_dense if cfg.moe else cfg.n_layers
            ),
        }
        if cfg.moe:
            out.update(
                n_routed_experts=cfg.n_routed_experts,
                num_experts_per_tok=cfg.experts_per_token,
                moe_intermediate_size=cfg.moe_d_ff,
                n_shared_experts=cfg.n_shared_experts or None,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=False,
                scoring_func="softmax",
                moe_layer_freq=1,
                **(
                    {
                        "topk_method": "group_limited_greedy",
                        "n_group": cfg.n_group,
                        "topk_group": cfg.topk_group,
                    }
                    if cfg.n_group
                    else {"topk_method": "greedy"}
                ),
            )
        ys = getattr(cfg, "rope_scaling", None)
        if ys is not None:
            out["rope_scaling"] = {
                "rope_type": "yarn",
                "factor": ys.factor,
                "original_max_position_embeddings": (
                    ys.original_max_position_embeddings
                ),
                "beta_fast": ys.beta_fast,
                "beta_slow": ys.beta_slow,
                **(
                    {"mscale": ys.mscale} if ys.mscale else {}
                ),
                **(
                    {"mscale_all_dim": ys.mscale_all_dim}
                    if ys.mscale_all_dim
                    else {}
                ),
                # Both read back by _compute_yarn_parameters; dropping
                # them would silently change every cos/sin on reload.
                **(
                    {"attention_factor": ys.attention_factor}
                    if ys.attention_factor is not None
                    else {}
                ),
                **({} if ys.truncate else {"truncate": False}),
            }
        return out

    out = {
        "model_type": "llama",
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps,
        **(
            {
                "rope_scaling": (
                    {
                        "rope_type": "linear",
                        "factor": cfg.rope_scaling.factor,
                    }
                    if cfg.rope_scaling.rope_type == "linear"
                    else {
                        "rope_type": "llama3",
                        "factor": cfg.rope_scaling.factor,
                        "low_freq_factor": (
                            cfg.rope_scaling.low_freq_factor
                        ),
                        "high_freq_factor": (
                            cfg.rope_scaling.high_freq_factor
                        ),
                        "original_max_position_embeddings": (
                            cfg.rope_scaling
                            .original_max_position_embeddings
                        ),
                    }
                )
            }
            if getattr(cfg, "rope_scaling", None) is not None
            else {}
        ),
        "max_position_embeddings": cfg.max_seq_len,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": False,
        "mlp_bias": False,
        "hidden_act": "silu",
        "torch_dtype": "float32",
    }
    from tpufw.models.gemma import GemmaConfig as _GemmaConfig

    if isinstance(cfg, MixtralConfig):
        out.update(
            model_type="mixtral",
            architectures=["MixtralForCausalLM"],
            num_local_experts=cfg.n_experts,
            num_experts_per_tok=cfg.experts_per_token,
        )
        if getattr(cfg, "sliding_window", None):
            # HF Mixtral carries the field too (it descends from
            # Mistral); the tpufw blocks honor it, so export must.
            out["sliding_window"] = cfg.sliding_window
        out.pop("mlp_bias")
    elif (
        getattr(cfg, "sliding_window", None)
        and not getattr(cfg, "attention_qkv_bias", False)
        and not isinstance(cfg, _GemmaConfig)
    ):
        out.update(
            model_type="mistral",
            architectures=["MistralForCausalLM"],
            sliding_window=cfg.sliding_window,
        )
        out.pop("mlp_bias", None)
    if getattr(cfg, "attention_qkv_bias", False):
        if getattr(cfg, "sliding_window", None):
            raise NotImplementedError(
                "export of qkv-bias + sliding_window is not implemented "
                "(the qwen2 branch would silently write "
                "use_sliding_window=False, changing the attention math)"
            )
        if isinstance(cfg, MixtralConfig):
            # Mixtral shares llama.Attention so the COMBINATION trains,
            # but no HF architecture expresses MoE + qkv-bias — export
            # would emit a nonsense config.
            raise NotImplementedError(
                "export of a Mixtral config with attention_qkv_bias is "
                "not representable as an HF architecture"
            )
        if cfg.head_dim != cfg.d_model // cfg.n_heads:
            # Qwen2Config has no head_dim field: transformers recomputes
            # it as hidden_size // num_attention_heads, so any other
            # value would export a checkpoint from_pretrained cannot
            # load (size mismatch at reload, long after this "success").
            raise NotImplementedError(
                f"Qwen2 export requires head_dim == d_model//n_heads "
                f"({cfg.d_model}//{cfg.n_heads}="
                f"{cfg.d_model // cfg.n_heads}), got {cfg.head_dim}"
            )
        out.update(
            model_type="qwen2",
            architectures=["Qwen2ForCausalLM"],
            use_sliding_window=False,
        )
        out.pop("attention_bias", None)
        out.pop("mlp_bias", None)
        out.pop("head_dim", None)
    from tpufw.models.gemma import GemmaConfig

    if isinstance(cfg, GemmaConfig):
        out.update(
            model_type="gemma2",
            architectures=["Gemma2ForCausalLM"],
            hidden_activation="gelu_pytorch_tanh",
            attn_logit_softcapping=cfg.attn_logit_soft_cap,
            final_logit_softcapping=cfg.final_logit_soft_cap,
            sliding_window=cfg.sliding_window,
            query_pre_attn_scalar=cfg.query_pre_attn_scalar,
            tie_word_embeddings=True,
        )
        out.pop("mlp_bias")
        out.pop("hidden_act")
    return out


def to_hf(params: dict, cfg: LlamaConfig) -> dict[str, np.ndarray]:
    """Inverse of ``from_hf``: tpufw param tree -> HF-keyed state dict
    (numpy fp32, HF [out, in] Linear layout, ``model.``-prefixed keys).
    Accepts both scan-stacked and per-layer trees."""
    from tpufw.models.deepseek import DeepseekConfig
    from tpufw.models.gemma import GemmaConfig
    from tpufw.models.lora import has_lora
    from tpufw.models.mixtral import MixtralConfig

    if isinstance(cfg, DeepseekConfig):
        return _deepseek_to_hf(params, cfg)
    if has_lora(params):
        # The emitters read only base kernels; exporting an un-merged
        # LoRA tree would silently ship the FROZEN base and drop the
        # entire fine-tune.
        raise ValueError(
            "to_hf/export_hf on a LoRA tree: run "
            "tpufw.tools.merge_lora first (adapters must fold into the "
            "kernels they modify)"
        )
    if isinstance(cfg, GemmaConfig):
        return _gemma_to_hf(params, cfg)
    is_moe = isinstance(cfg, MixtralConfig)
    d = cfg.d_model

    np32 = _np32

    def layer_tree(i: int) -> Mapping:
        return _slice_stack(params, cfg.scan_layers, i)

    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
    }
    if not cfg.tie_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]["kernel"]).T
    for i in range(cfg.n_layers):
        lp = layer_tree(i)
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = np32(
            lp["attn_norm"]["scale"]
        )
        _emit_attn(sd, pre, lp, d)
        norm_key = "moe_norm" if is_moe else "mlp_norm"
        sd[pre + "post_attention_layernorm.weight"] = np32(
            lp[norm_key]["scale"]
        )
        if is_moe:
            moe = lp["moe"]
            sd[pre + "block_sparse_moe.gate.weight"] = np32(
                moe["router"]["kernel"]
            ).T
            for e in range(cfg.n_experts):
                ep = pre + f"block_sparse_moe.experts.{e}."
                sd[ep + "w1.weight"] = np32(moe["w_gate"][e]).T
                sd[ep + "w3.weight"] = np32(moe["w_up"][e]).T
                sd[ep + "w2.weight"] = np32(moe["w_down"][e]).T
        else:
            _emit_mlp(sd, pre, lp)
    return sd


def _np32(x) -> np.ndarray:
    return np.asarray(x, np.float32)


def _slice_stack(params: dict, scan_layers: bool, i: int):
    """Layer/pair ``i`` of the (possibly scan-stacked) block params."""
    if scan_layers:
        import jax

        return jax.tree.map(lambda x: x[i], params["layers"])
    return params[f"layer_{i}"]


def _emit_attn(sd: dict, pre: str, lp: Mapping, d: int) -> None:
    """q/k/v/o -> HF [out, in] keys; ONE copy for every export branch."""
    attn = lp["attn"]
    sd[pre + "self_attn.q_proj.weight"] = (
        _np32(attn["q"]["kernel"]).reshape(d, -1).T
    )
    sd[pre + "self_attn.k_proj.weight"] = (
        _np32(attn["k"]["kernel"]).reshape(d, -1).T
    )
    sd[pre + "self_attn.v_proj.weight"] = (
        _np32(attn["v"]["kernel"]).reshape(d, -1).T
    )
    sd[pre + "self_attn.o_proj.weight"] = (
        _np32(attn["o"]["kernel"]).reshape(-1, d).T
    )
    for p in ("q", "k", "v"):
        if "bias" in attn[p]:
            sd[pre + f"self_attn.{p}_proj.bias"] = _np32(
                attn[p]["bias"]
            ).reshape(-1)


def _emit_mlp(sd: dict, pre: str, lp: Mapping) -> None:
    """Dense gate/up/down -> HF keys (Llama and Gemma blocks)."""
    mlp = lp["mlp"]
    sd[pre + "mlp.gate_proj.weight"] = _np32(mlp["gate"]["kernel"]).T
    sd[pre + "mlp.up_proj.weight"] = _np32(mlp["up"]["kernel"]).T
    sd[pre + "mlp.down_proj.weight"] = _np32(mlp["down"]["kernel"]).T


def _deepseek_to_hf(params: dict, cfg) -> dict[str, np.ndarray]:
    """Inverse of ``_deepseek_from_hf``: MLA (+ optional MoE) param
    tree -> DeepseekV2-keyed state dict."""
    d, h = cfg.d_model, cfg.n_heads
    np32 = _np32
    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
    }
    if not cfg.tie_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]["kernel"]).T
    for i in range(cfg.n_layers):
        lp = _slice_stack(params, cfg.scan_layers, i)
        pre = f"model.layers.{i}."
        ap = pre + "self_attn."
        attn = lp["attn"]
        sd[pre + "input_layernorm.weight"] = np32(
            lp["attn_norm"]["scale"]
        )
        if cfg.q_lora_rank is None:
            sd[ap + "q_proj.weight"] = (
                np32(attn["q"]["kernel"]).reshape(d, -1).T
            )
        else:
            sd[ap + "q_a_proj.weight"] = np32(attn["q_a"]["kernel"]).T
            sd[ap + "q_a_layernorm.weight"] = np32(
                attn["q_a_norm"]["scale"]
            )
            sd[ap + "q_b_proj.weight"] = (
                np32(attn["q_b"]["kernel"])
                .reshape(cfg.q_lora_rank, -1)
                .T
            )
        sd[ap + "kv_a_proj_with_mqa.weight"] = np32(
            attn["kv_a"]["kernel"]
        ).T
        sd[ap + "kv_a_layernorm.weight"] = np32(
            attn["kv_a_norm"]["scale"]
        )
        sd[ap + "kv_b_proj.weight"] = (
            np32(attn["kv_b_kernel"]).reshape(cfg.kv_lora_rank, -1).T
        )
        sd[ap + "o_proj.weight"] = (
            np32(attn["o"]["kernel"]).reshape(h * cfg.v_head_dim, d).T
        )
        sd[pre + "post_attention_layernorm.weight"] = np32(
            lp["mlp_norm"]["scale"]
        )
        if cfg.moe and i >= cfg.first_k_dense:
            mp = pre + "mlp."
            moe = lp["moe"]
            routed = moe["routed"]
            sd[mp + "gate.weight"] = np32(routed["router"]["kernel"]).T
            for e in range(cfg.n_routed_experts):
                ep = mp + f"experts.{e}."
                sd[ep + "gate_proj.weight"] = np32(
                    routed["w_gate"][e]
                ).T
                sd[ep + "up_proj.weight"] = np32(routed["w_up"][e]).T
                sd[ep + "down_proj.weight"] = np32(
                    routed["w_down"][e]
                ).T
            if cfg.n_shared_experts:
                sh = moe["shared"]
                sp = mp + "shared_experts."
                sd[sp + "gate_proj.weight"] = np32(
                    sh["gate"]["kernel"]
                ).T
                sd[sp + "up_proj.weight"] = np32(sh["up"]["kernel"]).T
                sd[sp + "down_proj.weight"] = np32(
                    sh["down"]["kernel"]
                ).T
        else:
            _emit_mlp(sd, pre, lp)
    return sd


def _gemma_to_hf(params: dict, cfg) -> dict[str, np.ndarray]:
    """Inverse of ``_gemma_from_hf``: pair p "local" -> HF layer 2p,
    "global" -> 2p+1; norm offsets copy directly (both sides store the
    offset-from-1); tied embeddings mean no lm_head tensor."""
    d = cfg.d_model
    np32 = _np32

    if not cfg.tie_embeddings:
        raise NotImplementedError(
            "Gemma export assumes tied embeddings (every released "
            "Gemma-2 checkpoint ties them); exporting an untied tree "
            "would silently re-tie the head to the embedding"
        )

    def pair_tree(p: int) -> Mapping:
        return _slice_stack(params, cfg.scan_layers, p)

    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
    }
    norms = {
        "pre_attn_norm": "input_layernorm",
        "post_attn_norm": "post_attention_layernorm",
        "pre_mlp_norm": "pre_feedforward_layernorm",
        "post_mlp_norm": "post_feedforward_layernorm",
    }
    for p in range(cfg.n_layers // 2):
        pt = pair_tree(p)
        for which, i in (("local", 2 * p), ("global", 2 * p + 1)):
            lp = pt[which]
            pre = f"model.layers.{i}."
            for ours, theirs in norms.items():
                sd[pre + theirs + ".weight"] = np32(lp[ours]["scale"])
            _emit_attn(sd, pre, lp, d)
            _emit_mlp(sd, pre, lp)
    return sd


def export_hf(params: dict, cfg: LlamaConfig, out_dir: str) -> dict:
    """Write an HF checkpoint dir (config.json + model.safetensors) that
    ``transformers.*ForCausalLM.from_pretrained`` loads directly."""
    from safetensors.numpy import save_file

    # Map BEFORE touching the filesystem: a validation error (e.g. an
    # untied Gemma tree) must not leave a half-written dir with a
    # config.json that from_pretrained then fails on confusingly.
    sd = to_hf(params, cfg)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_config_dict(cfg), f, indent=2)
    # ascontiguousarray: to_hf returns transposed VIEWS, and safetensors
    # serializes raw buffers — a non-contiguous view would be written in
    # its underlying (un-transposed) byte order, silently scrambling
    # every projection (caught by the transformers-reload parity test).
    # Replace per key so each fp32 base buffer is dropped as soon as its
    # contiguous copy exists (peak ~one model copy, not two).
    for k in list(sd):
        sd[k] = np.ascontiguousarray(sd[k])
    save_file(sd, os.path.join(out_dir, "model.safetensors"))
    return {
        "out": out_dir,
        "n_tensors": len(sd),
        "n_params": int(sum(v.size for v in sd.values())),
    }


def main(argv=None) -> int:
    """CLI. Default: HF checkpoint dir -> Orbax params dir. With
    ``--export MODEL``: the reverse — an Orbax bare-params dir (or a
    training TrainState checkpoint step dir) -> an HF checkpoint dir
    ``from_pretrained`` loads."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpufw.tools.import_hf",
        description="HF checkpoint <-> tpufw params (Orbax)",
    )
    ap.add_argument(
        "src",
        help="HF checkpoint dir (config.json + *.safetensors); with "
             "--export, an Orbax params / TrainState checkpoint dir",
    )
    ap.add_argument("--out", required=True, help="output dir")
    ap.add_argument(
        "--export",
        metavar="MODEL",
        default=None,
        help="reverse direction: export the Orbax tree at SRC as an HF "
             "checkpoint; MODEL names the architecture preset "
             "(LLAMA_CONFIGS / MIXTRAL_CONFIGS / GEMMA_CONFIGS / DEEPSEEK_CONFIGS)",
    )
    args = ap.parse_args(argv)

    import orbax.checkpoint as ocp

    if args.export:
        if args.export.endswith((".yaml", ".yml")):
            # YAML of record: honors model.overrides, so the exported
            # config.json matches what was actually trained (a bare
            # preset name would silently drop e.g. a rope_theta
            # override).
            from tpufw.configs.loader import load_run_config

            cfg = load_run_config(args.export).model_cfg
        else:
            from tpufw.configs.loader import resolve_model_preset

            cfg = resolve_model_preset(args.export)
        src = os.path.abspath(args.src)
        if os.path.isdir(os.path.join(src, "default")):
            src = os.path.join(src, "default")  # CheckpointManager step
        with ocp.StandardCheckpointer() as ckptr:
            meta = ckptr.metadata(src)
            # orbax >= 0.11 wraps the tree in CheckpointMetadata
            # (.item_metadata.tree); 0.x returns the metadata pytree
            # (a dict of ArrayMetadata) directly.
            item = getattr(meta, "item_metadata", None)
            meta_tree = item.tree if item is not None else meta
        if isinstance(meta_tree, dict) and "params" in meta_tree:
            # TrainState checkpoint: restore ONLY the params item —
            # PLACEHOLDER leaves (step, Adam moments, ~2x params) are
            # skipped, keeping peak memory at one model copy. Arrays
            # come back as host numpy (no device or sharding needed —
            # export is a host-side serialization job).
            import jax

            def abstract(m):
                return jax.ShapeDtypeStruct(tuple(m.shape), m.dtype)

            placeholder = getattr(ocp, "PLACEHOLDER", None)
            if placeholder is not None:
                target = {
                    k: jax.tree.map(
                        abstract if k == "params"
                        else (lambda _: placeholder),
                        v,
                    )
                    for k, v in meta_tree.items()
                }

                def rargs(x):
                    if x is placeholder:
                        return ocp.RestoreArgs()
                    return ocp.ArrayRestoreArgs(restore_type=np.ndarray)

                restore_args = jax.tree.map(
                    rargs, target, is_leaf=lambda x: x is placeholder
                )
                with ocp.PyTreeCheckpointer() as ckptr:
                    params = ckptr.restore(
                        src,
                        ocp.args.PyTreeRestore(
                            item=target, restore_args=restore_args
                        ),
                    )["params"]
            else:
                # orbax without PLACEHOLDER (< 0.11): partial restore
                # via transforms — item names ONLY the params subtree
                # and transforms={} drops every checkpoint key absent
                # from it, so step/opt-state bytes never leave disk.
                target = {
                    "params": jax.tree.map(abstract, meta_tree["params"])
                }
                restore_args = jax.tree.map(
                    lambda _: ocp.ArrayRestoreArgs(restore_type=np.ndarray),
                    target,
                )
                with ocp.PyTreeCheckpointer() as ckptr:
                    params = ckptr.restore(
                        src,
                        ocp.args.PyTreeRestore(
                            item=target,
                            restore_args=restore_args,
                            transforms={},
                        ),
                    )["params"]
        else:
            with ocp.StandardCheckpointer() as ckptr:
                params = ckptr.restore(src)
        info = export_hf(params, cfg, args.out)
        print(json.dumps(info))
        return 0

    with open(os.path.join(args.src, "config.json")) as f:
        cfg = config_from_hf(json.load(f))
    params = from_hf_llama(args.src, cfg)

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(args.out), params)
    ckptr.wait_until_finished()
    n = sum(x.size for x in __import__("jax").tree.leaves(params))
    print(json.dumps({"out": args.out, "n_params": int(n)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
