"""Inference serving workload: checkpoint -> batch generation / HTTP server.

The serving half of the same `kubectl apply` flow the trainer uses
(deploy/manifests/07-infer-v5e1.yaml): load the latest checkpoint from
TPUFW_CHECKPOINT_DIR, build the decode-mode model (KV cache + jitted
lax.scan loop, tpufw.infer.generate), and either

- batch mode (default): generate continuations for TPUFW_PROMPTS_FILE
  (JSON: list of token-id lists) or built-in demo prompts, printing one
  JSON line per prompt — `kubectl logs` is the result channel, the
  reference's verification pattern (reference README.md:331-335);
- server mode (TPUFW_SERVE_PORT > 0): a stdlib ThreadingHTTPServer with
  POST /generate {"prompts": [[ids]], "max_new_tokens": N} -> outputs,
  GET /healthz, and GET /metrics (Prometheus text exposition: request/
  error/tick/token counters + queue-depth gauge, the serving analog of
  the device plugin's endpoint). Prompt lengths are bucketed (multiples
  of 64) and batch
  rows padded to a power of two so repeat traffic reuses compiled programs
  instead of recompiling per ragged shape — the static-shape discipline
  XLA serving needs.

Without a checkpoint the model initializes randomly (flagged in output):
the manifest flow stays verifiable end-to-end before any training ran.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from tpufw.obs import events as obs_events
from tpufw.obs import goodput as obs_goodput
from tpufw.obs import perf as obs_perf
from tpufw.obs import trace as obs_trace
from tpufw.obs.health import NULL_WATCHDOG
from tpufw.obs.registry import Registry as ObsRegistry
from tpufw.workloads.env import env_bool, env_float, env_int, env_str

_T0 = time.time()


def _replica_mesh():
    """One replica, one device. The server does not shard: its slot
    pools, page arena and decode programs are single-device, so weights
    must not come out FSDP-split over every chip the host shows (each
    decode step would all-gather them). Further chips are for further
    replicas (ROADMAP R5 widens one replica)."""
    import jax

    from tpufw.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(fsdp=1), devices=jax.local_devices()[:1])


DEMO_PROMPTS = [[1, 42, 7, 99], [1, 5], [1, 1000, 2000, 3000, 17]]


def build_generator():
    """Construct (decode_model, params, cfg, restored) from TPUFW_* env."""
    import dataclasses

    import jax

    from tpufw.configs import bench_model_config
    from tpufw.models import (
        DEEPSEEK_CONFIGS,
        Deepseek,
        GEMMA_CONFIGS,
        Gemma,
        LLAMA_CONFIGS,
        Llama,
        MIXTRAL_CONFIGS,
        Mixtral,
        SOLAR_OPEN2_CONFIGS,
        SolarOpen2,
    )
    from tpufw.train import Trainer, TrainerConfig

    hf_dir = env_str("hf_checkpoint", "")
    if hf_dir:
        # Serve HF weights directly (TPUFW_HF_CHECKPOINT=<dir with
        # config.json + *.safetensors>): the torch-ecosystem on-ramp —
        # no Orbax conversion step needed. The HF config.json is the
        # source of truth for the architecture, so this branch runs
        # FIRST and TPUFW_MODEL is genuinely ignored (stale manifest
        # values can't break it). Params load onto the default device in
        # the activation dtype (bf16 — serving keeps no fp32 master
        # copy). A model larger than one chip cannot be served yet:
        # one replica is one device (ROADMAP R5).
        from tpufw.models.gemma import GemmaConfig
        from tpufw.models.mixtral import MixtralConfig
        from tpufw.tools.import_hf import config_from_hf, from_hf

        with open(os.path.join(hf_dir, "config.json")) as f:
            hf_cfg = config_from_hf(json.load(f))
        hf_cfg = dataclasses.replace(
            hf_cfg,
            max_seq_len=env_int("max_seq_len", hf_cfg.max_seq_len),
        )
        params = from_hf(hf_dir, hf_cfg, dtype=hf_cfg.dtype)
        from tpufw.models import model_for_config

        hf_cfg, params = _maybe_quantize(hf_cfg, params)
        hf_cfg, params = _maybe_unroll(hf_cfg, params)
        return (
            model_for_config(hf_cfg.decode_config()),
            params,
            hf_cfg,
            True,
        )

    name = env_str("model", "llama3_600m_bench")
    if name == "llama3_600m_bench":
        model_cfg = bench_model_config()
        model_cls = Llama
    elif name in LLAMA_CONFIGS:
        model_cfg, model_cls = LLAMA_CONFIGS[name], Llama
    elif name in MIXTRAL_CONFIGS:
        model_cfg, model_cls = MIXTRAL_CONFIGS[name], Mixtral
    elif name in GEMMA_CONFIGS:
        model_cfg, model_cls = GEMMA_CONFIGS[name], Gemma
    elif name in DEEPSEEK_CONFIGS:
        model_cfg, model_cls = DEEPSEEK_CONFIGS[name], Deepseek
    elif name in SOLAR_OPEN2_CONFIGS:
        model_cfg, model_cls = SOLAR_OPEN2_CONFIGS[name], SolarOpen2
    else:
        raise ValueError(
            f"unknown TPUFW_MODEL={name!r}; choose from "
            f"{['llama3_600m_bench', *LLAMA_CONFIGS, *MIXTRAL_CONFIGS, *GEMMA_CONFIGS, *DEEPSEEK_CONFIGS, *SOLAR_OPEN2_CONFIGS]}"
        )
    # Serving wants the full sequence budget but no training-only features.
    model_cfg = dataclasses.replace(
        model_cfg,
        max_seq_len=env_int("max_seq_len", model_cfg.max_seq_len),
    )

    params_dir = env_str("params_checkpoint", "")
    if params_dir:
        # Bare-params Orbax checkpoint (tpufw.tools.import_hf CLI
        # output) — TPUFW_MODEL still names the architecture. Restored
        # straight onto the replica's device (no throwaway init).
        params = _restore_bare_params(model_cfg, params_dir)
        model_cfg, params = _maybe_quantize(model_cfg, params)
        model_cfg, params = _maybe_unroll(model_cfg, params)
        return model_cls(model_cfg.decode_config()), params, model_cfg, True

    # Reuse the trainer's restore machinery (abstract state + reshard-on-
    # restore) rather than reimplementing orbax plumbing; params are then
    # pulled out of the restored TrainState.
    trainer = Trainer(
        model_cls(model_cfg),
        TrainerConfig(
            batch_size=1,
            seq_len=min(32, model_cfg.max_seq_len),
            total_steps=1,
            checkpoint_dir=env_str("checkpoint_dir", "") or None,
        ),
        mesh=_replica_mesh(),
    )
    restored = trainer.maybe_restore()
    if not restored:
        trainer.init_state(seed=env_int("seed", 0))
    params = trainer.state.params
    del trainer.state  # drop optimizer moments; serving only needs params

    model_cfg, params = _maybe_quantize(model_cfg, params)
    model_cfg, params = _maybe_unroll(model_cfg, params)
    decode_model = model_cls(model_cfg.decode_config())
    _ = jax  # backend initialized above via Trainer
    return decode_model, params, model_cfg, restored


def _maybe_unroll(model_cfg, params):
    """Decode with the UNSCANNED layer stack (default ON) — the scanned
    trunk's decode loop slices its stacked [L, ...] weights per layer
    per step, which the unrolled twin avoids. Measured on the v5e chip
    (docs/evidence/DECODE_PROFILE_r5.jsonl, 2026-08-01): 1.16x decode
    throughput on the Llama bench model (1.05x on MLA), at ~10x the
    compile time per serving shape bucket (38 s vs 4 s). The default
    bucket is compiled by _Server._warmup before the listener binds;
    OTHER buckets pay the bigger compile on their first live hit — a
    compile-latency/steady-throughput trade serving takes by default
    per VERDICT r4 item 4. TPUFW_DECODE_UNROLL=0 opts out (e.g.
    compile-latency-sensitive dev loops, very deep models).
    Checkpoints stay scanned on disk; the param tree is unstacked in
    memory (tpufw.models.unstack_layer_params). Applied to EVERY
    build_generator source, after quantization (the unstack is
    tree-generic, quantized leaves included)."""
    import dataclasses as _dc

    if not env_int("decode_unroll", 1):
        return model_cfg, params
    from tpufw.models import unstack_layer_params

    return (
        _dc.replace(model_cfg, scan_layers=False),
        # donate: every caller rebinds params immediately, and the
        # donation bounds startup peak memory at weights + one stacked
        # leaf instead of 2x weights.
        unstack_layer_params(params, donate=True),
    )


def _maybe_quantize(model_cfg, params):
    """TPUFW_QUANTIZE=int8: convert projection weights to the int8
    serving form (tpufw.ops.quant) and flip the config so the modules
    declare the quantized params. Applied to EVERY build_generator
    source (HF dir, bare params, TrainState checkpoint)."""
    import dataclasses as _dc

    mode = env_str("quantize", "")
    if not mode:
        return model_cfg, params
    if mode != "int8":
        raise ValueError(
            f"TPUFW_QUANTIZE={mode!r}: only 'int8' is implemented"
        )
    from tpufw.ops.quant import quantize_params

    return (
        _dc.replace(model_cfg, quantized_weights=True),
        quantize_params(params),
    )


def _bucket(n: int, mult: int) -> int:
    return ((max(n, 1) + mult - 1) // mult) * mult


def _pow2_ceil(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) — the ONE bucketing rung
    shared by batch-size padding and the KV-cache ladder."""
    size = floor
    while size < n:
        size *= 2
    return size


def _cache_bucket(need: int, cap: int, floor: int = 128) -> int:
    """Smallest pow-2 KV-cache length >= ``need`` (min ``floor``),
    capped at the model's ``cap``. Per-step attention/update traffic
    scales with cache length, so a short chat on a long-context model
    must not pay the full-cache bill; the pow-2 ladder bounds how many
    cache shapes the generate jit ever specializes on."""
    return min(_pow2_ceil(need, floor), cap)


def text_codec():
    """(encode, decode) for text prompts, from TPUFW_TOKENIZER.

    "bytes" (default) is the dependency-free byte-level codec shared
    with tpufw.tools.pack_corpus (id 0 reserved for padding); any other
    value is a HuggingFace tokenizer name/path — pair it with
    TPUFW_HF_CHECKPOINT so ids match the served model's vocab.
    """
    name = env_str("tokenizer", "bytes")
    if name == "bytes":
        from tpufw.tools.pack_corpus import byte_tokenizer

        def decode(ids: list[int]) -> str:
            return bytes(
                t - 1 for t in ids if 0 < t <= 256
            ).decode("utf-8", errors="replace")

        return byte_tokenizer, decode
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(name)
    return tok.encode, tok.decode


def make_sampling(
    temperature=0.0,
    top_k=0,
    top_p=1.0,
    min_p=0.0,
    repetition_penalty=1.0,
):
    """ONE copy of the sampling-knob coercion + validation rules,
    shared by the env path (``sampling_from_env``) and the untrusted
    per-request HTTP path — so explicit-default requests always compare
    equal to the env config and keep coalescing.

    Values are range-checked (clients can send anything) and floats
    QUANTIZED (temperature to 0.01, top_p/min_p/penalty to 0.001):
    sampling is a compiled-program parameter, and unquantized
    client-chosen floats would compile unboundedly many variants."""
    from tpufw.infer import SamplingConfig

    t = round(float(temperature), 2)
    if t < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    kf = float(top_k or 0)
    if kf != int(kf):
        raise ValueError(f"top_k must be an integer, got {top_k}")
    k = int(kf)
    if k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    p = round(float(1.0 if top_p is None else top_p), 3)
    if p <= 0:
        raise ValueError(f"top_p must be > 0, got {top_p}")
    m = round(float(min_p or 0.0), 3)
    if not 0 <= m <= 1:
        raise ValueError(f"min_p must be in [0, 1], got {min_p}")
    r = round(
        float(1.0 if repetition_penalty is None else repetition_penalty),
        3,
    )
    if r <= 0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}"
        )
    return SamplingConfig(
        temperature=t,
        top_k=k or None,
        top_p=p if p < 1.0 else None,
        min_p=m or None,
        repetition_penalty=None if r == 1.0 else r,
    )


def sampling_from_env():
    """SamplingConfig from TPUFW_* env — ONE resolution for the batch
    and HTTP serving modes. Default stays greedy/deterministic."""
    return make_sampling(
        temperature=env_float("temperature", 0.0),
        top_k=env_int("top_k", 0),
        top_p=env_float("top_p", 1.0),
        min_p=env_float("min_p", 0.0),
        repetition_penalty=env_float("repetition_penalty", 1.0),
    )


def eos_from_env() -> Optional[int]:
    """TPUFW_EOS_ID: stop rows at this token (the token itself is
    emitted, outputs are truncated after it — tpufw.infer.generate).
    Unset/negative = run every row to max_new_tokens."""
    eos = env_int("eos_id", -1)
    return eos if eos >= 0 else None


def build_draft_generator(sampling):
    """TPUFW_DRAFT_MODEL: enable speculative decoding
    (tpufw.infer.speculative) with this preset as the draft — greedy
    acceptance at TPUFW_TEMPERATURE=0, rejection-resampling otherwise
    (every sampler knob composes, including the repetition penalty —
    tpufw.infer.speculative threads the seen-token mask through both
    the draft proposals and the per-position verify distributions).

    Draft weights come from TPUFW_DRAFT_PARAMS_CHECKPOINT (bare Orbax
    params, e.g. an import_hf of the small family member) — without it
    the draft initializes randomly, which is only useful for wiring
    tests (proposals rarely match, throughput degrades to ~plain decode
    plus draft overhead; outputs stay exactly target-distributed either
    way). Returns (draft_model, draft_params, k) or None when
    speculation is off."""
    import dataclasses

    import jax

    name = env_str("draft_model", "")
    if not name:
        return None
    from tpufw.configs.loader import resolve_model_preset
    from tpufw.models import model_for_config

    base = resolve_model_preset(name)
    cfg = dataclasses.replace(
        base, max_seq_len=env_int("max_seq_len", base.max_seq_len)
    )
    ckpt = env_str("draft_params_checkpoint", "")
    if ckpt:
        params = _restore_bare_params(cfg, ckpt)
    else:
        model = model_for_config(cfg)
        params = jax.jit(model.init)(
            jax.random.key(env_int("seed", 0) + 1),
            jax.numpy.zeros((1, min(8, cfg.max_seq_len)), jax.numpy.int32),
        )["params"]
    return (
        model_for_config(cfg.decode_config()),
        params,
        env_int("draft_k", 4),
    )


def _restore_bare_params(model_cfg, params_dir: str):
    """Bare-params Orbax restore via the trainer's abstract-tree helper
    — onto the replica's device, no throwaway init. ONE copy for the
    target (TPUFW_PARAMS_CHECKPOINT) and draft
    (TPUFW_DRAFT_PARAMS_CHECKPOINT) paths."""
    from tpufw.models import model_for_config
    from tpufw.train import Trainer, TrainerConfig

    shape_trainer = Trainer(
        model_for_config(model_cfg),
        TrainerConfig(
            batch_size=1, seq_len=min(32, model_cfg.max_seq_len)
        ),
        mesh=_replica_mesh(),
    )
    params, _ = shape_trainer.restore_params(params_dir)
    return params


def _maybe_cast_decode(params):
    """Apply the TPUFW_DECODE_DTYPE serving-precision cast (e.g.
    ``bfloat16``; see tpufw.infer.cast_decode_params) if set — ONE
    knob for both the HTTP server and batch mode."""
    cast = env_str("decode_dtype", "")
    if not cast:
        return params
    import jax.numpy as jnp

    from tpufw.infer import cast_decode_params

    return cast_decode_params(params, jnp.dtype(cast))


def _pad_batch(
    prompts: list[list[int]], fill_id: int = 0
) -> tuple[list[list[int]], int]:
    """Pad the batch to a power of two so the jitted generate
    specializes on few batch shapes. Returns (padded, real_n).

    Filler rows are seeded with ``fill_id`` — callers pass the EOS id
    when one is configured, and thread the matching ``live_rows`` mask
    into generate so the done-mask kills fillers at step 1 instead of
    decoding max_new tokens of garbage (and, in the streaming path,
    holding the all-done early exit hostage)."""
    n = len(prompts)
    return prompts + [[fill_id]] * (_pow2_ceil(n) - n), n


def run_batch(prompts: list[list[int]], max_new_tokens: int) -> list[dict]:
    from tpufw.infer import generate_text, speculative_generate_text

    decode_model, params, cfg, restored = build_generator()
    params = _maybe_cast_decode(params)
    sampling = sampling_from_env()  # default greedy: deterministic
    draft = build_draft_generator(sampling)
    eos = eos_from_env()
    padded, real_n = _pad_batch(prompts, eos if eos is not None else 0)
    if draft is not None:
        draft_model, draft_params, k = draft
        draft_params = _maybe_cast_decode(draft_params)
        outs, _stats = speculative_generate_text(
            draft_model,
            draft_params,
            decode_model,
            params,
            padded,
            max_new_tokens=max_new_tokens,
            eos_id=eos,
            k=k,
            live_rows=[i < real_n for i in range(len(padded))],
            sampling=sampling,
            prefill_chunk_size=env_int("prefill_chunk", 0) or None,
        )
        outs = outs[:real_n]
    else:
        outs = generate_text(
            decode_model,
            params,
            padded,
            max_new_tokens=max_new_tokens,
            sampling=sampling,
            eos_id=eos,
            live_rows=[i < real_n for i in range(len(padded))],
            # Long-prompt lever: prefill activations scale with the
            # chunk, not the prompt (tpufw.infer.generate). 0 = off.
            prefill_chunk_size=env_int("prefill_chunk", 0) or None,
        )[:real_n]
    return [
        {
            "prompt": p,
            "output": o,
            "restored_checkpoint": restored,
            "model_params": cfg.n_params(),
        }
        for p, o in zip(prompts, outs)
    ]


def _oai_to_native(req: dict) -> dict:
    """OpenAI `/v1/completions` request -> the native `/generate`
    shape, so users switching stacks can point an existing client at
    the server. Supported: `prompt` (string, list of strings, token
    list, or list of token lists), `max_tokens`, `temperature`,
    `top_p`, `seed`-free determinism per the tick-seed contract.
    Unsupported knobs fail loudly with the native alternative named
    (an OpenAI client silently getting different semantics is worse
    than a 400)."""
    if "prompt" not in req:
        raise ValueError("prompt is required")
    if req.get("stream"):
        raise ValueError(
            "stream is not supported on /v1/completions; use "
            "/generate with \"stream\": true (SSE)"
        )
    # `n: 1` is the OpenAI default and many SDK wrappers send it
    # explicitly — it requests exactly this server's behavior.
    if req.get("n") not in (None, 1):
        raise ValueError(
            "n > 1 is not supported on /v1/completions; post the "
            "prompt n times (ticks draw fresh seeds)"
        )
    # Semantics-changing knobs must fail LOUDLY — a client silently
    # getting different semantics is worse than a 400 — but values
    # that REQUEST the default behavior pass (SDK wrappers send
    # explicit defaults: echo: false, zero penalties, best_of: 1,
    # stop: null/[]). logprobs: 0 is meaningful (sampled-token
    # logprobs, zero alternatives), so only None passes there.
    defaults = {
        "logprobs": (None,),
        "echo": (None, False),
        "best_of": (None, 1),
        "presence_penalty": (None, 0, 0.0),
        "frequency_penalty": (None, 0, 0.0),
        "stop": (None, "", []),
    }
    alts = {
        "logprobs": "not supported",
        "echo": "prepend the prompt client-side",
        "best_of": "post the prompt best_of times and rank",
        "presence_penalty": "use repetition_penalty on /generate",
        "frequency_penalty": "use repetition_penalty on /generate",
        "stop": "set TPUFW_EOS_ID on the server",
    }
    for knob, ok_values in defaults.items():
        if knob in req and req[knob] not in ok_values:
            raise ValueError(
                f"{knob} is not supported on /v1/completions; "
                f"{alts[knob]}"
            )
    p = req["prompt"]
    native: dict = {"_oai_model": req.get("model", "")}
    if isinstance(p, str):
        native["texts"] = [p]
    elif isinstance(p, list) and p and all(
        isinstance(x, str) for x in p
    ):
        native["texts"] = p
    elif isinstance(p, list) and p and all(
        isinstance(x, int) for x in p
    ):
        native["prompts"] = [p]
    else:
        native["prompts"] = p  # [[int]] — /generate validates
    if "max_tokens" in req:
        native["max_new_tokens"] = req["max_tokens"]
    for knob in ("temperature", "top_p"):
        if knob in req:
            native[knob] = req[knob]
    return native


def _oai_response(
    outs, texts, prompts, max_new: int, model: str
) -> dict:
    """OpenAI text_completion response shape. finish_reason: a row
    shorter than max_new ended at the server's eos ("stop"), otherwise
    it ran out of budget ("length")."""
    import uuid

    return {
        "id": f"cmpl-{uuid.uuid4().hex[:24]}",
        "object": "text_completion",
        "created": int(time.time()),
        "model": model or "tpufw",
        "choices": [
            {
                "text": texts[i],
                "index": i,
                "logprobs": None,
                "finish_reason": (
                    "stop" if len(outs[i]) < max_new else "length"
                ),
            }
            for i in range(len(outs))
        ],
        "usage": {
            "prompt_tokens": sum(len(p) for p in prompts),
            "completion_tokens": sum(len(o) for o in outs),
            "total_tokens": sum(len(p) for p in prompts)
            + sum(len(o) for o in outs),
        },
    }


class _Pending:
    """One enqueued /generate request awaiting its tick."""

    __slots__ = ("prompts", "max_new", "sampling", "done", "outputs",
                 "error", "batched_with", "stream_q")

    def __init__(
        self,
        prompts: list[list[int]],
        max_new: int,
        sampling=None,
        stream_q=None,
    ):
        self.prompts = prompts
        self.max_new = max_new
        # None = the server's env-default SamplingConfig; a request
        # override makes this tick-compatible only with same-config
        # requests (the rng and transforms are shared per device call).
        self.sampling = sampling
        # Streaming request: per-chunk outputs go onto this queue
        # (lists of per-row new tokens, then a ("done",)/("error", e)
        # sentinel). Stream requests run as SOLO ticks — their device
        # work is a chunk loop, not one coalescible call.
        self.stream_q = stream_q
        self.done = threading.Event()
        self.outputs: list | None = None
        self.error: Exception | None = None
        self.batched_with = 1


class _Metrics:
    """Serving metrics on the shared ``tpufw.obs`` registry — the same
    ``tpufw_serve_*`` names and text exposition as the original
    hand-rolled class; the exposition code itself now lives in
    ``tpufw.obs.registry`` (one implementation for this endpoint, the
    trainer's ``TPUFW_METRICS_PORT``, and the device-plugin analog).
    Call sites keep the short names ("requests_total"); the prefix is
    applied here."""

    PREFIX = "tpufw_serve_"

    def __init__(self, registry: Optional[ObsRegistry] = None):
        self.registry = registry if registry is not None else ObsRegistry()
        # Pre-initialized to 0 (client-library convention): an alert on
        # increase(...errors_total) must see a real 0-valued series
        # before the first error, not an absent one.
        self.register(
            "requests_total",
            "request_errors_total",
            "request_seconds_total",
            "ticks_total",
            "tick_rows_total",
            "tokens_generated_total",
        )

    def inc(self, name: str, v: float = 1.0) -> None:
        self.registry.counter(self.PREFIX + name).inc(v)

    def register(self, *names: str) -> None:
        """Expose counters at 0 before their first increment (same
        absent-series rationale as the pre-initialized set) — for
        feature-gated counters like the speculative pair."""
        for name in names:
            self.registry.counter(self.PREFIX + name)

    def reset(self, *names: str) -> None:
        """Zero counters that moved during work that must stay
        invisible to scrapes (warmup runs before the listener binds)."""
        for name in names:
            self.registry.counter(self.PREFIX + name).reset()

    def render(self, gauges: dict[str, float]) -> str:
        """Prometheus text exposition; ``gauges`` are the caller's
        point-in-time values, refreshed into the registry at scrape
        time (they have one source of truth elsewhere)."""
        for name, v in gauges.items():
            self.registry.gauge(self.PREFIX + name).set(float(v))
        return self.registry.render()


class _Batcher:
    """Continuous batching at request granularity (VERDICT r2 #7).

    Requests enqueue; one worker thread drains the queue per tick,
    coalescing every waiting request into ONE batched generate call
    (rows concatenated, padded to a power of two; max_new_tokens run to
    the tick's bucketed max and sliced per request). While a tick's
    generate runs on the device, new arrivals accumulate for the next
    tick — so N concurrent clients cost ~one batched call instead of N
    serialized full-latency calls. A short coalescing window
    (TPUFW_BATCH_WAIT_MS, default 5) after the first dequeue lets
    near-simultaneous requests land in the same tick; TPUFW_BATCH_MAX_ROWS
    (default 64) caps rows per tick, the rest stay queued.
    """

    def __init__(
        self,
        run_tick,
        metrics: Optional[_Metrics] = None,
        run_stream=None,
    ):
        self._run_tick = run_tick
        self._run_stream = run_stream
        self._metrics = metrics
        self._queue: list[_Pending] = []
        self._cv = threading.Condition()
        self.max_rows = env_int("batch_max_rows", 64)
        self.wait_s = env_int("batch_wait_ms", 5) / 1000.0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def submit(self, prompts: list[list[int]], max_new: int, sampling=None):
        p = _Pending(prompts, max_new, sampling)
        with self._cv:
            self._queue.append(p)
            self._cv.notify()
        p.done.wait()
        if p.error is not None:
            raise p.error
        return p.outputs, p.batched_with

    def submit_stream(
        self, prompts: list[list[int]], max_new: int, sampling, q
    ) -> None:
        """Enqueue a streaming request and return immediately — the
        caller consumes per-chunk row outputs from ``q`` until the
        ("done",)/("error", e) sentinel. Device order is still the
        batcher thread's: the stream runs as its own tick."""
        p = _Pending(prompts, max_new, sampling, stream_q=q)
        with self._cv:
            self._queue.append(p)
            self._cv.notify()

    def _take_tick(self) -> list[_Pending]:
        with self._cv:
            while not self._queue:
                self._cv.wait()
        time.sleep(self.wait_s)  # let near-simultaneous arrivals land
        with self._cv:
            tick: list[_Pending] = []
            rows = 0
            rest: list[_Pending] = []
            # One device call = one SamplingConfig (it's a jit static
            # arg and the rng transforms are shared): the head request
            # defines the tick's config and every compatible request
            # joins; mismatches keep their queue order for a later
            # tick. No starvation — the head of the remainder defines
            # the NEXT tick's config. FIFO holds WITHIN a config: once
            # a same-config request misses the row budget, no later
            # same-config request may overtake it into this tick (only
            # config mismatches are diverted past it).
            budget_closed = False
            solo = False
            for nxt in self._queue:
                if not tick:
                    tick.append(nxt)
                    rows += len(nxt.prompts)
                    # A streaming head runs alone: its device work is a
                    # chunk LOOP, not one coalescible call.
                    solo = nxt.stream_q is not None
                elif solo or nxt.stream_q is not None:
                    rest.append(nxt)
                elif nxt.sampling != tick[0].sampling:
                    rest.append(nxt)
                elif (
                    budget_closed
                    or rows + len(nxt.prompts) > self.max_rows
                ):
                    budget_closed = True
                    rest.append(nxt)
                else:
                    tick.append(nxt)
                    rows += len(nxt.prompts)
            # tpulint: disable=TPU020 — consumer-side pop: shrinking
            # the queue only makes the wait predicate ("queue
            # non-empty") falser; there is no waiter this write could
            # unblock, so a notify would be a spurious wakeup.
            self._queue = rest
            return tick

    def _run_group(self, group: list[_Pending]) -> None:
        """Run one coalesced device call for ``group``; raises on
        failure without touching the pendings (the caller decides
        whether to isolate)."""
        if len(group) == 1 and group[0].stream_q is not None:
            pend = group[0]
            self._run_stream(pend)
            pend.batched_with = 1
            return
        all_prompts = [p for pend in group for p in pend.prompts]
        # Bucket the group's max_new to a power of two: the scan
        # length is a compiled-shape dimension, so arbitrary
        # per-request values would each compile a fresh program.
        want = max(p.max_new for p in group)
        run_new = 1
        while run_new < want:
            run_new *= 2
        outs = self._run_tick(all_prompts, run_new, group[0].sampling)
        i = 0
        for pend in group:
            rows = outs[i: i + len(pend.prompts)]
            pend.outputs = [r[: pend.max_new] for r in rows]
            pend.batched_with = len(group)
            i += len(pend.prompts)

    def _loop(self):
        while True:
            tick = self._take_tick()
            if self._metrics is not None:
                self._metrics.inc("ticks_total")
                self._metrics.inc(
                    "tick_rows_total",
                    sum(len(p.prompts) for p in tick),
                )
            try:
                try:
                    self._run_group(tick)
                except Exception:  # noqa: BLE001 — serving loop
                    if len(tick) == 1:
                        raise
                    # Failure isolation: coalescing must not create a
                    # shared fate — one invalid request (or a prompt/
                    # max_new combination that only overflows the KV
                    # budget when COMBINED with a co-batched request's
                    # bucket) falls back to per-request runs so the
                    # innocent ones still succeed.
                    for pend in tick:
                        try:
                            self._run_group([pend])
                        except Exception as e:  # noqa: BLE001
                            pend.error = e
            except Exception as e:  # noqa: BLE001 — serving loop
                for pend in tick:
                    pend.error = e
                    if pend.stream_q is not None:
                        # The SSE handler is blocked on the queue, not
                        # the done event — it needs the sentinel.
                        pend.stream_q.put(("error", e))
            finally:
                if self._metrics is not None:
                    self._metrics.inc(
                        "tokens_generated_total",
                        sum(
                            len(r)
                            for p in tick
                            if p.outputs is not None
                            for r in p.outputs
                        ),
                    )
                for pend in tick:
                    pend.done.set()


class _SlotJob:
    """One prompt ROW moving through the slot pool. Rows are the
    schedulable unit: a request's rows may join across chunk
    boundaries as slots free up, and each retires independently at
    its own EOS/max_new."""

    __slots__ = ("req", "prompt", "p_bucket", "max_new", "cache_len",
                 "tokens", "unflushed", "cp", "t_grant", "pass0", "kv0",
                 "t_first", "t_last", "longest_s", "decodes0", "behind0")

    def __init__(self, req, prompt, p_bucket, max_new, cache_len):
        self.req = req
        self.prompt = prompt
        self.p_bucket = p_bucket
        self.max_new = max_new
        self.cache_len = cache_len
        self.tokens: list[int] = []
        self.unflushed: list[int] = []
        # In-flight chunked prefill (pages.ChunkedPrefill) while this
        # row occupies a slot as a PREFILLING citizen; None once the
        # first token lands (or always, in monolithic admission mode).
        self.cp = None
        # Slot grant (perf_counter) and the scheduler pass it fell in:
        # where ``req_prefill`` / tpufw_serve_prefill_seconds start.
        self.t_grant = 0.0
        self.pass0 = 0
        # Where ``req_decode`` starts: the first token's moment, the
        # newest delivery's, the longest stretch between two of them,
        # and the pass ledger's counts of decode passes (and of those
        # behind prefill) when the first token was sampled.
        self.t_first = self.t_last = 0.0
        self.longest_s = 0.0
        self.decodes0 = self.behind0 = 0
        # Cache slots the row held when it was installed in its slot:
        # the prompt's, left padding included. With the tokens sampled
        # since, the row's cursor (``_row_keys``).
        self.kv0 = 0


def _row_keys(job: _SlotJob) -> int:
    """Cache slots a decoding row holds once its next step's token is
    written: its cursor + 1. The first sampled token is written by the
    first decode step, so the cursor trails ``tokens`` by one."""
    return job.kv0 + len(job.tokens)


class _DecodeChunk:
    """One decode chunk from its launch to its read: the rows it was
    launched for, its length, what the emit needs of the moment of its
    launch (the page-table snapshot, the span's arguments) and, once it
    is enqueued, its pending tokens. ``chained``: it was enqueued before
    its predecessor was read (``_SlotScheduler._run_chunk``)."""

    __slots__ = ("active", "k", "page_snap", "ahead", "key_rung",
                 "row_rung", "live", "chained", "out", "t0")

    def __init__(self, active, k, page_snap, ahead, rungs, live, chained):
        self.active = active
        self.k = k
        self.page_snap = page_snap
        self.ahead = ahead
        self.key_rung, self.row_rung = rungs
        self.live = live
        self.chained = chained
        self.out = None  # [S, k] tokens, pending on the device
        self.t0 = 0.0


class _SlotReq:
    """Request-level bookkeeping around a _Pending: the per-row jobs,
    the admission cursor (``next_job``), and completion accounting."""

    __slots__ = ("pend", "sampling", "jobs", "next_job", "rows_left",
                 "cache_len", "t_submit", "started", "error",
                 "batched_with", "overtaken", "rid")

    def __init__(self, pend, sampling, jobs):
        self.pend = pend
        self.sampling = sampling  # resolved (never None)
        self.jobs = jobs
        self.next_job = 0  # first not-yet-admitted job
        self.rows_left = len(jobs)
        # _make_req constructs the req first (jobs reference it), then
        # fills jobs and recomputes this.
        self.cache_len = max((j.cache_len for j in jobs), default=0)
        self.t_submit = time.time()
        self.started = False  # first row admitted (join latency mark)
        self.error: Exception | None = None
        self.batched_with = 1
        self.overtaken = 0  # admission rounds later arrivals ran ahead
        # Per-scheduler request id (set at enqueue): the one identifier
        # req_queue, req_prefill and the serve_request event share.
        self.rid = 0


#: The spans that partition the scheduler thread's pass: each one's
#: SELF seconds feed ``tpufw_serve_phase_seconds_total{phase=<name>}``,
#: so over any interval they sum to the thread's wall time less what no
#: span covers. ``serve_device_wait`` is the thread blocked on the
#: device, ``serve_wait`` the thread with nothing queued or running,
#: ``serve_prefill_chunk`` / ``serve_decode_dispatch`` time to ENQUEUE a
#: program (dispatch is asynchronous); the rest is host work. Request-
#: level records (``req_queue``, ``req_prefill``, ``req_decode``) cross
#: passes and stay out. ``serve_device_wait`` ends when the results it
#: waits for are READY, so it is the device running what this thread
#: enqueued and nothing else; ``serve_fetch`` is their copy to the host.
#: A pass runs them in one of two orders (``_SlotScheduler._run_chunk``).
#: The plain one: admit, prefill chunks, ``serve_decode_chunk`` (dispatch,
#: wait, fetch), emit. The CHAINED one, where nothing is queued and
#: nobody prefills when the wait returns: the SUCCESSOR's
#: ``serve_decode_dispatch`` comes between the wait and the fetch, so the
#: fetch, the emit and the next pass's scan run beside the device, and
#: the next pass is its ``serve_decode_chunk`` alone: no admit, no
#: dispatch of its own, the wait for the chunk it found in flight.
SCHED_PHASES = (
    "serve_wait",
    "serve_pool_build",
    "serve_admit",
    "serve_row_alloc",
    "serve_prefill",
    "serve_prefill_chunk",
    "serve_decode_chunk",
    "serve_spec_chunk",
    "serve_decode_dispatch",
    "serve_device_wait",
    "serve_fetch",
    "serve_emit",
)

#: What a scheduler pass was: a decode (or speculative) chunk with
#: nothing enqueued ahead of it in the pass, one that ran behind at
#: least one prefill program or insert of the same pass, or no decode
#: chunk at all (``_PassLedger``).
PASS_KINDS = ("decode", "decode_behind_prefill", "prefill_only")

#: Buckets of the request-chain histograms: queue waits of a fraction
#: of a second and chunked prefills of several seconds both need finer
#: steps than the registry's default ladder has there.
_CHAIN_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.15, 0.2, 0.25, 0.3,
    0.35, 0.4, 0.5, 0.6, 0.75, 1.0, 1.5, 2.0, 2.5, 3.0, 3.5, 4.0, 5.0,
    6.0, 8.0, 10.0, 15.0, 20.0, 30.0, 45.0, 60.0, 120.0,
)


class _PassLedger:
    """The scheduler thread's passes on the books, always on: what each
    pass was (``PASS_KINDS``), its wall seconds, the decode steps it
    ran, and the seconds of it the device was STARVED.

    A pass is one turn of ``_SlotScheduler._loop`` after ``serve_wait``:
    it runs from the end of the pass before it (or of the wait) to
    ``end_pass``, so passes and ``serve_wait`` tile the thread's time
    and the passes' seconds sum to the time in service.

    Starved seconds rest on the one bit the thread knows. After a
    blocking read returns (``serve_device_wait`` or the blocking
    ``serve_prefill`` closed), nothing this thread enqueued is left on
    the device: it is *drained*. At the return of a dispatch (``fed``,
    called by the pools where a program's call returns: a decode or
    speculative chunk, a prefill program, an insert, a row's zero-fill)
    it is *fed*; a pool just built leaves it fed too, and so does
    start-up, until the first read says otherwise. Seconds of a pass
    spent drained are seconds the device idled for this thread with
    requests in service: a LOWER bound of the device's idle time (the
    launch after an enqueue is the device's, not booked; the step keys'
    split and a retired slot's table row are microseconds of device
    work and feed nothing), over the whole of the server's life, no
    profiler needed. They are booked by the leaf phase they fell in. **A
    phase that straddles a dispatch is split at the dispatch's return**:
    what came before is starved, under the span open on the thread at
    that moment; what came after is not. Drained seconds that no span
    covers (the loop's own lines between two ``with`` blocks) go to the
    next phase that closes, and what is left at ``end_pass`` to the
    last one that did, so a pass's starved seconds never pass its own.

    A pass has ONE decode chunk: the one it waits for and reads. Where
    the scheduler enqueues that chunk's successor before it reads it
    (``_run_chunk``'s chained order), the second ``fed("decode")`` of
    the pass feeds the device like any dispatch (the starved seconds of
    such a boundary run from the wait's return to it, under
    ``serve_decode_dispatch``; the fetch and the emit behind it book
    none) but is the NEXT pass's chunk: that pass starts as a ``decode``
    pass (a successor runs behind no prefill), is counted in ``decodes``
    where it starts, and books the chunk's steps where its span closes.

    Fed from what exists: it listens to the tracer's spans next to the
    phase counter's ``on_span`` (a close of a phase while drained costs
    one clock read), reads the innermost open span's name at a
    dispatch, and counts the programs dispatched ahead of the pass's
    decode chunk. ``clock`` is for tests that set the time by hand; it
    has to be the tracer's."""

    #: A dispatch of one of these ahead of the pass's decode chunk puts
    #: the chunk behind prefill ("chunk": any prefill program, the
    #: blocking whole-prompt one included).
    AHEAD = ("chunk", "insert")

    def __init__(self, tracer, registry=None, clock=time.perf_counter):
        self._tracer = tracer
        self._clock = clock
        self._seconds = self._steps = self._starved = None
        if registry is not None:
            self._seconds = registry.counter(
                "tpufw_serve_pass_seconds_total",
                "Scheduler passes' wall seconds by kind of pass",
            )
            self._steps = registry.counter(
                "tpufw_serve_pass_steps_total",
                "Decode steps the scheduler's passes ran, by kind of pass",
            )
            self._starved = registry.counter(
                "tpufw_serve_pass_starved_seconds_total",
                "Seconds of a pass with nothing of this thread's on the "
                "device, by kind of pass and phase",
            )
        self._lock = threading.Lock()  # counters: end_pass against reset
        self.reset()  # every label exposed at 0
        self._t_pass = self._mark = clock()
        self._drained = False
        self._last = "serve_emit"  # the phase that closed last
        self._by_phase: dict[str, float] = {}
        self._k = 0  # decode steps of the pass that is running
        self._kind = "prefill_only"
        self._chained = False  # this pass enqueued the next one's chunk
        #: Prefill programs and inserts dispatched so far in this pass.
        self.ahead = 0
        #: Passes that ran a decode chunk, and those of them that ran it
        #: behind prefill, since start-up: a row's ``req_decode`` is the
        #: difference between its first token and its last.
        self.decodes = 0
        self.behind = 0
        tracer.listeners.append(self.on_span)

    def _book(self, phase: str, now: float) -> None:
        self._by_phase[phase] = (
            self._by_phase.get(phase, 0.0) + now - self._mark
        )
        self._mark = now

    def on_span(self, name, dur_s, args, self_s) -> None:
        if name not in SCHED_PHASES:
            return
        if name == "serve_wait":
            # Nothing in service: no pass's seconds, nobody starved.
            self._t_pass = self._mark = self._clock()
            return
        if self._drained:
            self._book(name, self._clock())
        self._last = name
        if name in ("serve_device_wait", "serve_prefill"):
            if not self._drained:
                self._drained, self._mark = True, self._clock()
        elif name == "serve_pool_build":
            self._drained = False
        elif name == "serve_decode_chunk":
            self._k += args["k"]
        elif name == "serve_spec_chunk":
            self._k += args["k"] + 1  # the verify block's positions

    def fed(self, what: str) -> None:
        """The call that dispatched a ``what`` ("decode", "chunk",
        "insert", "row") just returned."""
        if what == "decode" and self._kind != "prefill_only":
            self._chained = True  # the next pass's chunk, enqueued early
        elif what == "decode":
            self._kind = "decode_behind_prefill" if self.ahead else "decode"
            self.decodes += 1
            self.behind += bool(self.ahead)
        elif what in self.AHEAD and self._kind == "prefill_only":
            self.ahead += 1
        if self._drained:
            self._book(self._tracer.open_name() or self._last, self._clock())
            self._drained = False

    def end_pass(self) -> None:
        now = self._clock()
        if self._drained:
            self._book(self._last, now)
        kind, t0 = self._kind, self._t_pass
        with self._lock:
            if self._seconds is not None and t0 >= self._void_before:
                self._seconds.inc(now - t0, **{"pass": kind})
                self._steps.inc(self._k, **{"pass": kind})
                for phase, s in self._by_phase.items():
                    self._starved.inc(s, phase=phase, **{"pass": kind})
        self._t_pass = now
        self._by_phase.clear()
        self._k, self.ahead = 0, 0
        self._kind = "decode" if self._chained else "prefill_only"
        self.decodes += self._chained
        self._chained = False

    def reset(self) -> None:
        """Zero the three families (warm-up's passes stay invisible to
        scrapes), the pass that is running included: one that began
        before this moment books nothing when it ends."""
        with self._lock:
            self._void_before = self._clock()
            if self._seconds is None:
                return
            for kind in PASS_KINDS:
                self._seconds.reset(**{"pass": kind})
                self._steps.reset(**{"pass": kind})
                for phase in SCHED_PHASES[1:]:  # serve_wait is no pass's
                    self._starved.reset(phase=phase, **{"pass": kind})


class _SlotScheduler:
    """Continuous batching at decode-STEP granularity — the tick
    batcher's successor (``tpufw.infer.slots`` holds the device side).

    Requests enqueue as per-row jobs; ONE worker thread admits rows
    into a persistent S-slot KV pool and advances ALL occupied slots k
    tokens per device call. Rows join whenever a slot frees at a chunk
    boundary and retire at their own EOS/max_new — a short request
    admitted next to a long one completes mid-flight instead of
    waiting out the long tail, and streaming requests are ordinary
    slot occupants sharing decode chunks with everyone else (the tick
    batcher ran them as solo ticks).

    Static-shape discipline: occupancy is DATA, so joins/leaves never
    recompile. The pool is keyed (cache_len, sampling) — cache_len
    from the serving ``_cache_bucket`` ladder, sampling because it is
    a compiled-program parameter — and REKEYS only when it drains
    empty. Chunk length k is itself pow-2-laddered against the
    largest remaining budget, so at most log2(chunk) decode programs
    exist per pool key; greedy outputs are invariant to how the run
    is chunked (the per-step carry is identical).

    Fairness: FIFO holds within a pool key — once a compatible
    request misses the free-slot budget, no later compatible request
    overtakes it. Incompatible requests are diverted past, but each
    diversion is counted and admission CLOSES after ``n_slots``
    overtakes, so a mismatched head request drains the pool instead
    of starving behind a steady compatible stream.

    Knobs: TPUFW_SERVE_SLOTS (pool size; 0 restores the tick
    batcher), TPUFW_SERVE_CHUNK (tokens per device call, default
    TPUFW_STREAM_CHUNK), TPUFW_SERVE_CACHE_FLOOR (smallest cache
    rung), TPUFW_BATCH_WAIT_MS (idle coalescing window, shared with
    the tick batcher).
    """

    def __init__(
        self,
        model,
        params,
        *,
        eos_id: Optional[int] = None,
        default_sampling=None,
        metrics: Optional[_Metrics] = None,
        seed_base: int = 0,
        events=None,
        tracer=None,
        goodput=None,
        watchdog=None,
        page: Optional[int] = None,
        kv_quant: Optional[str] = None,
        prefix_cache: Optional[bool] = None,
        arena_pages: Optional[int] = None,
        perf=None,
        page_export=None,
        spec_k: Optional[int] = None,
        spec_draft: Optional[str] = None,
        spec_min_accept: Optional[float] = None,
        spec_draft_built=None,
        prefill_chunk_pages: Optional[int] = None,
    ):
        import jax
        import numpy as np

        from tpufw.infer import slots as slots_mod

        self._jax = jax
        self._np = np
        self._slots_mod = slots_mod
        self.model = model
        self.params = params
        self._eos = eos_id
        self._default_sampling = (
            default_sampling
            if default_sampling is not None
            else sampling_from_env()
        )
        self._metrics = metrics
        self._seed_base = seed_base
        self._events = events if events is not None else obs_events.NULL
        # Always a live tracer: without a telemetry dir it buffers
        # nothing and writes no file, but its spans still show in a
        # profiler capture and still feed the phase counter below.
        self._tracer = (
            tracer
            if tracer is not None and tracer.enabled
            else obs_trace.Tracer(
                None, annotate=obs_trace.jax_annotation()
            )
        )
        self._goodput = goodput if goodput is not None else obs_goodput.NULL
        self._watchdog = watchdog if watchdog is not None else NULL_WATCHDOG
        self._perf = perf if perf is not None else obs_perf.NULL
        # Disaggregated handoff hook: called with (job, state) for
        # every naturally-completing paged row, where ``state`` is the
        # slot's export_slot() dict taken BEFORE the slot is retired.
        self._page_export = page_export
        self.n_slots = max(1, env_int("serve_slots", 8))
        self.chunk = max(
            1, env_int("serve_chunk", 0) or env_int("stream_chunk", 16)
        )
        self.cache_floor = env_int("serve_cache_floor", 128)
        self.wait_s = env_int("batch_wait_ms", 5) / 1000.0
        self.prefill_chunk = env_int("prefill_chunk", 0) or None
        # Paged-KV knobs: ctor kwargs win over the env so bench can
        # run both modes in one process without mutating os.environ.
        # page=0 keeps the legacy contiguous SlotPool bit-for-bit.
        self.page = (
            env_int("serve_page", 0) if page is None else int(page)
        )
        self.kv_quant = (
            env_str("serve_kv_quant", "")
            if kv_quant is None
            else str(kv_quant)
        )
        self.prefix_enabled = (
            env_bool("serve_prefix_cache", True)
            if prefix_cache is None
            else bool(prefix_cache)
        )
        self.arena_pages = arena_pages
        # Page-aligned chunked prefill: admission acquires only the
        # first chunk's pages and the row prefills one chunk per
        # scheduler pass, interleaved with decoding slots — a long
        # prompt no longer head-of-line-blocks the queue. 0 keeps the
        # legacy monolithic admission byte-identical.
        self.prefill_chunk_pages = (
            env_int("serve_prefill_chunk", 0)
            if prefill_chunk_pages is None
            else int(prefill_chunk_pages)
        )
        if self.prefill_chunk_pages and not self.page:
            raise ValueError(
                f"TPUFW_SERVE_PREFILL_CHUNK="
                f"{self.prefill_chunk_pages}: chunked prefill is "
                "page-granular and needs TPUFW_SERVE_PAGE > 0"
            )
        # KV fabric: host-RAM spill tier behind the page arena.
        # TPUFW_KV_SPILL budgets it in PAGES (the arena's own unit);
        # TPUFW_KV_SPILL_DIR adds the directory overflow / session
        # store. Evicted prefix pages demote there instead of dying,
        # and a later prompt sharing the prefix restores them through
        # the normal splice path instead of re-prefilling.
        self.kv_spill_pages = max(0, env_int("kv_spill", 0))
        self.kv_spill_dir = env_str("kv_spill_dir", "")
        self._spill = None
        if self.kv_spill_pages or self.kv_spill_dir:
            if not self.page:
                raise ValueError(
                    f"TPUFW_KV_SPILL={self.kv_spill_pages}: the spill "
                    "tier is page-granular and needs "
                    "TPUFW_SERVE_PAGE > 0"
                )
            from tpufw.infer.spill import SpillTier

            self._spill = SpillTier(
                self.kv_spill_pages, self.kv_spill_dir
            )
        # Scrape-time delta cursor: the tier's byte total is monotonic
        # but registry counters only inc, so /metrics advances the
        # counter by the delta since the last scrape.
        self._spill_seen_bytes = 0
        if self.page:
            cap = model.cfg.max_seq_len
            # Every cache-ladder rung is a pow2 >= cache_floor or the
            # model cap, so "page is pow2 and page <= floor and page
            # divides cap" guarantees page | cache_len at every rung.
            if self.page & (self.page - 1) or self.page < 1:
                raise ValueError(
                    f"TPUFW_SERVE_PAGE={self.page}: page size must be "
                    "a power of two"
                )
            if self.page > self.cache_floor:
                raise ValueError(
                    f"TPUFW_SERVE_PAGE={self.page} exceeds the cache "
                    f"floor ({self.cache_floor}); pages must divide "
                    "every cache-ladder rung"
                )
            if cap % self.page:
                raise ValueError(
                    f"TPUFW_SERVE_PAGE={self.page} does not divide "
                    f"max_seq_len={cap}"
                )
            if self.kv_quant not in ("", "int8"):
                raise ValueError(
                    f"TPUFW_SERVE_KV_QUANT={self.kv_quant!r}: "
                    "expected '' or 'int8'"
                )
            from tpufw.infer import pages as pages_mod

            self._pages_mod = pages_mod
        # Speculative decoding on the slot pool: TPUFW_SERVE_SPEC_K > 0
        # drafts spec_k tokens per pass and verifies them in ONE target
        # call (tpufw.infer.speculative chunked path). Ctor kwargs win
        # over env (bench runs both modes in one process). spec_draft
        # "" = self-drafting (n-gram prompt lookup, no extra HBM); a
        # model preset name builds a draft pool sharing the target's
        # page arena budget. spec_draft_built short-circuits the preset
        # resolution with a pre-built (decode_cfg, params) pair — the
        # server passes its TPUFW_DRAFT_MODEL build through this.
        self.spec_k = (
            env_int("serve_spec_k", 0) if spec_k is None else int(spec_k)
        )
        self.spec_draft = (
            env_str("serve_spec_draft", "")
            if spec_draft is None
            else str(spec_draft)
        )
        self.spec_min_accept = (
            env_float("serve_spec_min_accept", 0.25)
            if spec_min_accept is None
            else float(spec_min_accept)
        )
        self._draft_cfg = None
        self._draft_params = None
        self._draft_n_params = 0
        self._draft_pool = None
        self._ema = None
        # Cumulative accept bookkeeping behind tpufw_spec_accept_rate.
        self._spec_accept_sum = 0.0
        self._spec_accept_rows = 0
        if self.spec_k:
            if self.spec_k < 1:
                raise ValueError(
                    f"TPUFW_SERVE_SPEC_K={self.spec_k}: need >= 1"
                )
            if self.page and self.spec_k + 1 > self.page:
                # Clamp safety: a done row's junk verify block must fit
                # inside the row's own last page (writes clamp to
                # max_seq_len - (k+1)), so the block can never spill
                # into a neighbour's page.
                raise ValueError(
                    f"TPUFW_SERVE_SPEC_K={self.spec_k}: the k+1 verify "
                    f"block must fit one KV page (page={self.page})"
                )
            from tpufw.infer import speculative as spec_mod

            self._spec_mod = spec_mod
            if spec_draft_built is not None:
                self._draft_cfg, self._draft_params = spec_draft_built
            elif self.spec_draft and self.spec_draft != "ngram":
                self._draft_cfg, self._draft_params = (
                    self._build_spec_draft(self.spec_draft)
                )
            if self._draft_params is not None:
                # Wasted-draft-FLOPs accounting (~2 * params per drafted
                # token, decode-side); 0 for self-drafting — n-gram
                # lookup costs no device FLOPs.
                self._draft_n_params = sum(
                    int(np.prod(leaf.shape))
                    for leaf in jax.tree_util.tree_leaves(
                        self._draft_params
                    )
                )
        if metrics is not None:
            metrics.register(
                "retired_rows_total",
                "wasted_slot_steps_total",
                "pool_switches_total",
                # Decode chunks enqueued before their predecessor was
                # read (``_run_chunk``'s chained order), beside
                # ``ticks_total``, the chunks read.
                "chunks_chained_total",
                # Decode steps dispatched on a pool whose model has
                # routed experts, and those of them with no more live
                # rows than the pool's ``expert_rows``: the steps whose
                # expert matmuls ran over the live rows' assignments
                # alone (tpufw.ops.moe_live; ``_count_experts``). 0 for
                # a model without routed experts.
                "expert_steps_total",
                "expert_live_steps_total",
            )
            if self.page:
                # Feature-gated (register = expose at 0): legacy-mode
                # /metrics stays byte-identical with paging off.
                metrics.register(
                    "prefix_hits_total",
                    "prefix_misses_total",
                    "pages_freed_total",
                    # Host traces of a row model for its row cache's
                    # shapes: one per paged pool built, none per
                    # admission (PagedSlotPool._find_row_shapes). Not
                    # reset after warm-up: the pool warm-up built is
                    # the one that serves, and its 1 is the evidence.
                    "row_shape_traces_total",
                    # Key slots the device gathered (rows read x the
                    # slots of each), and key slots of the whole rows of
                    # the pool, over dispatched decode steps,
                    # speculative passes and prefill chunks: their ratio
                    # is the share of the pool's key slots the cached
                    # calls attended (tpufw.ops.kv_store's two ladders,
                    # by the rules the programs use; ``_count_keys``).
                    "attended_key_slots_total",
                    "row_key_slots_total",
                    # The same pair for the layers that keep a ring of
                    # their window (kv_store.ring_append): ring slots
                    # read and the whole rows' slots, x window layers;
                    # 0 for a model without one.
                    "window_key_slots_total",
                    "window_row_key_slots_total",
                )
                # Key slots read by layers through pages ANOTHER layer
                # wrote (tpufw.ops.kv_store, readers that are not the
                # writer): ``attended_key_slots_total``'s count once for
                # each such reader, by the kind of call; 0 for a model
                # whose layers read only what they wrote.
                for call in ("decode", "chunk"):
                    metrics.registry.counter(
                        "tpufw_serve_shared_key_slots_total"
                    ).inc(0.0, call=call)
                # Admissions whose prefix lookup the pool declined (a
                # model with per-slot state or window rings gets no
                # shared pages).
                for decline in slots_mod.DECLINES.values():
                    metrics.registry.counter(
                        "tpufw_serve_prefix_declined_total"
                    ).inc(0.0, reason=decline.reason)
            # Per-slot state (linear-attention layers) and window
            # layers' rings the pool holds beside its K/V pages: 0 for a
            # model that has none.
            for name in ("state", "window"):
                metrics.registry.gauge(f"tpufw_serve_{name}_bytes")
                metrics.registry.gauge(f"tpufw_serve_{name}_slots")
            if self.prefill_chunk_pages:
                # Chunked-prefill series live OUTSIDE the tpufw_serve_
                # prefix (the disagg PrefillEngine reports the same
                # names through its signals); gated so a monolithic
                # server's exposition stays byte-identical.
                metrics.registry.counter("tpufw_prefill_chunks_total")
                metrics.registry.counter("tpufw_prefill_resumes_total")
                metrics.registry.gauge("tpufw_prefill_inflight")
            if self._spill is not None:
                # KV-fabric series also live OUTSIDE the prefix (the
                # disagg engines report the same spill tier); gated so
                # a spill-less exposition stays byte-identical.
                metrics.registry.counter("tpufw_kv_spill_bytes_total")
                metrics.registry.gauge("tpufw_kv_spill_pages")
                metrics.registry.histogram(
                    "tpufw_kv_restore_seconds",
                    "Spill-tier restore wall (host fetch + decode)",
                )
            if self.spec_k:
                # Speculation metrics live OUTSIDE the tpufw_serve_
                # prefix (they also serve the disagg DecodeEngine);
                # registered at 0/absent-series like the rest, gated so
                # non-spec servers keep a byte-identical exposition.
                metrics.registry.counter(
                    "tpufw_spec_wasted_draft_flops_total"
                )
                metrics.registry.gauge("tpufw_spec_accept_rate")
                metrics.registry.gauge("tpufw_spec_fallback_slots")
            # The request chain: join = queue_wait + admission, and
            # ttft = queue_wait + prefill (plus the stream's flush).
            metrics.registry.histogram(
                "tpufw_serve_join_latency_seconds",
                "Request submit-to-first-slot-insert latency",
            )
            metrics.registry.histogram(
                "tpufw_serve_queue_wait_seconds",
                "Request submit-to-admission-start latency",
                buckets=_CHAIN_BUCKETS,
            )
            metrics.registry.histogram(
                "tpufw_serve_prefill_seconds",
                "Per-row slot grant to first token sampled",
                buckets=_CHAIN_BUCKETS,
            )
            # Where the scheduler thread's time went, by span self
            # time; every phase exposed at 0 before it first runs.
            phase_s = metrics.registry.counter(
                "tpufw_serve_phase_seconds_total",
                "Scheduler-thread self seconds by phase (span name)",
            )
            for phase in SCHED_PHASES:
                phase_s.inc(0.0, phase=phase)

            def on_span(name, dur_s, args, self_s):
                if name in SCHED_PHASES:
                    phase_s.inc(max(0.0, self_s), phase=name)

            self._tracer.listeners.append(on_span)
        # The passes' own books, beside the phases': kind, seconds,
        # steps and starved seconds of every pass (counters only where
        # there is a registry; ``req_decode`` reads it either way).
        self._ledger = _PassLedger(
            self._tracer, metrics.registry if metrics is not None else None
        )
        self._pool = None  # tpufw.infer.slots.SlotPool (lazy, keyed)
        self._pool_key: Optional[tuple] = None
        self._slots: list[Optional[_SlotJob]] = [None] * self.n_slots
        self._n_active = 0  # resource: counter slots-occupied
        # Monotonic indices namespacing the rng streams (fold_in of
        # two DIFFERENT base seeds, so prefill and chunk draws never
        # collide); both restored by reset_after_warmup so warmup is
        # invisible to seed replay.
        self._job_index = 0
        self._chunk_index = 0
        # Step keys made while a chunk ran, for the chunk after it:
        # ((chunk index, k), keys), taken only by the dispatch that has
        # that index and that length (``_dispatch_chunk``).
        self._keys_ahead = None
        # The decode chunk enqueued and not yet read, where a pass ended
        # with one in flight (at most one: ``_run_chunk``). The
        # scheduler thread's alone.
        self._inflight: Optional[_DecodeChunk] = None
        self._rid = 0  # request ids handed out (rid of the newest)
        self._pass = 0  # scheduler passes begun (req_prefill counts them)
        self._queue: list[_SlotReq] = []
        self._cv = threading.Condition()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="tpufw-serve-sched"
        )
        self._thread.start()

    # ---- client-facing interface (mirrors _Batcher) ----

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    @property
    def slots_total(self) -> int:
        return self.n_slots

    @property
    def slots_occupied(self) -> int:
        with self._cv:
            return self._n_active

    @property
    def pages_total(self) -> int:
        """Arena capacity of the CURRENT pool (0 before first build /
        in contiguous mode) — page 0 is the reserved junk sink and
        never allocatable, so it is excluded."""
        with self._cv:
            if not self.page or self._pool is None:
                return 0
            return self._pool.allocator.capacity

    @property
    def pages_in_use(self) -> int:
        with self._cv:
            if not self.page or self._pool is None:
                return 0
            return self._pool.allocator.in_use

    def submit(self, prompts: list[list[int]], max_new: int, sampling=None):
        pend = _Pending(prompts, max_new, sampling)
        self._enqueue(pend)
        pend.done.wait()
        if pend.error is not None:
            raise pend.error
        return pend.outputs, pend.batched_with

    def submit_stream(
        self, prompts: list[list[int]], max_new: int, sampling, q
    ) -> None:
        """Enqueue a streaming request and return immediately — the
        caller consumes per-chunk row outputs from ``q`` until the
        ("done", n)/("error", e) sentinel. Stream rows occupy slots
        like any other; their unflushed tokens are put once per decode
        chunk."""
        pend = _Pending(prompts, max_new, sampling, stream_q=q)
        self._enqueue(pend)

    def reset_after_warmup(self) -> None:
        """Restore the rng-stream indices so warmup prefills/chunks
        are invisible to seed replay (the compiled programs and the
        warm pool itself stay), drop the step keys made ahead under
        warm-up's indices, and zero the scheduler's own books: the
        passes' and the count of chained chunks."""
        with self._cv:
            self._job_index = 0
            self._chunk_index = 0
            self._keys_ahead = None
        self._ledger.reset()
        if self._metrics is not None:
            self._metrics.reset("chunks_chained_total")
            self._metrics.reset("expert_steps_total")
            self._metrics.reset("expert_live_steps_total")

    def _enqueue(self, pend: _Pending) -> None:
        req = self._make_req(pend)  # raises ValueError -> HTTP 400
        with self._cv:
            self._rid += 1
            req.rid = self._rid
            self._queue.append(req)
            self._cv.notify()

    def _make_req(self, pend: _Pending) -> _SlotReq:
        cap = self.model.cfg.max_seq_len
        sampling = (
            pend.sampling
            if pend.sampling is not None
            else self._default_sampling
        )
        jobs = []
        req = _SlotReq(pend, sampling, [])
        # Speculative slack: a live row's verify block writes up to
        # spec_k slots past its final cursor before rolling back, so
        # spec rows size their cache rung / page grant for it.
        slack = self._spec_slack(sampling)
        for prompt in pend.prompts:
            if self.page:
                # Paged rows prefill at their EXACT width (no 64-token
                # bucket): padding would burn whole pages per row and
                # misalign the prompt's page-granular prefix chunks.
                pb = max(len(prompt), 1)
            else:
                pb = _bucket(len(prompt), 64)
            # Validate at submit (not mid-pool): prefill writes pb
            # slots, decode writes max_new - 1 more (the first token
            # comes out of prefill).
            if pb + pend.max_new - 1 + slack > cap:
                raise ValueError(
                    f"prompt ({len(prompt)}, bucketed to {pb}) + "
                    f"max_new_tokens ({pend.max_new})"
                    + (f" + spec slack ({slack})" if slack else "")
                    + f" exceeds the KV cache (max_seq_len={cap})"
                )
            if self.page and self.arena_pages is not None:
                need = -(-(pb + pend.max_new - 1 + slack) // self.page)
                if need > self.arena_pages - 1:
                    # Reject now, not in the admission loop: a row
                    # that can NEVER fit the arena would deadlock the
                    # FIFO forever (page 0 is reserved). This bound is
                    # already max-resident: an in-place row must hold
                    # its whole prompt+budget page set at finalize
                    # even under chunked admission, so chunking only
                    # relaxes it on the disagg PrefillEngine (which
                    # exports prompt-only bundles — see serve/roles).
                    raise ValueError(
                        f"row needs {need} KV pages but the arena "
                        f"holds {self.arena_pages - 1}"
                    )
            jobs.append(_SlotJob(
                req,
                prompt,
                pb,
                pend.max_new,
                _cache_bucket(
                    pb + pend.max_new - 1 + slack, cap, self.cache_floor
                ),
            ))
        req.jobs = jobs
        req.rows_left = len(jobs)
        req.cache_len = max(j.cache_len for j in jobs)
        return req

    # ---- worker loop ----

    def _loop(self) -> None:
        # Every stretch of a pass lies in a leaf span (SCHED_PHASES).
        # No span encloses the pass: in a profiler capture it would
        # cover every idle gap of the device and hide the phase.
        while True:
            # A chunk in flight is service too (with an EOS every row of
            # it may have retired at its predecessor's emit): it is read
            # before the thread rests.
            inflight = self._inflight is not None
            with self._cv:
                if not self._queue and not self._n_active and not inflight:
                    with self._tracer.span("serve_wait"):
                        while not self._queue and not self._n_active:
                            self._cv.wait()
                idle = self._n_active == 0 and not inflight
            if idle and self.wait_s > 0:
                # Coalescing window: near-simultaneous arrivals land
                # in the same first admission round. Never slept while
                # the pool is running — joins happen at chunk
                # boundaries, which are the natural cadence.
                with self._tracer.span("serve_wait"):
                    time.sleep(self.wait_s)
            self._pass += 1
            # Watchdog window: one admit + one chunk. Both are a
            # bounded amount of device work (prefill / k decode
            # steps); if either wedges past TPUFW_HANG_TIMEOUT_S the
            # dump shows which. Idle waiting above stays disarmed.
            self._watchdog.arm()
            try:
                if not inflight:
                    # Behind a chunk in flight nothing is admitted: what
                    # arrived since it was enqueued waits for its read,
                    # as it would have behind the same chunk enqueued a
                    # pass later.
                    self._admit()
                if self._n_active or inflight:
                    self._run_chunk()
            except Exception as e:  # noqa: BLE001 — serving loop
                self._fail_active(e)
            finally:
                self._watchdog.disarm()
                self._ledger.end_pass()

    def _row_model(self, cache_len: int):
        """CONTIGUOUS model variant with the pool's KV budget — built
        inline; flax modules hash structurally, so equal configs hit
        the jit caches without memoization (same trick as
        _Server._model_for). In paged mode this is the B=1 prefill
        model (prefill stays dense; paging starts at row insert)."""
        import dataclasses

        if cache_len == self.model.cfg.max_seq_len:
            return self.model
        return type(self.model)(
            dataclasses.replace(self.model.cfg, max_seq_len=cache_len)
        )

    def _pool_model(self, cache_len: int):
        """Model variant the POOL decodes with: contiguous rows by
        default; with TPUFW_SERVE_PAGE set, the paged-arena variant
        (kv_page/kv_pages/kv_quant route the models' cached-attention
        through the page table)."""
        import dataclasses

        if not self.page:
            return self._row_model(cache_len)
        per_row = cache_len // self.page
        n_pages = (
            self.arena_pages
            if self.arena_pages is not None
            # +1 for the reserved junk-sink page 0: the default arena
            # holds exactly n_slots full rows, same HBM working set
            # as the contiguous pool it replaces.
            else self.n_slots * per_row + 1
        )
        return type(self.model)(
            dataclasses.replace(
                self.model.cfg,
                max_seq_len=cache_len,
                kv_page=self.page,
                kv_pages=n_pages,
                kv_quant=self.kv_quant,
            )
        )

    def _prefix_declined(self) -> bool:
        """True where the pool declined this admission's prefix lookup
        (``PagedSlotPool.prefix_decline``): counted by reason, neither
        a hit nor a miss."""
        reason = self._pool.prefix_decline
        if not reason:
            return False
        if self._metrics is not None:
            self._metrics.registry.counter(
                "tpufw_serve_prefix_declined_total"
            ).inc(1.0, reason=reason)
        return True

    def _spec_slack(self, sampling) -> int:
        """Extra KV slots a speculative row needs past max_new - 1 (0
        when speculation is off or ineligible for this sampling)."""
        if not self.spec_k:
            return 0
        if self._slots_mod._track_seen(sampling):
            return 0
        return self.spec_k

    def _build_spec_draft(self, name: str):
        """Resolve TPUFW_SERVE_SPEC_DRAFT as a model preset: weights
        from TPUFW_DRAFT_PARAMS_CHECKPOINT, else random init (wiring
        tests only — proposals rarely match, acceptance collapses and
        the EMA falls the pool back to plain decode). Returns the
        (decode_cfg, params) pair the per-pool variants derive from."""
        import dataclasses

        jax = self._jax
        from tpufw.configs.loader import resolve_model_preset
        from tpufw.models import model_for_config

        base = resolve_model_preset(name)
        cfg = dataclasses.replace(
            base, max_seq_len=env_int("max_seq_len", base.max_seq_len)
        )
        ckpt = env_str("draft_params_checkpoint", "")
        if ckpt:
            params = _restore_bare_params(cfg, ckpt)
        else:
            model = model_for_config(cfg)
            params = jax.jit(model.init)(
                jax.random.key(self._seed_base + 1),
                self._jax.numpy.zeros(
                    (1, min(8, cfg.max_seq_len)), self._jax.numpy.int32
                ),
            )["params"]
        return cfg.decode_config(), params

    def _draft_pool_models(self, cache_len: int):
        """Per-pool draft model variants (pool + contiguous prefill
        twin) mirroring _pool_model/_row_model's replace() trick, with
        the SAME page/arena geometry as the target so one shared
        PageAllocator id space covers both physical arenas."""
        import dataclasses

        from tpufw.models import model_for_config

        row_cfg = dataclasses.replace(
            self._draft_cfg, max_seq_len=cache_len
        )
        if not self.page:
            return model_for_config(row_cfg), model_for_config(row_cfg)
        per_row = cache_len // self.page
        n_pages = (
            self.arena_pages
            if self.arena_pages is not None
            else self.n_slots * per_row + 1
        )
        pool_cfg = dataclasses.replace(
            row_cfg,
            kv_page=self.page,
            kv_pages=n_pages,
            kv_quant=self.kv_quant,
        )
        return model_for_config(pool_cfg), model_for_config(row_cfg)

    def _build_pool(self, key) -> None:
        cache_len, sampling = key
        with self._tracer.span(
            "serve_pool_build", cache_len=cache_len, slots=self.n_slots
        ):
            if self.page:
                self._pool = self._pages_mod.PagedSlotPool.create_paged(
                    self._pool_model(cache_len),
                    self._row_model(cache_len),
                    self.params,
                    self.n_slots,
                    sampling=sampling,
                    pad_id=0,
                    eos_id=self._eos,
                    prefix_cache=self.prefix_enabled,
                )
            else:
                self._pool = self._slots_mod.SlotPool.create(
                    self._pool_model(cache_len),
                    self.params,
                    self.n_slots,
                    sampling=sampling,
                    pad_id=0,
                    eos_id=self._eos,
                )
        if self.page and self._spill is not None:
            # Re-wired on every pool rebuild: the spill closures close
            # over the pool they serialize for. The tier itself (and
            # its contents) survives rebuilds — a cache-ladder switch
            # does not forget spilled KV.
            from tpufw.serve import bundle as serve_bundle

            serve_bundle.attach_spill(
                self._pool,
                self._spill,
                events=self._events,
                on_restore=(
                    self._metrics.registry.histogram(
                        "tpufw_kv_restore_seconds"
                    ).observe
                    if self._metrics is not None
                    else None
                ),
            )
        # The pool reports the return of every dispatch it makes.
        self._pool.dispatched = self._ledger.fed
        if self.page:
            self._pool.tracer = self._tracer
            self._count_row_shape_traces(self._pool)
        if self.spec_k:
            self._slots_mod.reject_state(
                self._pool, f"TPUFW_SERVE_SPEC_K={self.spec_k}"
            )
        if self._metrics is not None:
            reg = self._metrics.registry
            state = self._pool.state_bytes
            reg.gauge("tpufw_serve_state_bytes").set(float(state))
            reg.gauge("tpufw_serve_state_slots").set(
                float(self.n_slots if state else 0)
            )
            if state:
                # Only where the pool holds state: a model of keys and
                # values alone keeps its /metrics as they were. Not
                # reset after warm-up (readers take deltas).
                self._metrics.register(
                    "state_moved_bytes_total", "state_live_bytes_total"
                )
            reg.gauge("tpufw_serve_window_bytes").set(
                float(self._pool.window_bytes)
            )
            reg.gauge("tpufw_serve_window_slots").set(
                float(self._pool.window_slots)
            )
        if self._perf.enabled:
            # Mount the cost observatory on the pool (dynamic attr:
            # SlotPool/PagedSlotPool read it via getattr) so insert /
            # decode programs harvest their XLA cost analysis.
            self._pool.perf = self._perf
        self._draft_pool = None
        self._ema = None
        if self.spec_k:
            track = self._slots_mod._track_seen(sampling)
            if track:
                # Acceptance at position j would change the penalized
                # distribution at j+1 — the one-pass verify cannot
                # compose with a repetition penalty, so this pool stays
                # on plain chunked decode.
                self._events.emit(
                    "serve_spec",
                    level="warn",
                    k=self.spec_k,
                    mode="plain_fallback",
                    reason="repetition_penalty",
                )
            else:
                if self._draft_cfg is not None:
                    d_pool, d_row = self._draft_pool_models(cache_len)
                    if self.page:
                        self._draft_pool = (
                            self._pages_mod.PagedSlotPool.create_paged(
                                d_pool,
                                d_row,
                                self._draft_params,
                                self.n_slots,
                                sampling=sampling,
                                pad_id=0,
                                eos_id=None,
                                prefix_cache=False,
                                allocator=self._pool.allocator,
                            )
                        )
                        self._count_row_shape_traces(self._draft_pool)
                    else:
                        self._draft_pool = self._slots_mod.SlotPool.create(
                            d_pool,
                            self._draft_params,
                            self.n_slots,
                            sampling=sampling,
                            pad_id=0,
                            eos_id=None,
                        )
                if self._draft_pool is not None:
                    self._draft_pool.dispatched = self._ledger.fed
                self._ema = self._spec_mod.AcceptEMA(
                    self.n_slots,
                    min_accept=self.spec_min_accept,
                    # Plain chunks leave a draft pool's KV stale (only
                    # the target advances), so a probe there would
                    # measure a stale-context draft: draft-pool
                    # fallback is sticky until the pool drains.
                    probe_every=0 if self._draft_pool is not None else 8,
                )
        self._pool_key = key
        self._slots = [None] * self.n_slots
        self._n_active = 0
        if self._metrics is not None:
            self._metrics.inc("pool_switches_total")
        self._events.emit(
            "serve_pool_switch", cache_len=cache_len, slots=self.n_slots
        )

    def _count_row_shape_traces(self, pool) -> None:
        if self._metrics is not None:
            self._metrics.inc(
                "row_shape_traces_total", pool.row_shape_traces
            )

    def _count_keys(self, calls, chunk: bool = False, width: int = 1) -> None:
        """Book the key slots the device read in dispatched cached
        calls of ``width`` tokens a row: decode steps and verify blocks,
        every slot of the pool a row, or (``chunk``) prefill chunks of
        one row. ``calls`` yields, per call, the slots each of its live
        rows holds with the call's own tokens (none: no row was live).
        ``attended_key_slots_total`` grows by what the store read of
        them (the pool names it, by the rule its programs choose their
        read with: each live row's own pages where a step reads the
        arena in place, else K x L, the rows and the key slots of each
        the two ladders' rungs hold), beside ``row_key_slots_total``'s B
        x ``max_seq_len``: the share of the pool's key slots the device
        read. Layers that keep a ring of their window are booked apart
        (x layers: they read every row's ring whatever the rows hold);
        ``shared_key_slots_total`` grows by the same read once for each
        layer that attends the pages beside the one that wrote them (the
        pool reads the count off the store's rule for its model)."""
        if self._metrics is None or not self.page:
            return
        calls = list(calls)
        read, whole = self._pool.attended_keys(
            calls, chunk=chunk, width=width
        )
        self._metrics.inc("attended_key_slots_total", read)
        self._metrics.inc("row_key_slots_total", whole)
        self._metrics.registry.counter(
            "tpufw_serve_shared_key_slots_total"
        ).inc(
            (self._pool.page_readers - 1) * read,
            call="chunk" if chunk else "decode",
        )
        rows = 1 if chunk else self.n_slots
        read, whole = self._pool.window_keys(len(calls), width)
        self._metrics.inc("window_key_slots_total", rows * read)
        self._metrics.inc("window_row_key_slots_total", rows * whole)

    def _count_state(self, moved: int, live: int) -> None:
        """Book the per-slot state (kv_store role STATE) that dispatched
        calls read and wrote, beside ``_count_keys``: ``moved`` slot-rows
        (EVERY slot of the pool at each decode step, live or not; the
        one row of a prefill chunk or of an insert), of which ``live``
        were in service there (at a decode step: still delivering a
        token), each x a slot's state bytes x 2 (read and written)."""
        if self._metrics is None or not self._pool.state_bytes:
            return
        both = 2 * (self._pool.state_bytes // self.n_slots)
        self._metrics.inc("state_moved_bytes_total", moved * both)
        self._metrics.inc("state_live_bytes_total", live * both)

    def _experts_live(self, live: int) -> int:
        """1 where a decode step of the pool with ``live`` live rows runs
        its routed experts over those rows' assignments alone: the
        program's own rule (``moe_live.takes`` of the pool's
        ``expert_rows``); 0 above that, off the chip, and for a model
        without routed experts."""
        from tpufw.ops.moe_live import takes

        rows = self._pool.expert_rows
        return int(rows is not None and bool(takes(rows, live)))

    def _count_experts(self, lives) -> None:
        """Book dispatched decode steps of a pool whose model has
        routed experts, beside ``_count_keys``: ``lives`` yields each
        step's live rows, and a step counts as live where
        ``_experts_live`` says so."""
        if self._metrics is None or self._pool.expert_rows is None:
            return
        lives = list(lives)
        self._metrics.inc("expert_steps_total", len(lives))
        self._metrics.inc(
            "expert_live_steps_total",
            sum(self._experts_live(n) for n in lives),
        )

    def _admit(self) -> None:
        with self._cv:
            queue = list(self._queue)
        if not queue:
            return
        # The pool rekeys ONLY when empty: the head request defines
        # the (cache_len, sampling) every later admission must match.
        if self._n_active == 0:
            head = queue[0]
            key = (head.cache_len, head.sampling)
            if self._pool is None or self._pool_key != key:
                try:
                    self._build_pool(key)
                except Exception as e:  # noqa: BLE001 — serving loop
                    self._fail_req(head, e)
                    return
        if self._pool is None:
            return
        cache_cap = self._pool.cache_len
        pool_sampling = self._pool.sampling
        free = [i for i, j in enumerate(self._slots) if j is None]
        budget_closed = False
        blocked: Optional[_SlotReq] = None
        with self._tracer.span("serve_admit", queued=len(queue)) as sp:
            n_free0 = len(free)
            for req in queue:
                if req.error is not None:
                    continue
                if (
                    req.sampling != pool_sampling
                    or req.cache_len > cache_cap
                ):
                    if blocked is None:
                        blocked = req
                        if req.overtaken >= self.n_slots:
                            # Fairness valve: this head has been
                            # diverted past enough times — stop
                            # feeding the pool and let it drain so
                            # the head can rekey it.
                            break
                    continue
                if budget_closed:
                    continue  # FIFO within a pool key: no overtaking
                if not free:
                    budget_closed = True
                    continue
                if self._admit_req(req, free) and blocked is not None:
                    blocked.overtaken += 1
                if req.next_job < len(req.jobs) and req.error is None:
                    budget_closed = True
            with self._cv:
                # tpulint: disable=TPU020 — consumer-side sweep of
                # finished/failed requests: removal only makes the
                # scheduler's own "queue non-empty" predicate falser;
                # completion waiters watch req.done events, not this
                # list, so there is nobody to notify.
                self._queue = [
                    r
                    for r in self._queue
                    if r.error is None and r.next_job < len(r.jobs)
                ]
            # batched_with: how many distinct requests share the pool
            # now.
            reqs = {
                id(j.req): j.req for j in self._slots if j is not None
            }
            for req in reqs.values():
                req.batched_with = max(req.batched_with, len(reqs))
            # Known only now, so the JSON trace and the listeners see
            # it and the profiler annotation does not.
            sp.args["admitted"] = n_free0 - len(free)

    def _admit_req(self, req: _SlotReq, free: list[int]) -> bool:
        """Admit as many of ``req``'s remaining rows as fit; returns
        True if at least one row ran (prefilled), slot-consuming or
        not."""
        t_admit0 = time.time()
        admitted = False
        while free and req.next_job < len(req.jobs):
            job = req.jobs[req.next_job]
            if self.page and self.prefill_chunk_pages:
                # Chunked admission: the row takes a slot immediately
                # as a PREFILLING citizen and acquires pages chunk by
                # chunk inside the pool passes — no whole-prompt page
                # grant, no monolithic prefill blocking this loop. The
                # reservation guard keeps part-admitted rows deadlock-
                # free (their summed outstanding need always fits).
                if not self._can_admit_chunked(job):
                    break
                try:
                    self._admit_chunked(req, job, free[0])
                except Exception as e:  # noqa: BLE001 — isolate req
                    self._fail_req(req, e)
                    return admitted
                req.next_job += 1
                admitted = True
                free.pop(0)
                continue
            grant = None
            if self.page:
                # Page-budget admission: the row needs every page of
                # its prompt+budget up front (writes may land anywhere
                # in that window). None = arena full even after trie
                # eviction — stop admitting and let retires free pages
                # (FIFO holds: nothing overtakes within the pool key).
                grant = self._pool.acquire_pages(
                    job.prompt,
                    len(job.prompt) + job.max_new - 1
                    + self._spec_slack(self._pool.sampling),
                )
                if grant is None:
                    break
            try:
                # Legacy mode keeps the historical 3-arg call (tests
                # spy on _admit_job with that arity).
                used_slot = (
                    self._admit_job(req, job, free[0], grant)
                    if grant is not None
                    else self._admit_job(req, job, free[0])
                )
            except Exception as e:  # noqa: BLE001 — isolate request
                if grant is not None:
                    self._free_pages(self._pool.release_pages(grant[0]))
                self._fail_req(req, e)
                return admitted
            req.next_job += 1
            admitted = True
            if used_slot:
                free.pop(0)
        if admitted and not req.started:
            req.started = True
            queue_s = max(0.0, t_admit0 - req.t_submit)
            self._tracer.complete(
                "req_queue",
                queue_s,
                rid=req.rid,
                prompt=sum(len(j.prompt) for j in req.jobs),
            )
            if self._metrics is not None:
                self._metrics.registry.histogram(
                    "tpufw_serve_join_latency_seconds"
                ).observe(time.time() - req.t_submit)
                self._metrics.registry.histogram(
                    "tpufw_serve_queue_wait_seconds"
                ).observe(queue_s)
        if admitted and req.pend.stream_q is not None:
            # First tokens reach the stream at admission, not a chunk
            # later — and every flush stays <= chunk-size tokens/row.
            self._flush_stream(req)
        if req.rows_left == 0 and req.next_job == len(req.jobs):
            self._finish(req)
        return admitted

    def _cp_deficit(self) -> int:
        """Pages still owed to in-flight chunked prefills — the gap
        between what they will hold at finalize and what they hold
        now. Admission and draft grants reserve around this sum so
        two part-admitted rows can never deadlock on the arena."""
        return sum(
            j.cp.deficit
            for j in self._slots
            if j is not None and j.cp is not None
        )

    def _can_admit_chunked(self, job: _SlotJob) -> bool:
        """Deadlock-free reservation: admit a new chunked prefill only
        when free + trie-evictable pages cover every in-flight
        prefill's remaining need PLUS this row's whole need. Chunk
        grabs are all-or-nothing per chunk, so under this invariant
        every admitted prefill eventually reaches its full grant."""
        a = self._pool.allocator
        evictable = sum(1 for i in a.held if not a.refs.get(i, 0))
        n_total = self._pool.n_pages_for(
            len(job.prompt) + job.max_new - 1
            + self._spec_slack(self._pool.sampling)
        )
        return self._cp_deficit() + n_total <= a.n_free + evictable

    def _admit_chunked(
        self, req: _SlotReq, job: _SlotJob, slot: int
    ) -> None:
        """Open a chunked prefill and seat it in ``slot`` WITHOUT any
        device call: the slot's pool state stays born-done (its junk
        decode writes land in reserved page 0), so the occupied slot
        pins the pool key while ``_run_prefill_chunks`` advances the
        row one page-aligned chunk per pass."""
        jax = self._jax
        with self._cv:
            job_index = self._job_index
            self._job_index += 1
        rng = jax.random.fold_in(
            jax.random.key(self._seed_base), job_index
        )
        need = (
            len(job.prompt) + job.max_new - 1
            + self._spec_slack(self._pool.sampling)
        )
        cp = self._pool.start_chunked(
            job.prompt, need, rng, self.prefill_chunk_pages
        )
        try:
            if self.prefix_enabled and not self._prefix_declined():
                hit = cp.shared_n > 0
                if self._metrics is not None:
                    self._metrics.inc(
                        "prefix_hits_total" if hit
                        else "prefix_misses_total"
                    )
                    if hit:
                        # Trie hits ARE the resume path: a preempted
                        # prefill's checkpointed pages come back here.
                        self._metrics.registry.counter(
                            "tpufw_prefill_resumes_total"
                        ).inc()
                self._events.emit(
                    "serve_prefix",
                    hit=hit,
                    shared_pages=cp.shared_n,
                    prompt_tokens=len(job.prompt),
                )
        except BaseException:
            # The caller's isolate-req handler swallows this raise
            # (_fail_req): the cursor's page refs would leak silently
            # if the metrics/event plumbing failed here (TPU019).
            self._free_pages(self._pool.abandon_chunked(cp))
            raise
        job.cp = cp  # resource: transfers pages
        job.t_grant = time.perf_counter()
        job.pass0 = self._pass
        self._slots[slot] = job
        self._n_active += 1
        self._set_prefill_inflight()

    def _set_prefill_inflight(self) -> None:
        if self._metrics is None or not self.prefill_chunk_pages:
            return
        self._metrics.registry.gauge("tpufw_prefill_inflight").set(
            float(sum(
                1 for j in self._slots
                if j is not None and j.cp is not None
            ))
        )

    def _admit_job(
        self, req: _SlotReq, job: _SlotJob, slot: int, grant=None
    ) -> bool:
        """Prefill one row and (unless it finishes at its first
        token) insert it into ``slot``. Returns True iff the slot was
        consumed. ``grant`` is the paged mode's (page_ids, shared_n)
        from acquire_pages — this method owns releasing it on the
        early-finish path (the caller releases on exceptions)."""
        # resource: transfers pages
        jax = self._jax
        # Namespaced, replayable prefill stream: a fresh base key per
        # call, folded with the monotonic job index. The paged shared
        # path draws the SAME per-token streams (split_prefill_keys),
        # so a prefix hit never perturbs sampled outputs.
        with self._cv:
            # _job_index is also reset from the caller side
            # (reset_after_warmup), so the bump must hold the monitor.
            job_index = self._job_index
            self._job_index += 1
        rng = jax.random.fold_in(
            jax.random.key(self._seed_base), job_index
        )
        if grant is not None:
            page_ids, shared_n = grant
            if self.prefix_enabled and not self._prefix_declined():
                hit = shared_n > 0
                if self._metrics is not None:
                    self._metrics.inc(
                        "prefix_hits_total"
                        if hit
                        else "prefix_misses_total"
                    )
                self._events.emit(
                    "serve_prefix",
                    hit=hit,
                    shared_pages=shared_n,
                    prompt_tokens=len(job.prompt),
                )
        job.t_grant = time.perf_counter()
        job.pass0 = self._pass
        # A blocking prefill: dispatched and read inside prefill_row /
        # prefill_shared, so the ledger takes the device as fed from
        # here (its starved seconds stay a lower bound) and as drained
        # when the span closes.
        self._ledger.fed("chunk")
        with self._tracer.span(
            "serve_prefill", prompt=len(job.prompt), width=job.p_bucket
        ):
            if grant is not None and shared_n > 0:
                cache, _first, first_int, _done, seen = (
                    self._pool.prefill_shared(
                        job.prompt, page_ids[:shared_n], rng
                    )
                )
            else:
                cache, _first, first_int, _done, seen = (
                    # tpulint: disable=TPU003 — exclusive if/else arms:
                    # exactly ONE of prefill_shared/prefill_row consumes
                    # this job's rng.
                    self._slots_mod.prefill_row(
                        getattr(
                            self._pool, "row_model", self._pool.model
                        ),
                        self.params,
                        job.prompt,
                        rng,
                        sampling=self._pool.sampling,
                        eos_id=self._eos,
                        pad_to=job.p_bucket,
                        prefill_chunk_size=self.prefill_chunk,
                    )
                )
        self._first_token(job, chunks=1)
        job.tokens.append(first_int)
        job.unflushed.append(first_int)
        if self._metrics is not None:
            self._metrics.inc("tokens_generated_total")
        if job.max_new == 1 or (
            self._eos is not None and first_int == self._eos
        ):
            # Finished at its first token: the row never occupies a
            # slot (the prefilled cache is dropped).
            if grant is not None:
                self._free_pages(self._pool.release_pages(page_ids))
            if self._metrics is not None:
                self._metrics.inc("retired_rows_total")
            req.rows_left -= 1
            return False
        # A cold row comes left-padded to its bucket; a prefix hit's
        # suffix prefill pads nothing.
        job.kv0 = len(job.prompt) if grant is not None and shared_n else (
            max(job.p_bucket or 0, len(job.prompt))
        )
        if grant is not None:
            self._pool.insert_paged(
                slot,
                cache,
                first_int,
                len(job.prompt),
                job.max_new - 1,
                page_ids,
                shared_n,
                row_seen=seen,
            )
            if self.prefix_enabled:
                # Register AFTER insert: the pages now hold the full
                # prompt's K/V. The trie holds its adopted ids so they
                # outlive this row.
                self._pool.register_prefix(job.prompt, page_ids)
        else:
            self._pool.insert(
                slot,
                cache,
                first_int,
                len(job.prompt),
                job.max_new - 1,
                row_seen=seen,
            )
        if self._draft_pool is not None:
            self._admit_draft(job, slot, rng)
        if self._ema is not None:
            self._ema.occupy(slot)
        self._slots[slot] = job
        self._n_active += 1
        return True

    def _first_token(self, job: _SlotJob, chunks: int) -> None:
        """``job``'s first token was just sampled: close the request
        chain's prefill link, slot grant -> first token, on both
        admission paths."""
        job.t_first = job.t_last = time.perf_counter()
        job.decodes0 = self._ledger.decodes
        job.behind0 = self._ledger.behind
        prefill_s = job.t_first - job.t_grant
        self._tracer.complete(
            "req_prefill",
            prefill_s,
            rid=job.req.rid,
            prompt=len(job.prompt),
            chunks=chunks,
            passes=self._pass - job.pass0 + 1,
        )
        if self._metrics is not None:
            self._metrics.registry.histogram(
                "tpufw_serve_prefill_seconds"
            ).observe(prefill_s)

    def _delivered(self, job: _SlotJob, t: float, last: bool) -> None:
        """``job`` was handed a chunk's tokens, read from the device at
        ``t``. With its ``last`` token the request chain's third link is
        written: ``req_decode``, first token -> last token, under the
        ``rid`` of ``req_queue`` and ``req_prefill``, with the decode
        passes the row sat in, how many of them ran behind a prefill
        program or insert of the same pass, and the longest stretch
        between two of its deliveries (one pass, read to read). It
        feeds no counter: it answers "this stream stalled after its
        first token: behind what"."""
        job.longest_s = max(job.longest_s, t - job.t_last)
        job.t_last = t
        if last:
            self._tracer.complete(
                "req_decode",
                t - job.t_first,
                rid=job.req.rid,
                tokens=len(job.tokens),
                passes=self._ledger.decodes - job.decodes0,
                behind_prefill=self._ledger.behind - job.behind0,
                longest_pass_s=round(job.longest_s, 6),
            )

    def _admit_draft(self, job: _SlotJob, slot: int, rng) -> None:
        """Prefill ``job``'s prompt through the draft model into the
        draft pool's matching slot. Draft pages come from the SHARED
        allocator but are granted strictly AFTER the target's, and a
        failed draft grant degrades the slot (its proposals verify as
        junk, acceptance collapses, the EMA routes the pool to plain
        decode) instead of blocking admission — speculation never
        starves target-page admission."""
        d_grant = None
        if self.page:
            d_need = self._draft_pool.n_pages_for(
                len(job.prompt) + job.max_new - 1 + self.spec_k
            )
            if (
                self._cp_deficit()
                and self._draft_pool.allocator.n_free
                < self._cp_deficit() + d_need
            ):
                # Draft pages would eat into the reservation in-flight
                # chunked prefills count on — degrade this slot rather
                # than stall prefill progress.
                self._events.emit(
                    "serve_spec",
                    level="warn",
                    k=self.spec_k,
                    mode="draft_starved",
                    slot=slot,
                )
                return
            d_grant = self._draft_pool.acquire_pages(
                job.prompt,
                len(job.prompt) + job.max_new - 1 + self.spec_k,
            )
            if d_grant is None:
                self._events.emit(
                    "serve_spec",
                    level="warn",
                    k=self.spec_k,
                    mode="draft_starved",
                    slot=slot,
                )
                return
        try:
            d_cache, _f, d_first, _d, d_seen = self._slots_mod.prefill_row(
                getattr(
                    self._draft_pool, "row_model", self._draft_pool.model
                ),
                self._draft_params,
                job.prompt,
                # Disjoint from the job's sampling stream (the drawn
                # first token is discarded; drafting re-proposes from
                # the target's actual last token each pass).
                self._jax.random.fold_in(rng, 11),
                sampling=self._draft_pool.sampling,
                eos_id=None,
                pad_to=(
                    len(job.prompt) if self.page else job.p_bucket
                ),
                prefill_chunk_size=self.prefill_chunk,
            )
            if d_grant is not None:
                self._draft_pool.insert_paged(
                    slot,
                    d_cache,
                    d_first,
                    len(job.prompt),
                    job.max_new - 1 + self.spec_k,
                    d_grant[0],
                    0,
                    row_seen=d_seen,
                )
            else:
                self._draft_pool.insert(
                    slot,
                    d_cache,
                    d_first,
                    len(job.prompt),
                    job.max_new - 1 + self.spec_k,
                    row_seen=d_seen,
                )
        except Exception as e:  # noqa: BLE001 — degrade, don't fail
            if d_grant is not None:
                self._free_pages(
                    self._draft_pool.release_pages(d_grant[0])
                )
            self._events.emit(
                "serve_spec",
                level="warn",
                k=self.spec_k,
                mode="draft_starved",
                slot=slot,
                reason=str(e),
            )

    def _free_pages(self, freed: int) -> None:
        if freed and self._metrics is not None:
            self._metrics.inc("pages_freed_total", freed)

    def _retire_slot(self, slot: int, *, device: bool) -> None:
        """Vacate ``slot``. ``device=True`` also freezes the row's
        done/remaining masks (error paths); natural completions
        already froze themselves inside the decode step. Paged pools
        always take the device path — it zeroes the slot's page-table
        row before the pages go back on the free list."""
        job = self._slots[slot]
        if job is not None and job.cp is not None:
            # Preempted chunked prefill: drop its page refs. The trie
            # keeps every checkpointed full page, so a re-submission
            # resumes from the last committed page, never restarts.
            self._free_pages(self._pool.abandon_chunked(job.cp))
            job.cp = None
            self._set_prefill_inflight()
        if self.page:
            self._free_pages(self._pool.release_slot(slot))
        elif device:
            self._pool.retire(slot)
        if self._draft_pool is not None:
            # Draft KV pages retire through the same allocator/refcount
            # path as the target's (a slot that never got a draft grant
            # releases an empty list — no-op).
            if self.page:
                self._free_pages(self._draft_pool.release_slot(slot))
            elif device:
                self._draft_pool.retire(slot)
        if self._ema is not None:
            self._ema.vacate(slot)
        self._slots[slot] = None
        self._n_active -= 1

    def _use_spec(self, active) -> bool:
        """Acceptance-aware scheduling: spec while the active slots'
        mean accept-EMA clears the threshold (None = spec off or this
        pool is penalty-ineligible)."""
        if self._ema is None:
            return False
        return self._ema.use_spec([slot for slot, _ in active])

    def _run_spec_chunk(self, active) -> None:
        """One speculative pass over every occupied slot: draft
        spec_k tokens (n-gram self-draft or the draft pool), verify
        them in ONE target call, advance each slot by its own accept
        count. Mirrors _run_chunk's retire/flush/accounting with the
        chunk length replaced by the per-slot emit counts."""
        k = self.spec_k
        with self._cv:
            chunk_index = self._chunk_index
            self._chunk_index += 1
        key = self._jax.random.fold_in(
            self._jax.random.key(self._seed_base + 1), chunk_index
        )
        page_snap: dict[int, list[int]] = {}
        if self.page and self._page_export is not None:
            page_snap = {
                slot: list(self._pool.slot_pages[slot])
                for slot, _ in active
            }
        key_rung, row_rung = self._rungs(active, k, width=k + 1)
        chunk_t0 = time.perf_counter()
        with self._tracer.span(
            "serve_spec_chunk", k=k, rows=len(active),
            ahead=self._ledger.ahead, key_rung=key_rung, row_rung=row_rung,
        ):
            with self._tracer.span("serve_decode_dispatch"):
                if self._draft_pool is not None:
                    out, n_emit, accept = self._pool.spec_draft_steps(
                        self._draft_pool, key, k
                    )
                else:
                    props = self._np.zeros(
                        (self.n_slots, k), self._np.int32
                    )
                    for slot, job in active:
                        props[slot] = self._spec_mod.ngram_propose(
                            list(job.prompt) + job.tokens, k
                        )
                    # tpulint: disable=TPU003 — exclusive if/else arms:
                    # exactly ONE of spec_draft_steps/spec_steps
                    # consumes this chunk's key.
                    out, n_emit, accept = self._pool.spec_steps(
                        props, key
                    )
            with self._tracer.span("serve_device_wait", **{"for": "spec"}):
                for a in (out, n_emit, accept):
                    a.copy_to_host_async()  # as np.asarray did: see _run_chunk
                self._jax.block_until_ready((out, n_emit, accept))
            with self._tracer.span("serve_fetch"):
                out = self._np.asarray(out)
                n_emit = self._np.asarray(n_emit)
                accept = self._np.asarray(accept)
        t_read = time.perf_counter()
        with self._tracer.span("serve_emit", rows=len(active)):
            self._emit_spec(active, k, out, n_emit, accept,
                            t_read - chunk_t0, page_snap, t_read)

    def _emit_spec(
        self, active, k, out, n_emit, accept, chunk_s, page_snap, t_read
    ) -> None:
        """Host post-processing of one speculative pass: per-slot
        accept bookkeeping, retires, stream flushes, completions.
        ``t_read``: when the pass's results reached the host."""
        live_tokens = 0
        flush: list[_SlotReq] = []
        finished: list[_SlotReq] = []
        accept_frac = 0.0
        # One verify call of k + 1 tokens a row, every active row live.
        self._count_keys(
            [[_row_keys(job) + k for _, job in active]], width=k + 1
        )
        self._count_state(self.n_slots, len(active))
        for slot, job in active:
            req = job.req
            take = min(int(n_emit[slot]), job.max_new - len(job.tokens))
            row = out[slot, :take].tolist()
            # The program already masks past the first EOS; this trim
            # is the same belt-and-braces as the plain path.
            if self._eos is not None and self._eos in row:
                row = row[: row.index(self._eos) + 1]
            job.tokens.extend(row)
            job.unflushed.extend(row)
            live_tokens += len(row)
            self._ema.update(slot, int(accept[slot]) / k)
            accept_frac += int(accept[slot]) / k
            if req.pend.stream_q is not None and req not in flush:
                flush.append(req)
            last = len(job.tokens) >= job.max_new or (
                self._eos is not None and row and row[-1] == self._eos
            )
            self._delivered(job, t_read, last)
            if last:
                if self.page and self._page_export is not None:
                    self._page_export(
                        job,
                        self._pool.export_slot(
                            slot, page_ids=page_snap[slot]
                        ),
                    )
                self._retire_slot(slot, device=False)
                if self._metrics is not None:
                    self._metrics.inc("retired_rows_total")
                req.rows_left -= 1
                if req.rows_left == 0 and req.next_job == len(req.jobs):
                    finished.append(req)
        rate = accept_frac / max(len(active), 1)
        self._spec_accept_sum += accept_frac
        self._spec_accept_rows += len(active)
        if self._metrics is not None:
            self._metrics.inc("ticks_total")
            self._metrics.inc("tick_rows_total", len(active))
            self._metrics.inc("tokens_generated_total", live_tokens)
            # Device work this pass = S * (k+1) verify token-positions
            # (the capacity denominator goodput splits below); rejected
            # draft work is tracked separately as wasted draft FLOPs.
            self._metrics.inc(
                "wasted_slot_steps_total",
                self.n_slots * (k + 1) - live_tokens,
            )
            reg = self._metrics.registry
            # Cumulative mean, not last-pass: a scrape after traffic
            # drains must still report what the server accepted.
            reg.gauge("tpufw_spec_accept_rate").set(
                self._spec_accept_sum / max(self._spec_accept_rows, 1)
            )
            reg.gauge("tpufw_spec_fallback_slots").set(
                float(
                    self._ema.fallback_slots([s for s, _ in active])
                )
            )
            reg.counter("tpufw_spec_wasted_draft_flops_total").inc(
                sum(k - int(accept[s]) for s, _ in active)
                * 2.0
                * self._draft_n_params
            )
        self._events.emit(
            "serve_spec",
            k=k,
            mode="pass",
            rows=len(active),
            accept_rate=round(rate, 4),
        )
        live_frac = live_tokens / (self.n_slots * (k + 1))
        self._goodput.add("busy", chunk_s * live_frac)
        self._goodput.add("wasted_slot", chunk_s * (1.0 - live_frac))
        for req in flush:
            if req not in finished:
                self._flush_stream(req)
        for req in finished:
            self._finish(req)

    def _run_prefill_chunks(self) -> bool:
        """Advance every PREFILLING slot by one page-aligned chunk —
        the prefill citizens of the same scheduler pass the decoding
        slots share (no separate tick). A row whose final chunk lands
        here is finalized immediately, so it decodes in THIS pass's
        chunk ladder. Returns True iff any chunk ran."""
        if not self.prefill_chunk_pages:
            return False
        progressed = False
        for slot, job in [
            (i, j)
            for i, j in enumerate(self._slots)
            if j is not None and j.cp is not None
        ]:
            cp = job.cp
            # The extent is for the span's arguments only; whether the
            # prefill ended is the pool's to say (``status`` below).
            width, _, final = self._pool.chunk_extent(cp)
            live = cp.cursor + width
            t0 = time.perf_counter()
            # DISPATCH time: the chunk program is enqueued, not waited
            # for, and its device time is paid by whoever blocks next
            # (serve_device_wait: in the final chunk's own read of the
            # first token, else in this pass's decode chunk). A
            # request's first chunk also allocates its row
            # (serve_row_alloc, nested).
            with self._tracer.span(
                "serve_prefill_chunk",
                slot=slot,
                cursor=cp.cursor,
                prompt=len(job.prompt),
                width=width,
                final=final,
            ):
                status = self._pool.chunk_step(cp)
            if status == "stalled":
                # Arena momentarily full: the row keeps its slot and
                # retries next pass (retires/evictions free pages; the
                # admission reservation guarantees eventual progress).
                continue
            progressed = True
            with self._tracer.span("serve_emit", slot=slot):
                self._count_keys([[live]], chunk=True, width=width)
                self._count_state(1, 1)
                if self._metrics is not None:
                    self._metrics.registry.counter(
                        "tpufw_prefill_chunks_total"
                    ).inc()
                self._events.emit(
                    "serve_prefill_chunk",
                    prompt_tokens=len(job.prompt),
                    cursor=cp.cursor,
                    dispatch_s=round(time.perf_counter() - t0, 6),
                    final=status == "done",
                    slot=slot,
                )
                if status == "done":
                    self._first_token(job, chunks=cp.n_chunks)
                    self._finalize_chunked(slot, job)
        self._set_prefill_inflight()
        return progressed

    def _finalize_chunked(self, slot: int, job: _SlotJob) -> None:
        """A chunked prefill sampled its first token: either finish
        the row outright (max_new == 1 / EOS-first — checkpointed
        pages stay trie-held, the rest free; the slot never saw a
        device call) or install it as a decoding citizen of its
        slot."""
        cp = job.cp
        req = job.req
        job.cp = None
        first_int = cp.first_int
        job.tokens.append(first_int)
        job.unflushed.append(first_int)
        if self._metrics is not None:
            self._metrics.inc("tokens_generated_total")
        if job.max_new == 1 or (
            self._eos is not None and first_int == self._eos
        ):
            self._free_pages(self._pool.abandon_chunked(cp))
            self._slots[slot] = None
            self._n_active -= 1
            if self._metrics is not None:
                self._metrics.inc("retired_rows_total")
            req.rows_left -= 1
        else:
            job.kv0 = len(job.prompt)
            self._pool.finalize_chunked(slot, cp, job.max_new - 1)
            self._count_state(1, 1)  # the insert: the row's, whole
            if self._draft_pool is not None:
                self._admit_draft(job, slot, cp.rng)
            if self._ema is not None:
                self._ema.occupy(slot)
        if req.pend.stream_q is not None:
            self._flush_stream(req)
        if req.rows_left == 0 and req.next_job == len(req.jobs):
            self._finish(req)

    def _run_chunk(self) -> None:
        """The pass's device work: one prefill chunk for every
        prefilling slot, then ONE decode chunk, waited for, read and
        emitted. A pass runs in one of two orders.

        The plain order: prefill chunks, then the chunk's step keys and
        program are enqueued (``serve_decode_dispatch``), the thread
        blocks until its tokens are ready (``serve_device_wait``), copies
        them (``serve_fetch``) and emits them (``serve_emit``: retires
        free slots and pages), and the next pass admits, prefills and
        enqueues the next chunk, the device idle from the wait's return
        to that enqueue.

        The CHAINED order, taken when the wait returns on a boundary
        where nothing could come between this chunk and the next: no
        request queued, no slot prefilling, no speculation for this
        pool, and a row with budget left. Then the SUCCESSOR is enqueued
        first, for the same rows, with the step keys that were made
        while this chunk ran (``_plan_successor``), and only then this
        chunk is fetched and emitted, beside the device. The next pass
        finds its chunk in flight (``_inflight``): it admits nothing
        (``_loop``: whoever arrived since waits for this chunk's read,
        as behind the same chunk enqueued a pass later), enqueues none
        of its own, and blocks. At most one chunk is ever in flight
        unread. The successor is the chunk the plain order would have
        enqueued: same rows, same chunk index and so the same keys, the
        length the budgets give once this chunk's tokens are counted.
        A row that ends in this chunk rides the successor frozen, as it
        rides the rest of a chunk it ends in the middle of (the step's
        ``done`` mask: segment id 0, writes past its cursor in its own
        last page or the junk page): the emit drops what the successor
        emitted for it (``_emit_chunk``), its ``release_slot`` is
        enqueued behind the successor and ahead of any insert or
        prefill chunk that could be granted its pages, and its export
        reads the pages of the snapshot taken at its own chunk's launch.
        (Where an EOS ends a row, the successor's length is the one the
        budgets gave, and a chunk whose rows all ended runs for
        nobody.)"""
        chunk, self._inflight = self._inflight, None
        if chunk is None:
            progressed = self._run_prefill_chunks()
            active = [
                (i, j)
                for i, j in enumerate(self._slots)
                if j is not None and j.cp is None
            ]
            if not active:
                if self._n_active and not progressed:
                    # Every occupied slot is a prefill stalled on pages
                    # and nothing is decoding: yield briefly so the loop
                    # doesn't spin hot waiting for a release/eviction.
                    time.sleep(0.001)
                return
            if self._use_spec(active):
                self._run_spec_chunk(active)
                return
            chunk = self._decode_chunk(active)
        # An asynchronous pass, recorded as it is: the time to enqueue
        # the step keys and the decode program, then the time blocked
        # until the device has run everything queued before the read —
        # prefill chunks dispatched earlier in this pass included, so
        # the wait is NOT the decode program's own device time.
        with self._tracer.span(
            "serve_decode_chunk", k=chunk.k, rows=len(chunk.active),
            ahead=chunk.ahead, key_rung=chunk.key_rung,
            row_rung=chunk.row_rung, live=chunk.live,
            chained=int(chunk.chained),
        ):
            if chunk.out is None:
                with self._tracer.span("serve_decode_dispatch"):
                    self._dispatch_chunk(chunk)
            # While the device runs the chunk: its successor planned and
            # the successor's step keys made (three eager calls, queued
            # behind the running program).
            successor = self._plan_successor(chunk)
            # The wait ends when the tokens are READY (the device ran
            # everything queued before them); their copy to the host is
            # the fetch. One read, as before, nothing merged or moved:
            # the transfer is asked for where ``np.asarray`` on the
            # pending array asked for it, queued behind the program, and
            # not a host round trip later, once the wait has returned
            # (that cost 0.23-0.30 ms a chunk on the chip: PERF.md §6).
            with self._tracer.span(
                "serve_device_wait", **{"for": "decode"}
            ):
                chunk.out.copy_to_host_async()
                self._jax.block_until_ready(chunk.out)
            if successor is not None and self._boundary_is_quiet():
                # The device is idle from the wait's return to this
                # call's: the whole of a chained boundary.
                with self._tracer.span("serve_decode_dispatch"):
                    self._dispatch_chunk(successor)
                self._inflight = successor
                if self._metrics is not None:
                    self._metrics.inc("chunks_chained_total")
            with self._tracer.span("serve_fetch"):
                out = self._np.asarray(chunk.out)
        t_read = time.perf_counter()
        with self._tracer.span("serve_emit", rows=len(chunk.active)):
            self._emit_chunk(
                chunk.active, chunk.k, out, t_read - chunk.t0,
                chunk.page_snap, t_read,
            )

    def _decode_chunk(self, active, ran: int = 0) -> _DecodeChunk:
        """The chunk to launch for ``active``, ``ran`` steps of theirs
        being enqueued and not yet emitted (a successor's: the length of
        the chunk it follows)."""
        # Pow-2 ladder on the chunk length: the scan length is a
        # compiled-shape dimension, so the tail of a nearly-done pool
        # shrinks k in big steps (at most log2(chunk) programs), never
        # per-value.
        max_left = max(j.max_new - len(j.tokens) for _, j in active) - ran
        # Chunk-boundary page-table snapshot for the export hook: a
        # row that finishes mid-chunk keeps absorbing the junk-sink
        # (page 0) writes for the chunk's remaining steps, and once it
        # retires its freed pages can be re-granted to a queued
        # admission within this same scheduler pass. Exports therefore
        # read THIS snapshot — the ids the row actually owned when the
        # chunk launched — never the post-retire allocator state.
        page_snap: dict[int, list[int]] = {}
        if self.page and self._page_export is not None:
            page_snap = {
                slot: list(self._pool.slot_pages[slot])
                for slot, _ in active
            }
        return _DecodeChunk(
            active, min(self.chunk, _pow2_ceil(max_left)), page_snap,
            0 if ran else self._ledger.ahead, self._rungs(active, ran),
            self._experts_live(len(active)), chained=ran > 0,
        )

    def _step_keys(self, chunk_index: int, k: int):
        """The ``k`` step keys of decode chunk ``chunk_index``: three
        eager calls, a function of the seed, the index and the length."""
        return self._jax.random.split(
            self._jax.random.fold_in(
                self._jax.random.key(self._seed_base + 1), chunk_index
            ),
            k,
        )

    def _dispatch_chunk(self, chunk: _DecodeChunk) -> None:
        """Enqueue ``chunk``: the next chunk index, its step keys (those
        made ahead, where they are this index's and this length's), the
        pool's decode program."""
        with self._cv:
            # Reset from the caller side in reset_after_warmup; bump
            # under the monitor so neither side loses an update.
            at = (self._chunk_index, chunk.k)
            self._chunk_index += 1
            ahead, self._keys_ahead = self._keys_ahead, None
        keys = (
            ahead[1] if ahead is not None and ahead[0] == at
            else self._step_keys(*at)
        )
        chunk.t0 = time.perf_counter()
        chunk.out = self._pool.decode_steps(keys)

    def _plan_successor(self, chunk: _DecodeChunk) -> Optional[_DecodeChunk]:
        """While ``chunk`` runs: the chunk that follows it if no row
        joins (None: no row has budget left after it), and that chunk's
        step keys, made ahead under the next chunk index. The rows are
        those that stay, the length what the parent's rule gives from
        the budgets as they will stand (``tokens`` still holds what was
        emitted before ``chunk``). Whichever order enqueues the next
        chunk takes the keys if it has that index and that length, and
        makes its own otherwise; no index is consumed here. A pool that
        speculates plans nothing: its passes choose their kind anew."""
        if self._ema is not None:
            return None
        staying = [
            (slot, job) for slot, job in chunk.active
            if self._slots[slot] is job
            and job.max_new - len(job.tokens) > chunk.k
        ]
        if not staying:
            return None
        successor = self._decode_chunk(staying, ran=chunk.k)
        with self._cv:
            at = (self._chunk_index, successor.k)
        keys = self._step_keys(*at)
        with self._cv:
            # Named by index and length, so keys that a reset overtook
            # are never taken for another chunk's.
            self._keys_ahead = (at, keys)
        return successor

    def _boundary_is_quiet(self) -> bool:
        """At a decode chunk's boundary, by what the thread can see and
        nothing else: may the successor go ahead of the read? Not with a
        request queued or a slot prefilling: an admission or a prefill
        chunk belongs between the two chunks (the plain order)."""
        with self._cv:
            if self._queue:
                return False
        return not any(
            j is not None and j.cp is not None for j in self._slots
        )

    def _rungs(self, active, extra: int = 0, width: int = 1):
        """(key rung, row rung) the pool's next cached call reads, for
        the decode span's arguments: the pair ``kv_store.attended_pair``
        names (``_count_keys`` books the same, step by step, once the
        chunk's tokens are known) at the chunk's first step, every
        active row live and the longest holding its next token (and
        ``extra`` more: a verify block of ``width``, or the steps of the
        chunk still in flight). Where the step reads the arena
        in place there is no rung: the longest row's own pages, and the
        rows that are live."""
        from tpufw.ops.kv_store import attended_pair, in_place

        cfg = self._pool.model.cfg
        longest = max(_row_keys(job) for _, job in active) + extra
        if in_place(cfg, self._pool.page_leaves, width):
            return -(-longest // self.page) * self.page, len(active)
        rows, keys = attended_pair(cfg, self.n_slots, len(active), longest)
        return keys, rows

    def _emit_chunk(
        self, active, k, out, chunk_s, page_snap, t_read
    ) -> None:
        """Host post-processing of one decode chunk: token
        bookkeeping, retires, stream flushes, completions. ``t_read``:
        when the chunk's tokens reached the host. A row that retired
        since the chunk was launched (it ended in the chunk before, and
        rode this one frozen: ``_run_chunk``'s chained order) is given
        nothing of it."""
        active = [(s, j) for s, j in active if self._slots[s] is j]
        if self._metrics is not None:
            self._metrics.inc("ticks_total")
            self._metrics.inc("tick_rows_total", len(active))
        live_tokens = 0
        flush: list[_SlotReq] = []
        finished: list[_SlotReq] = []
        spans = []  # per row: slots held at step 0, steps it was live
        for slot, job in active:
            req = job.req
            take = min(k, job.max_new - len(job.tokens))
            row = out[slot, :take].tolist()
            if self._eos is not None and self._eos in row:
                row = row[: row.index(self._eos) + 1]
            spans.append((_row_keys(job), len(row)))
            job.tokens.extend(row)
            job.unflushed.extend(row)
            live_tokens += len(row)
            if req.pend.stream_q is not None and req not in flush:
                flush.append(req)
            last = len(job.tokens) >= job.max_new or (
                self._eos is not None and row and row[-1] == self._eos
            )
            self._delivered(job, t_read, last)
            if last:
                # Retire: host-side in contiguous mode — the device
                # row froze itself via the done/remaining masks. Paged
                # mode also clears the page table and frees the pages.
                if self.page and self._page_export is not None:
                    self._page_export(
                        job,
                        self._pool.export_slot(
                            slot, page_ids=page_snap[slot]
                        ),
                    )
                self._retire_slot(slot, device=False)
                if self._metrics is not None:
                    self._metrics.inc("retired_rows_total")
                req.rows_left -= 1
                if req.rows_left == 0 and req.next_job == len(req.jobs):
                    finished.append(req)
        # A row is live at step i while it still delivers a token there:
        # the program's own ``done`` mask, read back from what it emitted.
        steps = ([at + i for at, n in spans if i < n] for i in range(k))
        self._count_keys(steps)
        self._count_state(self.n_slots * k, sum(n for _, n in spans))
        self._count_experts(
            sum(i < n for _, n in spans) for i in range(k)
        )
        if self._metrics is not None:
            self._metrics.inc("tokens_generated_total", live_tokens)
            # Capacity accounting: S * k device-steps ran; everything
            # not delivering a live token (empty slots, done rows
            # inside the chunk) is the batching overhead to tune
            # TPUFW_SERVE_SLOTS / _CHUNK against.
            self._metrics.inc(
                "wasted_slot_steps_total",
                self.n_slots * k - live_tokens,
            )
        # Goodput: the chunk's wall-clock split by the same capacity
        # accounting — the live-token fraction was busy, the rest was
        # wasted slot-steps (time the gap between them and true idle
        # is what TPUFW_SERVE_SLOTS / _CHUNK tuning reclaims).
        live_frac = live_tokens / (self.n_slots * k)
        self._goodput.add("busy", chunk_s * live_frac)
        self._goodput.add("wasted_slot", chunk_s * (1.0 - live_frac))
        for req in flush:
            if req not in finished:
                self._flush_stream(req)
        for req in finished:
            self._finish(req)

    # ---- completion / failure ----

    def _flush_stream(self, req: _SlotReq) -> None:
        rows = [list(j.unflushed) for j in req.jobs]
        if not any(rows):
            return
        for j in req.jobs:
            j.unflushed = []
        req.pend.stream_q.put(("chunk", rows))

    def _finish(self, req: _SlotReq) -> None:
        with self._cv:
            if req in self._queue:
                self._queue.remove(req)
        pend = req.pend
        outs = [list(j.tokens[: j.max_new]) for j in req.jobs]
        n_tokens = sum(len(o) for o in outs)
        self._events.emit(
            "serve_request",
            rows=len(req.jobs),
            new_tokens=n_tokens,
            latency_s=round(time.time() - req.t_submit, 6),
            rid=req.rid,
        )
        if pend.stream_q is not None:
            self._flush_stream(req)
            pend.stream_q.put(("done", n_tokens))
        else:
            pend.outputs = outs
        pend.batched_with = req.batched_with
        pend.done.set()

    def _fail_req(self, req: _SlotReq, e: Exception) -> None:
        """Fail ONE request (admission-time errors): its active slots
        retire, everything else keeps running."""
        req.error = e
        with self._cv:
            if req in self._queue:
                self._queue.remove(req)
        for i, job in enumerate(self._slots):
            if job is not None and job.req is req:
                self._retire_slot(i, device=True)
        pend = req.pend
        pend.error = e
        if pend.stream_q is not None:
            pend.stream_q.put(("error", e))
        pend.done.set()

    def _fail_active(self, e: Exception) -> None:
        """A decode chunk failed: every ACTIVE request shares that
        fate (their pool state is gone — the jit donated it), but
        queued requests survive and the pool rebuilds on the next
        admission."""
        reqs = {
            id(j.req): j.req for j in self._slots if j is not None
        }
        self._slots = [None] * self.n_slots
        self._n_active = 0
        self._inflight = None  # its rows fail with the rest
        self._pool = None  # donated buffers are suspect after a failure
        self._pool_key = None
        self._draft_pool = None  # rides the pool's allocator — same fate
        self._ema = None
        for req in reqs.values():
            req.error = e
            with self._cv:
                if req in self._queue:
                    self._queue.remove(req)
            pend = req.pend
            pend.error = e
            if pend.stream_q is not None:
                pend.stream_q.put(("error", e))
            pend.done.set()


class _Server:
    """Minimal HTTP serving loop over the jitted generator."""

    def __init__(self, port: int, max_new_tokens: int):
        from tpufw.infer import generate_text

        self._generate_text = generate_text
        self._sampling = sampling_from_env()
        (
            self.model,
            self.params,
            self.cfg,
            self.restored,
        ) = build_generator()
        # Serving-precision cast (TPUFW_DECODE_DTYPE=bfloat16): decode
        # is HBM-bound and fp32 master weights double the bytes per
        # token. Off by default — bf16 weights perturb logits, and the
        # parity tests pin exact fp32 serving. The draft's weight
        # streaming (k autoregressive steps per tick) matters as much
        # as the target's, so it casts too.
        self.params = _maybe_cast_decode(self.params)
        self.default_new = max_new_tokens
        self._eos_id = eos_from_env()
        self.metrics = _Metrics()
        self._draft = build_draft_generator(self._sampling)
        if self._draft is not None:
            dm, dp, k = self._draft
            self._draft = (dm, _maybe_cast_decode(dp), k)
            self.metrics.register(
                "spec_iterations_total", "spec_emitted_total"
            )
        self.port = port
        self._codec = None
        # Distinct per-request sampling configs admitted so far:
        # sampling is a compiled-program parameter, so an unbounded
        # variety would compile (and cache) unboundedly many programs.
        self._sampling_seen: set = set()
        self._sampling_cap = env_int("max_sampling_configs", 32)
        self._sampling_lock = threading.Lock()
        # Sampled requests must be able to differ across ticks (best-of
        # -n would otherwise return n identical copies): each tick's rng
        # seed is TPUFW_SEED + a monotonic tick index. Within a tick the
        # seed is shared — coalesced rows stay mutually deterministic —
        # and the whole server replays exactly given the same request
        # arrival order and TPUFW_SEED. Only the batcher thread runs
        # _run_tick, so the counter needs no lock. Greedy decode ignores
        # the rng entirely, so default traffic is unaffected. (The slot
        # scheduler keeps the same replay contract with its own pair of
        # namespaced monotonic streams.)
        self._seed_base = env_int("seed", 0)
        self._tick_index = 0
        # Optional serving telemetry (TPUFW_TELEMETRY_DIR): the full
        # Telemetry handle mounted on the server's own registry (so
        # /metrics and the telemetry snapshot render one truth) —
        # event log, capped scheduler span trace (a server runs
        # indefinitely; the interesting spans are at the head), plus
        # the run-health layer: goodput ledger (busy vs. wasted-slot
        # vs. idle), crash flight recorder (role="serve" terminates
        # on SIGTERM after flushing — no GracefulShutdown above us),
        # and the TPUFW_HANG_TIMEOUT_S watchdog around each chunk.
        from tpufw.obs import Telemetry

        self._tel = Telemetry.disabled()
        tdir = env_str("telemetry_dir", "")
        if tdir:
            import atexit

            import jax

            self._tel = Telemetry.create(
                telemetry_dir=tdir,
                role="serve",
                registry=self.metrics.registry,
                trace_name="trace-serve.json",
                trace_max_events=100_000,
            )
            self._tel.set_run_info(
                backend=jax.default_backend(),
                model=type(self.model).__name__,
                mesh="serve",
            )
            self._tel.record_config(
                {
                    "serve": {
                        "port": port,
                        "max_new_tokens": max_new_tokens,
                        "slots": env_int("serve_slots", 8),
                        "chunk": env_int("serve_chunk", 0)
                        or env_int("stream_chunk", 16),
                        "page": env_int("serve_page", 0),
                        "kv_quant": env_str("serve_kv_quant", ""),
                    }
                }
            )
            atexit.register(self._tel.close)
        self._events = self._tel.events
        self._tracer: object = self._tel.tracer
        # Scheduler backend: the slot scheduler (decode-step-granular
        # continuous batching) is the default; TPUFW_SERVE_SLOTS=0 opts
        # back into the tick batcher. Speculation COMPOSES with slots
        # now — a TPUFW_DRAFT_MODEL build seeds the scheduler's chunked
        # verify path (unless the TPUFW_SERVE_SPEC_* knobs claim it),
        # instead of silently rerouting all traffic through the tick
        # path as it used to.
        if env_int("serve_slots", 8) > 0:
            spec_kw = {}
            if (
                self._draft is not None
                and env_int("serve_spec_k", 0) == 0
                and not env_str("serve_spec_draft", "")
            ):
                dm, dp, dk = self._draft
                spec_kw = dict(
                    spec_k=dk, spec_draft_built=(dm.cfg, dp)
                )
            self._batcher = _SlotScheduler(
                self.model,
                self.params,
                eos_id=self._eos_id,
                default_sampling=self._sampling,
                metrics=self.metrics,
                seed_base=self._seed_base,
                events=self._events,
                tracer=self._tracer,
                goodput=self._tel.goodput,
                watchdog=self._tel.watchdog,
                perf=self._tel.perf,
                **spec_kw,
            )
        else:
            if self._draft is not None:
                # Legacy whole-batch speculative ticking: only reachable
                # by explicit TPUFW_SERVE_SLOTS=0 opt-out now. Schema'd
                # warn so operators notice the downgrade.
                self._events.emit(
                    "serve_spec",
                    level="warn",
                    k=self._draft[2],
                    mode="tick_fallback",
                    reason="TPUFW_SERVE_SLOTS=0 legacy tick batcher",
                )
            self._batcher = _Batcher(
                self._run_tick, self.metrics, run_stream=self._run_stream
            )
        if env_int("warmup", 1):
            self._warmup()

    def _warmup(self) -> None:
        """Compile serving shape buckets BEFORE the listener binds.
        Decode is unrolled by default, which costs a fresh compile per
        (batch bucket, prompt bucket, max_new bucket) program — ~38 s
        cold on the v5e chip (vs ~4 s scanned) — and without warmup
        that stall lands on the FIRST LIVE REQUEST of each bucket,
        well past typical client timeouts. Each warmup tick runs
        through _run_tick, compiling prefill + decode (+ the draft,
        when speculation is on) at the shortest prompt bucket and the
        default max_new.

        TPUFW_WARMUP_BUCKETS (comma-separated row counts, default
        "1") selects which BATCH buckets to pre-compile — e.g.
        "1,4,16" for a server expecting coalesced concurrent traffic
        (measured on the v5e chip: each un-warmed batch bucket costs
        ~6-35 s on its first live tick; docs/evidence/
        SERVE_TPU_r5.jsonl). Counts are pow2-bucketed like live
        traffic, deduplicated, compiled smallest first. The tick
        counter and speculative counters are restored afterwards so
        warmup is invisible to seed replay and metrics — safe because
        the listener is not up yet, so nothing can scrape or enqueue
        during the window. Disable entirely with TPUFW_WARMUP=0."""
        run_new = _pow2_ceil(self.default_new)
        if isinstance(self._batcher, _SlotScheduler):
            # Slot mode: the pool batch is ALWAYS n_slots, so there is
            # no batch-bucket ladder to walk — one tiny request
            # compiles the whole serving path (prefill + insert +
            # decode chunks, including the shrinking tail-k programs)
            # and leaves the default pool warm. The counters it moved
            # and the rng-stream indices are restored so warmup stays
            # invisible to scrapes and to seed replay.
            # A warm-up that fails is the server's first real failure
            # (on the chip, usually a program that does not compile):
            # it propagates and the listener never binds.
            try:
                self._batcher.submit([[1]], self.default_new, None)
            finally:
                self._batcher.reset_after_warmup()
                self.metrics.reset(
                    "ticks_total",
                    "tick_rows_total",
                    "tokens_generated_total",
                    "retired_rows_total",
                    "wasted_slot_steps_total",
                    "pool_switches_total",
                )
                if self._batcher.page:
                    # Paged-only names: resetting in contiguous mode
                    # would CREATE them (reset = zero the counter),
                    # leaking paged series into legacy /metrics.
                    self.metrics.reset(
                        "prefix_hits_total",
                        "prefix_misses_total",
                        "pages_freed_total",
                        "attended_key_slots_total",
                        "row_key_slots_total",
                        "window_key_slots_total",
                        "window_row_key_slots_total",
                    )
                    from tpufw.ops.kv_store import DECLINES

                    for decline in DECLINES.values():
                        self.metrics.registry.counter(
                            "tpufw_serve_prefix_declined_total"
                        ).reset(reason=decline.reason)
                if self._batcher.spec_k:
                    # Gated like the registration: the warmup request's
                    # speculative passes must stay invisible to scrapes.
                    reg = self.metrics.registry
                    reg.counter(
                        "tpufw_spec_wasted_draft_flops_total"
                    ).reset()
                    self._batcher._spec_accept_sum = 0.0
                    self._batcher._spec_accept_rows = 0
                    reg.gauge("tpufw_spec_accept_rate").set(0.0)
                    reg.gauge("tpufw_spec_fallback_slots").set(0.0)
                for chain in (
                    "tpufw_serve_join_latency_seconds",
                    "tpufw_serve_queue_wait_seconds",
                    "tpufw_serve_prefill_seconds",
                ):
                    self.metrics.registry.histogram(chain).reset()
                phase_s = self.metrics.registry.counter(
                    "tpufw_serve_phase_seconds_total"
                )
                for phase in SCHED_PHASES:
                    phase_s.reset(phase=phase)
            return
        tick0 = self._tick_index
        try:
            # Buckets clamp to the batcher's row cap — a bigger program
            # would compile but never be hit by live coalescing.
            max_rows = env_int("batch_max_rows", 64)
            buckets = sorted({
                min(_pow2_ceil(int(b)), _pow2_ceil(max_rows))
                for b in env_str("warmup_buckets", "1").split(",")
                if b.strip()
            })
            for rows in buckets:
                self._run_tick([[1]] * rows, run_new, None)
        finally:
            self._tick_index = tick0
            if self._draft is not None:
                self.metrics.reset(
                    "spec_iterations_total", "spec_emitted_total"
                )

    def admit_sampling(self, sampling) -> bool:
        """True if this non-default config is within the server's
        distinct-config budget (TPUFW_MAX_SAMPLING_CONFIGS, default
        32); known configs are always admitted."""
        with self._sampling_lock:
            if sampling in self._sampling_seen:
                return True
            if len(self._sampling_seen) >= self._sampling_cap:
                return False
            self._sampling_seen.add(sampling)
            return True

    def _model_for(self, longest: int, max_new: int):
        """KV cache sized to the request, not the model max: the
        smallest pow-2 cache variant covering this tick (plus the
        speculative path's k+1 bonus slack), capped at the model max.
        Attention/update traffic per step scales with cache length —
        a 256-token chat on an 8k-cache model would otherwise pay 32x
        the KV bytes; masking makes the result bit-identical
        (never-written slots carry segment 0, tests/test_infer.py).
        Variants are built inline: flax modules hash structurally, so
        equal configs hit the generate jit cache without memoization."""
        import dataclasses

        slack = (self._draft[2] + 1) if self._draft else 0
        n = _cache_bucket(
            longest + max_new + slack, self.model.cfg.max_seq_len
        )
        if n == self.model.cfg.max_seq_len:
            return self.model
        return type(self.model)(
            dataclasses.replace(self.model.cfg, max_seq_len=n)
        )

    def codec(self):
        if self._codec is None:
            self._codec = text_codec()
        return self._codec

    def _gauge_values(self) -> dict:
        """Point-in-time gauges for /metrics — one source of truth in
        the scheduler, refreshed at scrape time. Slot mode adds the
        occupancy pair (occupied/total IS the continuous-batching
        utilization a dashboard divides)."""
        g = {
            "queue_depth": float(self._batcher.queue_depth),
            "uptime_seconds": time.time() - _T0,
        }
        # Refresh goodput at scrape time too (the ledger otherwise
        # publishes only at close, and a server rarely closes).
        self._tel.goodput.publish()
        if isinstance(self._batcher, _SlotScheduler):
            g["slots_occupied"] = float(self._batcher.slots_occupied)
            g["slots_total"] = float(self._batcher.slots_total)
            if self._batcher.page:
                g["pages_in_use"] = float(self._batcher.pages_in_use)
                g["pages_total"] = float(self._batcher.pages_total)
            spill = getattr(self._batcher, "_spill", None)
            if spill is not None:
                # Unprefixed KV-fabric series refresh here too (same
                # scrape-time single-source-of-truth contract as the
                # gauges dict; the tier owns the numbers).
                st = spill.stats()
                reg = self.metrics.registry
                reg.gauge("tpufw_kv_spill_pages").set(
                    float(st["ram_pages"]), tier="ram"
                )
                reg.gauge("tpufw_kv_spill_pages").set(
                    float(st["dir_pages"]), tier="dir"
                )
                delta = (
                    st["spilled_bytes_total"]
                    - self._batcher._spill_seen_bytes
                )
                if delta > 0:
                    reg.counter("tpufw_kv_spill_bytes_total").inc(delta)
                    self._batcher._spill_seen_bytes = st[
                        "spilled_bytes_total"
                    ]
        return g

    def _run_tick(
        self, prompts: list[list[int]], max_new: int, sampling=None
    ):
        """One device call for one coalesced tick — only the batcher
        thread runs this, so device work is serialized by construction
        (the old per-request lock is gone). ``sampling`` is a
        per-request override (None = the env default); the batcher
        guarantees every request in the tick shares it.

        Bucket prompt length and batch size so the jitted generate
        specializes on few shapes. The length bucket rides
        pad_prompts' OWN left padding (a max-length filler row forces
        it), so bucketing zeros are real padding — pad_lens masks
        them, and the repetition penalty's seen-set never counts them
        (literal [0]*k prefixes would look like real tokens).
        """
        sampling, seed, padded, real_n, live, model = self._tick_prep(
            prompts, max_new, sampling
        )
        if self._draft is not None:
            import dataclasses

            from tpufw.infer import speculative_generate_text

            draft_model, draft_params, k = self._draft
            if model.cfg.max_seq_len != self.model.cfg.max_seq_len:
                draft_model = type(draft_model)(
                    dataclasses.replace(
                        draft_model.cfg,
                        max_seq_len=model.cfg.max_seq_len,
                    )
                )
            outs, stats = speculative_generate_text(
                draft_model,
                draft_params,
                model,
                self.params,
                padded,
                max_new_tokens=max_new,
                k=k,
                eos_id=self._eos_id,
                # Filler rows (pow-2 + length bucket) must not drag the
                # batch-min acceptance to zero; their outputs are
                # sliced off below anyway.
                live_rows=live,
                sampling=sampling,
                seed=seed,
                prefill_chunk_size=env_int("prefill_chunk", 0) or None,
            )
            # Draft-quality observability: emitted/iterations is the
            # mean accepted tokens per verify pass (k+1 max) — THE
            # number that says whether the draft is paying for itself.
            # rate(spec_emitted)/rate(spec_iterations) gives the live
            # acceptance from the same two counters.
            self.metrics.inc(
                "spec_iterations_total", stats["iterations"]
            )
            self.metrics.inc("spec_emitted_total", stats["emitted"])
            return outs[:real_n]
        outs = self._generate_text(
            model,
            self.params,
            padded,
            max_new_tokens=max_new,
            sampling=sampling,
            seed=seed,
            eos_id=self._eos_id,
            live_rows=live,
            prefill_chunk_size=env_int("prefill_chunk", 0) or None,
        )
        return outs[:real_n]

    def _tick_prep(self, prompts, max_new, sampling):
        """ONE copy of the per-tick preamble shared by the coalesced
        and streaming paths: env-default sampling resolution, the
        monotonic tick seed (batcher thread only — no lock), prompt
        length bucketing with the filler row, and the request-sized
        cache variant. Returns (sampling, seed, padded, real_n, live,
        model) — ``live`` masks the pow-2 fillers AND the length-bucket
        row so generate's done-mask freezes them at step 1."""
        if sampling is None:
            sampling = self._sampling
        seed = self._seed_base + self._tick_index
        self._tick_index += 1
        longest = _bucket(max(len(p) for p in prompts), 64)
        fill = self._eos_id if self._eos_id is not None else 0
        padded, real_n = _pad_batch(prompts, fill)
        padded = padded + [[fill] * longest]  # length-bucket filler row
        live = [i < real_n for i in range(len(padded))]
        model = self._model_for(longest, max_new)
        return sampling, seed, padded, real_n, live, model

    def _run_stream(self, pend) -> None:
        """Streaming tick (batcher thread only): the ``_tick_prep``
        preamble, then ``generate_text_stream``'s chunk loop — each
        chunk's per-row new tokens go onto the pending's queue the
        moment they exist. ``max_new`` runs at the same pow-2 bucket
        the coalesced path compiles (arbitrary per-request values
        would each compile fresh prefill/tail programs); emission is
        truncated to the REQUESTED length on the way out. One compiled
        chunk program serves every full chunk (and every later stream
        with the same shapes), so time-to-first-token is prefill + one
        chunk instead of the whole completion."""
        from tpufw.infer import generate_text_stream

        run_new = 1
        while run_new < pend.max_new:
            run_new *= 2
        sampling, seed, padded, real_n, live, model = self._tick_prep(
            pend.prompts, run_new, pend.sampling
        )
        emitted = 0  # live rows advance in lockstep; eos rows yield []
        n_tokens = 0  # total across rows (the metric the batch path counts)
        for chunk in generate_text_stream(
            model,
            self.params,
            padded,
            max_new_tokens=run_new,
            chunk_size=env_int("stream_chunk", 16),
            sampling=sampling,
            seed=seed,
            eos_id=self._eos_id,
            live_rows=live,
            prefill_chunk_size=env_int("prefill_chunk", 0) or None,
        ):
            budget = pend.max_new - emitted
            rows = [r[:budget] for r in chunk[:real_n]]
            emitted += max((len(r) for r in rows), default=0)
            n_tokens += sum(len(r) for r in rows)
            pend.stream_q.put(("chunk", rows))
            if emitted >= pend.max_new:
                break  # bucketed tail beyond the request: stop early
        self.metrics.inc("tokens_generated_total", n_tokens)
        pend.stream_q.put(("done", n_tokens))

    def generate(
        self, prompts: list[list[int]], max_new: int, sampling=None
    ):
        """Returns (outputs, batched_with): how many requests shared
        this device tick — surfaced in the response for observability
        (and the concurrency test pins coalescing actually happens)."""
        return self._batcher.submit(prompts, max_new, sampling)

    def generate_stream(
        self, prompts: list[list[int]], max_new: int, sampling=None
    ):
        """Queue-backed streaming: yields per-chunk row outputs as the
        batcher produces them; raises the tick's error if it failed."""
        import queue as _queue

        q: _queue.Queue = _queue.Queue()
        self._batcher.submit_stream(prompts, max_new, sampling, q)
        while True:
            kind, payload = q.get()
            if kind == "chunk":
                yield payload
            elif kind == "done":
                return
            else:
                raise payload

    def serve_forever(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet access log
                pass

            def _reply(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self._reply(
                        200,
                        {
                            "ok": True,
                            "restored_checkpoint": outer.restored,
                            "uptime_s": round(time.time() - _T0, 1),
                        },
                    )
                elif self.path == "/metrics":
                    # Prometheus text exposition — same scrape contract
                    # as the device plugin's shim endpoint.
                    body = outer.metrics.render(
                        outer._gauge_values()
                    ).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path.split("?", 1)[0] == "/debug/profile":
                    # On-demand jax.profiler capture (same contract as
                    # the training metrics server's endpoint).
                    profiler = getattr(outer._tel, "profiler", None)
                    if profiler is None:
                        self._reply(
                            404, {"error": "profiler not configured"}
                        )
                        return
                    from urllib.parse import parse_qs, urlsplit

                    q = parse_qs(urlsplit(self.path).query)
                    try:
                        seconds = float(q.get("seconds", ["2.0"])[0])
                    except ValueError:
                        seconds = 2.0
                    result = profiler.trigger(seconds)
                    code = 409 if "error" in result else 200
                    self._reply(code, result)
                else:
                    self._reply(404, {"error": "unknown path"})

            def do_POST(self):
                oai = self.path == "/v1/completions"
                if self.path != "/generate" and not oai:
                    self._reply(404, {"error": "unknown path"})
                    return
                outer.metrics.inc("requests_total")
                t_req = time.time()
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    if oai:
                        req = _oai_to_native(req)
                    as_text = "texts" in req
                    if as_text:
                        texts = req["texts"]
                        if (
                            not isinstance(texts, list)
                            or not texts
                            or not all(
                                isinstance(t, str) and t for t in texts
                            )
                        ):
                            raise ValueError(
                                "texts must be a non-empty list of "
                                "non-empty strings"
                            )
                        encode, decode = outer.codec()
                        prompts = [encode(t) for t in texts]
                    else:
                        prompts = req["prompts"]
                        if not prompts or not all(
                            isinstance(p, list) and all(
                                isinstance(t, int) for t in p
                            )
                            for p in prompts
                        ):
                            raise ValueError(
                                "prompts must be a non-empty list of "
                                "token-id lists"
                            )
                    max_new = int(
                        req.get("max_new_tokens", outer.default_new)
                    )
                    if max_new < 1:
                        # Validate BEFORE the batcher: the tick's
                        # pow2-bucketed run length would bypass
                        # generate()'s own >= 1 check and a negative
                        # per-request slice would return
                        # batch-composition-dependent output.
                        raise ValueError("max_new_tokens must be >= 1")
                    # Per-request sampling overrides layered on the env
                    # defaults, through the SAME make_sampling rules
                    # (validation + quantization); the batcher only
                    # coalesces same-config requests.
                    sampling = None
                    knobs = (
                        "temperature", "top_k", "top_p", "min_p",
                        "repetition_penalty",
                    )
                    if any(kb in req for kb in knobs):
                        base = outer._sampling
                        sampling = make_sampling(
                            temperature=req.get(
                                "temperature", base.temperature
                            ),
                            top_k=req.get("top_k", base.top_k),
                            top_p=req.get("top_p", base.top_p),
                            min_p=req.get("min_p", base.min_p),
                            repetition_penalty=req.get(
                                "repetition_penalty",
                                base.repetition_penalty,
                            ),
                        )
                        if sampling == base:
                            # Explicit values equal to the env defaults
                            # coalesce with default-sampling traffic.
                            sampling = None
                        elif not outer.admit_sampling(sampling):
                            raise ValueError(
                                "too many distinct sampling configs "
                                "(each compiles a program); reuse an "
                                "earlier configuration"
                            )
                    if bool(req.get("stream", False)):
                        # SSE streaming: chunks of per-row NEW token
                        # ids as the device produces them, then a done
                        # event (with full texts for "texts" requests —
                        # partial-sequence decodes can split multibyte
                        # characters, so text rides the final event).
                        # With a draft model configured the request
                        # degrades gracefully: the speculative path has
                        # no chunk loop, so the whole completion
                        # arrives as ONE chunk event — same wire
                        # format, no 400.
                        self.send_response(200)
                        self.send_header(
                            "Content-Type", "text/event-stream"
                        )
                        self.send_header("Cache-Control", "no-cache")
                        self.end_headers()
                        # Headers are OUT: from here every failure must
                        # end as an SSE event (or a silent stop on a
                        # dead socket) — a second HTTP status line would
                        # corrupt the stream, so nothing below may
                        # escape to the outer 400 handler.
                        dead = False

                        def event(obj) -> None:
                            nonlocal dead
                            if dead:
                                return
                            try:
                                self.wfile.write(
                                    b"data: "
                                    + json.dumps(obj).encode()
                                    + b"\n\n"
                                )
                                self.wfile.flush()
                            except OSError:
                                # Client left mid-stream — the normal
                                # way SSE consumers disconnect. Stop
                                # writing; the generator loop below
                                # still drains the batcher's queue.
                                dead = True

                        rows_acc = [[] for _ in prompts]
                        try:
                            if outer._draft is not None and not isinstance(
                                outer._batcher, _SlotScheduler
                            ):
                                outs, _bw = outer.generate(
                                    prompts, max_new, sampling
                                )
                                rows_acc = outs
                                event({"outputs": outs})
                            else:
                                for rows in outer.generate_stream(
                                    prompts, max_new, sampling
                                ):
                                    for acc, r in zip(rows_acc, rows):
                                        acc.extend(r)
                                    event({"outputs": rows})
                            final = {"done": True}
                            if as_text:
                                # Inside the try: a decode failure must
                                # surface as an error EVENT, not a 400
                                # line spliced into the stream body.
                                final["texts"] = [
                                    decode(o) for o in rows_acc
                                ]
                            event(final)
                        except Exception as e:  # noqa: BLE001
                            outer.metrics.inc("request_errors_total")
                            event(
                                {"error": f"{type(e).__name__}: {e}"}
                            )
                        return
                    outs, batched_with = outer.generate(
                        prompts, max_new, sampling
                    )
                    if oai:
                        # OpenAI responses carry text for token-id
                        # prompts too — decode through the codec.
                        self._reply(
                            200,
                            _oai_response(
                                outs,
                                [outer.codec()[1](o) for o in outs],
                                prompts,
                                max_new,
                                model=str(req.get("_oai_model", "")),
                            ),
                        )
                        return
                    payload = {
                        "outputs": outs,
                        "batched_with": batched_with,
                    }
                    if as_text:
                        payload["texts"] = [decode(o) for o in outs]
                    self._reply(200, payload)
                except Exception as e:  # noqa: BLE001 — serving loop
                    outer.metrics.inc("request_errors_total")
                    self._reply(400, {"error": f"{type(e).__name__}: {e}"})
                finally:
                    outer.metrics.inc(
                        "request_seconds_total", time.time() - t_req
                    )

        httpd = ThreadingHTTPServer(("0.0.0.0", self.port), Handler)
        self.port = httpd.server_address[1]  # resolve port 0 -> actual
        self.httpd = httpd
        import jax

        held = sorted(
            {
                d
                for leaf in jax.tree_util.tree_leaves(self.params)
                for d in leaf.devices()
            },
            key=lambda d: d.id,
        )
        print(
            json.dumps(
                {
                    "serving": True,
                    "port": self.port,
                    "model_params": self.cfg.n_params(),
                    "restored_checkpoint": self.restored,
                    "startup_s": round(time.time() - _T0, 1),
                    # One replica, one device (_replica_mesh): say which
                    # one holds the weights and how many the host shows.
                    "platform": held[0].platform,
                    "device_kind": held[0].device_kind,
                    "replica_devices": [str(d) for d in held],
                    "devices_visible": len(jax.devices()),
                }
            ),
            flush=True,
        )
        httpd.serve_forever()


def main() -> int:
    from tpufw.utils.profiling import enable_compile_cache

    role = env_str("serve_role", "")
    if role:
        # Disaggregated serving: this container is one replica role
        # (prefill/decode page-bundle server, or the front-door
        # router) instead of the monolithic endpoint below.
        from tpufw.serve.roles import main_role

        return main_role(role)
    enable_compile_cache()
    max_new = env_int("max_new_tokens", 16)
    port = env_int("serve_port", 0)
    if port:
        _Server(port, max_new).serve_forever()
        return 0

    prompts_file = env_str("prompts_file", "")
    if prompts_file:
        with open(prompts_file) as f:
            prompts = json.load(f)
    else:
        prompts = DEMO_PROMPTS
    for result in run_batch(prompts, max_new):
        print(json.dumps(result), flush=True)
    print(
        json.dumps(
            {
                "generate_ok": True,
                "n_prompts": len(prompts),
                "max_new_tokens": max_new,
                "total_s": round(time.time() - _T0, 1),
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
