"""Sharded training: state, loss, jitted step, and the Trainer driver.

Everything runs through one ``jax.jit``-compiled train step whose in/out
shardings are derived from the model's logical partitioning metadata + the
mesh rules (tpufw.mesh). XLA inserts all collectives (grad psum over
data/fsdp, all-gathers for fsdp params, tensor-parallel reductions) — there
is no hand-written communication anywhere, per SURVEY.md §2c.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from flax.core import meta
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpufw.mesh import MeshConfig, build_mesh, logical_axis_rules
from tpufw.parallel.context import use_mesh
from tpufw.train.metrics import Meter, StepMetrics, timed_batches


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    # Static fields (not traced).
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads):
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt,
        )


def cross_entropy_loss(
    logits: jax.Array,
    targets: jax.Array,
    mask: Optional[jax.Array] = None,
    z_loss_weight: float = 1e-4,
) -> tuple[jax.Array, jax.Array]:
    """Token CE with z-loss regularization (keeps the softmax normalizer
    bounded — standard for large-vocab LM training). Returns (loss, n_tokens).
    The per-token math lives in tpufw.ops.loss.token_cross_entropy, shared
    with the chunked-vocab path.
    """
    from tpufw.ops.loss import token_cross_entropy

    ce = token_cross_entropy(logits, targets, z_loss_weight)
    if mask is None:
        return ce.mean(), jnp.array(ce.size, jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    return (ce * mask).sum() / n, n


def default_optimizer(
    lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    b1: float = 0.9,
    b2: float = 0.95,
    mu_dtype: Optional[str] = None,
) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clipping — the Llama recipe.

    ``mu_dtype="bfloat16"`` stores the first moment in bf16 (half the mu
    buffer; the momentum direction tolerates bf16 rounding). The second
    moment stays fp32 — it feeds a sqrt and small values underflow bf16.
    """
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1), lr * 0.1
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(
            schedule,
            b1=b1,
            b2=b2,
            weight_decay=weight_decay,
            mu_dtype=jnp.dtype(mu_dtype) if mu_dtype else None,
        ),
    )


def frozen_copy(tree, dtype, out_shardings=None) -> Any:
    """Cast every floating leaf of ``tree`` to ``dtype`` THROUGH jit, so
    each output leaf is a FRESH buffer even when the cast is a dtype
    no-op (fp32 -> fp32): frozen side-trees (DPO reference, distillation
    teacher) live next to a train step that donates state.params, and an
    aliased leaf would be a use-after-donate at the first step.
    ``out_shardings`` additionally lays the copy out on the mesh (a
    large frozen teacher must shard like any other param tree)."""

    def cast(t):
        return jax.tree.map(
            lambda p: p.astype(dtype)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            t,
        )

    if out_shardings is None:
        return jax.jit(cast)(tree)
    return jax.jit(cast, out_shardings=out_shardings)(tree)


def head_kernel(params) -> jax.Array:
    """The [D, V] LM-head matrix from a decoder_lm param tree — the
    dedicated ``lm_head`` kernel, or the transposed embedding when tied."""
    if "lm_head" in params:
        return params["lm_head"]["kernel"]
    return params["embed"]["embedding"].T


def shift_and_mask(batch: dict):
    """LM target shift + packed-batch masking, shared by every objective.

    Returns (inputs, targets, input_segment_ids, loss_mask). With
    segment_ids: never train boundary positions to predict the next
    document's first token — attention (correctly) can't see across
    segments — and never train on padding targets (segment 0).
    """
    tokens = batch["tokens"]
    inputs = tokens[:, :-1]
    targets = tokens[:, 1:]
    seg = batch.get("segment_ids")
    seg_in = None if seg is None else seg[:, :-1]
    mask = batch.get("loss_mask")
    mask = None if mask is None else mask[:, 1:].astype(jnp.float32)
    if seg is not None:
        same_seg = (seg[:, :-1] == seg[:, 1:]).astype(jnp.float32)
        nonpad = (seg[:, 1:] > 0).astype(jnp.float32)
        seg_mask = same_seg * nonpad
        mask = seg_mask if mask is None else mask * seg_mask
    return inputs, targets, seg_in, mask


def batch_loss(
    apply_fn: Callable,
    params,
    batch: dict,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype: str = "bfloat16",
    final_logit_soft_cap: Optional[float] = None,
) -> tuple[jax.Array, jax.Array]:
    """LM objective for one batch: (loss, n_target_tokens).

    batch: tokens [B,T] (+ optional loss_mask, segment_ids). Targets are
    tokens shifted left; the final position is masked out.
    ``loss_chunk_size`` switches to the chunked-vocab CE (tpufw.ops.loss):
    the model skips its head matmul and loss is computed from hidden
    states chunk-by-chunk, never materializing [B,T,V] logits. Shared by
    the train and eval steps so their objectives can't drift.
    """
    inputs, targets, seg_in, mask = shift_and_mask(batch)

    kwargs = {"segment_ids": seg_in}
    if loss_chunk_size:
        kwargs["return_hidden"] = True
    out = apply_fn({"params": params}, inputs, **kwargs)
    # MoE models return (logits, aux_loss) — router losses join the
    # objective here.
    aux = 0.0
    if isinstance(out, tuple):
        out, aux = out
    if loss_chunk_size:
        from tpufw.ops.loss import chunked_cross_entropy

        loss, n = chunked_cross_entropy(
            out, head_kernel(params), targets, mask,
            chunk_size=loss_chunk_size,
            compute_dtype=jnp.dtype(loss_chunk_dtype),
            # Gemma-style cap; the model skipped its head (and cap) via
            # return_hidden, so the chunked path applies it per chunk.
            logits_soft_cap=final_logit_soft_cap,
        )
    else:
        loss, n = cross_entropy_loss(out, targets, mask)
    return loss + aux, n


def train_step(
    state: TrainState,
    batch: dict,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype: str = "bfloat16",
    grad_accum: int = 1,
    final_logit_soft_cap: Optional[float] = None,
) -> tuple[TrainState, dict]:
    """One optimizer update (objective: ``batch_loss``).

    ``grad_accum`` > 1 splits the batch into that many microbatches and
    accumulates token-weighted gradients under ``lax.scan`` before the
    single update — same numbers as the one-shot step (modulo fp
    summation order), at 1/A the activation memory. Microbatch rows are
    taken strided (row m, m+A, ...) so each device's local shard
    contributes equally to every microbatch and no resharding is needed.
    """

    def loss_and_n(params, mb):
        def lf(p):
            return batch_loss(
                state.apply_fn, p, mb, loss_chunk_size, loss_chunk_dtype,
                final_logit_soft_cap,
            )

        (loss, n), grads = jax.value_and_grad(lf, has_aux=True)(params)
        return loss, n, grads

    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if grad_accum == 1:
        loss, _, grads = loss_and_n(state.params, batch)
    else:
        mbs = jax.tree.map(
            lambda x: x.reshape(
                x.shape[0] // grad_accum, grad_accum, *x.shape[1:]
            ).swapaxes(0, 1),
            batch,
        )

        def body(carry, mb):
            l_acc, n_acc, g_acc = carry
            loss, n, grads = loss_and_n(state.params, mb)
            return (
                l_acc + loss * n,
                n_acc + n,
                jax.tree.map(lambda a, g: a + g * n, g_acc, grads),
            ), None

        # Accumulate in fp32 regardless of param dtype: the body's
        # `g * n` promotes to fp32 (n is fp32), so a bf16-params carry
        # would be a scan dtype mismatch — and fp32 accumulation is the
        # numerically right call anyway. Cast back at the end.
        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        (l_sum, n_sum, g_sum), _ = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32), zero_g),
            mbs,
        )
        n_safe = jnp.maximum(n_sum, 1.0)
        loss = l_sum / n_safe
        grads = jax.tree.map(
            lambda g, p: (g / n_safe).astype(p.dtype), g_sum, state.params
        )

    new_state = state.apply_gradients(grads)
    metrics = {
        "loss": loss,
        "grad_norm": optax.global_norm(grads),
    }
    return new_state, metrics


def eval_step(
    state: TrainState,
    batch: dict,
    loss_chunk_size: Optional[int] = None,
    loss_chunk_dtype: str = "bfloat16",
    final_logit_soft_cap: Optional[float] = None,
) -> dict:
    """Forward-only objective on one held-out batch: {loss, n_tokens}."""
    loss, n = batch_loss(
        state.apply_fn, state.params, batch, loss_chunk_size,
        loss_chunk_dtype, final_logit_soft_cap,
    )
    return {"loss": loss, "n_tokens": n}


def globalize_batch(mesh: Mesh, batch: dict) -> dict:
    """Multi-process: assemble each process's LOCAL batch shard into a
    global jax.Array (jit rejects raw numpy under a multi-host mesh).

    Contract: the configured batch size is the GLOBAL batch; each
    process's data iterator yields ``batch_size / process_count`` rows.
    In single-process runs this is the identity. Shared by the flax
    Trainer and the PipelineTrainer so the multi-host contract can't
    drift between them.
    """
    if jax.process_count() == 1:
        return batch
    row = NamedSharding(mesh, P(("data", "fsdp")))
    return {
        # Leaves that are already jax.Arrays (e.g. from
        # prefetch_to_device) are global already; only raw host
        # numpy needs assembling.
        k: v if isinstance(v, jax.Array)
        else jax.make_array_from_process_local_data(row, v)
        for k, v in batch.items()
    }


def _mesh_label(mesh: Mesh) -> str:
    """Compact mesh-shape label for the run_info gauge: ``data=8`` /
    ``data=4,fsdp=2`` (size-1 axes elided — they carry no sharding)."""
    parts = [
        f"{name}={size}"
        for name, size in mesh.shape.items()
        if size > 1
    ]
    return ",".join(parts) or "single"


def run_evaluation(
    data, n_batches, eval_batch_fn, globalize
) -> dict:
    """The ONE token-weighted held-out eval loop: accumulate
    {loss, n_tokens} outputs of ``eval_batch_fn(batch)`` over up to
    ``n_batches`` batches and report {eval_loss, eval_ppl, eval_tokens,
    eval_batches}. Shared by Trainer and PipelineTrainer so their eval
    reporting surfaces cannot drift."""
    total_loss = 0.0
    total_n = 0.0
    n_seen = 0
    for i, batch in enumerate(data):
        if n_batches is not None and i >= n_batches:
            break
        if not isinstance(batch, dict):
            batch = {"tokens": batch}
        batch = globalize(batch)
        out = eval_batch_fn(batch)
        n = float(out["n_tokens"])
        total_loss += float(out["loss"]) * n
        total_n += n
        n_seen += 1
    if n_seen == 0:
        raise ValueError("evaluate(): empty eval iterator")
    import math

    loss = total_loss / max(total_n, 1.0)
    return {
        "eval_loss": loss,
        "eval_ppl": math.exp(min(loss, 50.0)),
        "eval_tokens": int(total_n),
        "eval_batches": n_seen,
    }


def maybe_inloop_eval(trainer, step: int, eval_data, on_eval) -> None:
    """The ONE in-loop eval trigger (cadence + reporting), shared by the
    flax and pipeline trainers so eval cadence cannot drift."""
    cfg = trainer.cfg
    if not (cfg.eval_every and eval_data is not None):
        return
    if step % cfg.eval_every:
        return
    ev = trainer.evaluate(eval_data(), cfg.eval_batches)
    ev["step"] = step
    tel = getattr(trainer, "telemetry", None)
    if tel is not None:
        tel.events.emit(
            "eval",
            **{
                k: v if isinstance(v, int) else round(float(v), 6)
                for k, v in ev.items()
                if isinstance(v, (int, float))
            },
        )
    if on_eval:
        on_eval(ev)


def state_shardings(
    abstract_state: TrainState, mesh: Mesh, rules=None
) -> TrainState:
    """Derive NamedShardings for a TrainState pytree from logical metadata.

    Params carry flax ``Partitioned`` metadata; optimizer moments mirror the
    param they track (optax keeps the tree structure), so
    ``nn.logical_to_mesh_sharding`` resolves both. Scalars replicate.
    """
    rules = rules or logical_axis_rules()
    specs = nn.get_partition_spec(abstract_state)
    return nn.logical_to_mesh_sharding(specs, mesh, rules)


@dataclasses.dataclass
class TrainerConfig:
    batch_size: int = 8
    seq_len: int = 2048
    total_steps: int = 100
    lr: float = 3e-4
    warmup_steps: int = 10
    log_every: int = 10
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1000
    # Sequence positions per chunked-CE scan step; None = full logits.
    loss_chunk_size: Optional[int] = None
    # Head-matmul input dtype for the chunked path. "bfloat16" is the MXU
    # fast path (fp32 accumulation either way); "float32" restores bitwise
    # full-logits numerics at ~2x head-matmul cost.
    loss_chunk_dtype: str = "bfloat16"
    # XProf capture: trace steps [profile_start, profile_stop) into
    # profile_dir (None disables). Step 0 is excluded by default so the
    # window holds steady-state steps, not the XLA compile.
    profile_dir: Optional[str] = None
    profile_start: int = 3
    profile_stop: int = 6
    # Held-out evaluation: every eval_every steps (0 = off) run
    # eval_batches forward-only batches from the eval iterator passed to
    # ``Trainer.run(eval_data=...)``.
    eval_every: int = 0
    eval_batches: int = 8
    # Gradient accumulation: microbatches per optimizer step (1 = off).
    # Batch rows per microbatch must still divide over data x fsdp.
    grad_accum: int = 1
    # Adam first-moment storage dtype (None = fp32). "bfloat16" halves
    # the mu buffer — see default_optimizer.
    adam_mu_dtype: Optional[str] = None
    # Preemption handling: latch SIGTERM (k8s pod termination) and exit
    # the step loop cleanly with a forced final checkpoint, so a JobSet
    # gang restart resumes from the current step (tpufw.train.preemption).
    # Default ON — one default for library and deployed use; the handler
    # chains to any prior one and is uninstalled when run() returns.
    handle_preemption: bool = True
    # Steps between gang-consistency syncs of the stop flag (the
    # cross-host allgather in GracefulShutdown.should_stop); a stop is
    # acted on within this many steps of the signal. 1 = every step.
    preemption_sync_every: int = 1
    # Steps between host syncs of the loss (block_until_ready). 1 = the
    # classic per-step sync. >1 dispatches a WINDOW of steps and syncs
    # once: every sync costs a host<->device round trip, which
    # serializes against short steps. The loop always
    # syncs after the first step (compile boundary / first-step latency)
    # and the last; metrics entries then carry window averages
    # (StepMetrics.window_steps), and checkpoint saves, in-loop eval,
    # and preemption checks run at sync points only — align
    # checkpoint_every/eval_every to multiples of sync_every.
    sync_every: int = 1
    # MFU autotuning (tpufw.tune): "off" = fully inert; "cached" = apply
    # a persisted winner if one exists, never search; "search" = cache
    # hit or run the budgeted compile-and-measure search before the
    # first step and persist the winner. Resolved once at the top of
    # run(); the winner overwrites grad_accum / loss_chunk_size /
    # sync_every / remat policy / flash blocks on this trainer.
    autotune: str = "off"
    # Wall-clock budget for the "search" mode's measurement loop.
    autotune_budget_s: float = 120.0
    # Timed steps per candidate (median is the score).
    autotune_steps: int = 3
    # Unified telemetry (tpufw.obs). telemetry_dir: write the schema'd
    # events.jsonl + Chrome-trace trace.json (Perfetto-loadable) per
    # host under this dir, plus a final metrics.prom snapshot (None
    # disables the files). metrics_port: serve the Prometheus registry
    # at /metrics on this port from a daemon thread (None disables;
    # 0 binds an ephemeral port — tests read Trainer.telemetry
    # .bound_port). Set BOTH knobs uniformly across hosts: the skew
    # monitor's per-window allgather is a collective. With both off
    # the instrumentation degrades to shared no-ops (<1% per-step,
    # asserted in tests/test_obs.py).
    telemetry_dir: Optional[str] = None
    metrics_port: Optional[int] = None
    # A host is flagged (straggler_detected event, warn) when its sync
    # window's wall time exceeds the fleet median by this factor.
    straggler_factor: float = 2.0
    # Pipeline schedule override (PipelineTrainer only; the flax
    # Trainer ignores both). None keeps the PipelineConfig's own
    # schedule; "gpipe" | "1f1b" | "interleaved" | "zb1" replaces it.
    # pipeline_vstages is the interleaved schedule's virtual-stage
    # count v (bubble (S-1)/(v*M+S-1)); it must satisfy
    # PipelineConfig.validate's divisibility rules.
    pipeline_schedule: Optional[str] = None
    pipeline_vstages: int = 1


class Trainer:
    """Builds mesh + sharded state and runs the step loop with MFU metrics."""

    def __init__(
        self,
        model: nn.Module,
        trainer_cfg: TrainerConfig,
        mesh_cfg: MeshConfig | None = None,
        mesh: Mesh | None = None,
        tx: optax.GradientTransformation | None = None,
    ):
        self.model = model
        self.cfg = trainer_cfg
        self.mesh = mesh if mesh is not None else build_mesh(mesh_cfg)
        self.tx = tx or default_optimizer(
            lr=trainer_cfg.lr,
            warmup_steps=trainer_cfg.warmup_steps,
            total_steps=trainer_cfg.total_steps,
            mu_dtype=trainer_cfg.adam_mu_dtype,
        )
        if getattr(getattr(model, "cfg", None), "lora_rank", 0) > 0:
            # LoRA fine-tune: update ONLY adapter params; the frozen
            # base gets set_to_zero (optax.masked would PASS ITS RAW
            # GRADIENTS THROUGH, silently training the base). Moments
            # are allocated only for the adapter partition.
            from tpufw.models.lora import lora_mask

            def labels(params):
                return jax.tree.map(
                    lambda m: "lora" if m else "frozen", lora_mask(params)
                )

            self.tx = optax.multi_transform(
                {"lora": self.tx, "frozen": optax.set_to_zero()}, labels
            )
        self._compiled: dict = {}
        self.state = None
        self.state_sharding = None
        self.preempted = False
        # TuneResult of the last apply_autotune (tpufw.tune.runner);
        # None until cfg.autotune resolves in run().
        self.last_tune = None
        # tpufw.obs.Telemetry, built per run() from the cfg knobs;
        # the disabled singleton between runs so probes never branch.
        from tpufw.obs import Telemetry

        self.telemetry = Telemetry.disabled()

    def _abstract_state(self, rng):
        tokens = jnp.zeros(
            (self.cfg.batch_size, self.cfg.seq_len), jnp.int32
        )

        def init_fn(rng):
            variables = self.model.init(rng, tokens[:, :-1])
            params = variables["params"]
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.tx.init(params),
                apply_fn=self.model.apply,
                tx=self.tx,
            )

        # Trace under the mesh context: mesh-aware ops (ring attention)
        # resolve the current mesh during eval_shape too.
        with use_mesh(self.mesh):
            abstract = jax.eval_shape(init_fn, rng)
        return init_fn, abstract

    def init_state(self, seed: int = 0) -> TrainState:
        rng = jax.random.key(seed)
        init_fn, abstract = self._abstract_state(rng)
        self.state_sharding = state_shardings(abstract, self.mesh)
        with use_mesh(self.mesh):
            jit_init = jax.jit(init_fn, out_shardings=self.state_sharding)
            # _abstract_state only eval_shape's rng (abstract, no
            # randomness drawn); this jitted init is the key's one
            # real use.
            self.state = jit_init(rng)  # tpulint: disable=TPU003
        # Same jit object kept for the perf observatory (run() harvests
        # its cost_analysis once telemetry exists): the AOT lower hits
        # the executable this call just built.
        self._init_harvest = (jit_init, rng)
        # Unbox flax Partitioned wrappers: downstream code wants raw arrays.
        self.state = meta.unbox(self.state)
        self.state_sharding = meta.unbox(self.state_sharding)
        return self.state

    def restore_params(self, path: str):
        """Restore a bare-params Orbax checkpoint (the
        ``tpufw.tools.import_hf`` CLI's output) sharded onto this
        trainer's mesh, WITHOUT materializing any state — the abstract
        tree comes from eval_shape, same no-throwaway-init discipline as
        ``maybe_restore``. Returns (params, full_state_sharding)."""
        import orbax.checkpoint as ocp

        _, boxed = self._abstract_state(jax.random.key(0))
        shardings = meta.unbox(state_shardings(boxed, self.mesh))
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            meta.unbox(boxed).params,
            shardings.params,
        )
        with ocp.StandardCheckpointer() as ckptr:
            params = ckptr.restore(os.path.abspath(path), abstract)
        return params, shardings

    def init_from_params(self, path: str, seed: int = 0) -> TrainState:
        """Start training FROM a bare-params Orbax checkpoint: step 0,
        FRESH optimizer state, params restored sharded — the
        fine-tune-from-imported-weights entry point, distinct from
        ``maybe_restore`` (which resumes a full TrainState mid-run).
        Must be called on a fresh trainer: silently mixing restored
        params with an existing step/optimizer would corrupt the run.

        With LoRA enabled on the model (cfg.lora_rank > 0) the
        checkpoint holds only the BASE tree: base kernels restore from
        disk, adapters initialize fresh (B = 0, so step 0 equals the
        checkpointed model) — the import -> LoRA-fine-tune on-ramp."""
        if self.state is not None:
            raise RuntimeError(
                "init_from_params on an already-initialized trainer; "
                "construct a fresh Trainer (or use maybe_restore to "
                "resume a full TrainState)"
            )
        if getattr(getattr(self.model, "cfg", None), "lora_rank", 0) > 0:
            return self._init_lora_from_params(path, seed)
        del seed  # params come from the checkpoint, nothing is sampled
        params, self.state_sharding = self.restore_params(path)

        def make_state(p):
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=p,
                opt_state=self.tx.init(p),
                apply_fn=self.model.apply,
                tx=self.tx,
            )

        with use_mesh(self.mesh):
            self.state = jax.jit(
                make_state,
                out_shardings=self.state_sharding,
                donate_argnums=(0,),
            )(params)
        return self.state

    def _init_lora_from_params(self, path: str, seed: int) -> TrainState:
        """Base kernels from the checkpoint + fresh adapters (see
        init_from_params). The checkpoint tree is exactly what a rank-0
        twin of this model initializes, so its abstract/restore target
        comes from that twin; the restored leaves then overwrite the
        matching leaves of a fresh full init (adapters keep theirs)."""
        base_model = type(self.model)(
            dataclasses.replace(self.model.cfg, lora_rank=0)
        )
        base = Trainer(base_model, self.cfg, mesh=self.mesh, tx=self.tx)
        base_params, _ = base.restore_params(path)

        rng = jax.random.key(seed)
        init_fn, abstract = self._abstract_state(rng)
        self.state_sharding = meta.unbox(
            state_shardings(abstract, self.mesh)
        )

        def graft(full, restored):
            if isinstance(restored, dict):
                return {
                    k: graft(full[k], restored[k]) if k in restored else v
                    for k, v in full.items()
                }
            return restored

        def make_state(restored):
            # Full init traced, then base leaves replaced by the donated
            # checkpoint: XLA dead-code-eliminates the unused base random
            # init, so peak memory is ~one param tree + adapters (the
            # no-throwaway-init discipline, LoRA edition).
            state = meta.unbox(init_fn(rng))
            return state.replace(
                params=graft(state.params, restored)
            )

        with use_mesh(self.mesh):
            self.state = jax.jit(
                make_state,
                out_shardings=self.state_sharding,
                donate_argnums=(0,),
            )(base_params)
        return self.state

    def maybe_restore(self) -> bool:
        """Resume from the latest checkpoint in cfg.checkpoint_dir, if any —
        the JobSet gang-restart resume path (SURVEY.md §5)."""
        if not self.cfg.checkpoint_dir:
            return False
        from tpufw.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(self.cfg.checkpoint_dir)
        try:
            if mgr.latest_step() is None:
                return False
            if self.state is not None:
                abstract = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding
                    ),
                    self.state,
                )
            else:
                # Shapes + shardings WITHOUT materializing a throwaway init
                # (an 8B init would allocate full params+Adam just to be
                # overwritten by the restore).
                rng = jax.random.key(0)
                _, boxed = self._abstract_state(rng)
                self.state_sharding = meta.unbox(
                    state_shardings(boxed, self.mesh)
                )
                abstract = jax.tree.map(
                    lambda x, s: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=s
                    ),
                    meta.unbox(boxed),
                    self.state_sharding,
                )
            self.state = mgr.restore(abstract)
            return True
        finally:
            mgr.close()

    def _final_soft_cap(self) -> Optional[float]:
        """The model's final-logit soft-cap (Gemma), applied inside the
        chunked-CE path since return_hidden skips the model's own cap."""
        cfg = getattr(self.model, "cfg", None)
        return getattr(cfg, "final_logit_soft_cap", None)

    def globalize_batch(self, batch: dict) -> dict:
        return globalize_batch(self.mesh, batch)

    def compiled_step(self, batch: dict | None = None):
        """Jitted train step; batch shardings derived from the batch's own
        structure (every leaf is batch-major: shard dim 0 on data+fsdp)."""
        key = (
            ("tokens",)
            if batch is None
            else tuple(sorted(batch.keys()))
        )
        if key not in self._compiled:
            accum = self.cfg.grad_accum
            if accum < 1:
                raise ValueError(f"grad_accum must be >= 1, got {accum}")
            if accum > 1:
                dp = (
                    self.mesh.shape["data"] * self.mesh.shape["fsdp"]
                )
                if self.cfg.batch_size % accum or (
                    self.cfg.batch_size // accum
                ) % dp:
                    raise ValueError(
                        f"grad_accum={accum}: batch {self.cfg.batch_size} "
                        f"must split into {accum} microbatches whose rows "
                        f"divide over data x fsdp = {dp}"
                    )
            row = NamedSharding(self.mesh, P(("data", "fsdp")))
            batch_sharding = {k: row for k in key}
            self._compiled[key] = jax.jit(
                partial(
                    train_step,
                    loss_chunk_size=self.cfg.loss_chunk_size,
                    loss_chunk_dtype=self.cfg.loss_chunk_dtype,
                    grad_accum=self.cfg.grad_accum,
                    final_logit_soft_cap=self._final_soft_cap(),
                ),
                in_shardings=(self.state_sharding, batch_sharding),
                out_shardings=(self.state_sharding, None),
                donate_argnums=(0,),
            )
        return self._compiled[key]

    def lower_step(self, lowering_platforms=None):
        """Trace and lower the train step on an abstract state and
        batch: nothing is allocated, compiled or run. For looking at
        what the step lowers to — that the flash kernel is a Mosaic
        custom call, that it lowers over a multi-device mesh at all
        (``lowering_platforms=("tpu",)`` reaches the TPU rules from a
        CPU host)."""
        _, boxed = self._abstract_state(jax.random.key(0))
        self.state_sharding = meta.unbox(state_shardings(boxed, self.mesh))
        batch = {
            "tokens": jax.ShapeDtypeStruct(
                (self.cfg.batch_size, self.cfg.seq_len), jnp.int32
            )
        }
        with use_mesh(self.mesh):
            traced = self.compiled_step(batch).trace(
                meta.unbox(boxed), batch
            )
            return traced.lower(lowering_platforms=lowering_platforms)

    def compiled_eval_step(self, batch: dict):
        """Jitted forward-only step (no donation: state survives)."""
        key = ("eval", *sorted(batch.keys()))
        if key not in self._compiled:
            row = NamedSharding(self.mesh, P(("data", "fsdp")))
            batch_sharding = {k: row for k in sorted(batch.keys())}
            self._compiled[key] = jax.jit(
                partial(
                    eval_step,
                    loss_chunk_size=self.cfg.loss_chunk_size,
                    loss_chunk_dtype=self.cfg.loss_chunk_dtype,
                    final_logit_soft_cap=self._final_soft_cap(),
                ),
                in_shardings=(self.state_sharding, batch_sharding),
                out_shardings=None,
            )
        return self._compiled[key]

    def evaluate(
        self, data: Iterator[dict], n_batches: Optional[int] = None
    ) -> dict:
        """Token-weighted held-out loss + perplexity over ``n_batches``
        (None = until the iterator ends). The objective matches training
        (``batch_loss``, incl. z-loss / MoE aux), so eval_loss is directly
        comparable to the train curve; ppl = exp(eval_loss)."""
        if self.state is None:
            raise RuntimeError("evaluate() before init_state()/restore")

        def eval_one(b):
            fn = self.compiled_eval_step(b)
            self.telemetry.perf.observe_jit(
                "eval_step", fn, (self.state, b)
            )
            return fn(self.state, b)

        with use_mesh(self.mesh):
            return run_evaluation(
                data, n_batches, eval_one, self.globalize_batch
            )

    def run(
        self,
        data: Iterator[dict],
        model_flops_per_token: float,
        on_metrics: Callable[[StepMetrics], None] | None = None,
        eval_data: Callable[[], Iterator[dict]] | None = None,
        on_eval: Callable[[dict], None] | None = None,
        shutdown: "GracefulShutdown | None" = None,
    ) -> list[StepMetrics]:
        from tpufw.obs import Telemetry

        # Telemetry FIRST: autotune trials and checkpoint restores in
        # init_state are themselves events worth having.
        tel = self.telemetry = Telemetry.create(
            telemetry_dir=self.cfg.telemetry_dir,
            metrics_port=self.cfg.metrics_port,
            straggler_factor=self.cfg.straggler_factor,
        )
        tel.set_run_info(
            backend=jax.default_backend(),
            mesh=_mesh_label(self.mesh),
            model=type(self.model).__name__,
        )
        tel.record_config({"trainer": dataclasses.asdict(self.cfg)})
        if self.cfg.autotune != "off":
            # Resolve BEFORE state init: a remat-policy winner rebuilds
            # the model, and the jitted step bakes every tuned knob in.
            from tpufw.tune.runner import apply_autotune

            with tel.tracer.span("tune"):
                apply_autotune(self, events=tel.events, perf=tel.perf)
        if self.state is None:
            self.init_state()
        if tel.perf.enabled:
            # programs.json keyed like the tune winner cache, so a
            # cost table and a tune winner for the same (model, batch,
            # seq, mesh) point line up by construction.
            from tpufw.tune.runner import _trainer_cache_key

            tel.perf.set_key(_trainer_cache_key(self))
            init_harvest = getattr(self, "_init_harvest", None)
            if init_harvest is not None:
                with use_mesh(self.mesh):
                    tel.perf.observe_jit(
                        "state_init", init_harvest[0], (init_harvest[1],)
                    )
        owns_shutdown = False
        self.preempted = False
        meter = Meter(
            tokens_per_step=self.cfg.batch_size * (self.cfg.seq_len - 1),
            flops_per_token=model_flops_per_token,
            n_chips=len(self.mesh.devices.flatten()),
            registry=tel.registry,
        )
        ckpt = None
        if self.cfg.checkpoint_dir:
            from tpufw.train.checkpoint import CheckpointManager

            ckpt = CheckpointManager(
                self.cfg.checkpoint_dir,
                save_interval_steps=self.cfg.checkpoint_every,
                events=tel.events,
                tracer=tel.tracer,
            )
        from tpufw.obs.perf import resolve_profile_window
        from tpufw.utils.profiling import StepProfiler

        # TPUFW_PROFILE_STEPS=a:b overrides the config window; without
        # a configured profile dir the capture lands under the
        # telemetry dir so the trace is linkable from the run artifact.
        prof = StepProfiler(
            *resolve_profile_window(
                self.cfg.profile_dir,
                self.cfg.profile_start,
                self.cfg.profile_stop,
                telemetry_dir=self.cfg.telemetry_dir,
            )
        )
        from tpufw.train.preemption import checkpoint_stop, owned_shutdown

        shutdown, owns_shutdown = owned_shutdown(
            shutdown,
            self.cfg.handle_preemption,
            self.cfg.preemption_sync_every,
            events=tel.events,
        )
        # total_steps is the GLOBAL optimizer-step budget (it sized the LR
        # schedule): a restored run finishes the remaining steps, it does
        # not train total_steps more.
        start_step = int(self.state.step)
        remaining = max(0, self.cfg.total_steps - start_step)
        se = max(1, self.cfg.sync_every)
        window_n, window_wait = 0, 0.0
        history: list[StepMetrics] = []
        tel.events.emit(
            "run_start",
            workload="train",
            start_step=start_step,
            total_steps=self.cfg.total_steps,
            batch_size=self.cfg.batch_size,
            seq_len=self.cfg.seq_len,
            sync_every=se,
            n_chips=len(self.mesh.devices.flatten()),
        )

        def record_window(py_step, loss):
            # One host sync: meter.stop's float(loss) is the barrier.
            # Everything published here describes the window just
            # closed — StepMetrics to the caller, a step event to the
            # log, per-host gauges + straggler check to the skew
            # monitor (its allgather rides the sync the loop already
            # pays for).
            with tel.tracer.span("host_sync"):
                sm = meter.stop(
                    py_step, loss,
                    data_wait_s=window_wait, n_steps=window_n,
                )
                tel.events.emit("step", **sm.event_fields())
                if tel.skew is not None:
                    tel.skew.record(
                        sm.step,
                        sm.step_time_s * sm.window_steps,
                        sm.data_wait_s,
                    )
                # Static FLOPs x measured wall -> per-program MFU
                # (tpufw_program_mfu) and roofline attribution.
                tel.perf.record_wall("train_step", sm.step_time_s)
            return sm

        try:
            with use_mesh(self.mesh):
                for i, (wait, batch) in enumerate(timed_batches(data)):
                    if i >= remaining:
                        break
                    tel.tracer.complete("data_fetch", wait)
                    # Watchdog window: dispatch through host sync.
                    # Data fetch / eval / checkpoint are excluded —
                    # they have no progress guarantee, and the point
                    # is catching wedged collectives, not slow I/O.
                    tel.watchdog.arm()
                    with tel.tracer.span("step_dispatch"):
                        batch = self.globalize_batch(batch)
                        step_fn = self.compiled_step(batch)
                        # Cost harvest (first time per program only):
                        # abstract lower, so donation is untouched.
                        tel.perf.observe_jit(
                            "train_step", step_fn, (self.state, batch)
                        )
                        prof.maybe_start(i)
                        if window_n == 0:
                            meter.start()
                        with prof.step(i):
                            self.state, m = step_fn(self.state, batch)
                            window_n += 1
                            window_wait += wait
                            # state.step advances by exactly 1 per
                            # step_fn: tracking it host-side avoids a
                            # device fetch per step.
                            py_step = start_step + i + 1
                            # Sync at step 1 (compile boundary), then
                            # at steps that are MULTIPLES of sync_every
                            # — so checkpoint_every/eval_every aligned
                            # to sync_every actually fire — and at the
                            # last.
                            sync = (
                                i == 0
                                or py_step % se == 0
                                or i + 1 == remaining
                            )
                            if sync:
                                loss = m["loss"]  # Meter.stop float()s it: the barrier
                        prof.maybe_stop(i)
                    if not sync:
                        tel.watchdog.disarm()
                        continue
                    sm = record_window(py_step, loss)
                    tel.watchdog.disarm()
                    window_n, window_wait = 0, 0.0
                    history.append(sm)
                    if on_metrics and (
                        se > 1 or i % self.cfg.log_every == 0
                    ):
                        on_metrics(sm)
                    with tel.tracer.span("eval"):
                        maybe_inloop_eval(self, py_step, eval_data, on_eval)
                    if ckpt is not None:
                        with tel.tracer.span("checkpoint"):
                            ckpt.save(py_step, self.state)
                    # Collective decision (see preemption.py): the whole
                    # gang breaks at the same step or not at all.
                    with tel.tracer.span("preemption_sync"):
                        stop = checkpoint_stop(
                            shutdown, ckpt, py_step, self.state,
                            watchdog=tel.watchdog,
                        )
                    if stop:
                        self.preempted = True
                        tel.events.emit(
                            "preemption_stop", level="warn", step=py_step
                        )
                        break
                # Iterator exhausted mid-window: flush the open window
                # so every executed step is metered and checkpointable.
                if window_n:
                    loss = m["loss"]  # Meter.stop float()s it: the barrier
                    tel.watchdog.arm()
                    sm = record_window(py_step, loss)
                    tel.watchdog.disarm()
                    history.append(sm)
                    if on_metrics:
                        on_metrics(sm)
                    if ckpt is not None:
                        with tel.tracer.span("checkpoint"):
                            ckpt.save(py_step, self.state)
        finally:
            # Flush even on a mid-loop crash: the trace and the last
            # checkpoint are exactly what post-mortems need.
            prof.close()
            if ckpt is not None:
                ckpt.wait()
                ckpt.close()
            if owns_shutdown:
                shutdown.uninstall()
            tel.events.emit(
                "run_end",
                steps=len(history),
                last_step=history[-1].step if history else start_step,
                preempted=self.preempted,
            )
            tel.close()
        return history
