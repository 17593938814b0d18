"""Pipeline-parallel trainer: the Trainer's operational surface over the
GPipe schedule (tpufw.parallel.pipeline).

The Trainer's operational surface — jitted donated-state step,
tokens/s-per-chip + MFU metrics, async Orbax checkpoint/resume,
multi-host batch globalization — with the layer stack executing on the
``pipe`` mesh axis instead of under the flax scan trunk. The functional
pipeline params (stage stacks sharded over ``pipe``) replace the flax
TrainState; Meter, CheckpointManager, optimizer recipe, and
globalize_batch are the shared machinery.

Packed batches (segment_ids + loss_mask) train with the same masking as
the flax trainer (shift_and_mask); segment ids ride the pipe ring with
their microbatch. Held-out eval runs the forward-only pipeline
(pipeline_eval) with the flax trainer's token-weighted loss/ppl
surface. Chunked-vocab CE runs the head inside tpufw.ops.loss (the
pipelined forward returns hidden states), and XProf step windows work
as in the flax trainer; grad_accum is rejected loudly — microbatching
IS the GPipe schedule (size it via PipelineConfig.n_microbatches).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from tpufw.mesh import MeshConfig, build_mesh
from tpufw.models.llama import LlamaConfig
from tpufw.parallel.pipeline import (
    PipelineConfig,
    init_pipeline_params,
    pipeline_eval,
    pipeline_loss,
    pipeline_param_shardings,
)
from tpufw.train.metrics import Meter, StepMetrics, timed_batches
from tpufw.train.trainer import (
    TrainerConfig,
    default_optimizer,
    maybe_inloop_eval,
)


class PipeTrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any


def _pipe_state_step(
    state: PipeTrainState,
    batch: dict,
    tx,
    model_cfg: LlamaConfig,
    pipe: PipelineConfig,
    mesh,
    loss_chunk_size=None,
    loss_chunk_dtype=None,
) -> tuple[PipeTrainState, dict]:
    """TrainState-shaped step (the functional
    tpufw.parallel.pipeline.pipeline_train_step stays the public
    params/opt_state API; this private wrapper is the trainer's)."""
    if pipe.schedule == "1f1b":
        from tpufw.parallel.pipeline_1f1b import (
            pipeline_1f1b_value_and_grad,
        )

        loss, grads = pipeline_1f1b_value_and_grad(
            state.params, batch, model_cfg, pipe, mesh,
            loss_chunk_size=loss_chunk_size,
            loss_chunk_dtype=loss_chunk_dtype,
        )
    elif pipe.schedule == "interleaved":
        from tpufw.parallel.pipeline_interleaved import (
            pipeline_interleaved_value_and_grad,
        )

        loss, grads = pipeline_interleaved_value_and_grad(
            state.params, batch, model_cfg, pipe, mesh,
            loss_chunk_size=loss_chunk_size,
            loss_chunk_dtype=loss_chunk_dtype,
        )
    elif pipe.schedule == "zb1":
        from tpufw.parallel.pipeline_zb1 import (
            pipeline_zb1_value_and_grad,
        )

        loss, grads = pipeline_zb1_value_and_grad(
            state.params, batch, model_cfg, pipe, mesh,
            loss_chunk_size=loss_chunk_size,
            loss_chunk_dtype=loss_chunk_dtype,
        )
    else:
        loss, grads = jax.value_and_grad(pipeline_loss)(
            state.params, batch, model_cfg, pipe, mesh,
            loss_chunk_size, loss_chunk_dtype,
        )
    updates, new_opt = tx.update(grads, state.opt_state, state.params)
    return (
        PipeTrainState(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            opt_state=new_opt,
        ),
        {"loss": loss, "grad_norm": optax.global_norm(grads)},
    )


class PipelineTrainer:
    """Drives pipeline-parallel training with the standard tpufw surface."""

    def __init__(
        self,
        model_cfg: LlamaConfig,
        pipe: PipelineConfig,
        trainer_cfg: TrainerConfig,
        mesh_cfg: MeshConfig | None = None,
        tx: optax.GradientTransformation | None = None,
    ):
        # TrainerConfig schedule knob overrides the PipelineConfig —
        # one source of truth for workloads/manifests/autotuner, and
        # the replace keeps validate() as the single gatekeeper.
        if trainer_cfg.pipeline_schedule:
            pipe = dataclasses.replace(
                pipe,
                schedule=trainer_cfg.pipeline_schedule,
                n_virtual=(
                    trainer_cfg.pipeline_vstages
                    if trainer_cfg.pipeline_schedule == "interleaved"
                    else 1
                ),
            )
        if mesh_cfg is None:
            mesh_cfg = MeshConfig(pipe=pipe.n_stages, fsdp=-1)
        if mesh_cfg.pipe != pipe.n_stages:
            raise ValueError(
                f"mesh_cfg.pipe={mesh_cfg.pipe} != "
                f"PipelineConfig.n_stages={pipe.n_stages}"
            )
        pipe.validate(model_cfg, trainer_cfg.batch_size)
        unsupported = {
            # grad accumulation IS the GPipe schedule: n_microbatches
            # already splits the batch; a second accumulation layer
            # would just change the schedule's own knob.
            "grad_accum": trainer_cfg.grad_accum != 1,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"PipelineTrainer does not implement TrainerConfig "
                f"fields {bad}; unset them (the flax Trainer supports "
                "them all)"
            )
        self.model_cfg = model_cfg
        self.pipe = pipe
        self.cfg = trainer_cfg
        self.mesh = build_mesh(mesh_cfg)
        self.tx = tx or default_optimizer(
            lr=trainer_cfg.lr,
            warmup_steps=trainer_cfg.warmup_steps,
            total_steps=trainer_cfg.total_steps,
            mu_dtype=trainer_cfg.adam_mu_dtype,
        )
        self.state: PipeTrainState | None = None
        self._step_fn = None
        self._eval_fn = None
        self.preempted = False
        # TuneResult of the last apply_autotune (tpufw.tune.runner);
        # None until cfg.autotune resolves in run().
        self.last_tune = None
        from tpufw.obs import Telemetry

        self.telemetry = Telemetry.disabled()

    # -- state ---------------------------------------------------------

    def _init_fn(self, key):
        """ONE init body for both the abstract (restore-target) and real
        state so the two can never diverge."""
        params = init_pipeline_params(key, self.model_cfg, self.pipe)
        return PipeTrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.tx.init(params),
        )

    def _abstract_state(self) -> PipeTrainState:
        return jax.eval_shape(self._init_fn, jax.random.key(0))

    def _state_shardings(self, abstract: PipeTrainState) -> PipeTrainState:
        p_sh = pipeline_param_shardings(
            self.mesh, abstract.params,
            virtual=self.pipe.virtual_layout,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        # Optimizer moments mirror the params they track. optax state
        # trees interleave param-shaped moment trees with scalars, so
        # match by FULL shape against the stage stacks — every stage
        # stack is >=3-D with a distinct shape, so a collision would
        # need an identically-shaped replicated tensor (none exist).
        # The looked-up sharding is the param's own (pipe + tensor
        # split), so pp x tp moments shard exactly like their weights.
        stage_sharding_by_shape = {
            tuple(x.shape): s
            for x, s in zip(
                jax.tree.leaves(abstract.params["stages"]),
                jax.tree.leaves(p_sh["stages"]),
            )
        }

        def opt_shard(leaf):
            if hasattr(leaf, "shape"):
                hit = stage_sharding_by_shape.get(tuple(leaf.shape))
                if hit is not None:
                    return hit
            return rep

        return PipeTrainState(
            step=rep,
            params=p_sh,
            opt_state=jax.tree.map(opt_shard, abstract.opt_state),
        )

    def init_state(self, seed: int = 0) -> PipeTrainState:
        shardings = self._state_shardings(self._abstract_state())
        self.state = jax.jit(self._init_fn, out_shardings=shardings)(
            jax.random.key(seed)
        )
        self._shardings = shardings
        return self.state

    def maybe_restore(self) -> bool:
        if not self.cfg.checkpoint_dir:
            return False
        from tpufw.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(self.cfg.checkpoint_dir)
        try:
            if mgr.latest_step() is None:
                return False
            abstract = self._abstract_state()
            shardings = self._state_shardings(abstract)
            target = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=s
                ),
                abstract,
                shardings,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            self.state = mgr.restore(target)
            self._shardings = shardings
            return True
        finally:
            mgr.close()

    # -- loop ----------------------------------------------------------

    def _chunk_dtype(self):
        return (
            jnp.dtype(self.cfg.loss_chunk_dtype)
            if self.cfg.loss_chunk_size
            else None
        )

    def _batch_shardings(self, key) -> dict:
        """Batch-major row sharding over data x fsdp — ONE definition so
        the train and eval jits cannot disagree on batch layout."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        row = NamedSharding(self.mesh, P(("data", "fsdp")))
        return {k: row for k in key}

    def _compiled_step(self, batch: dict):
        key = tuple(sorted(batch.keys()))
        if self._step_fn is None:
            self._step_fn = {}
        if key not in self._step_fn:
            batch_sh = self._batch_shardings(key)
            self._step_fn[key] = jax.jit(
                partial(
                    _pipe_state_step,
                    tx=self.tx,
                    model_cfg=self.model_cfg,
                    pipe=self.pipe,
                    mesh=self.mesh,
                    loss_chunk_size=self.cfg.loss_chunk_size,
                    loss_chunk_dtype=self._chunk_dtype(),
                ),
                in_shardings=(self._shardings, batch_sh),
                out_shardings=(self._shardings, None),
                donate_argnums=(0,),
            )
        return self._step_fn[key]

    def _compiled_eval(self, batch: dict):
        key = tuple(sorted(batch.keys()))
        if self._eval_fn is None:
            self._eval_fn = {}
        if key not in self._eval_fn:
            batch_sh = self._batch_shardings(key)
            eval_pipe, eval_fn = self.pipe, pipeline_eval
            if self.pipe.virtual_layout:
                # The forward-only eval path speaks the canonical
                # [S, lps] layout; regroup INSIDE the jit (a reshape +
                # one resharding collective, amortized per eval batch)
                # and run the vanilla schedule.
                from tpufw.parallel.pipeline import to_canonical_stages

                eval_pipe = dataclasses.replace(
                    self.pipe, schedule="gpipe", n_virtual=1
                )

                def eval_fn(params, batch, **kw):
                    params = dict(params)
                    params["stages"] = to_canonical_stages(
                        params["stages"], self.pipe.n_stages
                    )
                    return pipeline_eval(params, batch, **kw)

            self._eval_fn[key] = jax.jit(
                partial(
                    eval_fn,
                    cfg=self.model_cfg,
                    pipe=eval_pipe,
                    mesh=self.mesh,
                    loss_chunk_size=self.cfg.loss_chunk_size,
                    loss_chunk_dtype=self._chunk_dtype(),
                ),
                in_shardings=(self._shardings.params, batch_sh),
                out_shardings=None,
            )
        return self._eval_fn[key]

    def evaluate(
        self, data: Iterator[dict], n_batches: Optional[int] = None
    ) -> dict:
        """Token-weighted held-out loss + perplexity through the
        forward-only pipeline — same reporting surface as
        Trainer.evaluate, so curves are directly comparable."""
        if self.state is None:
            raise RuntimeError("evaluate() before init_state()/restore")
        from tpufw.train.trainer import globalize_batch, run_evaluation

        return run_evaluation(
            data,
            n_batches,
            lambda b: self._compiled_eval(b)(self.state.params, b),
            lambda b: globalize_batch(self.mesh, b),
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
        owns_shutdown = False
        self.preempted = False
        from tpufw.obs import Telemetry

        tel = self.telemetry = Telemetry.create(
            telemetry_dir=self.cfg.telemetry_dir,
            metrics_port=self.cfg.metrics_port,
            straggler_factor=self.cfg.straggler_factor,
        )
        from tpufw.train.trainer import _mesh_label

        tel.set_run_info(
            backend=jax.default_backend(),
            mesh=_mesh_label(self.mesh),
            model=f"pipeline:{type(self.model_cfg).__name__}",
        )
        if self.cfg.autotune != "off":
            # Resolve BEFORE state init: a schedule winner changes the
            # stage layout ([S,...] vs [v,S,...]) the state is built in,
            # so tuning first skips the re-layout path entirely.
            from tpufw.tune.runner import apply_autotune

            with tel.tracer.span("tune"):
                apply_autotune(self, events=tel.events, perf=tel.perf)
        if self.state is None:
            self.init_state()
        if tel.perf.enabled:
            # programs.json keyed like the tune winner cache (same
            # discipline as Trainer.run).
            from tpufw.tune.runner import _trainer_cache_key

            tel.perf.set_key(_trainer_cache_key(self))
        tel.record_config(
            {
                "trainer": dataclasses.asdict(self.cfg),
                "pipeline": dataclasses.asdict(self.pipe),
            }
        )
        meter = Meter(
            tokens_per_step=self.cfg.batch_size * (self.cfg.seq_len - 1),
            flops_per_token=model_flops_per_token,
            n_chips=len(self.mesh.devices.flatten()),
            registry=tel.registry,
        )
        # Analytic schedule bubble for THIS run's (schedule, S, v, M)
        # — a constant, so one set at run start; the bench tier pairs
        # it with the measured value (docs/OBSERVABILITY.md).
        if tel.registry is not None:
            tel.registry.gauge(
                "tpufw_pipeline_bubble_fraction",
                "Analytic pipeline bubble fraction of the active schedule",
            ).set(self.pipe.bubble_fraction())
        ckpt = None
        if self.cfg.checkpoint_dir:
            from tpufw.train.checkpoint import CheckpointManager

            ckpt = CheckpointManager(
                self.cfg.checkpoint_dir,
                save_interval_steps=self.cfg.checkpoint_every,
                events=tel.events,
                tracer=tel.tracer,
            )
        from tpufw.train.trainer import globalize_batch

        from tpufw.obs.perf import resolve_profile_window
        from tpufw.train.preemption import checkpoint_stop, owned_shutdown
        from tpufw.utils.profiling import StepProfiler

        # TPUFW_PROFILE_STEPS=a:b overrides the config window (see
        # Trainer.run).
        prof = StepProfiler(
            *resolve_profile_window(
                self.cfg.profile_dir,
                self.cfg.profile_start,
                self.cfg.profile_stop,
                telemetry_dir=self.cfg.telemetry_dir,
            )
        )
        shutdown, owns_shutdown = owned_shutdown(
            shutdown,
            self.cfg.handle_preemption,
            self.cfg.preemption_sync_every,
            events=tel.events,
        )
        # Global step budget: a restored run finishes the remainder.
        start_step = int(self.state.step)
        remaining = max(0, self.cfg.total_steps - start_step)
        se = max(1, self.cfg.sync_every)
        window_n, window_wait = 0, 0.0
        history: list[StepMetrics] = []
        tel.events.emit(
            "run_start",
            workload="train_pipeline",
            start_step=start_step,
            total_steps=self.cfg.total_steps,
            batch_size=self.cfg.batch_size,
            seq_len=self.cfg.seq_len,
            sync_every=se,
            n_chips=len(self.mesh.devices.flatten()),
        )

        def record_window(py_step, loss):
            # Same shape as Trainer.run's: meter.stop (the float(loss)
            # barrier) + step event + skew allgather, all on the one
            # host sync per window.
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
                # Average per-tick wall of this window, derived
                # host-side (the scan's ticks run inside the jit where
                # the host tracer cannot see them). Against the chip
                # profile this localizes schedule stalls to a tick
                # budget without an XProf round trip.
                tel.tracer.complete(
                    "pipeline_tick",
                    sm.step_time_s / max(1, self.pipe.n_ticks()),
                )
                # Static FLOPs x measured wall -> per-program MFU
                # (tpufw_program_mfu) and roofline attribution.
                tel.perf.record_wall("pipeline_step", sm.step_time_s)
            return sm

        try:
            for i, (wait, batch) in enumerate(timed_batches(data)):
                if i >= remaining:
                    break
                tel.tracer.complete("data_fetch", wait)
                # Watchdog window: dispatch through host sync (same
                # contract as Trainer.run — see the comment there).
                tel.watchdog.arm()
                with tel.tracer.span("step_dispatch"):
                    prof.maybe_start(i)
                    if window_n == 0:
                        meter.start()
                    batch = globalize_batch(self.mesh, batch)
                    step_fn = self._compiled_step(batch)
                    # Cost harvest (first time per program only):
                    # abstract lower, so donation is untouched.
                    tel.perf.observe_jit(
                        "pipeline_step", step_fn, (self.state, batch)
                    )
                    with prof.step(i):
                        self.state, m = step_fn(self.state, batch)
                        window_n += 1
                        window_wait += wait
                        py_step = start_step + i + 1
                        # Step 1, multiples of sync_every, and the last.
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
                # Gang-consistent preemption stop (tpufw.train.preemption).
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
            # Iterator exhausted mid-window: flush the open window.
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
