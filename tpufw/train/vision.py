"""Image-classification training (ResNet-50, BASELINE config 2).

Separate from the LM trainer because vision models carry mutable batch-norm
statistics alongside params; everything else (mesh, logical shardings, MFU
metering) is shared machinery.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax import struct
from flax.core import meta
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpufw.mesh import MeshConfig, build_mesh
from tpufw.parallel.context import use_mesh
from tpufw.train.metrics import Meter, StepMetrics, timed_batches
from tpufw.train.trainer import state_shardings


class VisionTrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)


def vision_train_step(state: VisionTrainState, batch: dict):
    """One supervised step: images [B,H,W,C], labels [B]."""

    def loss_fn(params):
        logits, mutated = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            batch["images"],
            train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]
        ).mean()
        # Stat-free models (ViT) mutate nothing: keep the empty tree.
        return loss, (logits, mutated.get("batch_stats", state.batch_stats))

    (loss, (logits, new_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(state.params)
    updates, new_opt = state.tx.update(grads, state.opt_state, state.params)
    accuracy = jnp.mean(
        (jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32)
    )
    new_state = state.replace(
        step=state.step + 1,
        params=optax.apply_updates(state.params, updates),
        batch_stats=new_stats,
        opt_state=new_opt,
    )
    return new_state, {"loss": loss, "accuracy": accuracy}


@dataclasses.dataclass
class VisionTrainerConfig:
    batch_size: int = 256
    image_size: int = 224
    num_classes: int = 1000
    total_steps: int = 100
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    warmup_steps: int = 5
    # Orbax checkpoint/resume (None = off) — same elastic-recovery
    # contract as the LM TrainerConfig.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    # SIGTERM → gang-consistent stop → forced final checkpoint
    # (tpufw.train.preemption); same semantics as TrainerConfig.
    handle_preemption: bool = True
    preemption_sync_every: int = 1
    # Steps between host syncs (see TrainerConfig.sync_every): ResNet
    # steps are short (~100-300 ms), so per-step loss fetches serialize
    # against backend round trips; >1 dispatches a window per sync.
    sync_every: int = 1


class VisionTrainer:
    """SGD+momentum ResNet trainer over the tpufw mesh."""

    def __init__(
        self,
        model: nn.Module,
        cfg: VisionTrainerConfig,
        mesh_cfg: MeshConfig | None = None,
        mesh: Mesh | None = None,
    ):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else build_mesh(mesh_cfg)
        schedule = optax.warmup_cosine_decay_schedule(
            0.0,
            cfg.lr,
            cfg.warmup_steps,
            max(cfg.total_steps, cfg.warmup_steps + 1),
        )
        def decay_mask(params):
            # Standard ResNet recipe: no decay on BatchNorm scales/biases
            # (any rank-1 param).
            return jax.tree.map(lambda p: p.ndim > 1, params)

        self.tx = optax.chain(
            optax.add_decayed_weights(cfg.weight_decay, mask=decay_mask),
            optax.sgd(schedule, momentum=cfg.momentum, nesterov=True),
        )
        self.state = None
        self.state_sharding = None
        self._compiled = None
        self.preempted = False

    def _abstract_state(self, rng):
        imgs = jnp.zeros(
            (
                self.cfg.batch_size,
                self.cfg.image_size,
                self.cfg.image_size,
                3,
            ),
            jnp.float32,
        )

        def init_fn(rng):
            variables = self.model.init(rng, imgs, train=True)
            return VisionTrainState(
                step=jnp.zeros((), jnp.int32),
                params=variables["params"],
                # BN-free models (ViT) simply carry an empty tree here.
                batch_stats=variables.get("batch_stats", {}),
                opt_state=self.tx.init(variables["params"]),
                apply_fn=self.model.apply,
                tx=self.tx,
            )

        return init_fn, jax.eval_shape(init_fn, rng)

    def init_state(self, seed: int = 0) -> VisionTrainState:
        rng = jax.random.key(seed)
        init_fn, abstract = self._abstract_state(rng)
        self.state_sharding = state_shardings(abstract, self.mesh)
        with use_mesh(self.mesh):
            # tpulint: disable=TPU003 — _abstract_state only
            # eval_shape's rng (abstract, no randomness drawn); this
            # jitted init is the key's one real use.
            self.state = jax.jit(
                init_fn, out_shardings=self.state_sharding
            )(rng)
        self.state = meta.unbox(self.state)
        self.state_sharding = meta.unbox(self.state_sharding)
        return self.state

    def maybe_restore(self) -> bool:
        """Resume from the latest checkpoint in cfg.checkpoint_dir, if any
        — same pod-restart resume contract as the LM Trainer, without
        materializing a throwaway init."""
        if not self.cfg.checkpoint_dir:
            return False
        from tpufw.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(self.cfg.checkpoint_dir)
        try:
            if mgr.latest_step() is None:
                return False
            rng = jax.random.key(0)
            _, boxed = self._abstract_state(rng)
            self.state_sharding = meta.unbox(
                state_shardings(boxed, self.mesh)
            )
            abstract = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                meta.unbox(boxed),
                self.state_sharding,
            )
            self.state = mgr.restore(abstract)
            return True
        finally:
            mgr.close()

    def compiled_step(self):
        if self._compiled is None:
            row = NamedSharding(self.mesh, P(("data", "fsdp")))
            self._compiled = jax.jit(
                vision_train_step,
                in_shardings=(
                    self.state_sharding,
                    {"images": row, "labels": row},
                ),
                out_shardings=(self.state_sharding, None),
                donate_argnums=(0,),
            )
        return self._compiled

    def run(
        self,
        data: Iterator[dict],
        flops_per_image: Optional[float] = None,
        on_metrics: Callable[[StepMetrics], None] | None = None,
        shutdown: "GracefulShutdown | None" = None,
    ) -> list[StepMetrics]:
        if self.state is None:
            self.init_state()
        step_fn = self.compiled_step()
        meter = Meter(
            tokens_per_step=self.cfg.batch_size,  # "tokens" = images here
            flops_per_token=flops_per_image or 0.0,
            n_chips=len(self.mesh.devices.flatten()),
        )
        owns_shutdown = False
        self.preempted = False
        ckpt = None
        if self.cfg.checkpoint_dir:
            from tpufw.train.checkpoint import CheckpointManager

            ckpt = CheckpointManager(
                self.cfg.checkpoint_dir,
                save_interval_steps=self.cfg.checkpoint_every,
            )
        from tpufw.train.preemption import checkpoint_stop, owned_shutdown

        shutdown, owns_shutdown = owned_shutdown(
            shutdown,
            self.cfg.handle_preemption,
            self.cfg.preemption_sync_every,
        )
        # Global step budget: a restored run finishes the remainder.
        start_step = int(self.state.step)
        remaining = max(0, self.cfg.total_steps - start_step)
        se = max(1, self.cfg.sync_every)
        window_n, window_wait = 0, 0.0
        from tpufw.train.trainer import globalize_batch

        history = []
        try:
            with use_mesh(self.mesh):
                for i, (wait, batch) in enumerate(timed_batches(data)):
                    if i >= remaining:
                        break
                    batch = globalize_batch(self.mesh, batch)
                    if window_n == 0:
                        meter.start()
                    self.state, m = step_fn(self.state, batch)
                    window_n += 1
                    window_wait += wait
                    py_step = start_step + i + 1
                    # Step 1 (compile boundary), MULTIPLES of
                    # sync_every (so aligned checkpoint_every fires),
                    # and the last step.
                    if not (
                        i == 0
                        or py_step % se == 0
                        or i + 1 == remaining
                    ):
                        continue
                    loss = m["loss"]  # Meter.stop float()s it: the barrier
                    sm = meter.stop(
                        py_step, loss,
                        data_wait_s=window_wait, n_steps=window_n,
                    )
                    window_n, window_wait = 0, 0.0
                    history.append(sm)
                    if on_metrics:
                        on_metrics(sm)
                    if ckpt is not None:
                        ckpt.save(py_step, self.state)
                    # Gang-consistent preemption stop (preemption.py).
                    if checkpoint_stop(
                        shutdown, ckpt, py_step, self.state
                    ):
                        self.preempted = True
                        break
                # Iterator exhausted mid-window: flush the open window.
                if window_n:
                    loss = m["loss"]  # Meter.stop float()s it: the barrier
                    sm = meter.stop(
                        py_step, loss,
                        data_wait_s=window_wait, n_steps=window_n,
                    )
                    history.append(sm)
                    if on_metrics:
                        on_metrics(sm)
                    if ckpt is not None:
                        ckpt.save(py_step, self.state)
        finally:
            if ckpt is not None:
                ckpt.wait()
                ckpt.close()
            if owns_shutdown:
                shutdown.uninstall()
        return history


def synthetic_images(
    batch_size: int,
    image_size: int = 224,
    num_classes: int = 1000,
    seed: int = 0,
    pool: int = 4,
    on_device: bool = False,
) -> Iterator[dict]:
    """Cycles a small pre-generated batch pool: generating 38 MB of fresh
    gaussians per step costs more host time than the TPU step itself
    (measured 139 ms vs 174 ms) and would corrupt throughput numbers.

    ``on_device`` stages the pool onto the default device ONCE and
    yields committed jax.Arrays, so the step's jit re-uses them instead
    of re-uploading ~150 MB per step (bench r3: 14.7 img/s
    transfer-bound vs compute at batch 256)."""
    rng = np.random.default_rng(seed)
    batches = [
        {
            "images": rng.standard_normal(
                (batch_size, image_size, image_size, 3)
            ).astype(np.float32),
            "labels": rng.integers(
                0, num_classes, (batch_size,), dtype=np.int64
            ),
        }
        for _ in range(pool)
    ]
    if on_device:
        import jax

        batches = [
            {k: jax.device_put(v) for k, v in b.items()} for b in batches
        ]
    i = 0
    while True:
        yield batches[i % pool]
        i += 1
