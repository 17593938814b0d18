"""Device mesh + named-axis sharding: tpufw's communication backend.

The reference wires no communication backend at all — it is single-node,
single-GPU, and the north star names NCCL env-var wiring only as the thing to
*replace* (SURVEY.md §2c). tpufw's replacement is the TPU-idiomatic one: a
``jax.sharding.Mesh`` with five named axes, GSPMD/pjit sharding annotations,
and XLA-inserted collectives riding ICI. No user-level comm code exists
anywhere in this framework; every parallelism strategy is a (logical axis ->
mesh axis) rule set consumed here.

Axes
----
- ``data``     — pure data parallelism (gradient psum across replicas)
- ``pipe``     — pipeline parallelism over the layer stack (GPipe schedule,
                 point-to-point ppermute handoffs — tpufw.parallel.pipeline)
- ``fsdp``     — data parallelism with parameter/optimizer sharding (ZeRO-3
                 style: XLA all-gathers params per layer, reduce-scatters grads)
- ``sequence`` — context parallelism for long sequences (ring attention /
                 all-to-all, see tpufw.parallel)
- ``tensor``   — Megatron-style tensor parallelism inside a host's ICI domain
- ``expert``   — expert parallelism for MoE (Mixtral, BASELINE config 5)

Any axis of size 1 is free; configs 1-5 are all instances of one MeshConfig.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_DATA = "data"
AXIS_PIPE = "pipe"
AXIS_FSDP = "fsdp"
AXIS_SEQUENCE = "sequence"
AXIS_TENSOR = "tensor"
AXIS_EXPERT = "expert"

# Order matters: leftmost axes get the slowest-varying device dimension, so
# `tensor` (rightmost) stays within the densest ICI neighborhood and `data`
# (leftmost) spans hosts/DCN — the layout the scaling playbook prescribes.
# `pipe` sits next to `data`: stage handoffs are low-bandwidth point-to-point
# activations, the cheapest collective to push toward the sparse end.
MESH_AXES: tuple[str, ...] = (
    AXIS_DATA,
    AXIS_PIPE,
    AXIS_FSDP,
    AXIS_EXPERT,
    AXIS_SEQUENCE,
    AXIS_TENSOR,
)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for the five named mesh axes. -1 on at most one axis = "fill".

    ``dcn_data`` > 1 declares a multi-slice deployment: that many ICI
    slices joined over DCN, with pure data parallelism across slices (the
    only parallelism whose collectives amortize over DCN's bandwidth).
    The other five sizes then describe ONE slice; the built mesh's
    ``data`` axis has size ``dcn_data * data`` with DCN as the
    slowest-varying dimension, so every other axis's collectives stay
    inside a slice's ICI domain.
    """

    data: int = 1
    pipe: int = 1
    fsdp: int = -1
    expert: int = 1
    sequence: int = 1
    tensor: int = 1
    dcn_data: int = 1

    def sizes(self, n_devices: int) -> dict[str, int]:
        """Per-slice axis sizes (n_devices = devices in one slice)."""
        raw = {
            AXIS_DATA: self.data,
            AXIS_PIPE: self.pipe,
            AXIS_FSDP: self.fsdp,
            AXIS_EXPERT: self.expert,
            AXIS_SEQUENCE: self.sequence,
            AXIS_TENSOR: self.tensor,
        }
        bad = [k for k, v in raw.items() if v != -1 and v < 1]
        if bad:
            raise ValueError(f"axis sizes must be >=1 or -1 (fill), got {raw}")
        fills = [k for k, v in raw.items() if v == -1]
        if len(fills) > 1:
            raise ValueError(f"at most one axis may be -1, got {fills}")
        fixed = math.prod(v for v in raw.values() if v != -1)
        if fills:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {raw}"
                )
            raw[fills[0]] = n_devices // fixed
            fixed = n_devices
        if fixed != n_devices:
            raise ValueError(
                f"mesh {raw} needs {fixed} devices, have {n_devices}"
            )
        return raw

    def model_parallel_size(self, n_devices: int) -> int:
        """Devices holding one replica's model shards (excl. data/fsdp)."""
        sizes = self.sizes(n_devices)
        return (
            sizes[AXIS_TENSOR]
            * sizes[AXIS_SEQUENCE]
            * sizes[AXIS_EXPERT]
            * sizes[AXIS_PIPE]
        )


def build_mesh(
    config: MeshConfig | None = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the named device mesh for a MeshConfig.

    Uses ``mesh_utils.create_device_mesh`` when the devices are real TPUs so
    the physical ICI topology is respected; CPU/virtual meshes (tests,
    dryrun_multichip) have no topology and are a plain reshape.
    """
    config = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if config.dcn_data > 1:
        if len(devices) % config.dcn_data:
            raise ValueError(
                f"{len(devices)} devices not divisible into "
                f"{config.dcn_data} DCN slices"
            )
        sizes = config.sizes(len(devices) // config.dcn_data)
        shape = tuple(sizes[a] for a in MESH_AXES)
        dcn_shape = tuple(
            config.dcn_data if a == AXIS_DATA else 1 for a in MESH_AXES
        )
        if devices[0].platform == "tpu":
            # Real slices: let a genuine misconfiguration (wrong slice
            # count / ICI-incompatible shape) raise — a silent reshape
            # would put per-step collectives over DCN.
            dev_array = mesh_utils.create_hybrid_device_mesh(
                shape, dcn_shape, devices=devices
            )
        else:
            # CPU/virtual devices carry no slice_index: emulate with DCN as
            # the slowest-varying dim (same layout the hybrid mesh yields).
            combined = tuple(a * b for a, b in zip(dcn_shape, shape))
            dev_array = np.array(devices).reshape(combined)
        return Mesh(dev_array, MESH_AXES)
    sizes = config.sizes(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    if devices[0].platform == "tpu":
        # A shape the ICI topology cannot serve raises here — a plain
        # reshape would run, with collectives on links that do not exist.
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


# Logical axis names used by every model in tpufw.models. Sharding strategy
# changes are rule edits here, never model edits.
def logical_axis_rules(
    *,
    fsdp_also_data: bool = True,
) -> tuple[tuple[str, tuple[str, ...] | None], ...]:
    """(logical axis -> mesh axes) rules for flax logical partitioning.

    ``batch`` spans every data-like axis; parameters shard their largest dim
    over ``fsdp`` (ZeRO-3) and their model-parallel dim over ``tensor``;
    ``expert`` maps experts onto the expert axis; activations' sequence dim
    maps onto ``sequence`` for context parallelism.
    """
    batch_axes: tuple[str, ...] = (
        (AXIS_DATA, AXIS_FSDP) if fsdp_also_data else (AXIS_DATA,)
    )
    return (
        ("batch", batch_axes),
        ("act_seq", (AXIS_SEQUENCE,)),
        ("act_embed", None),
        ("act_heads", (AXIS_TENSOR,)),
        ("act_mlp", (AXIS_TENSOR,)),
        ("act_vocab", (AXIS_TENSOR,)),
        # Parameter axes.
        ("embed", (AXIS_FSDP,)),
        ("mlp", (AXIS_TENSOR,)),
        ("heads", (AXIS_TENSOR,)),
        ("q_heads", (AXIS_TENSOR,)),
        ("kv_heads", (AXIS_TENSOR,)),
        ("head_dim", None),
        ("lora", None),  # LoRA rank axis: tiny, replicated
        # MLA (deepseek) latent axes: small next to embed/mlp dims;
        # replicated keeps the absorbed-decode einsums local.
        ("kv_latent", None),
        ("q_latent", None),
        ("vocab", (AXIS_TENSOR,)),
        ("expert", (AXIS_EXPERT,)),
        ("expert_mlp", (AXIS_TENSOR,)),
        ("norm", None),
        # Conv/ResNet axes.
        ("conv_h", None),
        ("conv_w", None),
        ("conv_in", None),
        ("conv_out", (AXIS_FSDP,)),
    )


def mesh_sharding(
    mesh: Mesh, spec: PartitionSpec | None = None
) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else PartitionSpec())
