"""Worker subprocess for the multi-process jax.distributed test.

Forces the CPU backend, bootstraps via tpufw.cluster from TPUFW_* env,
and verifies a cross-process psum. Prints PSUM_OK:<value> on success.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpufw.cluster import initialize_cluster, resolve_cluster_env  # noqa: E402


def main():
    cfg = resolve_cluster_env()
    initialize_cluster(cfg, timeout_s=60)
    assert jax.process_count() == cfg.num_processes, (
        jax.process_count(),
        cfg,
    )
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()  # global devices across processes
    mesh = Mesh(devices, ("data",))

    # Each process contributes its local shard; the jitted sum needs a
    # cross-process collective to produce the global total.
    local = jnp.ones((1, 4)) * (cfg.process_id + 1)
    arr = jax.make_array_from_single_device_arrays(
        (len(devices), 4),
        NamedSharding(mesh, P("data")),
        [jax.device_put(local, jax.local_devices()[0])],
    )

    @jax.jit
    def total(a):
        return a.sum()

    out = float(total(arr))
    expected = 4.0 * sum(i + 1 for i in range(cfg.num_processes))
    assert abs(out - expected) < 1e-6, (out, expected)
    print(f"PSUM_OK:{out}", flush=True)


if __name__ == "__main__":
    main()
