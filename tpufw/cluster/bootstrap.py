"""Multi-host bootstrap: pod environment -> ``jax.distributed.initialize``.

This is the TPU-native replacement for NCCL env-var wiring (SURVEY.md §2c,
§5): each JobSet worker pod derives (coordinator_address, num_processes,
process_id) from its environment, calls ``jax.distributed.initialize``, and
from then on XLA emits ICI collectives inside the slice — DCN carries only
this bootstrap handshake.

Resolution order (first match wins):
1. Explicit ``TPUFW_*`` variables — escape hatch for tests/bare-metal.
2. JobSet + headless-Service environment (the deploy/ manifests set these
   from the downward API): JOBSET_NAME, REPLICATED_JOB_NAME,
   JOB_COMPLETION_INDEX, TPUFW_WORKERS_PER_SLICE, TPUFW_COORDINATOR_SVC.
3. GKE TPU node-pool conventions: TPU_WORKER_ID, TPU_WORKER_HOSTNAMES
   (comma-separated; worker 0 is the coordinator).
4. Single process (no distributed init) — BASELINE configs 1-3.

Worker identity must be *stable across pod restarts* (SURVEY.md §7.4 #2):
every source above is an index assigned by the controller (completion index
/ worker id), never a hostname hash, so a restarted pod rejoins with the
same process_id and the coordinator's barrier can release.
"""

from __future__ import annotations

# tpulint: disable-file=TPU004 — this module reads through an
# injectable ``env: Mapping`` (tests pass dicts), and its resolution
# order deliberately mixes TPUFW_* escape hatches with JobSet/GKE
# variables the typed helpers don't model. The knobs are cataloged in
# docs/ENV.md; the helper round-trip requirement stops at this
# process-bootstrap boundary.

import dataclasses
import os
import time
from typing import Mapping, Optional

DEFAULT_COORDINATOR_PORT = 8476


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    coordinator_address: Optional[str]  # None => single-process
    num_processes: int = 1
    process_id: int = 0
    source: str = "single"

    @property
    def is_distributed(self) -> bool:
        return self.coordinator_address is not None and self.num_processes > 1


def resolve_cluster_env(
    env: Optional[Mapping[str, str]] = None,
) -> ClusterConfig:
    env = os.environ if env is None else env

    if "TPUFW_COORDINATOR" in env:
        if "TPUFW_NUM_PROCESSES" not in env:
            # Same silent-gang-split hazard as the JobSet branch below: a
            # coordinator with a defaulted process count of 1 would no-op
            # the distributed init on every pod. Fail loudly instead.
            raise ValueError(
                "TPUFW_COORDINATOR is set but TPUFW_NUM_PROCESSES is "
                "missing — set it to the gang size (and TPUFW_PROCESS_ID "
                "per worker)"
            )
        return ClusterConfig(
            coordinator_address=env["TPUFW_COORDINATOR"],
            num_processes=int(env["TPUFW_NUM_PROCESSES"]),
            process_id=int(env.get("TPUFW_PROCESS_ID", "0")),
            source="explicit",
        )

    if "JOBSET_NAME" in env and "JOB_COMPLETION_INDEX" in env:
        if "TPUFW_WORKERS_PER_SLICE" not in env:
            # Defaulting to 1 would silently turn an N-pod gang into N
            # independent single-process runs; fail loudly instead.
            raise ValueError(
                "JobSet environment detected (JOBSET_NAME set) but "
                "TPUFW_WORKERS_PER_SLICE is missing — set it to the "
                "replicated job's worker count (deploy/ manifests do)"
            )
        num = int(env["TPUFW_WORKERS_PER_SLICE"])
        pid = int(env["JOB_COMPLETION_INDEX"])
        svc = env.get("TPUFW_COORDINATOR_SVC")
        if svc is None:
            # Headless-Service DNS for pod 0 of the replicated job:
            # <jobset>-<job>-0-0.<jobset> is the JobSet pod DNS convention.
            job = env.get("REPLICATED_JOB_NAME", "worker")
            svc = (
                f"{env['JOBSET_NAME']}-{job}-0-0.{env['JOBSET_NAME']}"
            )
        port = int(env.get("TPUFW_COORDINATOR_PORT", DEFAULT_COORDINATOR_PORT))
        return ClusterConfig(
            coordinator_address=f"{svc}:{port}",
            num_processes=num,
            process_id=pid,
            source="jobset",
        )

    if "TPU_WORKER_ID" in env and "TPU_WORKER_HOSTNAMES" in env:
        hosts = [
            h.strip()
            for h in env["TPU_WORKER_HOSTNAMES"].split(",")
            if h.strip()
        ]
        if not hosts:
            raise ValueError(
                "TPU_WORKER_HOSTNAMES is set but contains no hostnames"
            )
        port = int(env.get("TPUFW_COORDINATOR_PORT", DEFAULT_COORDINATOR_PORT))
        return ClusterConfig(
            coordinator_address=f"{hosts[0]}:{port}",
            num_processes=len(hosts),
            process_id=int(env["TPU_WORKER_ID"]),
            source="gke_tpu",
        )

    return ClusterConfig(coordinator_address=None)


def initialize_cluster(
    config: Optional[ClusterConfig] = None,
    timeout_s: float = 300.0,
) -> ClusterConfig:
    """Idempotent ``jax.distributed.initialize`` from the resolved env.

    Must run before any backend use. Single-process configs no-op, so
    workloads call this unconditionally (configs 1-3 need no changes to
    become config 4).
    """
    import jax

    config = config or resolve_cluster_env()
    if not config.is_distributed:
        return config
    if jax.distributed.is_initialized():
        return config
    if config.process_id >= config.num_processes or config.process_id < 0:
        raise ValueError(
            f"process_id {config.process_id} out of range for "
            f"{config.num_processes} processes"
        )
    deadline = time.monotonic() + timeout_s
    last_err: Exception | None = None
    # Retry: during gang (re)starts the coordinator pod may come up last;
    # failing hard here would turn one slow pod into a crash loop.
    # tpulint: disable=TPU016 — intentional: every host loops on the SAME
    # rendezvous until it succeeds; initialize() carries its own timeout,
    # so a host whose clock runs out raises instead of silently diverging.
    while time.monotonic() < deadline:
        try:
            jax.distributed.initialize(
                coordinator_address=config.coordinator_address,
                num_processes=config.num_processes,
                process_id=config.process_id,
            )
            return config
        except RuntimeError as e:
            msg = str(e).lower()
            # jax has raised both "already initialized" and "should only be
            # called once" for a repeat initialize across versions.
            if "already initialized" in msg or "called once" in msg:
                return config
            last_err = e
            time.sleep(min(5.0, max(0.5, deadline - time.monotonic())))
        except Exception as e:  # connection errors surface as various types
            last_err = e
            time.sleep(min(5.0, max(0.5, deadline - time.monotonic())))
    raise TimeoutError(
        f"jax.distributed.initialize failed for {config}: {last_err}"
    )
