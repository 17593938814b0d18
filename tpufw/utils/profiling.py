"""Profiling + persistent compile cache — SURVEY.md §5's tracing subsystem
and the §7.4 cold-start lever.

The reference's only observability channel is ``kubectl logs`` and a
``watch`` loop (reference ``README.md:282-286, 331-335``); there is no
profiler to port. The TPU-native build gets two real mechanisms:

- **XProf traces**: ``StepProfiler`` wraps ``jax.profiler`` so the trainer
  captures a window of steps (skipping compile-dominated step 0) into a
  TensorBoard-loadable directory. Per-step named scopes come for free via
  ``jax.profiler.StepTraceAnnotation``.
- **Persistent XLA compile cache**: first-compile dominates TPU pod
  cold-start -> first-step (the BASELINE metric); pointing the cache at a
  PV/GCS path makes recompiles across pod restarts near-free. This is the
  TPU analog of the reference's image-pull/reboot wall-clock sink
  (``README.md:70-74, 202``).
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

# Where the cache lives when JAX_COMPILATION_CACHE_DIR does not say: one
# fixed directory in the checkout. The path is part of what a later
# process must reproduce to hit, so it never carries a pid, a time or a
# temp name.
_DEFAULT_COMPILE_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".xla-cache"
)


def machine_fingerprint() -> str:
    """Short stable id of this host's CPU architecture + feature flags —
    the machine half of the autotuner's winner-cache key
    (tpufw.tune.cache): a tuned config is only valid on the machine
    class that measured it."""
    import hashlib
    import platform

    bits = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 "flags", arm64 "Features": first hit describes
                # every core uniformly on the machines we care about.
                if line.startswith(("flags", "Features")):
                    bits.append(" ".join(sorted(line.split(":", 1)[1].split())))
                    break
    except OSError:
        pass
    return hashlib.sha256(" ".join(bits).encode()).hexdigest()[:10]


def enable_compile_cache() -> str:
    """Turn on XLA's persistent compilation cache and return its directory.

    The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
    is set jax already holds it and this function sets none (a PV mount
    in the deploy manifests, the chip machine's own cache). Otherwise the
    cache goes to ``<checkout>/.xla-cache``. Every workload entry point
    calls this before its first compile; it is the one place the cache
    is configured.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
        # jax binds the persistent cache to the first directory it
        # initializes with; a compile before this call would have bound
        # "none", and re-pointing the config alone would not rebind it.
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        _cc.reset_cache()
    # Cache everything: tiny compiles are still worth skipping on restart.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def compile_cache_is_warm(path: str) -> bool:
    """True when ``path`` already holds cache entries: the bit that
    decides most of a run's start-to-first-step time."""
    try:
        return bool(os.listdir(path))
    except OSError:
        return False


class StepProfiler:
    """Captures steps [start, stop) of a train loop into an XProf trace.

    Usage from a step loop::

        prof = StepProfiler(dir, start_step=3, stop_step=6)
        for i, batch in enumerate(data):
            prof.maybe_start(i)
            with prof.step(i):
                run_step(batch)
            prof.maybe_stop(i)

    Inactive (``dir=None``) it is free: every method returns immediately.
    Start defaults past step 0 so the capture window holds steady-state
    steps, not the XLA compile.
    """

    def __init__(
        self,
        trace_dir: Optional[str],
        start_step: int = 3,
        stop_step: int = 6,
    ):
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.stop_step = stop_step
        self._active = False

    def maybe_start(self, step: int) -> None:
        if self.trace_dir and not self._active and step == self.start_step:
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self._active = True

    def step(self, step: int):
        if self._active:
            return jax.profiler.StepTraceAnnotation("train", step_num=step)
        import contextlib

        return contextlib.nullcontext()

    def maybe_stop(self, step: int) -> None:
        if self._active and step + 1 >= self.stop_step:
            # Block so the trace includes completed device work.
            jax.effects_barrier()
            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        if self._active:
            jax.effects_barrier()
            jax.profiler.stop_trace()
            self._active = False
