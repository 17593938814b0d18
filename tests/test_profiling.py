"""Profiling + compile-cache subsystem (SURVEY.md §5 tracing; §7.4 lever)."""

import os

import jax
import jax.numpy as jnp

from tpufw.utils.profiling import StepProfiler, enable_compile_cache


_CACHE_PROBE = """
import json, sys
import jax
from tpufw.utils.profiling import enable_compile_cache
updates = []
real = jax.config.update
def spy(name, value):
    updates.append(name)
    return real(name, value)
jax.config.update = spy
got = enable_compile_cache()
print(json.dumps({
    "returned": got,
    "config": jax.config.jax_compilation_cache_dir,
    "dir_set_in_code": "jax_compilation_cache_dir" in updates,
    "min_compile_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _probe_compile_cache(env_dir):
    """enable_compile_cache() in a fresh process: jax reads
    JAX_COMPILATION_CACHE_DIR at import, so only a child shows what an
    entry point really gets."""
    import json
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        env=env, cwd=repo, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_dir_from_environment_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    got = _probe_compile_cache(placed)
    # Exactly the directory that was set: nothing appended, and the
    # helper never re-points jax's config — only the thresholds move.
    assert got["returned"] == placed
    assert got["config"] == placed
    assert got["dir_set_in_code"] is False
    assert got["min_compile_secs"] == 0.0


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = _probe_compile_cache(None)
    second = _probe_compile_cache(None)
    # The path is part of what a later process must reproduce to hit:
    # the same across calls and processes, inside the checkout.
    assert first == second
    assert first["returned"] == os.path.join(repo, ".xla-cache")
    assert first["config"] == first["returned"]
    assert _dir_of_two_calls_in_this_process() == first["returned"]


def _dir_of_two_calls_in_this_process():
    """Two calls in THIS process agree (config restored afterwards; the
    suite keeps jax's cache master switch off, so nothing is written)."""
    prev = {
        n: getattr(jax.config, n)
        for n in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    try:
        a, b = enable_compile_cache(), enable_compile_cache()
        assert a == b == jax.config.jax_compilation_cache_dir
        return a
    finally:
        for name, value in prev.items():
            jax.config.update(name, value)
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        _cc.reset_cache()


def test_compile_cache_persists_a_fresh_compile(tmp_path):
    """With the directory placed from outside and jax's switch on, a
    compile leaves an entry behind — in a child, so the suite's own
    no-cache choice (conftest) is untouched."""
    import subprocess
    import sys

    cache = tmp_path / "xla-cache"
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "1"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import jax, jax.numpy as jnp\n"
            "from tpufw.utils.profiling import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(128.0))"
            ".block_until_ready()\n",
        ],
        env=env, cwd=repo, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(os.listdir(cache))


def test_step_profiler_inactive_is_free():
    prof = StepProfiler(None)
    for i in range(5):
        prof.maybe_start(i)
        with prof.step(i):
            pass
        prof.maybe_stop(i)
    prof.close()


def test_null_tracer_span_is_allocation_free():
    # The disabled tpufw.obs path mirrors StepProfiler's contract: the
    # hot loop takes the instrumented shape unconditionally, so the
    # no-op must not allocate a context manager per call.
    from tpufw.obs import trace as trace_mod

    t = trace_mod.NullTracer()
    spans = {t.span("data_fetch"), t.span("step_dispatch", step=3)}
    assert len(spans) == 1  # one shared no-op span instance
    with t.span("host_sync"):
        pass
    t.complete("data_fetch", 0.01)
    t.instant("marker")
    t.close()  # idempotent, writes nothing


def test_disabled_telemetry_keeps_trainer_shape():
    # Trainer.__init__ installs the shared disabled Telemetry so every
    # instrumented call site works before/without run().
    from tpufw.obs import Telemetry

    tel = Telemetry.disabled()
    assert tel.bound_port is None
    tel.events.emit(
        "step", step=1, loss=0.0, step_time_s=0.1, data_wait_s=0.0
    )
    tel.snapshot_metrics()  # no out_dir: must be a no-op, not an error
    tel.close()


def test_trainer_writes_trace(tmp_path):
    from tpufw.mesh import MeshConfig
    from tpufw.models import Llama, LLAMA_CONFIGS
    from tpufw.train import Trainer, TrainerConfig, synthetic_batches

    tiny = LLAMA_CONFIGS["llama3_tiny"]
    trace_dir = tmp_path / "trace"
    cfg = TrainerConfig(
        batch_size=8, seq_len=17, total_steps=4, lr=1e-3,
        profile_dir=str(trace_dir), profile_start=1, profile_stop=3,
    )
    trainer = Trainer(Llama(tiny), cfg, MeshConfig())
    trainer.init_state()
    trainer.run(
        synthetic_batches(8, 17, tiny.vocab_size),
        model_flops_per_token=tiny.flops_per_token(16),
    )
    # XProf writes plugins/profile/<run>/ with .xplane.pb capture files.
    found = [
        f for _, _, files in os.walk(trace_dir) for f in files
        if f.endswith(".xplane.pb")
    ]
    assert found, f"no xplane capture under {trace_dir}"
