"""Kernels: of the per-slot state bytes the dispatched calls read and
wrote, the share that belonged to rows delivering a token there. A decode
step steps EVERY slot of the pool, live or not, and per-slot state
(recurrent state, convolution tails) is read and written whole whatever
a row holds, so the step's state traffic is slots x a slot's state while
only the live rows' is needed: the state's twin of
``attended_keys_share``. The scheduler counts both per dispatched decode
chunk, speculative pass, prefill chunk and insert
(``tpufw_serve_state_moved_bytes_total``,
``tpufw_serve_state_live_bytes_total``); 100 = every slot stepped was
live. A program without the counters, or a pool without state, reports
nothing."""

from benchmarks.metrics import _prom


def read(obs: dict):
    live = _prom.delta(obs, "tpufw_serve_state_live_bytes_total")
    moved = _prom.delta(obs, "tpufw_serve_state_moved_bytes_total")
    if live is None or not moved:
        return None
    return 100.0 * live / moved
