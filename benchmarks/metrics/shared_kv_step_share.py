"""Kernels: of the bytes the window's decode steps had to move, the share
that was reads of the ONE page arena that several layers attend (a family
whose later layers read pages an earlier layer wrote). From the
scheduler's counters between the two scrapes, priced by the family's cost
functions: the arena's bytes are the key slots the steps read, the
writer's own (``tpufw_serve_attended_key_slots_total`` less the prefill
chunks') and every other reader's
(``tpufw_serve_shared_key_slots_total{call="decode"}``), x the cache's
bytes a token; beside them each step's weights and each live row's state
and rings (``costs.decode_step_bytes`` with no cached token), over the
steps (``tpufw_serve_pass_steps_total``) at the mean number of rows a
decode chunk stepped live (``tpufw_serve_tick_rows_total`` over
``tpufw_serve_ticks_total``). Which regime the cell is in, as
``state_hbm_share`` and ``window_hbm_share`` say of theirs: a share that
grows with every token any row holds. None for a program without the
counter (every commit before it was added), a family whose cost functions
name no readers, or a window with no decode step."""

import re

from benchmarks import costs
from benchmarks.metrics import _passes, _prom

SHARED = "tpufw_serve_shared_key_slots_total"
_CALL = re.compile(r'\bcall="([^"]+)"')


def shared_by_call(obs: dict):
    """{kind of call: growth of ``SHARED`` in the window}; None where the
    program exposes no such series."""
    out = {}
    for key, after in obs["prom1"].items():
        m = _CALL.search(key) if key.startswith(SHARED + "{") else None
        if m:
            out[m.group(1)] = after - obs["prom0"].get(key, 0.0)
    return out or None


def read(obs: dict):
    shared = shared_by_call(obs)
    steps = _passes.decodes(obs, _passes.STEPS)
    attended, ticks, tick_rows = (
        _prom.delta(obs, "tpufw_serve_" + name)
        for name in ("attended_key_slots_total", "ticks_total", "tick_rows_total")
    )
    if shared is None or steps is None or not sum(steps) or attended is None or not ticks or tick_rows is None:
        return None
    cost = costs.of(obs["family"])
    readers = cost.readers(obs["config"]) if hasattr(cost, "readers") else 1
    if readers < 2:
        return None
    c = obs["config"]
    # Every reader beside the writer books the writer's count once, so the
    # prefill chunks' part of ``attended`` follows from theirs.
    writer = attended - shared.get("chunk", 0.0) / (readers - 1)
    arena = (writer + shared.get("decode", 0.0)) * cost.cache_bytes_per_token(c)
    a_step, a_row = cost.decode_step_bytes(c, []), cost.decode_step_bytes(c, [0])
    rest = sum(steps) * (a_step + tick_rows / ticks * (a_row - a_step))
    return 100.0 * arena / (arena + rest)
