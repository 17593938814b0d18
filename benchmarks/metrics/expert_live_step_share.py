"""Kernels: the share of a pool's decode steps whose routed experts ran
over the live rows' assignments alone (``tpufw_serve_expert_live_steps_total``:
steps with no more live rows than ``tpufw.ops.moe_live.pool_rows`` gives
the pool, B/8, where the step's three ``ragged_dot``s over every slot's
rows and every group give way to the live-assignment kernel; booked by
the scheduler under the rule the program branches by) over the decode
steps dispatched on a pool whose model has routed experts
(``tpufw_serve_expert_steps_total``), between the two scrapes: the whole
window, traced run or not. The steps it leaves out ran with more rows
live, where the program is the parent's. None where no such step ran (a
model without routed experts), or where the program has no such counter
(every commit before it was added)."""

from benchmarks.metrics import _prom


def read(obs: dict):
    live = _prom.delta(obs, "tpufw_serve_expert_live_steps_total")
    steps = _prom.delta(obs, "tpufw_serve_expert_steps_total")
    if live is None or not steps:
        return None
    return 100.0 * live / steps
