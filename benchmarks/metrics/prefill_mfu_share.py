"""Model step: forward FLOPs that the prompt tokens prefilled in the traced
stretch need (``costs.prefill_chunk_flops``, a function of shapes) over the
device time the prefill-chunk program took in that stretch, at the chip's
published bf16 peak. Both sides come from the same executions in the trace:
tokens are each execution's chunk width, read off its operations. A chunk's
place in its prompt is not in the trace, so its attention is counted at the
mean number of keys a prompt token attends, over the prompts that were
prefilling in the traced stretch (``_steps.prefilling_prompts``)."""

from benchmarks import costs, harness
from benchmarks.metrics import _steps


def read(obs: dict):
    tr = obs["trace"]
    if tr is None:
        return None
    progs = [p for name, p in tr["programs"].items() if "prefill_chunk" in name]
    secs = sum(p["seconds"] for p in progs)
    tokens = sum(p.get("tokens", 0) for p in progs)
    if secs <= 0 or tokens <= 0 or any(p.get("widths_unread") for p in progs):
        return None
    lens = _steps.prefilling_prompts(obs)
    if not lens:
        return None
    need = costs.prefill_chunk_flops(obs["family"], obs["config"], tokens, lens)
    return 100.0 * need / (secs * harness.peaks(obs["device"]["kind"])["bf16_flops"])
