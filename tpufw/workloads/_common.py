"""Shared scaffolding for the training workload entry points.

One copy of the JSON-lines telemetry channel (cold-start record + step
metrics — ``kubectl logs`` is the metrics surface, the reference's
verification pattern, reference README.md:331-335) so train_llama and
train_pipeline can't silently diverge.
"""

from __future__ import annotations

import json
import time
from typing import Callable

from tpufw.train.metrics import StepMetrics


def check_global_batch(batch_size: int, n_processes: int) -> int:
    """Global-batch contract: returns the LOCAL batch size per process."""
    if batch_size % n_processes:
        raise ValueError(
            f"global batch {batch_size} not divisible by "
            f"{n_processes} processes"
        )
    return batch_size // n_processes


def metrics_printer(
    t0: float, compile_cache: str
) -> Callable[[StepMetrics], None]:
    """on_metrics callback: first call emits the cold-start->first-step
    record (BASELINE.md metric 2), every call emits the step JSON line."""
    first_step: dict = {}

    def on_metrics(m: StepMetrics) -> None:
        if not first_step:
            first_step["t"] = time.time()
            print(
                json.dumps(
                    {
                        "cold_start_to_first_step_s": round(
                            first_step["t"] - t0, 1
                        ),
                        "compile_cache": compile_cache,
                    }
                ),
                flush=True,
            )
        print(json.dumps(m.as_dict()), flush=True)

    return on_metrics


def resume_data_seed(base_seed: int, restored_step: int) -> int:
    """Data seed for a (possibly) resumed run.

    A restart resumes the OPTIMIZER at step N but a fresh data iterator
    would replay batches 1..N — the resumed run re-trains on data it
    already consumed and never sees the tail it skipped. Exact
    fast-forward would cost O(N) host-side packing, so tpufw makes the
    standard streaming-trainer trade instead: fold the restored step
    into the shuffle seed, giving the resumed run a FRESH permutation
    of the corpus. Not sample-exact resume, but no duplication bias,
    O(1), and deterministic given (seed, step).
    """
    if restored_step <= 0:
        return base_seed
    return base_seed + 1_000_003 * restored_step


def resolve_encode(tok_name: str):
    """Tokenizer selection shared by the SFT / DPO / RL data paths:
    "bytes" = the dependency-free byte tokenizer, anything else = a HF
    tokenizer name loaded context-free (no special-token injection, so
    span masks stay exact)."""
    if tok_name == "bytes":
        from tpufw.train.sft import byte_encode

        return byte_encode
    from transformers import AutoTokenizer

    _tok = AutoTokenizer.from_pretrained(tok_name)

    def encode(text):
        return _tok.encode(text, add_special_tokens=False)

    return encode


def report_preemption(trainer) -> None:
    """One JSON line when the run stopped on SIGTERM (the forced
    checkpoint is down; a clean exit lets the JobSet policy resume)."""
    if getattr(trainer, "preempted", False):
        print(
            json.dumps(
                {"preempted": True, "step": int(trainer.state.step)}
            ),
            flush=True,
        )


def report_telemetry(trainer) -> None:
    """One JSON line pointing at the run's telemetry artifacts
    (events.jsonl + trace.json under TPUFW_TELEMETRY_DIR) so log
    scrapers and CI can find them without knowing the env."""
    tel = getattr(trainer, "telemetry", None)
    if tel is not None and getattr(tel, "out_dir", None):
        print(
            json.dumps({"telemetry_dir": tel.out_dir}), flush=True
        )


def print_summary(history: list[StepMetrics]) -> None:
    if not history:
        return
    last = history[-1]
    print(
        f"TRAIN OK: {len(history)} steps, final loss {last.loss:.4f}, "
        f"{last.tokens_per_sec_per_chip:.0f} tok/s/chip"
        + mfu_suffix(last)
    )


def mfu_suffix(m: StepMetrics) -> str:
    """", MFU x%" for a summary line — empty where the device has no
    peak (a CPU run), so no utilization is printed that was not measured."""
    return "" if m.mfu is None else f", MFU {m.mfu:.1%}"
