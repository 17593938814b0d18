"""What every runner shares: the benchmark's own tables and file lookup.
Nothing here imports jax, so the launcher stays off the chip."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

from benchmarks import costs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
#: Hand-off between a run's phases, and traces; listed in .gitignore.
SCRATCH = os.path.join(ROOT, ".bench-scratch")

#: Published peaks of one chip, keyed by jax's ``device_kind``. Source:
#: Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
#: bf16, 16 GB HBM2e at 819 GB/s. A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def traffic_path(name: str) -> str:
    return os.path.join("benchmarks", "traffic", name + ".json")


def metrics_of(bench: dict, cell_name: str, kind: str) -> list:
    """The metrics of ``kind`` (end_to_end or per_layer) this cell reports."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def reader_module(metric_name: str) -> str:
    """The module that reads a per-layer metric: ``benchmarks.metrics.<q>``,
    q the name up to its first dot. One quantity whose cells report
    different end-to-end metrics is several entries of ``per_layer``
    (``ttft_max_ms`` moves ``ttft_p50_ms``; ``ttft_max_ms.tpot`` moves
    ``tpot_p50_ms`` in a cell that does not report the former) and one
    reader, so a split is entries and no new file."""
    return "benchmarks.metrics." + metric_name.split(".", 1)[0]


def family_modules(family: str):
    """(plain reference, adapter) of a family, found by name."""
    return (
        importlib.import_module(f"benchmarks.reference.{family}"),
        importlib.import_module(f"benchmarks.adapters.{family}"),
    )


def rehearse_path(family: str) -> str:
    return os.path.join("benchmarks", "configs", "rehearse", family + ".json")


def missing_parts(bench: dict, cell: dict, config: dict) -> list:
    """What this cell is made of and lacks, a line each naming the file:
    the family's plain reference, adapter, tiny rehearsal widths and cost
    functions, the traffic mix, and the reader of each per-layer metric
    the cell reports. Looks modules up without importing them (the
    references import jax); the cost functions, which are plain
    arithmetic, it imports."""
    family, out = config["family"], []
    for pkg in ("reference", "adapters"):
        if importlib.util.find_spec(f"benchmarks.{pkg}.{family}") is None:
            out.append(f"benchmarks/{pkg}/{family}.py is missing")
    for rel in (rehearse_path(family), traffic_path(cell["traffic"])):
        if not os.path.exists(os.path.join(ROOT, rel)):
            out.append(f"{rel} is missing")
    try:
        costs.of(family)
    except KeyError as e:
        out.append(e.args[0])
    for m in metrics_of(bench, cell["name"], "per_layer"):
        module = reader_module(m["name"])
        if importlib.util.find_spec(module) is None:
            out.append(f"{module.replace('.', '/')}.py, the reader of per-layer metric {m['name']}, is missing")
    return out


def model_keys(config: dict) -> dict:
    """The configuration's published keys, without the harness's own."""
    own = {"family", "runner", "source", "reduced", "assumed", "deployment", "memory", "note", "check"}
    return {k: v for k, v in config.items() if k not in own}


def cell_inputs(cell: dict, config: dict, rehearse: bool):
    """(traffic mix, published model keys, limits of ``correct``) a run of
    this cell uses; for a CPU rehearsal the mix's ``rehearse`` block and the
    family's tiny widths with the limits read at them."""
    mix = load_json(traffic_path(cell["traffic"]))
    if not rehearse:
        return mix, model_keys(config), config["check"]
    tiny = load_json(rehearse_path(config["family"]))
    return {**mix, **mix["rehearse"]}, model_keys(tiny), tiny["check"]
