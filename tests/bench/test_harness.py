"""BENCHMARK.json against the benchmark's contract, and every file it
names resolving by name. CPU, fast, touches no jax at import."""

import importlib
import json
import os
import re

import pytest

from benchmarks import costs, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_size$|_dim$|_rank$|head_dim|expansion|experts_per_tok|latent|state)")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/bench"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_cells(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert w["config"] in configs
        assert NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {w["config"] for w in bench["workloads"]} == configs, "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_file_resolves_by_name(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        config = harness.load_json(c["file"])
        importlib.import_module(f"benchmarks.runners.{config['runner']}")
        ref, adapter = harness.family_modules(config["family"])
        assert ref.FAMILY == adapter.FAMILY == costs.of(config["family"]).FAMILY == config["family"]
        assert sorted(c["reduced"]) == sorted(config["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not WIDTH.search(key), f"{key}: a width may never be reduced"
        assert config["source"] == c["source"]
        for check in (config["check"], harness.load_json(
                f"benchmarks/configs/rehearse/{config['family']}.json")["check"]):
            assert {"logit_noise", "gap_max", "gap_mean", "sequences", "routing_margin"} <= set(check)
        assert os.path.exists(
            os.path.join(harness.ROOT, "benchmarks", "configs", "rehearse", config["family"] + ".json")
        )
    for w in bench["workloads"]:
        config = harness.load_json(harness.config_entry(bench, w["config"])["file"])
        assert harness.missing_parts(bench, w, config) == [], "what the launcher checks before it starts a process"
        mix = harness.load_json(harness.traffic_path(w["traffic"]))
        assert {"arrivals", "prompt", "output", "server_env", "rehearse", "ramp_s", "drain_s", "shape_seed"} <= set(mix)
    for m in bench["per_layer"]:
        reader = importlib.import_module(harness.reader_module(m["name"]))
        assert callable(reader.read)


def test_catalog_keys_kept():
    """Every number of the catalog's DeepSeek-V2-Lite config is in the
    configuration file under the same key, or listed as reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2-Lite")
    config = harness.load_json("benchmarks/configs/deepseek-v2-lite-8l.json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key


def test_metrics_cover_cells(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    reports = {
        c: {m["name"] for m in harness.metrics_of(bench, c, "end_to_end")} for c in cells
    }
    for c in cells:
        assert len(reports[c]) >= 2, f"{c} reports setup_s and one more"
        assert harness.metrics_of(bench, c, "per_layer"), f"{c} has a per-layer metric"
    for m in bench["end_to_end"] + bench["per_layer"]:
        for c in m.get("workloads", []):
            assert c in cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for c in m.get("workloads", cells):
            assert m["moves"] in reports[c], f"{m['name']} in {c} moves a metric the cell lacks"
        if "workloads" not in m:
            assert all(m["moves"] in reports[c] for c in cells)
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), "one layer, two spellings"


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_a_cell_that_reports_ttft_reports_the_share_of_the_peak_that_moves_it(cell):
    """A kernel's gain in the prefill can be claimed in ``ttft_p50_ms``
    only while the whole step's share of the chip's peak bounds it: every
    cell that reports the one lists ``prefill_mfu_share``, which moves it."""
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
    mfu = [m for m in harness.metrics_of(bench, cell, "per_layer") if m["name"] == "prefill_mfu_share"]
    assert bool(mfu) == ("ttft_p50_ms" in e2e)
    assert all(m["moves"] == "ttft_p50_ms" and m["source"] == "device_trace" for m in mfu)


def test_a_split_quantity_has_one_reader(bench):
    """``<quantity>.<suffix>`` is read by ``benchmarks/metrics/<quantity>.py``:
    a quantity whose cells report different end-to-end metrics is entries
    of ``per_layer`` and no new file. Each split entry keeps its
    quantity's unit, sense, source and layer, moves another metric, and
    shares no cell with it."""
    assert harness.reader_module("ttft_max_ms.tpot") == harness.reader_module("ttft_max_ms") == "benchmarks.metrics.ttft_max_ms"
    whole = {m["name"]: m for m in bench["per_layer"]}
    split = [m for m in bench["per_layer"] if "." in m["name"]]
    assert split, "solar2-longdoc-answers does not hold ttft_p50_ms, so what moved it there is split"
    for m in split:
        base = whole[m["name"].split(".", 1)[0]]
        assert {k: m[k] for k in ("unit", "better", "source", "layer")} == {k: base[k] for k in ("unit", "better", "source", "layer")}
        assert m["moves"] != base["moves"]
        assert not set(m["workloads"]) & set(base["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_the_first_token_median_is_read_in_every_cell_with_long_prompts(cell):
    """End to end (``ttft_p50_ms``) where it repeats, per layer
    (``first_token_p50_ms``, the same statistic, no bound) where the
    median is one request whose wait hangs on the phase of a decode
    chunk; never both, and in neither only where no prompt is long
    (``dsv2l-decode-long``)."""
    bench = harness.load_benchmark()
    held = "ttft_p50_ms" in {m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
    beside = "first_token_p50_ms" in {m["name"] for m in harness.metrics_of(bench, cell, "per_layer")}
    assert not (held and beside)
    known = {"dsv2l-decode-long": (False, False), "solar2-longdoc-answers": (False, True),
             "mixtral-prefill-heavy": (True, False), "laguna-repo-context": (True, False)}
    assert (held, beside) == known.get(cell, (held, beside)), "a later cell chooses for itself"


def test_first_token_p50_ms_is_the_windows_median():
    from benchmarks.metrics import first_token_p50_ms

    assert first_token_p50_ms.read({"window": {"ttft_p50_ms": 230.5}}) == 230.5
    assert first_token_p50_ms.read({"window": {}}) is None


def test_run_fits_the_check(bench):
    """2 + 14 x 24 runs of run_seconds + 60 s, 180 s a cell to compile and
    1200 s spare fit into 43200 s with the full 24 cells."""
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_peaks_table():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
