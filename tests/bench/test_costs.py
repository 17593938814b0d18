"""The cost functions a roofline or MFU share divides by. They are the
family's to define (``benchmarks/costs/<family>.py``, found by name), so:
both accepted families give, through the public functions, the values the
one-table ``costs.py`` gave before them, to the last digit; a family made
of nothing but new modules gets through the launcher's check and the
roofline reader; and no generic file of the benchmark names a family."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchmarks import costs, harness, trace_reduce
from benchmarks.metrics import decode_roofline_share, prefill_mfu_share

FILES = {"deepseek_v2": "benchmarks/configs/deepseek-v2-lite-8l.json", "mixtral": "benchmarks/configs/mixtral-8x7b-3l.json"}

#: Written from commit e6987e6 (``benchmarks/costs.py``, one table with a
#: branch per family) before the functions moved: [arguments..., value].
PARENT = {'deepseek_v2': {'layer_params': {'attn': 13762560,
                                  'dense_ffn': 67239936,
                                  'expert': 8650752,
                                  'shared': 17301504,
                                  'router': 131072,
                                  'n_experts': 64,
                                  'top_k': 6,
                                  'n_dense': 1,
                                  'n_moe': 7,
                                  'embed': 209715200,
                                  'head': 209715200},
                 'cache_bytes_per_token': [[2, 9216], [1, 4608]],
                 'active_matmul_params': 872415232,
                 'prefill_flops': [[[1], 1744912384.0], [[64], 85415428096.0], [[256, 128], 513163657216.0],
                                   [[1024, 2048, 8064], 17639668711424.0],
                                   [[64, 320, 1024, 704, 64], 2954036772864.0]],
                 'prefill_chunk_flops': [[128, [256, 128], 170774932138.66666],
                                         [512, [1024, 2048, 8064], 810961398630.9885],
                                         [7360, [2048, 4096, 1024], 10681348259840.0],
                                         [576, [64, 320, 1024, 704, 64], 781395781993.4117]],
                 'decode_step_bytes': [[0, 0, 2, 1018167296.0], [0, 0, 1, 509083648.0], [1, 0, 2, 1744834560.0],
                                       [1, 0, 1, 872417280.0], [1, 273, 2, 1747350528.0], [1, 273, 1, 873675264.0],
                                       [2, 1000, 2, 2412593152.0], [2, 1000, 1, 1206296576.0],
                                       [3, 1000, 2, 3009397760.0], [3, 1000, 1, 1504698880.0],
                                       [4, 4321, 2, 3580858656.0], [4, 4321, 1, 1790429328.0],
                                       [7, 5000, 2, 4924020241.075195], [7, 5000, 1, 2462010120.5375977],
                                       [16, 30000, 2, 7441321856.837965], [16, 30000, 1, 3720660928.4189825],
                                       [64, 0, 2, 8755272241.637138], [64, 0, 1, 4377636120.818569],
                                       [64, 1000, 2, 8764488241.637138], [64, 1000, 1, 4382244120.818569],
                                       [64, 100001, 2, 9676881457.637138], [64, 100001, 1, 4838440728.818569]]},
 'mixtral': {'layer_params': {'attn': 41943040,
                              'dense_ffn': 0,
                              'expert': 176160768,
                              'shared': 0,
                              'router': 32768,
                              'n_experts': 8,
                              'top_k': 2,
                              'n_dense': 0,
                              'n_moe': 3,
                              'embed': 131072000,
                              'head': 131072000},
             'cache_bytes_per_token': [[2, 12288], [1, 6144]],
             'active_matmul_params': 1313964032,
             'prefill_flops': [[[1], 2627977216.0], [[64], 151774560256.0], [[256, 128], 911008071680.0],
                               [[1024, 2048, 8064], 28073410953216.0],
                               [[64, 320, 1024, 704, 64], 5189978292224.0]],
             'prefill_chunk_flops': [[128, [256, 128], 303494594560.0],
                                     [512, [1024, 2048, 8064], 1290695380285.7932],
                                     [7360, [2048, 4096, 1024], 17968012984320.0],
                                     [576, [64, 320, 1024, 704, 64], 1373470827941.647]],
             'decode_step_bytes': [[0, 0, 2, 513998848.0], [0, 0, 1, 256999424.0], [1, 0, 2, 2627936256.0],
                                   [1, 0, 1, 1313968128.0], [1, 273, 2, 2631290880.0], [1, 273, 1, 1315645440.0],
                                   [2, 1000, 2, 4225679360.0], [2, 1000, 1, 2112839680.0],
                                   [3, 1000, 2, 5414772736.0], [3, 1000, 1, 2707386368.0],
                                   [4, 4321, 2, 6347403264.0], [4, 4321, 1, 3173701632.0],
                                   [7, 5000, 2, 7902511104.0], [7, 5000, 1, 3951255552.0],
                                   [16, 30000, 2, 9253738552.03125], [16, 30000, 1, 4626869276.015625],
                                   [64, 0, 2, 8970239914.675983], [64, 0, 1, 4485119957.337992],
                                   [64, 1000, 2, 8982527914.675983], [64, 1000, 1, 4491263957.337992],
                                   [64, 100001, 2, 10199052202.675983], [64, 100001, 1, 5099526101.337992]]},
 'expected_experts_touched': [[64, 6, 0, 0.0], [64, 6, 1, 6.0], [64, 6, 2, 11.4375], [64, 6, 4, 20.83099365234375],
                              [64, 6, 13, 46.200646493349026], [64, 6, 16, 50.752061991158726],
                              [64, 6, 64, 63.88249584410315], [8, 2, 0, 0.0], [8, 2, 1, 2.0], [8, 2, 2, 3.5],
                              [8, 2, 4, 5.46875], [8, 2, 13, 7.80994188785553], [8, 2, 16, 7.919819233939052],
                              [8, 2, 64, 7.999999919274481]]}

BY_FAMILY = ("layer_params", "cache_bytes_per_token", "active_matmul_params", "prefill_flops",
             "prefill_chunk_flops", "decode_step_bytes")


def keys(family: str) -> dict:
    return harness.model_keys(harness.load_json(FILES[family]))


def same(got, want) -> bool:
    return got == want and type(got) is type(want)


@pytest.mark.parametrize("func", BY_FAMILY)
@pytest.mark.parametrize("family", sorted(FILES))
def test_public_function_gives_the_parents_value(family, func):
    c, want, fn = keys(family), PARENT[family][func], getattr(costs, func)
    if not isinstance(want, list):
        assert same(fn(family, c), want)
        return
    for *args, value in want:
        assert same(fn(family, c, *args), value), args


def test_experts_touched_held_and_width():
    for n, k, rows, value in PARENT["expected_experts_touched"]:
        assert same(costs.expected_experts_touched(n, k, rows), value)
        assert same(costs.expected_experts_touched(n, k, rows, held=n), value)
    # 8 experts held of a router 64 wide: each is reached as one of 64 is, and there are 8 to reach.
    assert costs.expected_experts_touched(64, 6, 16, held=8) == pytest.approx(8 * (1 - (1 - 6 / 64) ** 16))
    assert costs.expected_experts_touched(64, 6, 16, held=8) == pytest.approx(costs.expected_experts_touched(64, 6, 16) / 8)


@pytest.mark.parametrize("family", sorted(FILES))
def test_each_rows_count_or_their_sum(family):
    c = keys(family)
    whole = costs.decode_step_bytes(family, c, 3, 1000)
    assert costs.decode_step_bytes(family, c, 3, [1, 2, 997]) == whole
    assert costs.decode_step_bytes(family, c, 3, (334, 333, 333)) == whole
    with pytest.raises(ValueError):
        costs.decode_step_bytes(family, c, 2, [1, 2, 997])
    with pytest.raises(ValueError):
        costs.decode_step_bytes(family, c, 0, 5)
    more = {"prefill_flops": ([64],), "prefill_chunk_flops": (64, [64]), "decode_step_bytes": (1, [64])}
    for name in BY_FAMILY:
        with pytest.raises(KeyError, match="benchmarks/costs/unknown.py is missing"):
            getattr(costs, name)("unknown", c, *more.get(name, ()))


# ------------------------------------------- a family of new files only

#: Nothing of this family is in the repository: a window caps the cache a
#: row reads, a recurrent state is read and written per row whatever its
#: length, and 8 experts are held here of a router 64 wide.
RINGSTATE = {
    "hidden_size": 256, "num_hidden_layers": 4, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 128, "state_heads": 4, "moe_intermediate_size": 64, "router_width": 64, "experts_held": 8,
    "num_experts_per_tok": 6, "vocab_size": 4096,
}
RINGSTATE_COSTS = '''
from benchmarks import costs

FAMILY = "ringstate"


def layer_params(c):
    d, hd = c["hidden_size"], c["head_dim"]
    return {
        "attn": 2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd,
        "dense_ffn": 0, "expert": 3 * d * c["moe_intermediate_size"], "shared": 0,
        "router": d * c["router_width"], "n_experts": c["router_width"], "held": c["experts_held"],
        "top_k": c["num_experts_per_tok"], "n_dense": 0, "n_moe": c["num_hidden_layers"],
        "embed": c["vocab_size"] * d, "head": c["vocab_size"] * d,
    }


def cache_bytes_per_token(c, bytes_per=2):
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per * c["num_hidden_layers"]


def state_bytes_per_row(c, bytes_per=2):
    return c["state_heads"] * c["head_dim"] ** 2 * bytes_per * c["num_hidden_layers"]


def decode_step_bytes(c, row_tokens, bytes_per=2):
    weights = costs.moe_decode_weight_bytes(layer_params(c), c["hidden_size"], len(row_tokens), bytes_per)
    cache = sum(min(n, c["sliding_window"]) for n in row_tokens) * cache_bytes_per_token(c, bytes_per)
    return weights + cache + 2 * len(row_tokens) * state_bytes_per_row(c, bytes_per)


def prefill_flops(c, prompt_lens):
    p, w = layer_params(c), c["sliding_window"]
    body = costs.moe_active_params(p) - p["head"]
    per_key = c["num_attention_heads"] * 2 * c["head_dim"] * c["num_hidden_layers"]
    pairs = sum(n * (n + 1) // 2 if n <= w else w * (w + 1) // 2 + (n - w) * w for n in prompt_lens)
    return 2.0 * body * sum(prompt_lens) + 2.0 * p["head"] * len(prompt_lens) + 2.0 * per_key * pairs


def prefill_chunk_flops(c, tokens, prompt_lens):
    return costs.chunk_share(prefill_flops(c, prompt_lens), layer_params(c)["head"], tokens, prompt_lens)
'''


@pytest.fixture
def new_families(tmp_path, monkeypatch):
    """``ringstate``: reference, adapter, rehearsal widths and costs, each
    a new file beside (here: on the search path of) the package it joins.
    ``ringless``: the same without its costs."""
    import benchmarks.adapters
    import benchmarks.reference

    for pkg, text in ((costs, RINGSTATE_COSTS), (benchmarks.reference, "FAMILY = 'ringstate'\n"),
                      (benchmarks.adapters, "FAMILY = 'ringstate'\n")):
        d = tmp_path / pkg.__name__.rpartition(".")[2]
        d.mkdir()
        (d / "ringstate.py").write_text(text)
        if pkg is not costs:
            (d / "ringless.py").write_text(text)
        monkeypatch.setattr(pkg, "__path__", [*pkg.__path__, str(d)])
    for family in ("ringstate", "ringless"):
        (tmp_path / f"{family}.json").write_text(json.dumps(RINGSTATE))
    monkeypatch.setattr(harness, "rehearse_path", lambda family: str(tmp_path / f"{family}.json"))
    yield
    for name in [n for n in sys.modules if n.rpartition(".")[2] in ("ringstate", "ringless")]:
        del sys.modules[name]


def test_a_family_of_new_files_only(new_families):
    bench = {"per_layer": [{"name": "decode_roofline_share"}, {"name": "prefill_mfu_share", "workloads": ["other"]}]}
    cell = {"name": "ringstate-decode", "traffic": "reason-long"}
    assert harness.missing_parts(bench, cell, {"family": "ringstate"}) == []
    lacking = harness.missing_parts(bench, cell, {"family": "ringless"})
    assert len(lacking) == 1 and "benchmarks/costs/ringless.py is missing" in lacking[0]
    none = harness.missing_parts({"per_layer": [{"name": "no_such_reader"}]}, {"name": "c", "traffic": "no-such-mix"},
                                 {"family": "nothing_of_it"})
    named = ["benchmarks/reference/nothing_of_it.py", "benchmarks/adapters/nothing_of_it.py", "nothing_of_it.json",
             "benchmarks/traffic/no-such-mix.json", "benchmarks/costs/nothing_of_it.py",
             "benchmarks/metrics/no_such_reader.py"]
    assert len(none) == len(named) and all(name in line for name, line in zip(named, none))

    with open(os.path.join(harness.HERE, "data", "tiny_trace.json")) as f:
        raw = json.load(f)
    planes = {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()} for p, lines in raw.items() if p != "note"}
    obs = {"trace": trace_reduce.reduce_trace(planes, 1, 0.0, 2048, 256), "family": "ringstate", "config": RINGSTATE,
           "device": {"kind": "TPU v5 lite"}, "t0": 0.0, "seconds": 10.0, "records": [
               {"due": 1.0, "n_prompt": 256, "chunks": [(2.0, 1), (4.0, 16), (8.0, 16)], "done": 8.0},
               {"due": 1.5, "n_prompt": 64, "chunks": [(3.0, 1), (4.5, 8)], "done": None},
               {"due": 6.0, "n_prompt": 128, "chunks": [(7.0, 1)], "done": None}]}
    # Two rows live at mid-window, of 273 and 73 tokens: the window caps the first at 128.
    d, experts = 256, 8 * (1 - (1 - 6 / 64) ** 2)
    per_layer = (2 * d * 8 * 32 + 2 * d * 2 * 32) + experts * 3 * d * 64 + d * 64
    weights = 4 * per_layer + 4096 * d + 2 * d
    cache, state = (128 + 73) * 2 * 2 * 32 * 2 * 4, 2 * 2 * 4 * 32 * 32 * 2 * 4
    need = weights * 2 + cache + state
    assert costs.decode_step_bytes("ringstate", RINGSTATE, 2, [273, 73]) == pytest.approx(need)
    assert decode_roofline_share.read(obs) == pytest.approx(100 * (need / 819e9 * 1e3) / 0.002)
    # 6 of 64 routed to, 8 of 64 held: three quarters of one expert a token.
    p = costs.layer_params("ringstate", RINGSTATE)
    assert costs.moe_active_params(p) == 4 * (p["attn"] + 6 * p["expert"] * 8 // 64 + p["router"]) + p["head"]
    with pytest.raises(KeyError, match="ringstate.py defines no active_matmul_params"):
        costs.active_matmul_params("ringstate", RINGSTATE)  # not among what a family has to define
    assert prefill_mfu_share.read(obs) > 0


def test_launcher_names_the_missing_file_before_it_starts_anything(tmp_path):
    """A checkout whose cell lacks its family's costs: the launcher says
    which file, exits 2 and prints no result; it has started no process
    and has not imported jax."""
    import shutil

    shutil.copytree(harness.HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    os.remove(tmp_path / "benchmarks" / "costs" / "mixtral.py")
    os.remove(tmp_path / "benchmarks" / "metrics" / "slot_waste_share.py")
    code = textwrap.dedent(f"""
        import runpy, subprocess, sys
        def no(*a, **k): raise AssertionError("the launcher started a process")
        subprocess.Popen = no
        sys.argv = ["run.py", "--workload", "mixtral-prefill-heavy", "--seed", "3", "--seconds", "1", "--trace", "1"]
        try:
            runpy.run_path({str(tmp_path / "benchmarks" / "run.py")!r}, run_name="__main__")
        except SystemExit as e:
            print("exit", e.code, "jax" in sys.modules)
    """)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    got = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert got.stdout.split() == ["exit", "2", "False"], got.stderr[-2000:]
    lines = [ln for ln in got.stderr.splitlines() if ln.strip()]
    assert len(lines) == 2 and all(ln.startswith("bench: cell mixtral-prefill-heavy cannot run: ") for ln in lines)
    assert "benchmarks/costs/mixtral.py is missing" in lines[0] and "benchmarks/metrics/slot_waste_share.py" in lines[1]
    assert not os.path.exists(tmp_path / ".bench-scratch")


# ------------------------------------------- no generic file names a family


def test_no_generic_file_names_a_family():
    """What belongs to one family sits in files of its name, found by name;
    the rest of the harness knows of none, so a new family adds files and
    edits none. Neither a family, nor its leading word, nor a configuration
    or a cell of the benchmark is named in a generic source."""
    bench = harness.load_benchmark()
    families = {harness.load_json(c["file"])["family"] for c in bench["configs"]}
    words = families | {f.split("_")[0] for f in families}
    words |= {c["name"] for c in bench["configs"]} | {w["name"] for w in bench["workloads"]}
    words |= {w["name"].split("-")[0] for w in bench["workloads"]}
    generic = ["costs/__init__.py", "harness.py", "run.py", "procs.py", "runners/serve.py", "stats.py", "traffic.py",
               "client.py", "trace_reduce.py", "weights.py", "compile_log.py", "reference/common.py"]
    generic += ["metrics/" + f for f in sorted(os.listdir(os.path.join(harness.HERE, "metrics"))) if f.endswith(".py")]
    assert len(generic) > 25 and {"deepseek_v2", "mixtral"} <= families
    for rel in generic:
        with open(os.path.join(harness.HERE, rel)) as f:
            text = f.read().lower()
        named = sorted(w for w in words if w.lower() in text)
        assert not named, f"benchmarks/{rel} names {named}"
    for family in families:
        for pkg in ("costs", "reference", "adapters"):
            assert os.path.exists(os.path.join(harness.HERE, pkg, family + ".py"))
