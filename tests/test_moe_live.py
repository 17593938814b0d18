"""The live-assignment expert kernel (tpufw.ops.moe_live) under the
Pallas interpreter against the ``ragged_dot`` path it stands beside in
``MoEMLP._sorted_experts``, and the host's rule against the program's.
Toy widths; the chip's are ``scripts/moe_live_chip_check.py``'s and
``tests/test_program_text.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.linen import meta

from tpufw.models.mixtral import MixtralConfig, MoEMLP
from tpufw.ops import moe_live

TOL = 2e-5  # tests/test_paged_attend.py's (tests/test_flash.py's)


# ---- the kernel alone ------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Call:
    n: int  # live assignments of A
    a: int = 12
    experts: int = 6
    d_in: int = 256
    d_out: int = 384
    dtype: str = "float32"
    fused: bool = False
    block_bytes: int = 1 << 30  # the whole expert a block
    tol: float = TOL


CALLS = {
    "no_live_assignment": Call(0),
    "one": Call(1),
    "some": Call(5),
    "every_row": Call(12),
    "rows_not_a_whole_tile": Call(7, a=10),
    "cut_along_the_contraction": Call(5, block_bytes=128 * 384 * 4),
    "cut_along_the_output": Call(5, d_in=128, d_out=512, block_bytes=128 * 256 * 4),
    "gate_and_up_fused": Call(5, fused=True),
    "fused_and_cut": Call(12, fused=True, block_bytes=128 * 384 * 4),
    "bfloat16": Call(12, dtype="bfloat16", tol=1e-2),
    "bfloat16_fused": Call(12, dtype="bfloat16", fused=True, tol=1e-2),
}


def _call(c: Call, seed=0):
    rng = np.random.default_rng(seed)
    dtype = jnp.dtype(c.dtype)
    stack = lambda: jnp.asarray(
        rng.standard_normal((c.experts, c.d_in, c.d_out), np.float32) / 16, dtype
    )
    w, u = stack(), stack()
    xs = jnp.asarray(rng.standard_normal((c.a, c.d_in), np.float32), dtype)
    # Sorted over the live part, with runs of equal ids; past it: junk.
    eid = np.sort(rng.integers(0, c.experts, c.a)).astype(np.int32)
    eid[c.n:] = 99
    return xs, jnp.asarray(eid), w, u


def _reference(c: Call, xs, eid, w, u):
    """Each row with its own expert, float32 accumulation, rounded as
    ``ragged_dot`` rounds; zeros past n."""
    ids = np.clip(np.asarray(eid), 0, c.experts - 1)
    one = lambda s: jnp.einsum(
        "ad,adf->af", xs, s[ids], preferred_element_type=jnp.float32
    ).astype(xs.dtype)
    y = one(w)
    if c.fused:
        y = (jax.nn.silu(y.astype(jnp.float32)) * one(u).astype(jnp.float32)).astype(xs.dtype)
    return y.at[c.n:].set(0)


@pytest.mark.parametrize("name", CALLS)
def test_kernel_contracts_each_live_row_with_its_own_expert(name):
    c = CALLS[name]
    xs, eid, w, u = _call(c)
    got = moe_live.live_experts(
        xs, eid, c.n, w, u if c.fused else None,
        interpret=True, block_bytes=c.block_bytes,
    )
    want = _reference(c, xs, eid, w, u)
    assert got.shape == want.shape and got.dtype == xs.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=c.tol, rtol=c.tol,
    )
    # Rows past the live count are not computed: exact zeros.
    assert not np.asarray(got, np.float32)[c.n:].any()


def test_the_cases_cut_the_blocks_they_say():
    tiles = lambda c: moe_live._tiles(c.d_in, c.d_out, 4, c.block_bytes)
    assert tiles(CALLS["some"]) == (256, 384)
    assert tiles(CALLS["cut_along_the_contraction"]) == (128, 384)
    assert tiles(CALLS["cut_along_the_output"]) == (128, 256)
    # The cells' stacks in bfloat16 under the kernel's own block size.
    own = lambda d_in, d_out: moe_live._tiles(d_in, d_out, 2, moe_live.BLOCK_BYTES)
    assert own(2048, 1408) == (2048, 1408) and own(1408, 2048) == (1408, 2048)
    for d_in, d_out in ((4096, 14336), (14336, 4096), (4096, 1280), (1280, 4096)):
        tk, tn = own(d_in, d_out)
        assert d_in % tk == 0 and d_out % tn == 0 and tk % 128 == 0 and tn % 128 == 0
        assert tk * tn * 2 <= moe_live.BLOCK_BYTES


def test_kernel_reads_no_expert_no_live_assignment_names():
    """Every expert the live ids do not name holds NaN: the result is
    the same. A NaN in a named expert reaches its own rows alone."""
    c = CALLS["some"]
    xs, eid, w, _ = _call(c)
    run = lambda stack: np.asarray(
        moe_live.live_experts(xs, eid, c.n, stack, interpret=True)
    )
    clean = run(w)
    named = np.zeros(c.experts, bool)
    named[np.asarray(eid)[: c.n]] = True
    assert not named.all()
    np.testing.assert_array_equal(
        run(jnp.where(named[:, None, None], w, jnp.nan)), clean
    )
    hit = int(np.asarray(eid)[2])
    bad = np.isnan(run(w.at[hit].set(jnp.nan))).any(axis=1)
    assert bad.tolist() == (np.asarray(eid) == hit).tolist()


# ---- behind MoEMLP: a pool's step -------------------------------------

B, D, FF = 16, 64, 32  # R = 2 of 16 rows


def _steer(monkeypatch, calls=None):
    """The kernel's rule steered on (here the backend is the CPU and the
    widths are toys), the kernel through the interpreter."""
    real = moe_live.live_experts

    def interpreted(*a, **k):
        if calls is not None:
            calls.append(a[0].shape)
        return real(*a, interpret=True, **k)

    monkeypatch.setattr(moe_live, "serves", lambda *a: True)
    monkeypatch.setattr(moe_live, "live_experts", interpreted)


#: The four routing conventions the benchmark's cells serve.
CONVENTIONS = {
    "mixtral_softmax_top2_normalised": dict(
        cfg=dict(n_experts=4, experts_per_token=2), layer={}),
    "deepseek_raw_softmax_top6": dict(
        cfg=dict(n_experts=16, experts_per_token=6), layer=dict(norm_topk=False)),
    "solar_sigmoid_bias_held": dict(
        cfg=dict(n_experts=16, experts_per_token=4),
        layer=dict(scoring="sigmoid", held=(4, 8))),
    "laguna_sigmoid_held_top_of_the_range": dict(
        cfg=dict(n_experts=16, experts_per_token=5),
        layer=dict(scoring="sigmoid", held=(8, 8))),
}


def _layer(name, dtype=jnp.float32):
    conv = CONVENTIONS[name]
    cfg = MixtralConfig(
        vocab_size=64, d_model=D, n_layers=1, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=FF, max_seq_len=32, dtype=dtype, param_dtype=dtype,
        moe_dispatch="sorted", scan_layers=False, capacity_factor=8.0,
        **conv["cfg"],
    )
    layer = MoEMLP(cfg, **conv["layer"])
    x = jax.random.normal(jax.random.key(1), (B, 1, D), dtype)
    params = meta.unbox(layer.init(jax.random.key(0), x)["params"])
    if "router_bias" in params:
        params = {**params, "router_bias": jax.random.normal(
            jax.random.key(2), params["router_bias"].shape) * 0.1}
    return layer, params, x


def _valid(live_rows):
    v = np.zeros((B, 1), bool)
    v[list(live_rows)] = True
    return jnp.asarray(v)


LIVE = {
    "none": (),
    "one": (5,),
    "R": (3, 11),
    # Two rows with one input: they name the same experts.
    "two_rows_on_the_same_experts": (2, 9),
}


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("name", CONVENTIONS)
def test_a_step_under_R_live_rows_is_the_ragged_path_on_the_live_rows(
    name, live, monkeypatch
):
    layer, params, x = _layer(name)
    rows = LIVE[live]
    if live == "two_rows_on_the_same_experts":
        x = x.at[rows[1]].set(x[rows[0]])
    valid = _valid(rows)
    assert moe_live.live_rows(B) == 2 and len(rows) <= 2
    want, _ = jax.jit(layer.apply)({"params": params}, x, valid=valid)
    calls = []
    _steer(monkeypatch, calls)
    got, _ = jax.jit(layer.apply)({"params": params}, x, valid=valid)
    k = layer.cfg.experts_per_token
    assert calls == [(2 * k, D), (2 * k, FF)]  # gate/up fused, down
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=TOL, rtol=TOL
    )
    dead = ~np.asarray(valid)[:, 0]
    assert not np.asarray(got)[dead].any()
    if rows:
        assert np.asarray(got)[~dead].any()


@pytest.mark.parametrize("name", CONVENTIONS)
def test_a_step_reads_no_expert_its_live_rows_do_not_name(name, monkeypatch):
    """The stacks hold NaN in every expert no live row's assignment
    names, the last held one (where the dead rows ride in the ragged
    path) among them unless a live row names it: the step's result is
    the clean one. On the ragged path the dead rows meet the last
    expert's NaN."""
    layer, params, x = _layer(name)
    valid = _valid((3, 11))
    route = _route(layer, params, x, valid)
    n = int(route.eids.shape[0] - route.counts[-1])
    named = np.zeros(layer._n_held(), bool)
    named[np.asarray(route.eids)[:n]] = True
    assert n and not named.all()
    stacks = ("w_gate", "w_up", "w_down")
    poisoned = {
        **params,
        **{s: jnp.where(named[:, None, None], params[s], jnp.nan) for s in stacks},
    }
    if not named[-1]:
        ragged, _ = jax.jit(layer.apply)({"params": poisoned}, x, valid=valid)
        assert np.isnan(np.asarray(ragged)).any()
    _steer(monkeypatch)
    clean, _ = jax.jit(layer.apply)({"params": params}, x, valid=valid)
    got, _ = jax.jit(layer.apply)({"params": poisoned}, x, valid=valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def _route(layer, params, x, valid):
    """The layer's own sorted route for ``x`` (router in float32)."""
    from tpufw.ops.moe import expert_capacity, sorted_route

    cfg = layer.cfg
    logits = x.reshape(B, D).astype(jnp.float32) @ params["router"]["kernel"]
    kw = dict(norm_topk=layer.norm_topk, scoring=layer.scoring)
    if layer.scoring != "softmax":
        kw["select_bias"] = params["router_bias"]
    if layer._held() is not None:
        kw["held"] = layer._held()
    return sorted_route(
        logits, cfg.experts_per_token,
        expert_capacity(B, cfg.experts_per_token, cfg.n_experts, cfg.capacity_factor),
        valid=valid.reshape(B), dtype=x.dtype, **kw,
    )


@pytest.mark.parametrize("name", CONVENTIONS)
def test_above_R_live_rows_the_step_is_the_parents_bit_for_bit(name, monkeypatch):
    layer, params, x = _layer(name, jnp.bfloat16)
    valid = _valid((1, 6, 12))  # R + 1
    want, _ = jax.jit(layer.apply)({"params": params}, x, valid=valid)
    calls = []
    _steer(monkeypatch, calls)
    got, _ = jax.jit(layer.apply)({"params": params}, x, valid=valid)
    assert calls  # the kernel's branch is in the program; it did not run
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_calls_that_are_no_pool_step_keep_the_parents_program(monkeypatch):
    """A prefill chunk or verify block (t > 1), a call without ``valid``,
    a pool the ladder gives no rung under (its rows are not a multiple
    of 8), a scanned trunk, LoRA beside the stacks: no kernel in the
    trace, whatever ``serves`` says."""
    layer, params, x = _layer("mixtral_softmax_top2_normalised")
    calls = []
    _steer(monkeypatch, calls)
    apply = lambda l, *a, **k: jax.eval_shape(l.apply, {"params": params}, *a, **k)
    apply(layer, x.reshape(B // 2, 2, D), valid=jnp.ones((B // 2, 2), bool))
    apply(layer, x)
    apply(layer, x[:12], valid=jnp.ones((12, 1), bool))
    scanned = MoEMLP(dataclasses.replace(layer.cfg, scan_layers=True))
    apply(scanned, x, valid=_valid((1,)))
    lora = MoEMLP(dataclasses.replace(layer.cfg, lora_rank=2))
    jax.eval_shape(lambda: lora.init_with_output(jax.random.key(0), x, valid=_valid((1,))))
    assert not calls
    apply(layer, x, valid=_valid((1,)))
    assert calls


@pytest.mark.parametrize("slots", [8, 16, 64])
def test_the_hosts_rule_is_the_programs_at_every_live_count(slots, monkeypatch):
    """For 0..B live rows: the branch the program takes (seen through a
    kernel that answers a constant) is the one ``moe_live.takes`` names
    from the pool's ``expert_rows``, which is what the scheduler counts."""
    layer, params, _ = _layer("mixtral_softmax_top2_normalised")
    monkeypatch.setattr(moe_live, "serves", lambda *a: True)
    monkeypatch.setattr(
        moe_live, "live_experts",
        lambda xs, eid, n, w, w_up=None: jnp.full((xs.shape[0], w.shape[2]), 7.0, xs.dtype),
    )
    x = jax.random.normal(jax.random.key(3), (slots, 1, D))
    step = jax.jit(layer.apply)
    rows = moe_live.pool_rows(layer.cfg, slots, D, FF)
    assert rows == slots // 8
    assert moe_live.expert_widths(params) == (D, FF)
    for live in range(slots + 1):
        valid = jnp.arange(slots)[:, None] < live
        y, _ = step({"params": params}, x, valid=valid)
        # The constant kernel's rows come back 7 x their gates' sum.
        took = live > 0 and bool(np.allclose(np.asarray(y)[0], 7.0, atol=1e-4))
        assert took == bool(live and moe_live.takes(rows, live)), live
    assert moe_live.takes(rows, 0) and not moe_live.takes(0, 0)


def test_a_pool_without_a_rung_under_it_and_a_model_without_experts():
    assert [moe_live.live_rows(b) for b in (1, 4, 7, 8, 12, 16, 64)] == [0, 0, 0, 1, 0, 2, 8]
    from tpufw.models.llama import LLAMA_CONFIGS, Llama

    model = Llama(LLAMA_CONFIGS["llama3_tiny"])
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert moe_live.expert_widths(params) is None


# ---- a paged pool, and the scheduler's count ---------------------------


def _moe_pool(slots=8):
    from tests import test_pages as tp
    from tpufw.infer import pages as pages_mod
    from tpufw.models.deepseek import DEEPSEEK_CONFIGS, Deepseek

    cfg = dataclasses.replace(
        DEEPSEEK_CONFIGS["deepseek_moe_tiny"].decode_config(),
        max_seq_len=64, moe_dispatch="sorted", scan_layers=False,
        dtype=jnp.float32,
    )
    row_model = Deepseek(cfg)
    params = jax.jit(row_model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    pcfg = dataclasses.replace(
        cfg, kv_page=tp.PAGE, kv_pages=slots * (64 // tp.PAGE) + 1
    )
    return pages_mod.PagedSlotPool.create_paged(
        Deepseek(pcfg), row_model, params, slots,
        sampling=tp.GREEDY, eos_id=None,
    )


def test_a_pool_steps_through_the_kernel_as_through_ragged_dot(monkeypatch):
    """A paged pool of 8 slots (R = 1) serves two rows, one of 4 tokens
    and one of 12: its steps run ``ragged_dot`` while both are live and
    the kernel (steered here: the interpreter, toy widths) once the
    short one is done, and serve the tokens the ``ragged_dot`` path
    serves, in float32, where the two differ by rounding order alone."""
    from tests import test_pages as tp

    prompts, budgets = [[1, 5, 9, 2, 7], [3, 4]], [12, 4]

    def serve(kernel: bool):
        calls = []
        if kernel:
            _steer(monkeypatch, calls)
        pool = _moe_pool()
        firsts = {}
        for i, p in enumerate(prompts):
            firsts[i], _ = tp._admit(pool, i, p, i, max_new=budgets[i])
        rows = {i: [first] for i, first in firsts.items()}
        for ci in range(3):
            keys = jax.random.split(jax.random.fold_in(jax.random.key(1), ci), 4)
            out = np.asarray(pool.decode_steps(keys))
            for i in rows:
                rows[i].extend(out[i, : budgets[i] - len(rows[i])].tolist())
        return rows, calls, pool

    want, none, plain = serve(False)
    jax.clear_caches()  # equal models share a trace: this one is steered
    got, calls, pool = serve(True)
    jax.clear_caches()  # and is no later test's
    assert not none and calls  # traced into the decode programs
    assert got == want and [len(got[i]) for i in got] == budgets
    # Off the chip the pool's rule says never; steered, an eighth.
    assert (plain.expert_rows, pool.expert_rows) == (0, 1)


def test_the_scheduler_books_the_steps_the_rule_names(monkeypatch):
    """``_count_experts`` over a chunk's live counts: every step of a
    pool with routed experts, and of them those ``takes`` names; nothing
    for a model without experts."""
    from tpufw.workloads import serve

    class Metrics:
        def __init__(self):
            self.seen = {}

        def inc(self, name, by=1):
            self.seen[name] = self.seen.get(name, 0) + by

    sched = serve._SlotScheduler.__new__(serve._SlotScheduler)
    sched._metrics = Metrics()
    sched._pool = type("P", (), {"expert_rows": 2})
    sched._count_experts([1, 2, 3, 0, 16])
    assert sched._metrics.seen == {"expert_steps_total": 5, "expert_live_steps_total": 3}
    sched._pool = type("P", (), {"expert_rows": 0})  # off the chip
    sched._count_experts([1, 2])
    assert sched._metrics.seen == {"expert_steps_total": 7, "expert_live_steps_total": 3}
    sched._pool = type("P", (), {"expert_rows": None})  # no routed experts
    sched._count_experts([1, 2])
    assert sched._metrics.seen == {"expert_steps_total": 7, "expert_live_steps_total": 3}
