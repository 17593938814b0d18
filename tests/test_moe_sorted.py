"""Sorted (ragged_dot) MoE dispatch vs the einsum reference.

The sorted path exists for throughput (the one-hot dispatch einsums
cost 5x the expert matmuls at bench scale — docs/PERF.md r5), but its
SEMANTICS are pinned here to be identical to route_topk_capacity:
same expert selection, same slot-0-first/earlier-tokens-first capacity
priority, same drops, same aux statistics, same gradients.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.models import Mixtral, MixtralConfig
from tpufw.models.mixtral import MoEMLP
from tpufw.ops.moe import (
    expert_capacity,
    route_topk_capacity,
    route_topk_sorted,
)

F32 = jnp.float32


def _logits(g, e, seed=0):
    return jax.random.normal(jax.random.key(seed), (g, e), F32) * 2.0


def _einsum_out(logits, x, k, cap, valid=None, norm_topk=True,
                group_limit=None):
    dispatch, combine, aux, z = route_topk_capacity(
        logits, k, cap, valid=valid, dtype=F32,
        norm_topk=norm_topk, group_limit=group_limit,
    )
    # Identity "experts": expert i multiplies its tokens by (i+1), so
    # routing/capacity/gate differences show up directly in y.
    scale = jnp.arange(1.0, logits.shape[1] + 1.0)
    xe = jnp.einsum("gec,gd->ecd", dispatch, x)
    ye = xe * scale[:, None, None]
    y = jnp.einsum("gec,ecd->gd", combine, ye)
    return y, aux, z


def _sorted_out(logits, x, k, cap, valid=None, norm_topk=True,
                group_limit=None):
    g, e = logits.shape
    token, group_sizes, gates, aux, z = route_topk_sorted(
        logits, k, cap, valid=valid, dtype=F32,
        norm_topk=norm_topk, group_limit=group_limit,
    )
    xs = x[token]
    # group_sizes has E entries: sentinel (invalid-token) rows ride in
    # expert E-1's group and are zeroed by their gate alone.
    assert group_sizes.shape == (e,)
    scale = jnp.arange(1.0, e + 1.0)
    eid = jnp.searchsorted(
        jnp.cumsum(group_sizes),
        jnp.arange(token.shape[0]),
        side="right",
    )
    ys = xs * scale[eid][:, None]
    return (
        jnp.zeros_like(x).at[token].add(ys * gates[:, None]),
        aux,
        z,
    )


@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize(
    "cap_factor", [4.0, 0.6]  # ample vs forcing real drops
)
def test_sorted_matches_einsum_routing(norm_topk, cap_factor):
    g, e, k, d = 64, 8, 2, 16
    logits = _logits(g, e)
    x = jax.random.normal(jax.random.key(1), (g, d), F32)
    cap = expert_capacity(g, k, e, cap_factor)
    y0, aux0, z0 = _einsum_out(logits, x, k, cap, norm_topk=norm_topk)
    y1, aux1, z1 = _sorted_out(logits, x, k, cap, norm_topk=norm_topk)
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux0, aux1, rtol=1e-6)
    np.testing.assert_allclose(z0, z1, rtol=1e-6)


def test_sorted_matches_einsum_with_valid_mask():
    g, e, k, d = 48, 4, 2, 8
    logits = _logits(g, e, seed=3)
    x = jax.random.normal(jax.random.key(4), (g, d), F32)
    valid = jax.random.bernoulli(jax.random.key(5), 0.7, (g,))
    cap = expert_capacity(g, k, e, 1.0)
    y0, aux0, z0 = _einsum_out(logits, x, k, cap, valid=valid)
    y1, aux1, z1 = _sorted_out(logits, x, k, cap, valid=valid)
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux0, aux1, rtol=1e-6)
    np.testing.assert_allclose(z0, z1, rtol=1e-6)
    # Invalid tokens contribute nothing.
    assert np.all(np.asarray(y1)[~np.asarray(valid)] == 0.0)


def test_sorted_matches_einsum_group_limited():
    g, e, k = 32, 8, 2
    logits = _logits(g, e, seed=7)
    x = jax.random.normal(jax.random.key(8), (g, 4), F32)
    cap = expert_capacity(g, k, e, 2.0)
    gl = (4, 2)  # 8 experts, 4 groups, top-2 groups survive
    y0, aux0, _ = _einsum_out(
        logits, x, k, cap, norm_topk=False, group_limit=gl
    )
    y1, aux1, _ = _sorted_out(
        logits, x, k, cap, norm_topk=False, group_limit=gl
    )
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux0, aux1, rtol=1e-6)


def _tiny(moe_dispatch, capacity_factor=4.0):
    return MixtralConfig(
        vocab_size=128,
        d_model=32,
        n_layers=2,
        n_heads=2,
        n_kv_heads=1,
        head_dim=16,
        d_ff=64,
        max_seq_len=32,
        n_experts=4,
        experts_per_token=2,
        capacity_factor=capacity_factor,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        remat=False,
        moe_dispatch=moe_dispatch,
    )


@pytest.mark.parametrize("capacity_factor", [4.0, 0.6])
def test_mixtral_model_sorted_matches_einsum(capacity_factor):
    """Full-model parity: SAME params (the two dispatch paths create
    identical checkpoints), same batch -> same logits, same loss,
    same grads."""
    tokens = jax.random.randint(
        jax.random.key(0), (2, 16), 0, 128
    )
    cfg0 = _tiny("einsum", capacity_factor)
    cfg1 = _tiny("sorted", capacity_factor)
    m0, m1 = Mixtral(cfg0), Mixtral(cfg1)
    params = jax.jit(m0.init)(jax.random.key(1), tokens)["params"]

    out0 = m0.apply({"params": params}, tokens)
    out1 = m1.apply({"params": params}, tokens)
    logits0, aux0 = out0
    logits1, aux1 = out1
    np.testing.assert_allclose(logits0, logits1, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(aux0, aux1, rtol=1e-5, atol=1e-6)

    def loss(model):
        def f(p):
            lg, aux = model.apply({"params": p}, tokens)
            return jnp.mean(jnp.square(lg)) + aux

        return f

    g0 = jax.grad(loss(m0))(params)
    g1 = jax.grad(loss(m1))(params)
    flat0 = jax.tree_util.tree_leaves_with_path(g0)
    flat1 = dict(jax.tree_util.tree_leaves_with_path(g1))
    for path, leaf in flat0:
        np.testing.assert_allclose(
            leaf, flat1[path], rtol=5e-4, atol=5e-4,
            err_msg=jax.tree_util.keystr(path),
        )


def test_sorted_rejects_unknown_mode():
    cfg = _tiny("nope")
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="moe_dispatch"):
        jax.jit(Mixtral(cfg).init)(jax.random.key(0), tokens)


def _live_lora_b(params):
    """lora_b zero-inits; perturb it so the LoRA term is actually live."""
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: (
            jax.random.normal(jax.random.key(3), leaf.shape, leaf.dtype)
            * 0.1
            if "lora_b" in jax.tree_util.keystr(p)
            else leaf
        ),
        params,
    )


def test_mixtral_model_sorted_matches_einsum_with_lora():
    """The sorted path's grouped LoRA branch (ragged_dot over the
    lora_a/lora_b stacks) must match the einsum LoRA path from the
    SAME params — covers the one sorted-path branch the base parity
    tests leave cold (lora_rank=0)."""
    tokens = jax.random.randint(jax.random.key(0), (2, 16), 0, 128)
    cfg0 = dataclasses.replace(_tiny("einsum"), lora_rank=4)
    cfg1 = dataclasses.replace(_tiny("sorted"), lora_rank=4)
    m0, m1 = Mixtral(cfg0), Mixtral(cfg1)
    params = jax.jit(m0.init)(jax.random.key(1), tokens)["params"]
    params = _live_lora_b(params)
    logits0, aux0 = m0.apply({"params": params}, tokens)
    logits1, aux1 = m1.apply({"params": params}, tokens)
    np.testing.assert_allclose(logits0, logits1, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(aux0, aux1, rtol=1e-5, atol=1e-6)


def _valid_mask(g, seed=5, p=0.7):
    return jax.random.bernoulli(jax.random.key(seed), p, (g,))


@pytest.mark.parametrize("with_valid", [False, True])
@pytest.mark.parametrize("cap_factor", [4.0, 0.6])
def test_group_sizes_cover_every_row(with_valid, cap_factor):
    """ragged_dot's contract: E groups (the stacks as stored) whose
    sizes sum to the k*G sorted rows, so no output row is undefined —
    with the sentinel rows of invalid tokens folded into group E-1."""
    g, e, k = 48, 4, 2
    logits = _logits(g, e, seed=3)
    valid = _valid_mask(g) if with_valid else None
    cap = expert_capacity(g, k, e, cap_factor)
    token, group_sizes, gates, _, _ = route_topk_sorted(
        logits, k, cap, valid=valid, dtype=F32
    )
    assert group_sizes.shape == (e,)
    assert token.shape == gates.shape == (k * g,)
    assert int(jnp.sum(group_sizes)) == k * g
    # Real assignments per expert, from the selection itself.
    _, topk_idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    keep = np.ones(g, bool) if valid is None else np.asarray(valid)
    n_sentinel = k * int((~keep).sum())
    want = np.bincount(np.asarray(topk_idx)[keep].ravel(), minlength=e)
    want[e - 1] += n_sentinel
    np.testing.assert_array_equal(np.asarray(group_sizes), want)
    # Sentinel rows sort last and carry a zero gate.
    if n_sentinel:
        tail = np.asarray(token)[-n_sentinel:]
        assert not keep[tail].any()
        assert np.all(np.asarray(gates)[-n_sentinel:] == 0.0)
        assert keep[np.asarray(token)[:-n_sentinel]].all()


@pytest.mark.parametrize("cap_factor", [4.0, 0.6])
def test_last_expert_serves_real_and_sentinel_rows(cap_factor):
    """Expert E-1 is every valid token's first choice AND hosts the
    sentinel rows: its real assignments keep their ranks and drops
    (y equals the einsum path), the sentinel rows add exactly 0."""
    g, e, k, d = 48, 4, 2, 8
    logits = _logits(g, e, seed=11).at[:, e - 1].add(6.0)
    x = jax.random.normal(jax.random.key(12), (g, d), F32)
    valid = _valid_mask(g, seed=13, p=0.6)
    cap = expert_capacity(g, k, e, cap_factor)
    _, group_sizes, _, _, _ = route_topk_sorted(
        logits, k, cap, valid=valid, dtype=F32
    )
    n_valid = int(jnp.sum(valid))
    assert 0 < n_valid < g
    # Every valid token picked E-1, plus k sentinel rows per invalid.
    assert int(group_sizes[e - 1]) == n_valid + k * (g - n_valid)
    y0, aux0, z0 = _einsum_out(logits, x, k, cap, valid=valid)
    y1, aux1, z1 = _sorted_out(logits, x, k, cap, valid=valid)
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux0, aux1, rtol=1e-6)
    np.testing.assert_allclose(z0, z1, rtol=1e-6)
    assert np.all(np.asarray(y1)[~np.asarray(valid)] == 0.0)
    assert np.any(np.asarray(y1)[np.asarray(valid)] != 0.0)


def _moe_layers(lora_rank):
    """The (einsum, sorted) MoE layers of ``_tiny``, same param tree."""
    return tuple(
        MoEMLP(dataclasses.replace(_tiny(mode), lora_rank=lora_rank))
        for mode in ("einsum", "sorted")
    )


@pytest.mark.parametrize("lora_rank", [0, 4])
def test_moe_layer_grads_match_einsum_under_valid_mask(lora_rank):
    """d(loss)/d(stack) through ragged_dot with the stacks as stored:
    the sentinel rows multiply against expert E-1 but reach the loss
    through a zero gate, so expert E-1's gradient (and every other
    stack's) equals the einsum path's."""
    m0, m1 = _moe_layers(lora_rank)
    b, t, d = 2, 16, 32
    x = jax.random.normal(jax.random.key(20), (b, t, d), F32)
    valid = _valid_mask(b * t, seed=21, p=0.6).reshape(b, t)
    params = jax.jit(m0.init)(jax.random.key(22), x)["params"]
    params = _live_lora_b(params)

    def loss(model):
        def f(p, xin):
            y, aux = model.apply({"params": p}, xin, valid)
            return jnp.sum(jnp.square(y)) + aux, y

        return f

    (l0, y0), g0 = jax.value_and_grad(loss(m0), (0, 1), has_aux=True)(
        params, x
    )
    (l1, y1), g1 = jax.value_and_grad(loss(m1), (0, 1), has_aux=True)(
        params, x
    )
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    np.testing.assert_allclose(y0, y1, rtol=2e-4, atol=2e-5)
    assert np.all(np.asarray(y1)[~np.asarray(valid)] == 0.0)
    flat1 = dict(jax.tree_util.tree_leaves_with_path(g1))
    stacks = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(g0):
        assert np.all(np.isfinite(flat1[path]))
        np.testing.assert_allclose(
            leaf, flat1[path], rtol=5e-4, atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )
        name = jax.tree_util.keystr(path)
        if "w_" in name and leaf.ndim == 3:
            stacks += 1
            assert leaf.shape[0] == 4
            assert np.any(np.asarray(leaf)[-1] != 0.0), name
    assert stacks == (9 if lora_rank else 3)


@pytest.mark.parametrize("lora_rank", [0, 4])
@pytest.mark.parametrize("with_valid", [False, True])
def test_sorted_lowering_has_no_expert_stack_copy(lora_rank, with_valid):
    """The copy cannot come back unnoticed: the lowered sorted layer
    holds no [E+1, ...] stack-shaped tensor (the zero expert the
    sentinel group used to need), so no concatenate/pad builds one
    and ragged_dot reads the [E, in, out] parameters themselves."""
    _, m1 = _moe_layers(lora_rank)
    e = m1.cfg.n_experts
    x = jnp.zeros((2, 16, 32), F32)
    valid = jnp.ones((2, 16), bool) if with_valid else None
    params = jax.eval_shape(m1.init, jax.random.key(0), x)["params"]
    # Lowered for the chip (no chip needed): there ragged_dot stays one
    # op, chlo.ragged_dot; XLA:CPU expands it into masked dense dots.
    text = (
        jax.jit(lambda p, xin, v: m1.apply({"params": p}, xin, v))
        .trace(params, x, valid)
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    grown = re.findall(rf"tensor<{e + 1}x\d+x\d+x\w+>", text)
    assert not grown, sorted(set(grown))
    # Every ragged_dot reads an [E, in, out] stack and E group sizes.
    calls = re.findall(
        r"chlo\.ragged_dot.*: \(tensor<\d+x\d+x\w+>, "
        r"tensor<(\d+)x\d+x\d+x\w+>, tensor<(\d+)xi32>\)",
        text,
    )
    assert len(calls) == (9 if lora_rank else 3)
    assert set(calls) == {(str(e), str(e))}, calls


# ------------------------------ held experts and sigmoid scoring (PR 28)

def _moe_layer(family, dispatch, held=None):
    """One expert layer at test widths: Mixtral's MoEMLP, or DeepSeek's
    routed + shared experts."""
    from tpufw.models.deepseek import DeepseekConfig, DeepseekMoE

    if family == "mixtral":
        cfg = MixtralConfig(
            vocab_size=256, d_model=64, n_layers=1, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, n_experts=8, experts_per_token=2,
            capacity_factor=4.0, moe_dispatch=dispatch, remat=False,
            dtype=F32, param_dtype=F32,
        )
        return MoEMLP(cfg, held=held)
    return DeepseekMoE(DeepseekConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=128,
        n_routed_experts=8, experts_per_token=2, moe_d_ff=48,
        n_shared_experts=2, capacity_factor=4.0, moe_dispatch=dispatch,
        remat=False, scan_layers=False, dtype=F32, param_dtype=F32,
        experts_held=held,
    ))


@pytest.mark.parametrize("dispatch", ["sorted", "einsum"])
@pytest.mark.parametrize("family", ["mixtral", "deepseek"])
def test_all_experts_held_is_the_same_program(family, dispatch):
    """``held`` naming every expert, with softmax scoring, lowers to the
    text the layer lowers to without the option — the program the two
    benchmark families ran before the option existed (compared with the
    parent commit's text by hand in PR 28: byte-identical)."""
    x = jnp.zeros((2, 16, 64), F32)
    valid = jnp.ones((2, 16), bool)

    def text(mod):
        p = jax.eval_shape(
            lambda a, b: mod.init(jax.random.key(0), a, valid=b), x, valid
        )
        return jax.jit(
            lambda p, a, b: mod.apply(p, a, valid=b)
        ).lower(p, x, valid).as_text()

    assert text(_moe_layer(family, dispatch, held=(0, 8))) == text(
        _moe_layer(family, dispatch)
    )


def _reference_sigmoid_gates(logits, bias, k, norm):
    """Dense [G, E] gates of sigmoid scoring with a selection bias, in
    numpy: chosen by score + bias, weighed by the score alone."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    idx = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :k]
    w = np.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    gates = np.zeros_like(s)
    np.put_along_axis(gates, idx, w, axis=-1)
    return gates, idx


@pytest.mark.parametrize("norm_topk", [True, False])
def test_sigmoid_scoring_with_a_bias_matches_the_reference(norm_topk):
    g, e, k = 96, 16, 4
    logits = _logits(g, e, seed=3)
    bias = jax.random.normal(jax.random.key(4), (e,), F32) * 0.3
    want, idx = _reference_sigmoid_gates(logits, bias, k, norm_topk)
    _, idx0 = _reference_sigmoid_gates(logits, jnp.zeros((e,)), k, norm_topk)
    assert (np.sort(idx, -1) != np.sort(idx0, -1)).any(), "the bias decides some choices"
    cap = g  # dropless
    dispatch, combine, _, _ = route_topk_capacity(
        logits, k, cap, dtype=F32, norm_topk=norm_topk,
        scoring="sigmoid", select_bias=bias,
    )
    np.testing.assert_allclose(np.asarray(combine.sum(-1)), want, atol=1e-6)
    token, sizes, gates, _, _ = route_topk_sorted(
        logits, k, cap, dtype=F32, norm_topk=norm_topk,
        scoring="sigmoid", select_bias=bias,
    )
    eid = np.searchsorted(np.cumsum(np.asarray(sizes)), np.arange(k * g), side="right")
    dense = np.zeros((g, e))
    np.add.at(dense, (np.asarray(token), eid), np.asarray(gates))
    np.testing.assert_allclose(dense, want, atol=1e-6)


@pytest.mark.parametrize("with_valid", [False, True])
def test_held_experts_take_their_share_and_nothing_else(with_valid):
    """With ``held = (first, n)`` both routings return the columns
    [first, first + n) of the whole layer's gates: the same selection
    and weights over all E, assignments to experts held elsewhere in
    the sentinel group with a zero gate; the shares add up."""
    g, e, k, n = 64, 16, 4, 4
    logits = _logits(g, e, seed=5)
    bias = jax.random.normal(jax.random.key(6), (e,), F32) * 0.2
    valid = (jnp.arange(g) % 5 != 0) if with_valid else None
    kw = dict(dtype=F32, scoring="sigmoid", select_bias=bias, valid=valid)
    whole = np.asarray(route_topk_capacity(logits, k, g, **kw)[1].sum(-1))
    total = np.zeros_like(whole)
    for first in range(0, e, n):
        d, c, _, _ = route_topk_capacity(logits, k, g, held=(first, n), **kw)
        assert d.shape == (g, n, g)
        part = np.asarray(c.sum(-1))
        np.testing.assert_allclose(part, whole[:, first:first + n], atol=1e-6)
        token, sizes, gates, _, _ = route_topk_sorted(logits, k, g, held=(first, n), **kw)
        assert sizes.shape == (n,) and int(sizes.sum()) == k * g
        eid = np.searchsorted(np.cumsum(np.asarray(sizes)), np.arange(k * g), side="right")
        dense = np.zeros((g, n))
        np.add.at(dense, (np.asarray(token), eid), np.asarray(gates))
        np.testing.assert_allclose(dense, part, atol=1e-6)
        total[:, first:first + n] = part
    np.testing.assert_allclose(total, whole, atol=1e-6)


@pytest.mark.parametrize("dispatch", ["sorted", "einsum"])
def test_the_eight_shares_add_up_to_the_uncut_layer(dispatch):
    """The guide's share test on the program's layer: the routed parts
    that the eight shares of an expert layer give, plus the shared
    expert once, are the uncut layer's output."""
    from tpufw.models.solar_open2 import SOLAR_OPEN2_CONFIGS
    from tpufw.models.deepseek import DeepseekMoE

    base = dataclasses.replace(
        SOLAR_OPEN2_CONFIGS["solar_open2_tiny"], dtype=F32, param_dtype=F32,
        moe_dispatch=dispatch, experts_held=None,
    )
    x = jax.random.normal(jax.random.key(0), (2, 24, base.d_model), F32)
    whole = DeepseekMoE(base)
    from flax.linen import meta

    params = meta.unbox(whole.init(jax.random.key(1), x)["params"])
    params["routed"]["router_bias"] = (
        jax.random.normal(jax.random.key(2), (16,), F32) * 0.05
    )
    want, _ = whole.apply({"params": params}, x)
    total = jnp.zeros_like(want)
    for i in range(8):
        part = dataclasses.replace(base, experts_held=(2 * i, 2))
        p = {
            "shared": params["shared"],
            "routed": {
                **params["routed"],
                **{n: params["routed"][n][2 * i:2 * i + 2]
                   for n in ("w_gate", "w_up", "w_down")},
            },
        }
        y, _ = DeepseekMoE(part).apply({"params": p}, x)
        total = total + y
    # Every share added the shared expert: count it once.
    from tpufw.models.llama import MLP

    shared = MLP(base, d_ff=base.moe_d_ff * base.n_shared_experts).apply(
        {"params": params["shared"]}, x
    )
    np.testing.assert_allclose(
        np.asarray(total - 7 * shared), np.asarray(want), atol=2e-5
    )
