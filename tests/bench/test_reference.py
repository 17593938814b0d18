"""The plain references against the program at tiny widths on the CPU:
they agree in float32 (full forward, and prefill-then-decode through the
cache), and they tell a model whose weights went through int8 from a sound
one."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness
from benchmarks.reference import common
from benchmarks.weights import make_weights

FAMILIES = ["deepseek_v2", "mixtral"]
#: float32 against float32 over a few layers: rounding order only.
F32_TOL = 2e-4


def build(family, seed=3):
    keys = harness.model_keys(harness.load_json(f"benchmarks/configs/rehearse/{family}.json"))
    ref, adapter = harness.family_modules(family)
    weights = make_weights(ref.weight_specs(keys), seed)
    cls, pc = adapter.program_model(keys, {"moe_dispatch": "sorted"})
    pc32 = dataclasses.replace(pc, dtype=jnp.float32)
    return keys, ref, adapter.to_program(weights, keys), weights, cls, pc32


def program_logits(cls, pc, params, tokens):
    with jax.default_matmul_precision("highest"):
        return cls(pc).apply({"params": params}, tokens[None], return_aux=False)[0]


@pytest.mark.parametrize("family", FAMILIES)
def test_full_forward_agrees(family):
    keys, ref, params, weights, cls, pc32 = build(family)
    tokens = jax.random.randint(jax.random.key(1), (64,), 1, keys["vocab_size"])
    want, margin = ref.logits(weights, keys, tokens, jnp.arange(64))
    got = program_logits(cls, pc32, params, tokens)
    assert float(jnp.std(want)) > 0.5, "seeded weights give logits of unit scale"
    assert margin.shape == (64,) and bool(jnp.all(margin >= 0)) and float(jnp.min(margin)) < 0.05
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


@pytest.mark.parametrize("family", FAMILIES)
def test_prefill_then_decode_through_the_cache_agrees(family):
    keys, ref, params, weights, cls, pc32 = build(family)
    n_prompt, n_new = 40, 24
    tokens = jax.random.randint(jax.random.key(2), (n_prompt + n_new,), 1, keys["vocab_size"])
    want, _ = ref.logits(weights, keys, tokens, jnp.arange(n_prompt + n_new))
    model = cls(pc32.decode_config())

    def apply(cache, toks, pos):
        out, new = model.apply(
            {"params": params, **cache}, toks, positions=pos,
            segment_ids=jnp.ones_like(toks), mutable=["cache"],
        )
        return (out[0] if isinstance(out, tuple) else out), {"cache": new["cache"]}

    with jax.default_matmul_precision("highest"):
        logits, cache = apply({}, tokens[None, :n_prompt], jnp.arange(n_prompt)[None])
        worst = float(jnp.max(jnp.abs(logits[0] - want[:n_prompt])))
        for i in range(n_prompt, n_prompt + n_new):
            logits, cache = apply(cache, tokens[None, i : i + 1], jnp.array([[i]]))
            worst = max(worst, float(jnp.max(jnp.abs(logits[0, 0] - want[i]))))
    assert worst < F32_TOL


@pytest.mark.parametrize("family", FAMILIES)
def test_int8_weights_are_told_apart(family):
    """The program run on weights that went through int8 leaves the
    reference by far more than the float32 tolerance: the comparison has
    the teeth the control of ``correct`` needs."""
    keys, ref, params, weights, cls, pc32 = build(family)
    tokens = jax.random.randint(jax.random.key(4), (64,), 1, keys["vocab_size"])
    want, margin = ref.logits(weights, keys, tokens, jnp.arange(64))
    rounded = {
        k: v if any(s in k for s in ref.INT8_KEEP) else common.int8_round_trip(v, v.ndim - 2)
        for k, v in weights.items()
    }
    _, adapter = harness.family_modules(family)
    got = program_logits(cls, pc32, adapter.to_program(rounded, keys), tokens)
    assert float(jnp.max(jnp.abs(got - want))) > 50 * F32_TOL


def test_int8_round_trip_is_int8():
    w = jax.random.normal(jax.random.key(0), (4, 64, 32), jnp.float32).astype(jnp.bfloat16)
    r = common.int8_round_trip(w, 1).astype(jnp.float32)
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1, keepdims=True) / 127.0
    levels = r / scale
    assert float(jnp.max(jnp.abs(levels - jnp.round(levels)))) < 0.51  # bf16 storage of the level
    assert float(jnp.max(jnp.abs(r - w.astype(jnp.float32)))) <= float(jnp.max(scale)) * 0.76


def test_yarn_frequencies_ramp_between_the_two_regimes():
    from benchmarks.reference import deepseek_v2 as ds

    s = {"factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
         "mscale": 0.707, "mscale_all_dim": 0.707}
    inv = ds.yarn_inv_freq(64, 10000.0, s)
    plain = 1.0 / (10000.0 ** (jnp.arange(0, 64, 2) / 64))
    assert float(inv[0]) == pytest.approx(float(plain[0]))  # fastest: unscaled
    assert float(inv[-1]) == pytest.approx(float(plain[-1]) / 40, rel=1e-5)  # slowest: interpolated
    assert ds.yarn_attention_factor(s) == pytest.approx(1.0)


def test_weights_follow_the_seed():
    specs = {"a": ((8, 16), 8), "norm": ((16,), 0), "embed": ((32, 8), -1)}
    a, b, c = make_weights(specs, 5), make_weights(specs, 5), make_weights(specs, 2**31 + 9)
    assert bool(jnp.all(a["a"] == b["a"])) and not bool(jnp.all(a["a"] == c["a"]))
    assert a["a"].dtype == jnp.bfloat16 and a["norm"].dtype == jnp.float32
    assert bool(jnp.all(a["norm"] == 1.0))
