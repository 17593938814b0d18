"""The gated delta rule (tpufw.ops.kda): the chunkwise form against the
one-step form against a token-by-token recurrence written out here, the
identity under ``valid``, and the short convolution's carried tail. Each
under both shapes of decay: one a channel with a square state (KDA), and
one a head with d_k != d_v, neither a power of two (Gated DeltaNet)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.ops.kda import BLOCK, causal_conv, kda_chunk, kda_step

F32 = jnp.float32
#: form -> (d_k, d_v, one decay a head).
FORMS = {"channel": (16, 16, False), "head": (12, 20, True)}
both_forms = pytest.mark.parametrize("form", sorted(FORMS))


def wide(g, k):
    """A decay [.., H] or [.., H, dk] as [.., H, dk]."""
    return g if g.ndim == k.ndim else jnp.broadcast_to(g[..., None], k.shape)


def token_by_token(q, k, v, g, beta, s):
    outs = []
    g = wide(g, k)
    for t in range(q.shape[1]):
        s = s * jnp.exp(g[:, t])[..., None]
        delta = v[:, t] - jnp.einsum("bhkv,bhk->bhv", s, k[:, t], precision="highest")
        s = s + (beta[:, t][..., None] * k[:, t])[..., None] * delta[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, t], precision="highest"))
    return jnp.stack(outs, 1), s


def inputs(t, b=2, h=3, form="channel", seed=0, decay=5.0):
    d, dv, per_head = FORMS[form]
    ks = jax.random.split(jax.random.key(seed), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    # The seeded weights' spread: -exp(N(0,1)) * softplus(N(0,2)), and stronger.
    g = -jnp.exp(jax.random.normal(ks[3], (h,)))[:, None] * jax.nn.softplus(
        jax.random.normal(ks[4], (b, t, h, d)) * 2 ** 0.5) * decay
    if per_head:
        g = g[..., 0]
    # (0, 2): past 1 the transition reflects along k (a negative eigenvalue).
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, h)) * 2)
    s0 = jax.random.normal(ks[6], (b, h, d, dv))
    return q, k, v, g, beta, s0


@both_forms
@pytest.mark.parametrize("t", [1, BLOCK - 1, BLOCK, 2 * BLOCK + 22])
def test_chunk_step_and_token_by_token_agree(t, form):
    q, k, v, g, beta, s0 = inputs(t, form=form)
    assert t < BLOCK or float(jnp.max(beta)) > 1.9
    want_o, want_s = token_by_token(q, k, v, g, beta, s0)
    got_o, got_s = kda_chunk(q, k, v, g, beta, s0)
    # float32 against float32: the order of sums only.
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=2e-5)
    s, outs = s0, []
    for i in range(t):
        o, s = kda_step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)), np.asarray(want_o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=1e-6)


@both_forms
def test_one_decay_a_head_is_the_per_channel_rule_under_a_broadcast_decay(form):
    """The scalar form builds no [C, C, d_k] product; handed the same
    decay on every channel, the per-channel form answers the same."""
    t = BLOCK + 22
    q, k, v, g, beta, s0 = inputs(t, form=form, seed=7)
    g = g if g.ndim == beta.ndim else g[..., 0]
    got_o, got_s = kda_chunk(q, k, v, g, beta, s0)
    want_o, want_s = kda_chunk(q, k, v, wide(g, k), beta, s0)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=2e-5)
    o1, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    o2, s2 = kda_step(q[:, 0], k[:, 0], v[:, 0], wide(g, k)[:, 0], beta[:, 0], s0)
    assert bool(jnp.all(o1 == o2)) and bool(jnp.all(s1 == s2))


@both_forms
@pytest.mark.parametrize("pad", [5, BLOCK + 9])
def test_a_padded_tail_leaves_the_state_bit_equal(pad, form):
    t = BLOCK + 22
    q, k, v, g, beta, s0 = inputs(t, form=form, seed=1)
    _, want = kda_chunk(q, k, v, g, beta, s0)
    junk = inputs(pad, form=form, seed=2)
    cat = lambda a, j: jnp.concatenate([a, j], axis=1)
    valid = jnp.broadcast_to(jnp.arange(t + pad) < t, (2, t + pad))
    o, got = kda_chunk(cat(q, junk[0]), cat(k, junk[1]), cat(v, junk[2]), cat(g, junk[3]),
                       cat(beta, junk[4]), s0, valid)
    assert bool(jnp.all(got == want))
    assert bool(jnp.all(o[:, :t] == kda_chunk(q, k, v, g, beta, s0)[0]))
    # All padding: the identity, to the bit; and the one-step rule's mask.
    none = jnp.zeros((2, pad), bool)
    assert bool(jnp.all(kda_chunk(*junk[:5], s0, none)[1] == s0))
    zero = jnp.zeros_like(junk[3][:, 0])
    assert bool(jnp.all(kda_step(junk[0][:, 0], junk[1][:, 0], junk[2][:, 0], zero,
                                 jnp.zeros_like(junk[4][:, 0]), s0)[1] == s0))


@both_forms
def test_left_padding_is_the_rows_empty_past(form):
    t, pad = 40, 24
    q, k, v, g, beta, s0 = inputs(t, form=form, seed=3)
    s0 = jnp.zeros_like(s0)
    want_o, want_s = kda_chunk(q, k, v, g, beta, s0)
    junk = inputs(pad, form=form, seed=4)
    cat = lambda j, a: jnp.concatenate([j, a], axis=1)
    valid = jnp.broadcast_to(jnp.arange(t + pad) >= pad, (2, t + pad))
    o, s = kda_chunk(cat(junk[0], q), cat(junk[1], k), cat(junk[2], v), cat(junk[3], g),
                     cat(junk[4], beta), s0, valid)
    # Another alignment to the blocks: another order of float32 sums.
    np.testing.assert_allclose(np.asarray(o[:, pad:]), np.asarray(want_o), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=1e-4)


@both_forms
def test_no_decay_however_strong_overflows_and_the_state_stays_finite(form):
    """8,192 positions under the seeded weights' spread of decays, and
    under decays a hundred times stronger (exp(-G) would overflow float32
    within a block and exp(G) underflows to 0 in it; no exponent here is
    positive)."""
    for decay in (1.0, 100.0):
        q, k, v, g, beta, s0 = inputs(8192, b=1, h=2, form=form, seed=5, decay=decay)
        if decay > 1:
            assert float(jnp.min(jnp.sum(g[:, :BLOCK], axis=1))) < -200  # exp underflows
        o, s = jax.jit(kda_chunk)(q, k, v, g, beta, jnp.zeros_like(s0))
        assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
        assert float(jnp.max(jnp.abs(s))) < 1e3


@both_forms
def test_bfloat16_state_is_told_apart(form):
    """Keeping the state in bfloat16 between steps moves the outputs by
    far more than float32 rounding: what the chip run's tolerance is held
    against."""
    t = 256
    q, k, v, g, beta, s0 = inputs(t, b=1, form=form, decay=0.05, seed=6)
    want, _ = token_by_token(q, k, v, g, beta, jnp.zeros_like(s0))
    s, outs = jnp.zeros(s0.shape, jnp.bfloat16), []
    for i in range(t):
        o, s = kda_step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        outs.append(o)
    assert s.dtype == jnp.bfloat16
    err = float(jnp.max(jnp.abs(jnp.stack(outs, 1) - want)))
    assert err > 100 * 2e-5, err


def conv_by_hand(x, w):
    k = w.shape[0]
    past = np.concatenate([np.zeros((x.shape[0], k - 1, x.shape[2])), np.asarray(x)], axis=1)
    return sum(past[:, j:j + x.shape[1]] * np.asarray(w)[j] for j in range(k))


def test_causal_conv_carries_its_tail_across_calls_and_padding():
    x = jax.random.normal(jax.random.key(0), (2, 37, 12))
    w = jax.random.normal(jax.random.key(1), (4, 12))
    tail0 = jnp.zeros((2, 3, 12))
    whole, tail = causal_conv(x, w, tail0)
    np.testing.assert_allclose(np.asarray(whole), conv_by_hand(x, w), atol=1e-5)
    assert bool(jnp.all(tail == x[:, -3:]))
    # In pieces of 16, 16 and a 5 padded to 16 with junk: the same outputs,
    # and the tail where the last real token left it.
    y1, t1 = causal_conv(x[:, :16], w, tail0)
    y2, t2 = causal_conv(x[:, 16:32], w, t1)
    junk = jax.random.normal(jax.random.key(2), (2, 11, 12))
    valid = jnp.broadcast_to(jnp.arange(16) < 5, (2, 16))
    y3, t3 = causal_conv(jnp.concatenate([x[:, 32:], junk], 1), w, t2, valid)
    got = jnp.concatenate([y1, y2, y3[:, :5]], 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=1e-5)
    assert bool(jnp.all(t3 == tail))
    # One token at a time (decode), and a fully padded call: unchanged.
    y, t4 = causal_conv(x[:, :1], w, tail0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole[:, :1]), atol=1e-5)
    assert bool(jnp.all(causal_conv(junk, w, tail, jnp.zeros((2, 11), bool))[1] == tail))
    # Left padding counts as zeros: the row's empty past.
    left = jnp.concatenate([junk, x], 1)
    lv = jnp.broadcast_to(jnp.arange(48) >= 11, (2, 48))
    yl, tl = causal_conv(left, w, tail0, lv)
    np.testing.assert_allclose(np.asarray(yl[:, 11:]), np.asarray(whole), atol=1e-5)
    assert bool(jnp.all(tl == tail))
