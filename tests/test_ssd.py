"""The state-space recurrence (tpufw.ops.ssd): the chunkwise form against
the one-step form against a token-by-token recurrence written out here,
the identity under ``valid`` (holes and a padded tail leave the state
bit-equal), and no overflow at the strongest decay over 8,192 positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.ops.ssd import ssd_chunk, ssd_step

BLOCK = 16
H, P, N, G = 4, 8, 16, 2


def token_by_token(x, dt, a_rate, b_in, c_in, d_skip, s):
    """Written head by head, each reading the B and C of group h // (H/G)."""
    outs = []
    rep = lambda a: jnp.repeat(a, H // G, axis=1)  # [B,G,N] -> [B,H,N]
    for t in range(x.shape[1]):
        a = jnp.exp(-dt[:, t] * a_rate)  # [B,H]
        s = a[..., None, None] * s + (dt[:, t][..., None] * x[:, t])[..., None] * rep(b_in[:, t])[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", s, rep(c_in[:, t]), precision="highest")
        outs.append(y + d_skip[:, None] * x[:, t])
    return jnp.stack(outs, 1), s


def inputs(t, b=2, seed=0, decay=1.0):
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (b, t, H, P))
    # The seeded weights' spread: softplus(N(0,1) + a bias of -7 to -2), A in [1, 16], and stronger.
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, H)) + jnp.linspace(-7.0, -2.0, H)) * decay
    a_rate = jnp.linspace(1.0, 16.0, H)
    b_in = jax.random.normal(ks[2], (b, t, G, N))
    c_in = jax.random.normal(ks[3], (b, t, G, N))
    d_skip = 1.0 + 0.1 * jax.random.normal(ks[4], (H,))
    s0 = jax.random.normal(ks[5], (b, H, P, N))
    return x, dt, a_rate, b_in, c_in, d_skip, s0


@pytest.mark.parametrize("t", [1, BLOCK - 1, BLOCK, 2 * BLOCK + 5])
@pytest.mark.parametrize("decay", [1.0, 30.0])
def test_chunk_step_and_token_by_token_agree(t, decay):
    x, dt, a_rate, b_in, c_in, d_skip, s0 = inputs(t, decay=decay)
    want_y, want_s = token_by_token(x, dt, a_rate, b_in, c_in, d_skip, s0)
    got_y, got_s = ssd_chunk(x, dt, a_rate, b_in, c_in, d_skip, s0, block=BLOCK)
    # float32 against float32: the order of sums only (outputs reach tens).
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), rtol=2e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=2e-5, atol=3e-5)
    s, outs = s0, []
    for i in range(t):
        y, s = ssd_step(x[:, i], dt[:, i], a_rate, b_in[:, i], c_in[:, i], d_skip, s)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)), np.asarray(want_y), atol=3e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=3e-6)


@pytest.mark.parametrize("pad", [5, BLOCK + 9])
def test_a_padded_tail_leaves_the_state_bit_equal(pad):
    t = BLOCK + 6
    x, dt, a_rate, b_in, c_in, d_skip, s0 = inputs(t, seed=1)
    _, want = ssd_chunk(x, dt, a_rate, b_in, c_in, d_skip, s0, block=BLOCK)
    junk = inputs(pad, seed=2)
    padded = [jnp.concatenate([a, j], axis=1) for a, j in zip((x, dt, b_in, c_in), (junk[0], junk[1], junk[3], junk[4]))]
    valid = jnp.arange(t + pad)[None, :] < t
    valid = jnp.broadcast_to(valid, (x.shape[0], t + pad))
    y, got = ssd_chunk(padded[0], padded[1], a_rate, padded[2], padded[3], d_skip, s0, valid, block=BLOCK)
    assert bool(jnp.all(got == want)), "the padded tail is the identity on the state, to the bit"
    y_want, _ = ssd_chunk(x, dt, a_rate, b_in, c_in, d_skip, s0, block=BLOCK)
    np.testing.assert_allclose(np.asarray(y[:, :t]), np.asarray(y_want), atol=1e-6)


def test_holes_are_the_identity_and_left_padding_keeps_zero_zero():
    """Positions that are not valid neither decay nor write: the state
    after a row with holes is the state after the row without them; a
    step whose row is not live leaves the state bit-equal; a zero state
    under left padding stays zero to the bit."""
    t = 2 * BLOCK + 3
    x, dt, a_rate, b_in, c_in, d_skip, s0 = inputs(t, seed=3)
    valid = jnp.asarray(np.random.default_rng(0).random((2, t)) > 0.3)
    _, got = ssd_chunk(x, dt, a_rate, b_in, c_in, d_skip, s0, valid, block=BLOCK)
    for row in range(2):
        keep = np.flatnonzero(np.asarray(valid[row]))
        pick = lambda a: a[row:row + 1, keep]
        _, want = ssd_chunk(pick(x), pick(dt), a_rate, pick(b_in), pick(c_in), d_skip, s0[row:row + 1], block=BLOCK)
        np.testing.assert_allclose(np.asarray(got[row:row + 1]), np.asarray(want), atol=3e-5)
    _, same = ssd_step(x[:, 0], jnp.zeros_like(dt[:, 0]), a_rate, b_in[:, 0], c_in[:, 0], d_skip, s0)
    assert bool(jnp.all(same == s0))
    left = jnp.broadcast_to(jnp.arange(t)[None, :] >= BLOCK + 2, (2, t))
    zero = jnp.zeros_like(s0)
    _, after_pad = ssd_chunk(x[:, :BLOCK + 2], dt[:, :BLOCK + 2], a_rate, b_in[:, :BLOCK + 2], c_in[:, :BLOCK + 2],
                             d_skip, zero, left[:, :BLOCK + 2], block=BLOCK)
    assert bool(jnp.all(after_pad == 0.0))


def test_the_state_stays_finite_over_8192_positions_at_the_strongest_decay():
    """dt at its largest (0.1, and ten times it) with A = 16: exponents of
    -1.6 to -16 a token, -200 to -2,000 a block of 128; every one is a
    difference L_t - L_s <= 0, so nothing overflows and nothing is NaN."""
    t, block = 8192, 128
    ks = jax.random.split(jax.random.key(4), 4)
    x = jax.random.normal(ks[0], (1, t, 2, P))
    b_in = jax.random.normal(ks[1], (1, t, 1, N))
    c_in = jax.random.normal(ks[2], (1, t, 1, N))
    dt = jnp.stack([jnp.full((1, t), 0.1), jnp.full((1, t), 1.0)], axis=-1)
    y, s = jax.jit(lambda *a: ssd_chunk(*a, block=block))(
        x, dt, jnp.full((2,), 16.0), b_in, c_in, jnp.ones((2,)), jnp.zeros((1, 2, P, N)))
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))
    assert 1e-3 < float(jnp.std(y[:, -512:])) < 1e2 and 1e-4 < float(jnp.std(s)) < 1e2
