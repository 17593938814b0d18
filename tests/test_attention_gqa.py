"""``xla_attention``'s grouped contraction against the plain form it replaced.

``tpufw.ops.attention.xla_attention`` contracts each kv head with its group
of query heads where the cache stores it (``"btkgd,bskd->bkgts"``); the
reference here is written the long way in float32: K and V repeated to the
query heads, one mask built from the arguments' meaning (not through
``attention_mask``), one softmax. Every way a caller masks is a case, at
every head grouping the models use (MHA, GQA, MQA, Laguna's 48 over 8) and
for a decode step (T = 1), a verify block (T = 5) and a whole row (T = S).
The whole row has more queries than ``_repeat_is_cheaper``'s bound at this
head width, so it runs the per-head spelling kept for many queries over a
short row, as MHA does at every length; the other two run the grouped one.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.ops.attention import _repeat_is_cheaper, xla_attention

B, S, D = 2, 24, 8
HEADS = [(8, 8), (8, 2), (6, 1), (48, 8)]
LENGTHS = [1, 5, S]
CASES = [
    "causal", "bidirectional", "segments", "q_positions", "sliding_window",
    "ring", "soft_cap", "wide_values",
]
# bf16 inputs against the float32 reference: the probabilities and the
# output are each rounded to bf16 (2**-8 relative, half that on average) on
# outputs of magnitude up to ~2 here: the largest difference over this
# file's grid reads 1.35e-2; a K or V head paired with the wrong query head
# reads 0.5 or more.
BF16_TOL = 2.5e-2


def reference(q, k, v, *, causal=True, segment_ids=None, kv_segment_ids=None,
              q_positions=None, logits_soft_cap=None, sliding_window=None,
              kv_positions=None):
    """Repeat, contract, mask, softmax, contract: float32 throughout."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    qpos = (
        jnp.broadcast_to(jnp.arange(t) + (s - t), (b, t))
        if q_positions is None else jnp.broadcast_to(q_positions, (b, t))
    )
    kpos = (
        jnp.broadcast_to(jnp.arange(s), (b, s))
        if kv_positions is None else kv_positions
    )
    behind = qpos[:, :, None] - kpos[:, None, :]  # [B,T,S]
    seen = jnp.ones((b, t, s), bool)
    if causal:
        seen &= behind >= 0
    if sliding_window is not None:
        seen &= behind < sliding_window
    if segment_ids is not None:
        kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
        seen &= segment_ids[:, :, None] == kv_seg[:, None, :]
    logits = jnp.where(seen[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def inputs(h, kh, t, case, dtype):
    """(q, k, v, kwargs) of one case; the queries are the row's last T
    positions unless the case says where they are."""
    kq, kk, kv = jax.random.split(jax.random.key(h * 1000 + kh * 10 + t), 3)
    dv = 12 if case == "wide_values" else D
    q = jax.random.normal(kq, (B, t, h, D)).astype(dtype)
    k = jax.random.normal(kk, (B, S, kh, D)).astype(dtype)
    v = jax.random.normal(kv, (B, S, kh, dv)).astype(dtype)
    kwargs = {}
    if case == "bidirectional":
        kwargs["causal"] = False
    elif case == "segments":
        # Two packed documents behind three pad slots; row 1 splits later.
        kv_seg = jnp.array([
            [0] * 3 + [1] * 6 + [2] * (S - 9),
            [0] * 3 + [1] * 9 + [2] * (S - 12),
        ])
        kwargs["kv_segment_ids"] = kv_seg
        kwargs["segment_ids"] = kv_seg[:, S - t:]
    elif case == "q_positions":
        # Rows at their own cursors inside a longer canvas (a slot pool).
        kwargs["q_positions"] = (
            jnp.array([[S - t - 3], [2]]) + jnp.arange(t)[None, :]
        ).clip(0, S - 1)
    elif case == "sliding_window":
        kwargs["sliding_window"] = 4
    elif case == "ring":
        # S ring slots that hold positions base .. base+S-1, wrapped at a
        # different slot in each row; the queries are the last T of them.
        base = jnp.array([[37], [100]])
        kwargs["kv_positions"] = base + (jnp.arange(S)[None, :] - base) % S
        kwargs["q_positions"] = base + S - t + jnp.arange(t)[None, :]
        kwargs["sliding_window"] = 6
    elif case == "soft_cap":
        kwargs["logits_soft_cap"] = 1.5
    return q, k, v, kwargs


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("h,kh", HEADS)
def test_float32_values_are_the_repeated_forms(h, kh, t, case):
    q, k, v, kwargs = inputs(h, kh, t, case, jnp.float32)
    got = xla_attention(q, k, v, **kwargs)
    want = reference(q, k, v, **kwargs)
    assert got.shape == (B, t, h, v.shape[-1]) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("h,kh", HEADS)
def test_bfloat16_values_stay_within_their_rounding(h, kh, t, case):
    q, k, v, kwargs = inputs(h, kh, t, case, jnp.bfloat16)
    got = xla_attention(q, k, v, **kwargs)
    want = reference(q, k, v, **kwargs)
    assert got.shape == (B, t, h, v.shape[-1]) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32), want, rtol=0, atol=BF16_TOL
    )


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("h,kh", HEADS)
def test_gradients_are_the_repeated_forms(h, kh, case):
    q, k, v, kwargs = inputs(h, kh, 5, case, jnp.float32)
    w = jax.random.normal(jax.random.key(7), (B, 5, h, v.shape[-1]))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **kwargs) * w)

    got = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(g, r, rtol=0, atol=1e-5, err_msg=name)


def _intermediate_shapes(jaxpr):
    """Shapes of everything a jaxpr computes, inner jaxprs too."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval.shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _intermediate_shapes(sub)


def _kv_at_query_heads(fn, q, k, v, **kwargs):
    """What a call computes that is as large as one row's K or V repeated
    to the query heads ([S, H, D]) and ends in the head's channels, in any
    dtype. (The logits of a many-query call are larger and end in S.)"""
    (s, d), h = k.shape[1::2], q.shape[2]
    jaxpr = jax.make_jaxpr(lambda q, k, v: fn(q, k, v, **kwargs))(q, k, v)
    return [
        shape for shape in _intermediate_shapes(jaxpr.jaxpr)
        if shape[-1:] == (d,) and math.prod(shape) >= s * h * d
    ]


def test_a_decode_step_holds_nothing_at_the_query_heads_width():
    """Laguna's 48 over 8 at T = 1, as a pool's step calls it. The
    repeated form holds K and V there, which shows the walk finds them."""
    h, kh, s, d = 48, 8, 64, 16
    q = jax.ShapeDtypeStruct((B, 1, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, s, kh, d), jnp.bfloat16)
    kwargs = dict(
        segment_ids=jnp.ones((B, 1), jnp.int32),
        kv_segment_ids=jnp.ones((B, s), jnp.int32),
        q_positions=jnp.full((B, 1), s - 1),
    )
    assert not _kv_at_query_heads(xla_attention, q, kv, kv, **kwargs)
    assert len(_kv_at_query_heads(reference, q, kv, kv, **kwargs)) >= 2


def test_a_long_rows_chunk_holds_nothing_at_the_query_heads_width():
    """512 queries over 8,192 keys, the rung where the per-head form falls
    off its cliff on the chip: the group is contracted in place there too."""
    q = jax.ShapeDtypeStruct((1, 512, 48, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    assert not _kv_at_query_heads(xla_attention, q, kv, kv)


@pytest.mark.parametrize("t,s,d,repeats", [
    (1, 16384, 128, False),   # a decode step
    (5, 2048, 128, False),    # a verify block
    (64, 2048, 128, False),   # a short prompt's tail chunk
    (512, 2048, 128, True),   # a prefill chunk over a low rung
    (512, 1024, 128, True),   # ... over a window layer's ring
    (512, 4096, 128, True),
    (512, 8192, 128, False),  # past the fused softmax's reach
    (512, 16384, 128, False),
])
def test_only_many_queries_over_a_short_row_repeat_the_kv_heads(
    t, s, d, repeats
):
    assert _repeat_is_cheaper(t, s, d) is repeats


def test_query_heads_must_divide_over_kv_heads():
    q = jnp.zeros((1, 1, 6, D))
    kv = jnp.zeros((1, S, 4, D))
    with pytest.raises(ValueError, match="not divisible"):
        xla_attention(q, kv, kv)
