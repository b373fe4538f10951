"""``ops/kda_state_step``: the gated delta rule's decode step as one
Pallas pass over the live rows' state (interpret mode here), against the
whole-array ``jax.numpy`` form and against the blocked form the prefill
chunk runs."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dora_tpu.models.hf import glm5_next as G
from dora_tpu.ops import kda_state_step as K

#: (rows, heads, d_k, d_v): small ones (one block a row; a head count
#: that is no multiple of 8; d_k != d_v; two blocks a row) and one block
#: at the published head shape
SHAPES = [(5, 8, 16, 16), (3, 3, 8, 8), (4, 4, 16, 32), (3, 16, 128, 64),
          (2, 16, 128, 128)]
ACTIVE = {
    "all": lambda r: [1] * r,
    "some": lambda r: [i % 2 for i in range(r)],
    "first_off": lambda r: [0] + [1] * (r - 1),
    "last_off": lambda r: [1] * (r - 1) + [0],
    "none": lambda r: [0] * r,
}


def inputs(shape, seed=4, lower=-5.0):
    """(state, g, k, q, v, beta) as ``kda_step`` hands them over: ``k``
    l2-normed, ``g`` in ``(lower, 0)``, ``beta`` in (0, 1)."""
    rng = np.random.default_rng(seed)
    r, h, dk, dv = shape

    def f(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    k = f(r, h, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (f(r, h, dk, dv), lower * jax.nn.sigmoid(f(r, h, dk)), k,
            f(r, h, dk) * dk ** -0.5, f(r, h, dv), jax.nn.sigmoid(f(r, h)))


@pytest.mark.parametrize("active", sorted(ACTIVE))
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_the_kernel_equals_the_whole_array_form(shape, active):
    """To float32 rounding (the kernel sums ``d_k`` in another order),
    for every shape of the grid's tables."""
    state, *rest = inputs(shape)
    on = jnp.asarray(ACTIVE[active](shape[0]), bool)
    o_ref, s_ref = K.kda_state_step_reference(state, *rest, on)
    o, s = K.kda_state_step(state, *rest, on)
    assert o.dtype == s.dtype == jnp.float32 and s.shape == state.shape
    assert np.abs(np.asarray(o) - np.asarray(o_ref)).max() < 1e-5
    assert np.abs(np.asarray(s) - np.asarray(s_ref)).max() < 1e-5


@pytest.mark.parametrize("active", sorted(ACTIVE))
def test_an_inactive_row_keeps_its_state_bit_for_bit_and_reads_zeros(active):
    """Also where the row's inputs would overflow a float32 if they were
    applied: the kernel must not touch the row at all."""
    shape = (5, 16, 16, 16)
    state, g, k, q, v, beta = inputs(shape, seed=5)
    on = jnp.asarray(ACTIVE[active](shape[0]), bool)
    off = ~np.asarray(on)
    v = jnp.where(on[:, None, None], v, jnp.inf)
    o, s = K.kda_state_step(state, g, k, q, v, beta, on)
    assert (np.asarray(s)[off] == np.asarray(state)[off]).all()
    assert not np.asarray(o)[off].any()
    assert np.isfinite(np.asarray(s)).all() and np.isfinite(np.asarray(o)).all()
    if on.any():
        assert (np.asarray(s)[~off] != np.asarray(state)[~off]).any()


@pytest.mark.parametrize("lower,name", [(-5.0, "near exp(-5)"), (-1e-3, "near 1"),
                                        (-0.7, "between")])
def test_stepping_a_token_at_a_time_equals_the_blocked_delta_rule(lower, name):
    """T tokens through the kernel, one at a time, against
    ``delta_rule_blocks`` over the same T rows from the same state (the
    inputs of ``test_the_blocked_delta_rule_equals_the_recurrence``): what
    a prefill chunk leaves is what decode would have left."""
    rng = np.random.default_rng(7)
    c, h, d = 32, 3, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v = f(c, h, d), f(c, h, d), f(c, h, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = lower * jax.nn.sigmoid(f(c, h, d) + (4.0 if lower == -5.0 else 0.0))
    beta = jax.nn.sigmoid(f(c, h))
    s0 = f(h, d, d)
    o_want, s_want = G.delta_rule_blocks(q, k, v, g, beta, s0, 16)
    # row 0 is the stream; row 1 is a frozen neighbour
    state = jnp.stack([s0, s0])
    on = jnp.asarray([True, False])
    two = lambda t: jnp.stack([t, t])  # noqa: E731
    outs = []
    for t in range(c):
        o, state = K.kda_state_step(
            state, two(g[t]), two(k[t]), two(q[t]), two(v[t]), two(beta[t]), on)
        outs.append(o[0])
    assert np.abs(np.asarray(jnp.stack(outs) - o_want)).max() < 2e-5, name
    assert np.abs(np.asarray(state[0] - s_want)).max() < 2e-5, name
    assert (np.asarray(state[1]) == np.asarray(s0)).all()


@pytest.mark.parametrize("beta", [0.0, 1.0], ids=["beta0", "beta1"])
def test_decays_at_the_bound_leave_no_overflow_and_the_right_state(beta):
    """``g`` = ``gate_lower_bound`` on every channel: the state shrinks by
    ``exp(-5)`` a token. With ``beta`` 0 nothing is written, so after T
    tokens the state is ``exp(-5 T)`` of what it was (to the point of
    underflow, never a NaN); with ``beta`` 1 the state then holds ``v``
    for ``k`` exactly: ``pred`` of the same ``k`` reads ``v``."""
    shape = (2, 8, 16, 16)
    state, _, k, q, v, _ = inputs(shape, seed=6)
    g = jnp.full(k.shape, -5.0)  # linear_attn_config.gate_lower_bound
    on = jnp.ones((2,), bool)
    b = jnp.full(shape[:2], beta, jnp.float32)
    s = state * 1e30  # large, finite: a decay must not turn it into inf
    for t in range(1, 25):
        o, s = K.kda_state_step(s, g, k, q, v, b, on)
        assert np.isfinite(np.asarray(s)).all() and np.isfinite(np.asarray(o)).all()
        if beta == 0.0 and t <= 3:
            want = np.asarray(state * 1e30) * np.exp(np.float32(-5.0 * t))
            assert np.allclose(np.asarray(s), want, rtol=1e-5)
    held = (np.asarray(s) * np.asarray(k)[..., None]).sum(-2)  # S^T k
    if beta == 0.0:
        assert np.abs(np.asarray(s)).max() < 1e-15  # 1e30 exp(-120)
    else:
        assert np.abs(held - np.asarray(v)).max() < 1e-4


def test_the_state_is_aliased_and_the_call_carries_its_name():
    """One ``pallas_call`` named ``kda_state_step`` (what the device
    trace's breakdown lists it under), its state operand aliased to its
    state result, and no operand below float32."""
    state, *rest = inputs((2, 16, 128, 128))
    traced = jax.make_jaxpr(K.kda_state_step.__wrapped__)(
        state, *rest, jnp.ones((2,), bool))
    calls = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    (call,) = calls
    assert call.params["name"] == "kda_state_step"
    assert tuple(call.params["input_output_aliases"]) == ((5, 1),)
    assert call.invars[5].aval.shape == state.shape
    floats = {v.aval.dtype for v in call.invars
              if jnp.issubdtype(v.aval.dtype, jnp.floating)}
    assert floats == {jnp.dtype(jnp.float32)}
    assert K.head_block(64, 128, 128) == 16  # 1 MB a grid step, 4 steps a row


@pytest.mark.parametrize("active", ["all", "some"])
def test_the_kernel_holds_the_references_step_at_30_heads_of_96_by_192(active):
    """Olmo-Hybrid-7B's heads (one block of all 30: no divisor of 30 is a
    multiple of 8), ONE decay a head spread over the key channels by
    ``models/delta_rule.delta_rule_step``, ``beta`` in (1, 2)
    (``linear_allow_neg_eigval``): against the plain reference's step,
    ``S~ = alpha S; S' = S~ + beta k (v - S~^T k)^T; o = S'^T q``, as
    ``olmo_hybrid_reference.delta_rule`` writes it."""
    from dora_tpu.models.delta_rule import delta_rule_step

    shape = (3, 30, 96, 192)
    assert K.head_block(*shape[1:]) == 30
    state, _g, k, q, v, beta = inputs(shape, seed=9)
    beta = 1.0 + beta
    assert float(beta.min()) > 1.0 and float(beta.max()) < 2.0
    rng = np.random.default_rng(10)
    g = -jnp.exp(jnp.asarray(rng.standard_normal(shape[:2]), jnp.float32) - 2.0)
    on = jnp.asarray(ACTIVE[active](shape[0]), bool)

    def step(s, g_, k_, q_, v_, b_):
        s = s * jnp.exp(g_)[:, None, None]
        pred = jnp.einsum("hkv,hk->hv", s, k_)
        s = s + (b_[:, None] * k_)[:, :, None] * (v_ - pred)[:, None, :]
        return jnp.einsum("hkv,hk->hv", s, q_), s

    o_ref, s_ref = jax.vmap(step)(state, g, k, q, v, beta)
    o, s = delta_rule_step(state, g, k, q, v, beta, on)
    live = np.asarray(on)
    assert np.abs(np.asarray(o) - np.asarray(o_ref))[live].max() < 2e-5
    assert np.abs(np.asarray(s) - np.asarray(s_ref))[live].max() < 2e-5
    assert (np.asarray(s)[~live] == np.asarray(state)[~live]).all()
