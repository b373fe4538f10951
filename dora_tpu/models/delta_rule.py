"""The gated delta rule, for the model files whose layers keep its state
(``models/hf/glm5_next.py``: KDA, a decay a key channel; ``models/hf/
olmo_hybrid.py``: the Gated DeltaNet, ONE decay a head): the blocked (WY)
form a prefill chunk runs and the one-token step a decode tick runs
(``ops/kda_state_step``). A change here is a change to both models'
programs; ``tests/program_text.py`` says whether either moved.

    S~  = diag(exp(g_t)) S_{t-1}            g_t [d_k] a head, or a scalar a head
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

``S [d_k, d_v]`` float32 a head; every product and sum read from it is
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dora_tpu.ops.kda_state_step import kda_state_step

_HIGHEST = jax.lax.Precision.HIGHEST


def delta_rule_step(state, g, k, q, v, beta, active):
    """One token of every ``active`` row, in one pass over the rows' state
    (``ops/kda_state_step``: rows that are not live move none of it).
    ``g`` is ``[R, H, d_k]`` (a decay a key channel) or ``[R, H]`` (one a
    head, spread over its channels here: the kernel multiplies a column
    either way). Returns (o ``[R, H, d_v]`` float32, the state)."""
    if g.ndim == k.ndim - 1:
        g = jnp.broadcast_to(g[..., None], k.shape)
    return kda_state_step(state, g, k, q, v, beta, active)


def _blocks(block: int, *rows):
    """``[C, ...]`` arrays as ``[C / block, block, ...]``."""
    c = rows[0].shape[0]
    qn = min(block, c)
    assert c % qn == 0, (c, qn)
    return qn, [t.reshape(c // qn, qn, *t.shape[1:]) for t in rows]


def _mm(x, y):
    return jnp.matmul(x, y, precision=_HIGHEST)


def _scan_blocks(s0, qb, kb, vb, bb, gsum, inv, b_mat):
    """The pass over the blocks that both forms share, one block a step
    from ``s0``: ``gsum`` is the running sum of the log decays inside a
    block, ``[nb, Q, H, d_k]`` or ``[nb, Q, H, 1]``; ``inv = (I + A)^-1``
    and ``b_mat = B``, ``[nb, H, t, s]``, as the callers' docstrings have
    them. Returns (o ``[C, H, d_v]``, the state after the last row)."""
    decay = jnp.exp(gsum)  # from the block's start to each row
    to_end = jnp.exp(gsum[:, -1:] - gsum)  # from each row to the block's end

    def body(s, inp):
        q_, k_, v_, beta_, decay_, to_end_, inv_, b_ = inp
        rhs = beta_[..., None] * (v_ - jnp.einsum(
            "thk,hkv->thv", k_ * decay_, s, precision=_HIGHEST))
        u = jnp.einsum("hts,shv->thv", inv_, rhs, precision=_HIGHEST)
        o = jnp.einsum("thk,hkv->thv", q_ * decay_, s, precision=_HIGHEST) \
            + jnp.einsum("hts,shv->thv", b_, u, precision=_HIGHEST)
        s = s * decay_[-1][..., None] + jnp.einsum(
            "thk,thv->hkv", k_ * to_end_, u, precision=_HIGHEST)
        return s, o

    s, o = jax.lax.scan(body, s0, (qb, kb, vb, bb, decay, to_end, inv, b_mat))
    return o.reshape(-1, *o.shape[2:]), s


def delta_rule_blocks(q, k, v, g, beta, s0, block: int):
    """The blocked (WY) form of the gated delta rule over ``C`` rows,
    ``block`` at a time: q, k ``[C, H, d_k]``, v ``[C, H, d_v]``, g ``[C,
    H, d_k]`` (log decays, <= 0; 0 with beta 0 for a row that must leave
    the state alone), beta ``[C, H]``, s0 ``[H, d_k, d_v]``, float32.
    Returns (o ``[C, H, d_v]``, the state after the last row).

    With ``G`` the running sum of ``g`` inside a block and ``u_t = beta_t
    (v_t - S~_t^T k_t)``: ``(I + A) U = beta (V - (K exp(G)) S_0)`` where
    ``A[t, s] = beta_t sum_d k_t k_s exp(G_t - G_s)`` for ``s < t``; ``O =
    (Q exp(G)) S_0 + B U`` with ``B[t, s] = sum_d q_t k_s exp(G_t - G_s)``
    for ``s <= t``; ``S_end = exp(G_end) S_0 + (K exp(G_end - G))^T U``.
    Every exponent is <= 0, so nothing overflows at any decay; ``A`` and
    ``B`` do not depend on the state and are computed for all blocks at
    once; ``(I + A)^-1 = prod_j (I + (-A)^(2^j))`` (``A`` is strictly
    lower triangular). The matrix products run at HIGHEST precision."""
    qn, (qb, kb, vb, gb, bb) = _blocks(block, q, k, v, g, beta)
    assert qn & (qn - 1) == 0, qn
    gsum = jnp.cumsum(gb, axis=1)  # [nb, Q, H, d_k], inclusive
    t_idx = jnp.arange(qn)
    lower = t_idx[:, None] >= t_idx[None, :]  # s <= t
    pair = jnp.exp(jnp.where(
        lower[None, :, :, None, None],
        gsum[:, :, None] - gsum[:, None, :], -jnp.inf))  # [nb, t, s, H, d_k]
    kk = (kb[:, :, None] * kb[:, None, :] * pair).sum(-1)  # [nb, t, s, H]
    qk = (qb[:, :, None] * kb[:, None, :] * pair).sum(-1)
    a = jnp.where((t_idx[:, None] > t_idx[None, :])[None, :, :, None],
                  bb[:, :, None, :] * kk, 0.0)
    a = jnp.moveaxis(a, -1, 1)  # [nb, H, t, s]
    b_mat = jnp.moveaxis(qk, -1, 1)
    power = -a
    inv = jnp.eye(qn, dtype=a.dtype) + power
    for _ in range(qn.bit_length() - 2):
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
    return _scan_blocks(s0, qb, kb, vb, bb, gsum, inv, b_mat)


def head_gated_delta_rule_blocks(q, k, v, g, beta, s0, block: int):
    """:func:`delta_rule_blocks` for ONE decay a head, ``g [C, H]``: the
    pairwise decay ``exp(G_t - G_s)`` no longer depends on the key
    channel, so ``A`` and ``B`` are matrix products ``K K^T`` and ``Q
    K^T`` times it (the per-channel form multiplies and sums ``block^2
    d_k`` values a head on the vector unit). ``beta`` may reach 2
    (``linear_allow_neg_eigval``): ``(I + A)`` is unit lower triangular
    whatever its entries, and it is SOLVED (a triangular solve against
    the identity, forward substitution), not expanded in powers of ``A``,
    whose entries grow as ``(beta block)^j`` before they cancel. Same
    operands otherwise, same results: (o ``[C, H, d_v]``, the state after
    the last row)."""
    qn, (qb, kb, vb, gb, bb) = _blocks(block, q, k, v, g, beta)
    gsum = jnp.cumsum(gb, axis=1)  # [nb, Q, H], inclusive
    gh = jnp.moveaxis(gsum, -1, 1)  # [nb, H, Q]
    t_idx = jnp.arange(qn)
    lower = t_idx[:, None] >= t_idx[None, :]  # s <= t
    pair = jnp.exp(jnp.where(lower, gh[..., :, None] - gh[..., None, :],
                             -jnp.inf))  # [nb, H, t, s]
    kh, qh = (jnp.moveaxis(t, 2, 1) for t in (kb, qb))  # [nb, H, Q, d_k]
    kk = _mm(kh, jnp.swapaxes(kh, -1, -2)) * pair
    b_mat = _mm(qh, jnp.swapaxes(kh, -1, -2)) * pair
    a = jnp.where(t_idx[:, None] > t_idx[None, :],
                  jnp.moveaxis(bb, -1, 1)[..., None] * kk, 0.0)
    eye = jnp.eye(qn, dtype=a.dtype)
    inv = jax.lax.linalg.triangular_solve(
        eye + a, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True)
    return _scan_blocks(s0, qb, kb, vb, bb, gsum[..., None], inv, b_mat)
