"""Gated DeltaNet's token mixer (Yang, Kautz & Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464): a causal depthwise convolution, then the
gated delta rule, a linear-attention recurrence whose state is one
``[d_k, d_v]`` matrix a head. Per head, from ``S_0 = 0``::

    S' = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

:func:`chunked_delta_rule` computes it chunk by chunk (the paper's
section 3, the WY / UT form). Inside a chunk of ``C`` tokens, with
``G_r = g_1 + ... + g_r`` and ``S_0`` the state the chunk starts from, the
updates ``u_r = beta_r (v_r - S'^T k_r)`` solve the unit lower-triangular
system ``(I + A) U = beta V - diag(beta exp(G)) K S_0``, ``A[r, s] = beta_r
exp(G_r - G_s) k_r . k_s`` for ``s < r``; then ``o_r = exp(G_r) S_0^T q_r +
sum_{s <= r} exp(G_r - G_s) (q_r . k_s) u_s`` and the chunk hands on
``exp(G_C) S_0 + sum_s exp(G_C - G_s) k_s u_s^T``. A ``lax.scan`` over the
chunks carries the state through the two products that need it (``U``'s
term in ``S_0`` and the state handed on); everything else, the solve and
the outputs included, is batched over every chunk at once.

Everything here runs in float32, the products at ``highest`` precision:
the state, the decays and the solve. A decay is the exponential of a
difference of cumulative sums inside one chunk, masked before ``exp``, so
it never exceeds one and a large ``exp(A_log)`` cannot overflow it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.observability import metrics as _metrics

#: tokens a chunk: the triangular system and the in-chunk scores are
#: ``[CHUNK, CHUNK]`` a head and chunk
CHUNK = 64

_mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)


def chunk_length(tokens: int) -> int:
    """The chunk :func:`gated_delta_rule` takes for a row of ``tokens``."""
    return min(CHUNK, tokens)


def causal_conv(x, w):
    """Depthwise causal convolution over the tokens of ``x`` ``[B, T, C]``
    with ``w`` ``[C, K]`` (torch's ``Conv1d(groups=C)`` weight without its
    middle axis), zeros before the first token:
    ``out[t, c] = sum_j w[c, j] x[t - K + 1 + j, c]``."""
    taps, t = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[:, j] for j in range(taps))


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis."""
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def chunked_delta_rule(q, k, v, g, beta, chunk: int):
    """The gated delta rule over ``q``, ``k`` ``[B, T, H, d_k]``, ``v``
    ``[B, T, H, d_v]``, ``g`` (log-decay, at most 0) and ``beta``
    ``[B, T, H]``, in chunks of ``chunk`` tokens: ``o`` ``[B, T, H, d_v]``
    float32. A row whose length is no multiple of ``chunk`` is padded with
    tokens that neither decay nor write the state, after its last."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // chunk)

    def chunks(x):
        # [B, T, H, ...] -> [B, H, N, C, ...], float32
        x = jnp.pad(x.astype(jnp.float32),
                    [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, chunk, h, *x.shape[3:]), 3, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)                               # G_r
    rows = jnp.arange(chunk)
    causal = rows[:, None] >= rows[None, :]
    strict = rows[:, None] > rows[None, :]
    diff = cum[..., :, None] - cum[..., None, :]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    a = jnp.where(strict, beta[..., :, None] * _mm("...rd,...sd->...rs", k, k)
                  * decay, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(cum))[..., None] * k], -1)
    solved = lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    w_v, w_k = solved[..., :dv], solved[..., dv:]
    scores = _mm("...rd,...sd->...rs", q, k) * decay
    q_in = q * jnp.exp(cum)[..., None]
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]
    carry_on = jnp.exp(cum[..., -1])                            # [B, H, N]

    def step(state, xs):
        w_v, w_k, k_out, carry_on = xs
        u = w_v - _mm("bhcd,bhde->bhce", w_k, state)
        new = (carry_on[..., None, None] * state
               + _mm("bhcd,bhce->bhde", k_out, u))
        return new, (u, state)

    by_chunk = [jnp.moveaxis(x, 2, 0) for x in (w_v, w_k, k_out, carry_on)]
    _, (u, starts) = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32),
                              by_chunk)
    o = (_mm("bhncd,nbhde->bhnce", q_in, starts)
         + _mm("bhnrs,nbhse->bhnre", scores, u))
    return jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)[:, :t]


def gated_delta_rule(q, k, v, g, beta):
    """:func:`chunked_delta_rule` in chunks of :func:`chunk_length`."""
    t = q.shape[1]
    chunk = chunk_length(t)
    if _metrics.enabled():
        _metrics.gauge("gdn_chunk",
                       help="tokens a chunk of the gated delta rule, fixed "
                            "at trace time").set(chunk)
        _metrics.gauge("gdn_chunks",
                       help="chunks a row of the gated delta rule, one "
                            "after another in its scan").set(-(-t // chunk))
    return chunked_delta_rule(q, k, v, g, beta, chunk)


def gated_delta_mixer(qkvz, ba, conv, a_log, dt_bias, norm, *, key_heads: int,
                      key_dim: int, value_dim: int, eps: float = 1e-6):
    """What a Gated DeltaNet layer computes between its in- and
    out-projections, as the published checkpoints lay the projections out
    (Qwen3-Next's ``in_proj_qkvz`` and ``in_proj_ba``): ``qkvz`` ``[B, T,
    key_heads x (2 d_k + 2 r d_v)]`` holds per key head its ``q``, ``k``, its
    ``r`` value heads' ``v`` and their ``z``; ``ba`` ``[B, T, key_heads x
    2 r]`` per key head its value heads' ``b`` and ``a``. ``conv`` ``[2
    key_heads d_k + value_heads d_v, K]`` over the channels ``[q | k | v]``;
    ``a_log``, ``dt_bias`` ``[value_heads]``; ``norm`` ``[d_v]``.

    ``[q | k | v]`` pass through the causal convolution and ``silu``; ``q``
    and ``k`` are normalised to length one, ``q`` then scaled by ``d_k^-1/2``,
    and each key head serves its ``r`` value heads; ``beta = sigmoid(b)``,
    ``g = -exp(a_log) softplus(a + dt_bias)``; the gated delta rule; then per
    value head ``rmsnorm(o) norm * silu(z)``. Returns ``[B, T, value_heads
    x d_v]`` float32, for the out-projection."""
    b, t, _ = qkvz.shape
    heads = a_log.shape[0]
    r = heads // key_heads
    groups = qkvz.astype(jnp.float32).reshape(
        b, t, key_heads, 2 * key_dim + 2 * r * value_dim)
    q, k, v, z = jnp.split(
        groups, [key_dim, 2 * key_dim, 2 * key_dim + r * value_dim], -1)
    ba = ba.astype(jnp.float32).reshape(b, t, key_heads, 2 * r)
    b_in, a = (x.reshape(b, t, heads) for x in jnp.split(ba, 2, -1))
    mixed = jax.nn.silu(causal_conv(jnp.concatenate(
        [q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1)], -1),
        conv))
    q, k, v = jnp.split(mixed, [key_heads * key_dim, 2 * key_heads * key_dim],
                        -1)
    # key head j serves value heads j r ... j r + r - 1
    q = jnp.repeat(l2_normalize(q.reshape(b, t, key_heads, key_dim)), r, 2)
    k = jnp.repeat(l2_normalize(k.reshape(b, t, key_heads, key_dim)), r, 2)
    beta = jax.nn.sigmoid(b_in)
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    o = gated_delta_rule(q * key_dim ** -0.5, k,
                         v.reshape(b, t, heads, value_dim), g, beta)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    y = o * norm * jax.nn.silu(z.reshape(b, t, heads, value_dim))
    return y.reshape(b, t, heads * value_dim)
