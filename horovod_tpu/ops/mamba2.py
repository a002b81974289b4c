"""Mamba-2's token mixer (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060):
a causal depthwise convolution, then the selective state-space recurrence
with a scalar decay a head (SSD), then a gated group norm. Per head ``h`` of
``P`` channels, on its group's ``B`` and ``C`` of ``N``, from ``S_0 = 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      # S: [P, N]
    y_t = S_t C_t + D x_t

:func:`ssd_chunked` computes it chunk by chunk (the paper's section 6, the
"SSD minimal" form). Inside a chunk of ``L`` tokens, with ``G_r = dt_1 A +
... + dt_r A``, the within-chunk part is a masked product ``y_r = sum_{s <=
r} exp(G_r - G_s) (C_r . B_s) dt_s x_s``; each chunk hands on the state
``sum_s exp(G_L - G_s) dt_s x_s B_s^T``; the states chunks start from are
the decayed sums of those before, one ``[chunks, chunks]`` product over the
chunks' totals (no scan); and each token reads its chunk's starting state
through ``exp(G_r) C_r``. A head's decay is a difference of cumulative sums
inside one chunk (or across chunks' totals), masked before ``exp``, so it
never exceeds one.

Everything here runs in float32, the products at ``highest`` precision:
the state, the decays, the norm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.ops.gated_delta import causal_conv

_mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)


def _decays(cum):
    """``exp(cum[..., r] - cum[..., s])`` for ``s <= r``, else 0, over the
    last axis: ``[..., L] -> [..., L, L]``."""
    n = cum.shape[-1]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    diff = cum[..., :, None] - cum[..., None, :]
    return jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The recurrence over ``x`` ``[B, T, H, P]``, ``dt`` ``[B, T, H]``
    (after its softplus), ``a`` ``[H]`` (negative), ``b`` and ``c`` ``[B, T,
    G, N]`` (head ``h`` reads group ``h // (H / G)``), in chunks of
    ``chunk`` tokens: ``y`` ``[B, T, H, P]`` float32, without the ``D``
    term. A row whose length is no multiple of ``chunk`` is padded after its
    last token with tokens that neither decay nor write the state."""
    bs, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    nc = -(-t // chunk)

    def chunks(v):
        # [B, T, ...] -> [B, NC, L, ...], float32, zeros after the last token
        v = jnp.pad(v.astype(jnp.float32),
                    [(0, 0), (0, nc * chunk - t)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape(bs, nc, chunk, *v.shape[2:])

    x, dt, b, c = map(chunks, (x, dt, b, c))
    x = (x * dt[..., None]).reshape(bs, nc, chunk, g, r, p)    # dt_s x_s
    # log-decays by head: [B, G, R, NC, L]
    log_a = jnp.moveaxis((dt * a.astype(jnp.float32)).reshape(
        bs, nc, chunk, g, r), (3, 4), (1, 2))
    cum = jnp.cumsum(log_a, axis=-1)                          # G_r
    # within a chunk: (C_r . B_s) of the head's group, decayed
    scores = _mm("bcign,bcjgn->bgcij", c, b)
    within = _mm("bgrcij,bcjgrp->bcigrp", scores[:, :, None] * _decays(cum),
                 x)
    # each chunk's own contribution to the state it hands on: [B, NC, G, R,
    # P, N]
    to_end = jnp.exp(cum[..., -1:] - cum)
    handed = _mm("bcjgn,bgrcj,bcjgrp->bcgrpn", b, to_end, x)
    # the state each chunk starts from: the states handed on before it,
    # each decayed through the chunks between (``across[z, c]``, c < z)
    totals = jnp.pad(cum[..., -1], [(0, 0)] * 3 + [(1, 0)])   # [B,G,R,NC+1]
    across = _decays(jnp.cumsum(totals, axis=-1))[..., :-1, 1:]
    starts = _mm("bgrzc,bcgrpn->bzgrpn", across, handed)
    entering = _mm("bcign,bcgrpn->bcigrp", c, starts) * jnp.moveaxis(
        jnp.exp(cum), (1, 2), (3, 4))[..., None]
    y = (within + entering).reshape(bs, nc * chunk, h, p)
    return y[:, :t]


def chunk_length(tokens: int, chunk: int) -> int:
    """The chunk :func:`ssd` takes for a row of ``tokens``: the model's, or
    the row where it is shorter."""
    return min(chunk, tokens)


def ssd(x, dt, a, b, c, chunk: int):
    """:func:`ssd_chunked` in chunks of :func:`chunk_length`."""
    t = x.shape[1]
    chunk = chunk_length(t, chunk)
    if _metrics.enabled():
        _metrics.gauge("ssm_chunk",
                       help="tokens a chunk of the Mamba-2 recurrence, fixed "
                            "at trace time").set(chunk)
        _metrics.gauge("ssm_chunks",
                       help="chunks a row of the Mamba-2 recurrence, "
                            "batched in its products").set(-(-t // chunk))
    return ssd_chunked(x, dt, a, b, c, chunk)


def mamba2_mixer(zxbcdt, conv, conv_bias, dt_bias, a_log, d, norm, *,
                 heads: int, head_dim: int, groups: int, state: int,
                 chunk: int, eps: float = 1e-5):
    """What a Mamba-2 layer computes between its in- and out-projections, as
    the published checkpoints lay the in-projection out: ``zxbcdt`` ``[B, T,
    I + I + 2 G N + H]`` is ``[z | x | B | C | dt]`` with ``I = heads x
    head_dim``; ``conv`` ``[I + 2 G N, K]`` and ``conv_bias`` over the
    channels ``[x | B | C]``; ``dt_bias``, ``a_log``, ``d`` ``[heads]``;
    ``norm`` ``[I]``.

    ``[x | B | C]`` pass through the causal convolution (plus its bias) and
    ``silu``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the
    recurrence (:func:`ssd`) plus ``D x``; then ``y * silu(z)`` normalised
    by RMS over each of the ``groups`` groups of ``I / groups`` channels,
    times ``norm``. Returns ``[B, T, I]`` float32, for the
    out-projection."""
    bs, t, _ = zxbcdt.shape
    inner, n = heads * head_dim, groups * state
    zxbcdt = zxbcdt.astype(jnp.float32)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n], -1)
    xbc = jax.nn.silu(causal_conv(xbc, conv) + conv_bias)
    x, b, c = jnp.split(xbc, [inner, inner + n], -1)
    x = x.reshape(bs, t, heads, head_dim)
    dt = jax.nn.softplus(dt + dt_bias)
    y = ssd(x, dt, -jnp.exp(a_log), b.reshape(bs, t, groups, state),
            c.reshape(bs, t, groups, state), chunk)
    y = (y + d[:, None] * x).reshape(bs, t, inner) * jax.nn.silu(z)
    y = y.reshape(bs, t, groups, inner // groups)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y.reshape(bs, t, inner) * norm
