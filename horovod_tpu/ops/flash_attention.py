"""Blockwise (flash) attention: the single-chip building block of the
long-context stack (:mod:`horovod_tpu.parallel.ring_attention`).

No counterpart exists in the reference — Horovod 0.19.2 shards only the batch
axis (SURVEY.md §5.7) — so this module is TPU-native capability: an online-
softmax attention whose working set stays in VMEM-sized tiles feeding the MXU,
written as a Pallas kernel (grid ``[batch*heads, q_blocks, k_blocks]``, each
step a static schedule of sub-tiles, accumulators in VMEM scratch) with a
mathematically identical ``lax.scan`` implementation used off-TPU.

The backward pass is the standard flash backward: the forward saves only
``out`` and the log-sum-exp rows (O(T) extra memory, not the O(T²) score
matrix); the backward recomputes each block's probabilities from (q, k, lse)
and accumulates dq/dk/dv blockwise. On the Pallas path it is Pallas too
(:func:`_flash_bwd_pallas`: the tiles stay in VMEM; one fused call where the
sequence is one block, a dk/dv call and a dq call where it is more);
elsewhere a ``lax.scan`` over K/V blocks, whose block primitive
(:func:`_block_bwd`) also powers ring attention's distributed backward.

``window=W`` beside ``causal=True`` narrows the mask to the band
``0 <= i - j < W`` (sliding-window attention). The kernels already leave
out every tile above the diagonal and copy none of its blocks; a window is
the same device from the other side: tiles wholly before the band are left
out and their blocks not copied, tiles the band's edges cross are masked,
in the forward, the fused backward and both calls of the two-call one.
``window=None`` traces the program it always traced.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.observability import metrics as _metrics

NEG_INF = -1e30
#: lse stand-in for fully-masked rows: exp(s - BIG) == 0 for any real score
LSE_MASKED = 1e30


def _block_sizes(t_q: int, t_k: int, block_q: int, block_k: int):
    bq = min(block_q, t_q)
    bk = min(block_k, t_k)
    while t_q % bq:
        bq //= 2
    while t_k % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def _causal_mask(q_ids, k_ids, window: Optional[int] = None):
    """Row i sees column j where ``j <= i`` and, under a ``window``, where
    ``i - j < window``."""
    mask = q_ids[:, None] >= k_ids[None, :]
    if window is not None:
        mask = mask & (q_ids[:, None] - k_ids[None, :] < window)
    return mask


def lse_from_state(m, l):
    """log-sum-exp rows from online-softmax state; fully-masked rows get
    ``LSE_MASKED`` so recomputed probabilities vanish."""
    return jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), LSE_MASKED)


# --------------------------------------------------------------------------
# scan implementation (CPU / reference) — forward state


def _attention_scan(q, k, v, *, causal: bool, sm_scale: float,
                    q_offset, kv_offset, block_k: int,
                    window: Optional[int] = None):
    """Online-softmax attention over K/V blocks with a lax.scan.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]. ``q_offset``/``kv_offset`` are the
    global sequence positions of element 0 (used by ring attention to mask
    causally across devices); they may be traced values.

    Returns online-softmax state ``(m, l, acc)`` with m/l: [B, H, Tq],
    acc: [B, H, Tq, D].
    """
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    _, bk = _block_sizes(t_q, t_k, t_q, block_k)
    n_k = t_k // bk

    qf = q.astype(jnp.float32) * sm_scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [B, H, Tq, D] so the matmul contracts the trailing dim on the MXU
    qf = qf.transpose(0, 2, 1, 3)
    kf = kf.transpose(0, 2, 1, 3).reshape(b, h, n_k, bk, d)
    vf = vf.transpose(0, 2, 1, 3).reshape(b, h, n_k, bk, d)

    q_ids = q_offset + jnp.arange(t_q)

    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, j = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk)  # [B,H,Tq,bk]
        if causal:
            k_ids = kv_offset + j * bk + jnp.arange(bk)
            s = jnp.where(_causal_mask(q_ids, k_ids, window)[None, None], s,
                          NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, t_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    acc0 = jnp.zeros((b, h, t_q, d), jnp.float32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, acc0),
        (kf.transpose(2, 0, 1, 3, 4), vf.transpose(2, 0, 1, 3, 4),
         jnp.arange(n_k)),
    )
    return m, l, acc


def _finalize(m, l, acc, dtype):
    # fully-masked rows (ring attention with kv entirely in the causal
    # future) have l == 0; emit zeros, not NaNs
    safe_l = jnp.where(l > 0, l, 1.0)
    out = acc / safe_l[..., None]
    out = jnp.where((l > 0)[..., None], out, 0.0)
    return out.transpose(0, 2, 1, 3).astype(dtype)  # [B, Tq, H, D]


# --------------------------------------------------------------------------
# shared block backward primitive


def _block_bwd(q, k_blk, v_blk, dout, delta, lse, *, causal: bool,
               sm_scale: float, q_offset, kv_offset,
               window: Optional[int] = None):
    """Gradient contributions of one K/V block, recomputing p from lse.

    q/dout: [B, Tq, H, D]; k_blk/v_blk: [B, Tk, H, D];
    delta/lse: [B, H, Tq] (delta = rowsum(dout * out)).
    Returns (dq_contrib [B,Tq,H,D], dk_blk, dv_blk [B,Tk,H,D]) in float32.
    """
    qf = q.astype(jnp.float32)
    kf = k_blk.astype(jnp.float32)
    vf = v_blk.astype(jnp.float32)
    dof = dout.astype(jnp.float32)

    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sm_scale
    if causal:
        t_q, t_k = q.shape[1], k_blk.shape[1]
        q_ids = q_offset + jnp.arange(t_q)
        k_ids = kv_offset + jnp.arange(t_k)
        s = jnp.where(_causal_mask(q_ids, k_ids, window)[None, None], s,
                      NEG_INF)
    p = jnp.exp(s - lse[..., None])                      # [B,H,Tq,Tk]
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * sm_scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * sm_scale
    return dq, dk, dv


def _delta(out, dout):
    """delta = rowsum(dout * out): [B, Tq, H, D] -> [B, H, Tq]."""
    return jnp.einsum(
        "bqhd,bqhd->bhq",
        out.astype(jnp.float32), dout.astype(jnp.float32))


# --------------------------------------------------------------------------
# pallas kernel (TPU hot path) — emits out AND lse

#: K/V block of the scans (the off-TPU forward, and the backward wherever
#: the Pallas backward has no block) when the caller names none. The
#: kernels' tiles are chosen apart from it (:func:`_fwd_tile`,
#: :func:`_bwd_tile`): the scans' f32 temporaries grow with this block.
SCAN_BLOCK_K = 128
#: the forward kernel's widest block (what one grid step holds of q and of
#: k/v) and sub-tile (what one pair of products covers), in rows
_FWD_BLOCK = 1024
_FWD_SUBTILE = 512
#: VMEM one grid step may fill (Mosaic's scoped default on a v5e is 16 MiB)
_FWD_VMEM_BUDGET = 12 << 20
_LANES = 128


def _lane_pad(n: int) -> int:
    """``n`` lanes as VMEM holds them: whole vregs of 128."""
    return -(-n // _LANES) * _LANES


def _fwd_vmem_bytes(block, sub, head_dim: int, itemsize: int) -> int:
    """Upper estimate of the VMEM one grid step of the forward kernel holds:
    the double-buffered q, o, k, v and lse blocks, the softmax state, and
    a sub-tile's live values (f32 scores and probabilities, the
    probabilities in v's dtype, the int32 mask). Rows of fewer than 128
    lanes are padded to 128."""
    pad = _lane_pad
    (bq, bk), (cq, ck) = block, sub
    blocks = 2 * (2 * bq + 2 * bk) * pad(head_dim) * itemsize
    lse = 2 * bq * _LANES * 4
    state = bq * (2 * _LANES + pad(head_dim)) * 4
    live = cq * pad(ck) * (4 + 4 + itemsize + 4)
    return blocks + lse + state + live


def _fit(t: int, cap: int) -> Optional[int]:
    """The largest block of at most ``cap`` rows that divides ``t`` and the
    TPU tiles: a multiple of 8 rows, or ``t`` itself. None if there is
    none."""
    if t <= cap:
        return t
    return next((b for b in range(cap - cap % 8, 7, -8) if t % b == 0), None)


def _sub_tile(blk: int) -> int:
    """Rows of a block's sub-tile: every sub-tile is unrolled into the
    kernel, so a block that only splits into more than four a side is
    computed whole."""
    c = _fit(blk, _FWD_SUBTILE)
    return c if c and blk // c <= 4 else blk


def _fwd_tile(t_q: int, t_k: int, head_dim: int, dtype):
    """The forward kernel's block ``(bq, bk)`` and sub-tile ``(cq, ck)``
    from the shapes alone: the widest that divide the sequence and fit the
    VMEM budget. A Pallas grid step costs about 0.4 us whatever it does and
    ends the compiler's overlap of one sub-tile's products with another's
    softmax, so few wide steps beat many narrow ones; the sub-tile bounds
    the live [cq, ck] values and, under a causal mask, what is computed
    beyond the diagonal. A side with no legal block (no multiple of 8
    divides it) comes back as None."""
    itemsize = jnp.dtype(dtype).itemsize
    block = [_fit(t_q, _FWD_BLOCK), _fit(t_k, _FWD_BLOCK)]
    if None in block:
        return tuple(block), None
    while True:
        sub = tuple(_sub_tile(blk) for blk in block)
        if _fwd_vmem_bytes(block, sub, head_dim, itemsize) <= _FWD_VMEM_BUDGET:
            break
        # shrink the wider side (both, if equal: square blocks cross the
        # diagonal only on it) to its next legal block, if it has one
        widest = max(block)
        smaller = [_fit(t, blk - 1) if blk == widest else blk
                   for t, blk in zip((t_q, t_k), block)]
        if None in smaller:
            break
        block = smaller
    return tuple(block), sub


def _lane_cols(x, n: int):
    """``x`` [rows, 128], every lane of a row the same value, as [rows, n]."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _band_blocks(window: int, blk: int) -> int:
    """How many square blocks of ``blk`` rows a q block's band touches, the
    diagonal's included: k block ``qi - d`` holds a column with
    ``r - c < window`` for d below this."""
    return (window + blk - 2) // blk + 1


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scratch, l_scratch, acc_scratch,
                      *, sm_scale: float, causal: bool, block, sub,
                      past_blocks: bool, window: Optional[int] = None):
    """One grid step: the q block ``[bq, D]`` against the k/v block
    ``[bk, D]``, as a static schedule of ``[cq, ck]`` sub-tiles in one basic
    block, so the compiler overlaps one sub-tile's products with another's
    softmax. ``past_blocks``: some grid step lies wholly below the diagonal
    (the q sequence spans more than one block). ``window`` (causal only):
    row r sees column c where ``0 <= r - c < window``; blocks and sub-tiles
    wholly outside that band are left out from both sides, those its edges
    cross are masked."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    (bq, bk), (cq, ck) = block, sub
    head_dim = q_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # The online-softmax state m, l is lane-dense: [bq, 128] with every lane
    # of a row the same value, so reads, writes and the broadcasts against
    # s [cq, ck] and acc [cq, D] are whole-vreg operations.
    # A power-of-two scale (1/8 at D 64) is exact on q in any dtype; any
    # other is applied to the f32 scores.
    scale_q = math.frexp(sm_scale)[0] == 0.5

    def _products(i: int, j: int, diagonal, band=None):
        """Sub-tile (i, j) of the block. ``diagonal``: None for no mask,
        else row r sees column c where ``r - c >= diagonal``; ``band``:
        None, else only where ``r - c < band`` too."""
        rows, cols = pl.ds(i * cq, cq), pl.ds(j * ck, ck)
        q, k, v = q_ref[0, rows, :], k_ref[0, cols, :], v_ref[0, cols, :]
        if scale_q:
            q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
        # operands in the dtype they arrive in (bf16 x bf16 products are
        # exact in f32), accumulation in f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [cq, ck]
        if not scale_q:
            s = s * sm_scale
        if diagonal is not None or band is not None:
            r = lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
            c = lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
            if diagonal is not None:
                s = jnp.where(r - c >= diagonal, s, NEG_INF)
            if band is not None:
                # a row masked whole here has m = NEG_INF and p = 1: the
                # diagonal's sub-tile, which comes later, rescales it away
                s = jnp.where(r - c < band, s, NEG_INF)
        m_prev = m_scratch[rows, :]                      # [cq, 128]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lane_cols(m_new, ck))
        alpha = jnp.exp(m_prev - m_new)                  # [cq, 128]
        l_scratch[rows, :] = (
            l_scratch[rows, :] * alpha + p.sum(axis=-1, keepdims=True))
        acc_scratch[rows, :] = (
            acc_scratch[rows, :] * _lane_cols(alpha, head_dim)
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_scratch[rows, :] = m_new

    def _block(rel):
        """The block whose first q row is ``rel`` rows past its first k
        column: None for no mask at all, an int for a schedule known now
        (only sub-tiles on or below the diagonal, masks only on it), a
        traced value for one decided per sub-tile on the chip."""
        for i in range(bq // cq):
            for j in range(bk // ck):
                if rel is None:
                    _products(i, j, None)
                    continue
                # first row and column of the sub-tile, from the block's
                # first column
                r0, c0 = rel + i * cq, j * ck
                needed = c0 <= r0 + (cq - 1)
                if not isinstance(rel, int):
                    if window is None:
                        pl.when(needed)(
                            functools.partial(_products, i, j, c0 - r0))
                    else:
                        pl.when(jnp.logical_and(
                            needed, r0 - c0 - (ck - 1) < window))(
                            functools.partial(_products, i, j, c0 - r0,
                                              window - (r0 - c0)))
                elif needed and (window is None
                                 or r0 - c0 - (ck - 1) < window):
                    past = c0 + (ck - 1) <= r0
                    inside = window is None or r0 - c0 + (cq - 1) < window
                    _products(i, j, None if past else c0 - r0,
                              None if inside else window - (r0 - c0))

    if not causal:
        _block(None)
    elif window is not None:
        rel = qi * bq - kj * bk
        # the block wholly inside the band; the blocks an edge crosses, by a
        # schedule known now where the blocks are square (rel is a multiple
        # of the block), else decided per sub-tile on the chip
        inside = jnp.logical_and(rel >= bk - 1, rel + (bq - 1) < window)
        if past_blocks and bq + bk - 1 <= window:
            pl.when(inside)(functools.partial(_block, None))
        if bq == bk:
            for d in range(_band_blocks(window, bk)):
                if not (d >= 1 and d * bk + (bq - 1) < window):
                    pl.when(rel == d * bk)(functools.partial(_block, d * bk))
        else:
            crossed = jnp.logical_and(rel > -bq, rel - (bk - 1) < window)
            pl.when(jnp.logical_and(crossed, jnp.logical_not(inside)))(
                functools.partial(_block, rel))
    else:
        rel = qi * bq - kj * bk
        if past_blocks:
            pl.when(rel >= bk - 1)(functools.partial(_block, None))
        if bq == bk:
            # square blocks cross the diagonal only on it
            pl.when(rel == 0)(functools.partial(_block, 0))
        else:
            pl.when(jnp.logical_and(rel < bk - 1, rel > -bq))(
                functools.partial(_block, rel))

    @pl.when(kj == n_k - 1)
    def _write():
        m, l = m_scratch[:], l_scratch[:]                # [bq, 128]
        safe_l = jnp.where(l > 0, l, 1.0)
        out = acc_scratch[:] / _lane_cols(safe_l, head_dim)
        o_ref[0] = jnp.where(
            _lane_cols(l, head_dim) > 0, out, 0.0).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), LSE_MASKED)
        lse_ref[0] = lse[:, :1]


def _record_fwd_tile(bq: int, bk: int, grid) -> None:
    """Trace-time gauges of the tile a run compiled its forward with."""
    if not _metrics.enabled():
        return
    for dim, blk in (("q", bq), ("k", bk)):
        _metrics.gauge(
            "flash_fwd_tile",
            help="rows of the flash forward kernel's q / k tile, chosen "
                 "from the shapes at trace time",
            dim=dim,
        ).set(blk)
    _metrics.gauge(
        "flash_fwd_grid_steps",
        help="grid steps of one flash forward call (batch*heads x q blocks "
             "x k blocks)",
    ).set(math.prod(grid))


def _flash_fwd_pallas(q, k, v, *, causal: bool, sm_scale: float,
                      block_q: Optional[int], block_k: Optional[int],
                      interpret: bool, window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    h_kv = k.shape[2]
    g = h // h_kv
    if block_q or block_k:
        # an explicit block is honoured (down to what divides the sequence)
        block = _block_sizes(t_q, t_k, block_q or _FWD_BLOCK,
                             block_k or _FWD_BLOCK)
        sub = tuple(_sub_tile(blk) for blk in block)
    else:
        block, sub = _fwd_tile(t_q, t_k, d, q.dtype)
    for name, blk, t in (("q", block[0], t_q), ("k", block[1], t_k)):
        if blk is None or (blk % 8 and blk != t):
            raise ValueError(
                f"flash attention: no block of at least 8 rows divides the "
                f"{name} sequence length {t} (got {blk}), below the 8-row "
                f"TPU tile; pad the sequence to a multiple of 8 (128 for "
                f"full-size blocks)")
    (bq, bk), (cq, ck) = block, sub
    grid = (b * h, t_q // bq, t_k // bk)
    _record_fwd_tile(bq, bk, grid)

    # [B*H, T, D] layout: one grid row per (batch, head). K/V keep their
    # H_kv rows; GQA maps each query head's grid row onto its kv head in
    # the BlockSpec index map — zero-copy, no H-wide K/V buffer exists.
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h_kv, t_k, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h_kv, t_k, d)

    def kv_index(bh, qi, kj):
        # grid row bh = batch*h + head  ->  kv row = batch*h_kv + head//g
        row = (bh // h) * h_kv + (bh % h) // g
        if causal:
            # a wholly-future block keeps the index of the last block its
            # q rows need: the pipeline sees no change and issues no copy
            kj = jnp.minimum(kj, (qi * bq + (bq - 1)) // bk)
            if window is not None:
                # and so does a block wholly before the window
                kj = jnp.maximum(
                    kj, jnp.maximum(qi * bq - (window - 1), 0) // bk)
        return row, kj, 0

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block=(bq, bk), sub=(cq, ck), past_blocks=t_q > bq, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
            # trailing singleton keeps the lse block 2-D per grid row:
            # (bq, 1) satisfies Mosaic's tiling rule (dim -2 divisible by
            # 8, dim -1 equal to the array's), which a (1, bq) block of a
            # rank-2 [B*H, Tq] array does not
            pl.BlockSpec((1, bq, 1), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_flash_fwd",
    )(qr, kr, vr)
    out = out.reshape(b, h, t_q, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, t_q)
    return out, lse


# --------------------------------------------------------------------------
# pallas backward (TPU hot path): p recomputed from (q, k, lse) in VMEM

#: VMEM one backward grid step may fill, and the scoped limit its calls ask
#: Mosaic for (the default is 16 MiB of the v5e's 128; the cells' tile needs
#: about 14, wide float32 heads more)
_BWD_VMEM_BUDGET = 24 << 20
_BWD_VMEM_LIMIT = 32 << 20


def _bwd_vmem_bytes(blk: int, sub: int, head_dim: int, itemsize: int) -> int:
    """Upper estimate of the VMEM one grid step of a backward kernel holds:
    the double-buffered q, k, v, o, do blocks and dq, dk, dv blocks, the
    lane-dense lse and delta and their transposes, three f32 accumulators, and
    a sub-tile's live values (f32 scores, probabilities, dp and ds, the
    probabilities and ds in the operands' dtype, the int32 mask)."""
    pad = _lane_pad
    blocks = 2 * 8 * blk * pad(head_dim) * itemsize
    stats = (2 + 2) * blk * _LANES * 4
    acc = 3 * blk * pad(head_dim) * 4
    live = sub * pad(sub) * (4 * 4 + 2 * itemsize + 4)
    return blocks + stats + acc + live


def _bwd_tile(t_q: int, t_k: int, head_dim: int, dtype,
              block_q: Optional[int] = None, block_k: Optional[int] = None):
    """The Pallas backward's square block and sub-tile ``(blk, sub)`` in
    rows, from the shapes alone as :func:`_fwd_tile` chooses the forward's:
    the widest block of at most 1024 rows that divides the sequence and
    fits the VMEM budget, computed as sub-tiles of at most 512 x 512. An
    explicit block is honoured, down to what divides the sequence. None
    where the kernels have no block and the scan serves: ``t_q != t_k``,
    unequal explicit blocks, a sequence of several blocks that no multiple
    of 128 divides."""
    if t_q != t_k:
        return None
    if block_q or block_k:
        bq, bk = _block_sizes(t_q, t_k, block_q or _FWD_BLOCK,
                              block_k or _FWD_BLOCK)
        blk = bq if bq == bk else None
    else:
        itemsize = jnp.dtype(dtype).itemsize
        blk = _fit(t_q, _FWD_BLOCK)
        while blk and _bwd_vmem_bytes(
                blk, _sub_tile(blk), head_dim, itemsize) > _BWD_VMEM_BUDGET:
            blk = _fit(t_q, blk - 1)
    # lse reaches the kernels as rows [1, blk]: whole lanes, or all of them
    if not blk or (blk % _LANES and blk != t_q):
        return None
    return blk, _sub_tile(blk)


def _record_bwd_tile(blk: int, grid_steps: int) -> None:
    """Trace-time gauges of the tile a run compiled its Pallas backward
    with; a backward that scans sets none."""
    if not _metrics.enabled():
        return
    for dim in ("q", "k"):
        _metrics.gauge(
            "flash_bwd_tile",
            help="rows of the flash backward kernels' (square) q / k block, "
                 "chosen from the shapes at trace time",
            dim=dim,
        ).set(blk)
    _metrics.gauge(
        "flash_bwd_grid_steps",
        help="grid steps of one flash backward: batch*heads for the fused "
             "call, the dk/dv call's plus the dq call's otherwise",
    ).set(grid_steps)


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_block(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *,
               sm_scale: float, sub: int, rel: Optional[int],
               want_dq: bool, want_dkv: bool, window: Optional[int] = None):
    """Gradient contributions of the square block the refs hold (q, o, do,
    lse: its q rows; k, v: its k rows), as a static schedule of ``[sub,
    sub]`` sub-tiles in one basic block. ``rel``: None for every sub-tile,
    unmasked; else the block's first q row lies ``rel`` rows past its first
    k column under the causal mask (0: the block on the diagonal), so only
    sub-tiles that hold a column with ``0 <= r - c`` (``< window``) are
    computed and only those an edge crosses are masked.

    Per sub-tile, the flash backward: s = q k^T and p = exp(s - lse) again,
    dp = do v^T, ds = p (dp - delta) with delta = rowsum(do o), all in f32;
    then dv += p^T do, dk += ds^T q, dq += ds k with p and ds rounded to
    the operands' dtype and f32 accumulation. Returns ``(dq, dk, dv)``:
    per sub-tile row (dq) or column (dk, dv) an f32 ``[sub, D]`` value with
    ``sm_scale`` applied, or None where unwanted."""
    from jax.experimental import pallas as pl

    n = q_ref.shape[1] // sub
    dtype = q_ref.dtype
    # a power-of-two scale (1/8 at D 64) is exact on q in any dtype, and
    # the scaled q gives dk its scale too; any other goes on the f32 values
    scale_q = math.frexp(sm_scale)[0] == 0.5
    # lse and delta are read by every sub-tile: lane-dense [rows, 128],
    # once a block. lse arrives as a row [1, rows] (a [rows, 1] column is
    # padded to 128 lanes in HBM, and as the forward's residual cost 67 MB a
    # layer at [128, 1024]): broadcast over sublanes, then transposed
    lse = jnp.broadcast_to(lse_ref[0], (_LANES, n * sub)).T
    delta = jnp.broadcast_to(
        jnp.sum(do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                axis=-1, keepdims=True), (n * sub, _LANES))
    dq, dk, dv = [None] * n, [None] * n, [None] * n

    def add(acc, at, x):
        acc[at] = x if acc[at] is None else acc[at] + x

    for i in range(n):
        rows = pl.ds(i * sub, sub)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        if scale_q:
            q = (q.astype(jnp.float32) * sm_scale).astype(dtype)
        for j in range(n):
            # r - c at the sub-tile's first row and column
            off = None if rel is None else rel + (i - j) * sub
            if off is not None and (off + sub <= 0 or (
                    window is not None and off - sub >= window - 1)):
                continue
            cols = pl.ds(j * sub, sub)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            s = _dot(q, k, 1, 1)                         # [sub, sub]
            if not scale_q:
                s = s * sm_scale
            if off is not None and (off < sub - 1 or (
                    window is not None and off + sub > window)):
                r = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
                c = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
                if off < sub - 1:
                    s = jnp.where(r >= c if off == 0 else r - c >= -off,
                                  s, NEG_INF)
                if window is not None and off + sub > window:
                    s = jnp.where(r - c < window - off, s, NEG_INF)
            # a fully-masked row's lse is LSE_MASKED: p underflows to 0
            p = jnp.exp(s - _lane_cols(lse[i * sub:(i + 1) * sub], sub))
            dp = _dot(do, v, 1, 1)
            ds = p * (dp - _lane_cols(delta[i * sub:(i + 1) * sub], sub))
            ds = ds.astype(dtype)
            if want_dkv:
                add(dv, j, _dot(p.astype(dtype), do, 0, 0))
                add(dk, j, _dot(ds, q, 0, 0))
            if want_dq:
                add(dq, i, _dot(ds, k, 1, 0))
    if want_dq:
        dq = [None if x is None else x * sm_scale for x in dq]
    if want_dkv and not scale_q:
        dk = [None if x is None else x * sm_scale for x in dk]
    return dq, dk, dv


def _store_tiles(ref, tiles, sub: int) -> None:
    """Write a block's f32 sub-tile values to its output block."""
    from jax.experimental import pallas as pl

    for i, x in enumerate(tiles):
        ref[0, pl.ds(i * sub, sub), :] = x.astype(ref.dtype)


def _add_tiles(scratch, tiles, sub: int) -> None:
    """Add a block's f32 sub-tile values to its accumulator (None: a row
    or column of sub-tiles the window left out whole)."""
    from jax.experimental import pallas as pl

    for i, x in enumerate(tiles):
        if x is not None:
            scratch[pl.ds(i * sub, sub), :] += x


def hvd_flash_bwd(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                  dq_ref, dk_ref, dv_ref, *scratch,
                  sm_scale: float, causal: bool, sub: int,
                  window: Optional[int] = None):
    """The whole backward of one (batch, head) whose sequence is one block:
    five products a sub-tile. Grid ``[batch*kv_heads, group]``: under GQA
    the group's q heads take turns on the resident dk/dv block, summed in
    f32 scratch."""
    from jax.experimental import pallas as pl

    dq, dk, dv = _bwd_block(
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sm_scale=sm_scale,
        sub=sub, rel=0 if causal else None, want_dq=True, want_dkv=True,
        window=window)
    _store_tiles(dq_ref, dq, sub)
    if not scratch:                   # one q head a kv head: no sum
        _store_tiles(dk_ref, dk, sub)
        _store_tiles(dv_ref, dv, sub)
        return
    dk_scratch, dv_scratch = scratch
    gi = pl.program_id(1)

    @pl.when(gi == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    _add_tiles(dk_scratch, dk, sub)
    _add_tiles(dv_scratch, dv, sub)

    @pl.when(gi == pl.num_programs(1) - 1)
    def _write():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_blocks(causal: bool, qi, kj, block, window: Optional[int] = None,
                blk: int = 0):
    """Run ``block(rel)`` for the grid step's (q block, k block) pair (rel
    as :func:`_bwd_block` takes it): under the causal mask the diagonal
    block by its triangle, a past block whole, a future block not at all;
    under a ``window`` of square blocks of ``blk`` rows besides, a block
    the band's far edge crosses by what lies inside it, and a block wholly
    before the band not at all."""
    from jax.experimental import pallas as pl

    if not causal:
        block(None)
        return
    pl.when(qi == kj)(functools.partial(block, 0))
    if window is None:
        pl.when(qi > kj)(functools.partial(block, None))
        return
    whole = [d for d in range(1, _band_blocks(window, blk))
             if (d + 1) * blk - 1 < window]
    if whole:
        pl.when(jnp.logical_and(qi > kj, qi - kj <= whole[-1]))(
            functools.partial(block, None))
    for d in range(len(whole) + 1, _band_blocks(window, blk)):
        pl.when(qi - kj == d)(functools.partial(block, d * blk))


def hvd_flash_bwd_dkv(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dk_ref, dv_ref, dk_scratch, dv_scratch,
                      *, sm_scale: float, causal: bool, sub: int,
                      window: Optional[int] = None):
    """dk and dv of one k block, summed over the q blocks (and, under GQA,
    the group's q heads) that see it. Grid ``[batch*kv_heads, k blocks,
    group, q blocks]``, the last two the reduction."""
    from jax.experimental import pallas as pl

    kj, gi, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last = jnp.logical_and(gi == pl.num_programs(2) - 1,
                           qi == pl.num_programs(3) - 1)

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def block(rel):
        _, dk, dv = _bwd_block(
            q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sm_scale=sm_scale,
            sub=sub, rel=rel, want_dq=False, want_dkv=True, window=window)
        _add_tiles(dk_scratch, dk, sub)
        _add_tiles(dv_scratch, dv, sub)

    _bwd_blocks(causal, qi, kj, block, window, q_ref.shape[1])

    @pl.when(last)
    def _write():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def hvd_flash_bwd_dq(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                     dq_ref, dq_scratch,
                     *, sm_scale: float, causal: bool, sub: int,
                     window: Optional[int] = None):
    """dq of one q block, summed over the k blocks it sees. Grid
    ``[batch*heads, q blocks, k blocks]``, the last the reduction."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    def block(rel):
        dq, _, _ = _bwd_block(
            q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sm_scale=sm_scale,
            sub=sub, rel=rel, want_dq=True, want_dkv=False, window=window)
        _add_tiles(dq_scratch, dq, sub)

    _bwd_blocks(causal, qi, kj, block, window, q_ref.shape[1])

    @pl.when(kj == pl.num_programs(2) - 1)
    def _write():
        dq_ref[0] = dq_scratch[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, dout, *, causal: bool,
                      sm_scale: float, blk: int, sub: int, interpret: bool,
                      window: Optional[int] = None):
    """dq, dk, dv by the Pallas kernels, in square blocks of ``blk`` rows.
    One block a sequence: one fused call (:func:`hvd_flash_bwd`). More: dk
    and dv accumulate over q blocks and dq over k blocks, which no one grid
    order keeps resident, so two calls share the sub-tile body and each
    recomputes p (seven products for five). The calls carry no ``name=``:
    their device time then lies under the caller's ``hvd.flash_bwd`` scope
    with the kernel *functions'* names as Mosaic kernel names."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    h_kv = k.shape[2]
    g = h // h_kv
    n = t // blk
    _record_bwd_tile(blk, b * h if n == 1 else 2 * b * h * n * n)

    def rows_first(x):               # [B, T, H, D] -> [B*H, T, D]
        return x.transpose(0, 2, 1, 3).reshape(-1, t, d)

    def heads_first(x, heads):       # and back
        return x.reshape(b, heads, t, d).transpose(0, 2, 1, 3)

    # lse as rows [1, T]: a [T, 1] column is padded to 128 lanes in HBM
    args = (rows_first(q), rows_first(k), rows_first(v), rows_first(out),
            rows_first(dout), lse.reshape(b * h, 1, t))
    kernel_kw = dict(sm_scale=sm_scale, causal=causal, sub=sub,
                     window=window)
    # a block wholly before the band keeps the index of the nearest one the
    # step needs too, as a wholly-future block does
    far = None if window is None else _band_blocks(window, blk) - 1
    call_kw = dict(
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BWD_VMEM_LIMIT))
    q_shape = jax.ShapeDtypeStruct((b * h, t, d), q.dtype)
    kv_shape = jax.ShapeDtypeStruct((b * h_kv, t, d), k.dtype)
    f32 = functools.partial(pltpu.VMEM, dtype=jnp.float32)

    if n == 1:
        # grid row bkv = batch*h_kv + kv head; its group's q heads are the
        # g rows from bkv*g
        def q_index(bkv, gi):
            return bkv * g + gi, 0, 0

        q_spec = pl.BlockSpec((1, t, d), q_index)
        kv_spec = pl.BlockSpec((1, t, d), lambda bkv, gi: (bkv, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(hvd_flash_bwd, **kernel_kw),
            grid=(b * h_kv, g),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec,
                      pl.BlockSpec((1, 1, t), q_index)],
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[q_shape, kv_shape, kv_shape],
            scratch_shapes=[f32((t, d))] * 2 if g > 1 else [],
            **call_kw)(*args)
    else:
        # under the causal mask a wholly-future block keeps the index of the
        # nearest block the step needs: the pipeline sees no change and
        # issues no copy
        def dkv_q_index(bkv, kj, gi, qi):
            if causal:
                qi = jnp.maximum(qi, kj)
                if window is not None:
                    qi = jnp.minimum(qi, jnp.minimum(kj + far, n - 1))
            return bkv * g + gi, qi

        q_spec = pl.BlockSpec(
            (1, blk, d), lambda *at: (*dkv_q_index(*at), 0))

        def dkv_lse_index(*at):
            row, qi = dkv_q_index(*at)
            return row, 0, qi

        lse_spec = pl.BlockSpec((1, 1, blk), dkv_lse_index)
        kv_spec = pl.BlockSpec(
            (1, blk, d), lambda bkv, kj, gi, qi: (bkv, kj, 0))
        dk, dv = pl.pallas_call(
            functools.partial(hvd_flash_bwd_dkv, **kernel_kw),
            grid=(b * h_kv, n, g, n),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
            out_specs=[kv_spec, kv_spec],
            out_shape=[kv_shape, kv_shape],
            scratch_shapes=[f32((blk, d))] * 2,
            **call_kw)(*args)

        def dq_kv_index(bh, qi, kj):
            # grid row bh = batch*h + head  ->  kv row, as the forward's
            if causal:
                kj = jnp.minimum(kj, qi)
                if window is not None:
                    kj = jnp.maximum(kj, jnp.maximum(qi - far, 0))
            return (bh // h) * h_kv + (bh % h) // g, kj, 0

        q_spec = pl.BlockSpec((1, blk, d), lambda bh, qi, kj: (bh, qi, 0))
        kv_spec = pl.BlockSpec((1, blk, d), dq_kv_index)
        (dq,) = pl.pallas_call(
            functools.partial(hvd_flash_bwd_dq, **kernel_kw),
            grid=(b * h, n, n),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec,
                      pl.BlockSpec((1, 1, blk),
                                   lambda bh, qi, kj: (bh, 0, qi))],
            out_specs=[q_spec],
            out_shape=[q_shape],
            scratch_shapes=[f32((blk, d))],
            **call_kw)(*args)
    return heads_first(dq, h), heads_first(dk, h_kv), heads_first(dv, h_kv)


# --------------------------------------------------------------------------
# public op with flash (blockwise-recompute) backward


def gqa_group(q, k) -> int:
    """Query-group size for GQA/MQA (1 = standard multi-head)."""
    h, h_kv = q.shape[2], k.shape[2]
    if h % h_kv != 0:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({h_kv})"
        )
    return h // h_kv


def rep_group(x, g: int):
    """Broadcast K/V heads over query groups (jit fuses the broadcast;
    repeat lays the g copies of each kv head adjacently)."""
    return jnp.repeat(x, g, axis=2) if g > 1 else x


def reduce_group(dx, g: int):
    """Transpose of :func:`rep_group` for gradients: sum each kv head's
    adjacent query-group copies. Expects a 4-D [B, T, H, D] block (heads on
    axis 2, matching :func:`rep_group`)."""
    if g == 1:
        return dx
    b, t, h, d = dx.shape
    return dx.reshape(b, t, h // g, g, d).sum(axis=3)


def _fwd_impl(q, k, v, causal, sm_scale, block_sizes, window):
    block_q, block_k, use_pallas, interpret = block_sizes
    if use_pallas:
        # GQA handled zero-copy inside the kernel's kv index map
        return _flash_fwd_pallas(
            q, k, v, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window)
    g = gqa_group(q, k)
    m, l, acc = _attention_scan(
        q, rep_group(k, g), rep_group(v, g), causal=causal,
        sm_scale=sm_scale,
        q_offset=0, kv_offset=0, block_k=block_k or SCAN_BLOCK_K,
        window=window)
    return _finalize(m, l, acc, q.dtype), lse_from_state(m, l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, block_sizes, window):
    return _fwd_impl(q, k, v, causal, sm_scale, block_sizes, window)[0]


@jax.named_scope("hvd.flash_fwd")
def _flash_fwd(q, k, v, causal, sm_scale, block_sizes, window):
    out, lse = _fwd_impl(q, k, v, causal, sm_scale, block_sizes, window)
    return out, (q, k, v, out, lse)


@jax.named_scope("hvd.flash_bwd")
def _flash_bwd(causal, sm_scale, block_sizes, window, res, g):
    """O(T) extra-memory backward: p is recomputed from lse block by block
    (saves no score matrix — the flash-attention trade). On the Pallas path
    the kernels of :func:`_flash_bwd_pallas`, wherever :func:`_bwd_tile`
    has a block for the shapes; else the scan."""
    block_q, block_k, use_pallas, interpret = block_sizes
    q, k, v, out, lse = res
    tile = use_pallas and _bwd_tile(
        q.shape[1], k.shape[1], q.shape[3], q.dtype, block_q, block_k)
    if tile:
        return _flash_bwd_pallas(
            q, k, v, out, lse, g, causal=causal, sm_scale=sm_scale,
            blk=tile[0], sub=tile[1], interpret=interpret, window=window)
    return _flash_bwd_scan(q, k, v, out, lse, g, causal=causal,
                           sm_scale=sm_scale, block_k=block_k, window=window)


def _flash_bwd_scan(q, k, v, out, lse, g, *, causal: bool, sm_scale: float,
                    block_k: Optional[int], window: Optional[int] = None):
    """The backward as a scan over K/V blocks of ``block_k`` (default
    ``SCAN_BLOCK_K``) rows in f32. Residual K/V stay H_kv-wide under GQA;
    each block is broadcast per step and its gradient group-summed back
    (repeat's transpose — adjacent-copy layout)."""
    block_k = block_k or SCAN_BLOCK_K
    b, t_k, h_kv, d = k.shape
    h = q.shape[2]
    grp = h // h_kv
    _, bk = _block_sizes(q.shape[1], t_k, q.shape[1], block_k)
    n_k = t_k // bk
    delta = _delta(out, g)

    k_blocks = k.reshape(b, n_k, bk, h_kv, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_k, bk, h_kv, d).transpose(1, 0, 2, 3, 4)

    def step(dq, blk):
        k_blk, v_blk, j = blk
        dq_c, dk_b, dv_b = _block_bwd(
            q, rep_group(k_blk, grp), rep_group(v_blk, grp), g, delta,
            lse, causal=causal,
            sm_scale=sm_scale, q_offset=0, kv_offset=j * bk, window=window)
        return dq + dq_c, (reduce_group(dk_b, grp), reduce_group(dv_b, grp))

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        step, dq0, (k_blocks, v_blocks, jnp.arange(n_k)))
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(b, t_k, h_kv, d)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(b, t_k, h_kv, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q, k_cache, v_cache, start_pos):
    """Attention of a new chunk q ``[B, T, H, D]`` (query t sits at global
    position ``start_pos[b] + t``) against a kv cache ``[B, L, H_kv, D]``,
    causally masked per row. T=1 is the decode step; T=prompt_len (or a
    prefill chunk) is the prefill. GQA-aware. Cache positions beyond a
    row's frontier are masked to ``-1e30`` — ``exp`` underflows them to an
    exact 0, so garbage (or page-pool padding) past the frontier
    contributes nothing.

    This is the single decode-attention primitive: the contiguous-cache
    path (:class:`horovod_tpu.models.transformer.TransformerBlock` with
    ``decode=True``) calls it directly, and the serving engine's paged
    cache reaches it through :func:`paged_decode_attention`."""
    if k_cache.shape[2] != q.shape[2]:
        k_cache, v_cache = repeat_kv_heads(q, k_cache, v_cache)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) * q.shape[-1] ** -0.5
    t, l = q.shape[1], k_cache.shape[1]
    qpos = start_pos[:, None] + jnp.arange(t)[None, :]           # [B, T]
    valid = jnp.arange(l)[None, None, :] <= qpos[:, :, None]     # [B, T, L]
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v_cache)


def paged_decode_attention(q, k_pages, v_pages, page_table, start_pos, *,
                           page_size: int):
    """Decode attention against a **paged** KV cache (vLLM-style).

    ``k_pages``/``v_pages``: the shared page pool ``[P, page_size, H_kv,
    D]`` — fixed-size pages owned by a free-list allocator, so any batch
    composition shares one preallocated buffer. ``page_table``: ``[B,
    pages_per_seq]`` int32 page ids per sequence slot, position-ordered
    (token at global position p lives in page ``page_table[b, p //
    page_size]`` at offset ``p % page_size``). ``q``: ``[B, T, H, D]``
    with query t at ``start_pos[b] + t``.

    The gather re-linearizes each slot's pages into ``[B, pages_per_seq *
    page_size, H_kv, D]`` and defers to :func:`decode_attention`; slots
    past a row's frontier (pool padding, recycled pages) are causally
    masked there, so the pool's contents beyond ``start_pos + T`` are
    never observable. Those slots are additionally **zeroed** before the
    matmuls: the causal mask zeroes their softmax weight, but a recycled
    page can hold non-finite garbage from a poisoned weight generation,
    and IEEE ``0 × NaN = NaN`` would leak it through the ``p @ v``
    contraction (zeroing is exact for finite garbage too — a masked
    position contributes ``0 × 0`` either way, so parity with the
    contiguous path is unchanged). On TPU the gather is a cheap HBM-local
    take (the future Pallas variant fuses it into the attention kernel);
    the semantics here are the contract both share.
    """
    b = q.shape[0]
    k_cache = k_pages[page_table].reshape(
        b, -1, k_pages.shape[2], k_pages.shape[3])
    v_cache = v_pages[page_table].reshape(
        b, -1, v_pages.shape[2], v_pages.shape[3])
    frontier = start_pos + q.shape[1]  # exclusive per-row high-water mark
    live = jnp.arange(k_cache.shape[1])[None, :] < frontier[:, None]
    k_cache = jnp.where(live[..., None, None], k_cache, 0)
    v_cache = jnp.where(live[..., None, None], v_cache, 0)
    return decode_attention(q, k_cache, v_cache, start_pos)


def tp_paged_decode_attention(q, k_pages, v_pages, page_table, start_pos, *,
                              page_size: int, axis: str = "tp", mesh=None):
    """:func:`paged_decode_attention` sharded over a tensor-parallel axis.

    Attention is independent per head, so head-sharding the query and the
    page pool (``q`` on dim 2, ``k_pages``/``v_pages`` on dim 2) makes the
    paged decode embarrassingly parallel: each rank runs the plain kernel
    on its head block and the results concatenate — no collectives, hence
    **token-identical** to the single-chip path. ``page_table`` and
    ``start_pos`` are replicated (every rank walks the same pages).

    Inside a shard_map region over ``axis`` the inputs are already the
    local head shards and this validates + defers. Outside one it wraps
    itself in a shard_map over ``mesh`` (default: the active global mesh)
    with specs ``P(None, None, axis, None)`` for q and the page pools.
    Head counts must divide by the axis size — the serving engine checks
    this once at construction.
    """
    from horovod_tpu.ops.collective import _axis_bound, _axis_size, _smap

    if _axis_bound(axis):
        return paged_decode_attention(
            q, k_pages, v_pages, page_table, start_pos, page_size=page_size)
    if mesh is None:
        from horovod_tpu import basics

        mesh = basics.mesh()
    n = mesh.shape[axis]
    if q.shape[2] % n or k_pages.shape[2] % n:
        raise ValueError(
            f"heads={q.shape[2]} / kv_heads={k_pages.shape[2]} not "
            f"divisible by tp axis size {n}")
    from jax.sharding import PartitionSpec as P

    hsharded = P(None, None, axis, None)
    fn = functools.partial(
        paged_decode_attention, page_size=page_size)
    return _smap(fn, mesh,
                 (hsharded, hsharded, hsharded, P(), P()),
                 hsharded)(q, k_pages, v_pages, page_table, start_pos)


def repeat_kv_heads(q, k, v):
    """Broadcast K/V heads over query groups for GQA/MQA: ``q`` has H
    heads, ``k``/``v`` have H_kv with ``H % H_kv == 0``. Under jit the
    repeat is a broadcast XLA folds into the attention matmuls, so no
    H-wide K/V is materialized in HBM."""
    g = gqa_group(q, k)
    return rep_group(k, g), rep_group(v, g)


def flash_attention(q, k, v, *, causal: bool = False,
                    window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False):
    """Memory-efficient attention. ``q``: [B, Tq, H, D]; ``k``/``v``:
    [B, Tk, H_kv, D] with ``H % H_kv == 0`` — grouped-query attention
    (H_kv < H) broadcasts each K/V head over its query group; MQA is
    ``H_kv == 1``. Returns [B, Tq, H, D].

    ``window`` (with ``causal=True``): row i sees column j only where
    ``0 <= i - j < window`` (sliding-window attention). The kernels, forward
    and backward, leave out every tile wholly outside that band, copy none
    of its blocks, and mask the tiles its edges cross; the scans, which
    hold every q row against each K/V block, mask.

    ``use_pallas`` defaults to True on TPU backends (the VMEM-tiled
    kernels, forward and backward) and False elsewhere (the scan path).
    Both backwards recompute p blockwise from the saved lse. GQA is
    zero-copy end-to-end: the Pallas kernels map each query head's grid row
    onto its kv head (no H-wide K/V buffer exists; the backward sums a
    group's dk/dv in VMEM), residuals save the H_kv-wide K/V, and the scan
    path's per-block broadcast fuses under jit.

    ``block_q`` / ``block_k`` default to None: the kernels tile themselves
    from ``(t_q, t_k, head_dim, dtype)`` (:func:`_fwd_tile`,
    :func:`_bwd_tile`: the widest blocks of at most 1024 rows that divide
    the sequences and fit VMEM, computed as sub-tiles of at most 512 x 512;
    the gauges ``flash_fwd_tile`` / ``flash_fwd_grid_steps`` and
    ``flash_bwd_tile`` / ``flash_bwd_grid_steps`` say what a trace chose),
    while the scans (off-TPU, and the backward where the kernels have no
    block: ``t_q != t_k``, unequal explicit blocks) keep K/V blocks of
    ``SCAN_BLOCK_K`` = 128 rows: their f32 temporaries grow with the block.
    An explicit integer is honoured by all of them, down to what divides
    the sequence.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("q/k/v must be [batch, seq, heads, head_dim]")
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    gqa_group(q, k)  # validate H % H_kv == 0
    if window is not None:
        if not causal or window < 1 or q.shape[1] != k.shape[1]:
            raise ValueError(
                "flash attention: window needs causal=True, window >= 1 and "
                f"q and k of one length (got causal={causal}, window="
                f"{window}, t_q={q.shape[1]}, t_k={k.shape[1]})")
        if window >= k.shape[1]:
            window = None            # the band is the whole triangle
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    return _flash(q, k, v, causal, sm_scale,
                  (block_q, block_k, use_pallas, interpret), window)
