"""Pallas kernels for the int8 wire-format hot path, Adasum, and the
fused ZeRO-1 Adam shard update.

The int8 wire (PR 5) saved bytes but paid in HBM round-trips: the HLO
path materializes the quantize's abs/max/scale/cast intermediates, the
post-``all_to_all`` ``[N, sp]`` f32 dequantized matrix, and the reduced
shard between accumulate and requantize — each a full trip through HBM
around a purely memory-bound epilogue. PR 10's bucketing made the unit
of work one ~64 MB bucket chunked into VMEM-sized tiles, so the whole
epilogue now runs on-chip:

- :func:`quantize_blockwise` — max-abs scale per block + int8 cast in
  ONE VMEM pass (the multi-op HLO sequence in
  :func:`horovod_tpu.compression.quantize_blockwise` collapsed);
  :func:`quantize_roundtrip` additionally emits the dequantized wire
  image in the same pass, so error feedback's residual and the
  ``all_to_all`` payload share a single quantize (the HLO path
  quantizes the corrected buffer twice).
- :func:`dequant_accumulate` / :func:`dequant_accumulate_requantize` —
  consume the post-``all_to_all`` int8 chunks + bf16 scales and emit
  the f32 sum shard (reduce-scatter epilogue) or the requantized shard
  (allreduce epilogue) without materializing the ``[N, sp]`` f32
  dequant matrix or round-tripping the reduced shard.
- :func:`adasum_pair_combine` / :func:`adasum_segment_combine` — the
  Adasum combine's three reductions (``a·b``, ``|a|²``, ``|b|²``) out
  of ONE fused read of both operands (the role of the reference's
  ``FusedPairwiseReduceWithComm``), then one blend pass; used by the
  VHDD butterfly at every halving level, grouped path included.
- :func:`fused_adam_update` — Adam moment update + bias correction +
  parameter step in one kernel over the per-bucket ``[N, shard_k]``
  buffers of ``optim._zero_update`` (via :func:`horovod_tpu.optim.
  fused_adam`).

Collectives are NEVER issued from a kernel: Pallas replaces the
elementwise HLO *around* ``all_to_all``/``all_gather``/``ppermute``,
so the collective schedule — and the PR-8 fingerprint matrix — is
invariant under ``HOROVOD_PALLAS``.

``HOROVOD_PALLAS`` semantics (read at trace time, so tests can flip it
per-case; the compiled eager-kernel caches key on it):

- ``auto`` (default/unset) — kernels on TPU backends only.
- ``1`` — kernels everywhere; non-TPU backends run them via Pallas
  ``interpret=True``, which executes the same kernel body as jax ops.
  That is the equivalence harness: CPU tier-1 pins the kernels
  bit-identical (quantize) / within pinned tolerances (Adasum) against
  the discrete HLO path without TPU hardware. Interpret mode is a
  correctness surface, NOT a performance mode.
- ``0`` — discrete HLO everywhere (the pre-PR-12 path, bit-for-bit).

Backend resolution for ``auto`` reuses
:func:`horovod_tpu.tuning._target_platform` when no backend exists yet,
so consulting the knob never initializes a backend before
``hvd.tuning.apply_xla_flags`` has run.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "PALLAS_ENV",
    "enabled",
    "interpret",
    "cache_key",
    "quantize_blockwise",
    "quantize_roundtrip",
    "dequant_accumulate",
    "dequant_accumulate_requantize",
    "adasum_pair_combine",
    "adasum_segment_combine",
    "fused_adam_update",
]

#: env knob: auto (TPU only) | 1 (everywhere, interpret off-TPU) | 0 (off)
#: (documented in docs/performance.md's Pallas knob table)
PALLAS_ENV = "HOROVOD_PALLAS"

#: Mosaic tiling. Every block's last two dims must be a multiple of the
#: dtype's native (sublane, 128-lane) tile — f32 (8, 128), bf16 (16, 128),
#: int8 (32, 128) — or equal the array's own dims. Blocks that mix dtypes
#: therefore align their row count to the int8 tile height.
_LANES = 128
_ROW_ALIGN = 32

#: most block rows per grid step of the (rows, block) int8-wire kernels
_MAX_ROWS = 512

#: flat vectors (Adasum operands, Adam shards) ride as (rows, _VEC_COLS)
#: with up to _VEC_ROWS rows per grid step
_VEC_COLS = 1024
_VEC_ROWS = 256

#: VMEM budget for one resident (N, rows, block) int8 sender stack
_STACK_BYTES = 2 << 20


def _mode() -> str:
    v = os.environ.get(PALLAS_ENV, "auto").strip().lower()
    if v in ("", "auto"):
        return "auto"
    if v in ("1", "true", "yes", "on"):
        return "1"
    if v in ("0", "false", "no", "off"):
        return "0"
    raise ValueError(
        f"{PALLAS_ENV}={v!r}: expected auto|1|0"
    )


def _platform() -> str:
    """The backend the kernels would compile for — the live backend when
    one exists, else the same resolution ``tuning.apply_xla_flags`` uses
    (consulting the knob must never initialize a backend early)."""
    from horovod_tpu import tuning

    if tuning.backend_initialized():
        return jax.default_backend()
    return tuning._target_platform(os.environ)


def enabled() -> bool:
    """Are the Pallas kernels armed for the next trace? Read from the
    environment at trace time — flipping ``HOROVOD_PALLAS`` between
    steps retraces correctly (the eager-kernel caches key on
    :func:`cache_key`)."""
    m = _mode()
    if m == "0":
        return False
    if m == "1":
        return True
    return _platform() == "tpu"


def interpret() -> bool:
    """Run kernels through the Pallas interpreter? True off-TPU under
    ``HOROVOD_PALLAS=1`` — the CPU equivalence harness."""
    return enabled() and _platform() != "tpu"


def cache_key():
    """(enabled, interpret) — mixed into every compiled eager-kernel
    cache key whose traced body consults the knob, so flipping
    ``HOROVOD_PALLAS`` can never replay a stale compiled program."""
    if _mode() == "0":
        return (False, False)
    return (enabled(), interpret())


def _pl():
    from jax.experimental import pallas as pl

    return pl


def _coef_rows(*coefs):
    """Traced scalar coefficients as one lane-dense ``(k, _VEC_COLS)``
    f32 operand, row i filled with ``coefs[i]``: the kernel reads
    ``c_ref[i:i + 1, :]`` and sublane-broadcasts it over its rows — no
    scalar loads, and under ``jax.vmap`` a batched coefficient is just
    one more leading block dim."""
    c = jnp.stack(coefs).astype(jnp.float32)
    return jnp.broadcast_to(c[:, None], (len(coefs), _VEC_COLS))


def _coef_spec(k: int):
    return _pl().BlockSpec((k, _VEC_COLS), lambda i: (0, 0))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(m, axis: int, multiple: int):
    """Zero-pad one axis of an array up to a multiple of ``multiple``."""
    pad = (-m.shape[axis]) % multiple
    if not pad:
        return m
    widths = [(0, 0)] * m.ndim
    widths[axis] = (0, pad)
    return jnp.pad(m, widths)


def _sublanes(dtype) -> int:
    """Native tile height of ``dtype``: 8 rows of 32-bit words, packed
    dtypes stack 2 (16-bit) or 4 (8-bit) rows per word."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _as_rows(flat):
    """A flat vector as a zero-padded ``(rows, _VEC_COLS)`` matrix plus
    the rows-per-grid-step that divides it."""
    rows = _round_up(-(-flat.shape[0] // _VEC_COLS), _sublanes(flat.dtype))
    tile = min(_VEC_ROWS, rows)
    rows = _round_up(rows, tile)
    m = _pad_axis(flat, 0, rows * _VEC_COLS).reshape(rows, _VEC_COLS)
    return m, tile


# --------------------------------------------------------------------------
# blockwise int8 quantize (+ fused wire roundtrip)


def _quantize_rows(m, q_ref, s_ref):
    """Max-abs → bf16 scale → int8 cast over (rows, block), mirroring
    ``compression.quantize_blockwise`` expression for expression so the
    interpret-mode output is BIT-identical to the HLO path (pinned by
    tests/test_pallas.py). Returns the f32 scale column and the int8
    rows for a fused roundtrip."""
    amax = jnp.max(jnp.abs(m), axis=1, keepdims=True)
    sc = (amax / 127.0).astype(jnp.bfloat16)
    s_ref[...] = sc
    sf = sc.astype(m.dtype)
    safe = jnp.where(sf > 0, sf, jnp.ones_like(sf))
    q = jnp.where(sf > 0, m / safe, jnp.zeros_like(m))
    qi = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)
    q_ref[...] = qi
    return sf, qi


def _quantize_kernel(x_ref, q_ref, s_ref):
    _quantize_rows(x_ref[...], q_ref, s_ref)


def _quantize_roundtrip_kernel(x_ref, q_ref, s_ref, d_ref):
    sf, qi = _quantize_rows(x_ref[...], q_ref, s_ref)
    d_ref[...] = qi.astype(d_ref.dtype) * sf


def _row_tile(nb: int, n: int = 1, block: int = 1) -> int:
    """Block rows per grid step: a multiple of the int8 tile height,
    capped so an ``(n, rows, block)`` int8 sender stack fits its VMEM
    budget."""
    cap = max(_STACK_BYTES // max(n * block, 1), _ROW_ALIGN)
    cap = min(_MAX_ROWS, cap // _ROW_ALIGN * _ROW_ALIGN)
    return min(cap, _round_up(nb, _ROW_ALIGN))


def _quantize_call(flat, block: int, roundtrip: bool):
    pl = _pl()
    nb = flat.shape[0] // block
    tile = _row_tile(nb)
    m = _pad_axis(flat.reshape(nb, block), 0, tile)
    nbp = m.shape[0]
    rows = pl.BlockSpec((tile, block), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((nbp, block), jnp.int8),
        jax.ShapeDtypeStruct((nbp, 1), jnp.bfloat16),
    ]
    out_specs = [rows, pl.BlockSpec((tile, 1), lambda i: (i, 0))]
    if roundtrip:
        out_shape.append(jax.ShapeDtypeStruct((nbp, block), flat.dtype))
        out_specs.append(rows)
    out = pl.pallas_call(
        _quantize_roundtrip_kernel if roundtrip else _quantize_kernel,
        grid=(nbp // tile,),
        in_specs=[rows],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret(),
        name="hvd_quantize_roundtrip" if roundtrip else "hvd_quantize",
    )(m)
    q = out[0][:nb].reshape(-1)
    s = out[1][:nb].reshape(-1)
    if roundtrip:
        return q, s, out[2][:nb].reshape(-1)
    return q, s


def quantize_blockwise(flat, block: int):
    """Fused blockwise int8 quantize of a flat float vector whose length
    is a multiple of ``block``. Returns ``(q int8 [L], scales bf16
    [L/block])`` — bit-identical to the discrete HLO
    ``compression.quantize_blockwise`` (interpret mode pins it)."""
    return _quantize_call(flat, block, roundtrip=False)


def quantize_roundtrip(flat, block: int):
    """Like :func:`quantize_blockwise` but ALSO emits the dequantized
    wire image in the same VMEM pass: ``(q, scales, deq [L])``. One read
    of the corrected gradient buffer serves both the ``all_to_all``
    payload and the error-feedback residual — the HLO path pays two full
    quantize passes for the same pair."""
    return _quantize_call(flat, block, roundtrip=True)


# --------------------------------------------------------------------------
# post-all_to_all epilogues: dequant-accumulate(-requantize)
#
# The wire image ``qr [N, sp]`` + ``scr [N, sp/block]`` rides as
# ``(N, nb, block)`` int8 beside an ``(N, nb, 1)`` scale column: one
# quantization block per sublane row, its scale broadcast along the
# lanes — no in-kernel reshape, every block a whole number of tiles.


def _wire_stack(qr, scr, block: int):
    n, sp = qr.shape
    nb = sp // block
    tile = _row_tile(nb, n, block)
    q3 = _pad_axis(qr.reshape(n, nb, block), 1, tile)
    s3 = _pad_axis(scr.reshape(n, nb, 1), 1, tile)
    return q3, s3, nb, tile


def _stack_specs(n: int, tile: int, block: int):
    pl = _pl()
    return [
        pl.BlockSpec((n, tile, block), lambda j: (0, j, 0)),
        pl.BlockSpec((n, tile, 1), lambda j: (0, j, 0)),
    ]


def _deq_sum(q_ref, s_ref, dtype):
    """Dequantize the N sender rows and sum them in ``dtype`` — the same
    ``(q * scale).sum(axis=0)`` the HLO path runs, sender by sender."""
    d = q_ref[...].astype(dtype) * s_ref[...].astype(dtype)
    return jnp.sum(d, axis=0)


def _deq_acc_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = _deq_sum(q_ref, s_ref, o_ref.dtype)


def _deq_acc_requant_kernel(q_ref, s_ref, q2_ref, s2_ref, *, divisor,
                            dtype):
    acc = _deq_sum(q_ref, s_ref, dtype)
    if divisor is not None:
        acc = acc / jnp.asarray(divisor, dtype=acc.dtype)
    _quantize_rows(acc, q2_ref, s2_ref)


def _deq_rows_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(o_ref.dtype) \
        * s_ref[...].astype(o_ref.dtype)


def dequant_accumulate(qr, scr, dtype, block: int):
    """Fused reduce-scatter epilogue: the post-``all_to_all`` int8
    chunks ``qr [N, sp]`` + bf16 scales ``scr [N, sp/block]`` →
    dequantize, ACCUMULATE over the N senders in ``dtype`` (f32
    widening), emit the summed shard ``[sp]`` — without materializing
    the ``[N, sp]`` dequantized matrix in HBM. Accumulation order
    matches the HLO ``deq.sum(axis=0)`` exactly (interpret mode is
    bit-identical)."""
    pl = _pl()
    n, sp = qr.shape
    q3, s3, nb, tile = _wire_stack(qr, scr, block)
    nbp = q3.shape[1]
    out = pl.pallas_call(
        _deq_acc_kernel,
        grid=(nbp // tile,),
        in_specs=_stack_specs(n, tile, block),
        out_specs=pl.BlockSpec((tile, block), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, block), jnp.dtype(dtype)),
        interpret=interpret(),
        name="hvd_dequant_accumulate",
    )(q3, s3)
    return out[:nb].reshape(-1)


def dequant_accumulate_requantize(qr, scr, dtype, block: int,
                                  divisor=None):
    """Fused allreduce epilogue: dequantize + accumulate (+ divide by
    ``divisor`` for Average) + blockwise REQUANTIZE in one pass — the
    reduced shard feeds the int8 all-gather leg without a round trip
    through HBM between accumulate and requantize. Returns ``(q2 int8
    [sp], scales2 bf16 [sp/block])``, bit-identical to the discrete
    sum → div → ``quantize_blockwise`` sequence. ``sp`` must be a
    multiple of ``block`` (the allreduce pads to ``N·block``)."""
    pl = _pl()
    n, sp = qr.shape
    q3, s3, nb, tile = _wire_stack(qr, scr, block)
    nbp = q3.shape[1]
    q2, s2 = pl.pallas_call(
        functools.partial(
            _deq_acc_requant_kernel, divisor=divisor,
            dtype=jnp.dtype(dtype)),
        grid=(nbp // tile,),
        in_specs=_stack_specs(n, tile, block),
        out_specs=[
            pl.BlockSpec((tile, block), lambda j: (j, 0)),
            pl.BlockSpec((tile, 1), lambda j: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, block), jnp.int8),
            jax.ShapeDtypeStruct((nbp, 1), jnp.bfloat16),
        ],
        interpret=interpret(),
        name="hvd_dequant_accumulate_requantize",
    )(q3, s3)
    return q2[:nb].reshape(-1), s2[:nb].reshape(-1)


def dequantize_rows(qr, scr, dtype, block: int):
    """Fused all-gather epilogue: gathered int8 rows ``qr [N, sp]`` + bf16
    scales ``scr [N, sp/block]`` → per-row dequantized ``[N, sp]`` in
    ``dtype`` — NO accumulation (every row is a different rank's
    parameter shard; contrast :func:`dequant_accumulate`, the
    reduce-scatter epilogue that sums the senders). One VMEM pass per
    row tile, bit-identical to the discrete HLO
    ``compression.dequantize_rows`` (interpret mode pins it). The ZeRO-3
    int8 parameter gather (``collective.quantized_all_gather``) runs this
    right after its ``all_gather`` pair."""
    pl = _pl()
    n, sp = qr.shape
    q3, s3, nb, tile = _wire_stack(qr, scr, block)
    nbp = q3.shape[1]
    out = pl.pallas_call(
        _deq_rows_kernel,
        grid=(nbp // tile,),
        in_specs=_stack_specs(n, tile, block),
        out_specs=pl.BlockSpec((n, tile, block), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, nbp, block), jnp.dtype(dtype)),
        interpret=interpret(),
        name="hvd_dequantize_rows",
    )(q3, s3)
    return out[:, :nb].reshape(n, sp)


# --------------------------------------------------------------------------
# Adasum pairwise combine (single-tensor + segmented group form)


def _pair_reduce_kernel(a_ref, b_ref, o_ref):
    """Partials of ``a·b``, ``|a|²``, ``|b|²`` out of ONE read of both
    operands: each tile-high slab of the block adds into three resident
    (slab, _VEC_COLS) accumulators (whole-vreg adds, no cross-lane
    work); the wrapper folds the accumulators to scalars."""
    pl = _pl()

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    h = o_ref.shape[1]

    def slab(i, acc):
        r = pl.multiple_of(i * h, h)
        a = a_ref[pl.ds(r, h), :].astype(jnp.float32)
        b = b_ref[pl.ds(r, h), :].astype(jnp.float32)
        return acc[0] + a * b, acc[1] + a * a, acc[2] + b * b

    zero = jnp.zeros((h, _VEC_COLS), jnp.float32)
    ab, aa, bb = lax.fori_loop(
        0, a_ref.shape[0] // h, slab, (zero, zero, zero))
    o_ref[0] += ab
    o_ref[1] += aa
    o_ref[2] += bb


def _blend_kernel(c_ref, a_ref, b_ref, o_ref):
    o_ref[...] = (c_ref[0:1, :] * a_ref[...].astype(jnp.float32)
                  + c_ref[1:2, :] * b_ref[...].astype(jnp.float32))


def _adasum_coefficients(dot, na, nb):
    ca = jnp.where(na == 0, 0.0, 1.0 - dot / (2.0 * jnp.maximum(na, 1e-30)))
    cb = jnp.where(nb == 0, 0.0, 1.0 - dot / (2.0 * jnp.maximum(nb, 1e-30)))
    return ca, cb


def adasum_pair_combine(a, b):
    """One Adasum pairwise combine (``ops/adasum.py::_pair_combine``)
    as two fused VMEM passes: pass 1 reads ``a``/``b`` ONCE for all
    three scalar reductions (the discrete path reads each operand three
    times), pass 2 applies the scaled blend. The tiled partial
    reduction changes the f32 summation order vs ``jnp.vdot``, so
    equivalence is pinned to tolerance, not bits."""
    pl = _pl()
    shape, dtype = a.shape, a.dtype
    L = a.size
    a2, tile = _as_rows(a.reshape(-1))
    b2, _ = _as_rows(b.reshape(-1))
    rows = a2.shape[0]
    vec = pl.BlockSpec((tile, _VEC_COLS), lambda i: (i, 0))
    acc = (3, _sublanes(dtype), _VEC_COLS)
    part = pl.pallas_call(
        _pair_reduce_kernel,
        grid=(rows // tile,),
        in_specs=[vec, vec],
        out_specs=pl.BlockSpec(acc, lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(acc, jnp.float32),
        interpret=interpret(),
        name="hvd_adasum_pair_reduce",
    )(a2, b2)
    dot, na, nb = jnp.sum(part, axis=(1, 2))
    coef = _coef_rows(*_adasum_coefficients(dot, na, nb))
    out = pl.pallas_call(
        _blend_kernel,
        grid=(rows // tile,),
        in_specs=[_coef_spec(2), vec, vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((rows, _VEC_COLS), jnp.float32),
        interpret=interpret(),
        name="hvd_adasum_pair_blend",
    )(coef, a2, b2)
    return out.reshape(-1)[:L].reshape(shape).astype(dtype)


def _seg_reduce_kernel(a_ref, b_ref, seg_ref, o_ref):
    """Segmented variant of :func:`_pair_reduce_kernel`: each row's
    three products contract against an in-register one-hot segment
    matrix on the MXU, yielding per-SEGMENT partials — all tensors of a
    fused Adasum group reduced in one read of the group buffer. Each
    product row is sublane-broadcast to a full 8-row MXU operand, so the
    (3, 8, nsp) accumulator holds every partial eight times over (the
    wrapper reads row 0)."""
    pl = _pl()

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    nsp = o_ref.shape[2]
    cols = a_ref.shape[1]
    seg_iota = lax.broadcasted_iota(jnp.int32, (nsp, cols), 0)
    ab = aa = bb = jnp.zeros((8, nsp), jnp.float32)
    for r in range(a_ref.shape[0]):
        a = a_ref[r:r + 1, :]
        b = b_ref[r:r + 1, :]
        onehot = (seg_iota == seg_ref[r:r + 1, :]).astype(jnp.float32)

        def contract(x):
            # HIGHEST: the MXU's default single bf16 pass would round
            # the f32 products to 8 mantissa bits before summing them
            return lax.dot_general(
                jnp.broadcast_to(x, (8, cols)), onehot,
                (((1,), (1,)), ((), ())),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        ab = ab + contract(a * b)
        aa = aa + contract(a * a)
        bb = bb + contract(b * b)
    o_ref[0] += ab
    o_ref[1] += aa
    o_ref[2] += bb


def _seg_blend_kernel(a_ref, b_ref, ca_ref, cb_ref, o_ref):
    o_ref[...] = ca_ref[...] * a_ref[...] + cb_ref[...] * b_ref[...]


def adasum_segment_combine(a, b, seg_ids, n_segments: int):
    """Per-tensor Adasum combine over a concatenated flat f32 group
    buffer (``ops/adasum.py::_segment_combine``): per-segment
    ``dot``/``na``/``nb`` partials come out of ONE fused read of
    ``a``/``b`` (pass 1), the per-segment blend out of a second
    (pass 2). The flat layout — and therefore the butterfly's
    ``ppermute`` signature — is untouched; padding happens inside the
    kernel wrappers only."""
    pl = _pl()
    L = a.shape[0]
    a2, tile = _as_rows(a)
    b2, _ = _as_rows(b)
    rows = a2.shape[0]
    # ghost id n_segments marks the zero-pad tail; it matches no one-hot
    # row (nsp > n_segments) or contributes only to a sliced-off row
    seg_p = jnp.pad(seg_ids.astype(jnp.int32), (0, rows * _VEC_COLS - L),
                    constant_values=n_segments)
    s2 = seg_p.reshape(rows, _VEC_COLS)
    nsp = _round_up(n_segments + 1, _LANES)
    slab = pl.BlockSpec((8, _VEC_COLS), lambda i: (i, 0))
    part = pl.pallas_call(
        _seg_reduce_kernel,
        grid=(rows // 8,),
        in_specs=[slab, slab, slab],
        out_specs=pl.BlockSpec((3, 8, nsp), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, 8, nsp), jnp.float32),
        interpret=interpret(),
        name="hvd_adasum_segment_reduce",
    )(a2, b2, s2)
    ca, cb = _adasum_coefficients(*part[:, 0, :n_segments])
    # per-element coefficients: one gather (the same gather the discrete
    # path's ca[seg_ids] performs), fed tile-wise into the blend pass
    ca_e = jnp.concatenate([ca, jnp.zeros((1,), jnp.float32)])[s2]
    cb_e = jnp.concatenate([cb, jnp.zeros((1,), jnp.float32)])[s2]
    vec = pl.BlockSpec((tile, _VEC_COLS), lambda i: (i, 0))
    out = pl.pallas_call(
        _seg_blend_kernel,
        grid=(rows // tile,),
        in_specs=[vec, vec, vec, vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((rows, _VEC_COLS), jnp.float32),
        interpret=interpret(),
        name="hvd_adasum_segment_blend",
    )(a2, b2, ca_e, cb_e)
    return out.reshape(-1)[:L]


# --------------------------------------------------------------------------
# fused Adam shard update (ZeRO-1 per-bucket [N, shard_k] buffers)


def _adam_kernel(c_ref, g_ref, mu_ref, nu_ref, u_ref, mu2_ref, nu2_ref,
                 *, b1, b2, eps, eps_root, neg_lr):
    """Adam moment update + bias correction + parameter step in one VMEM
    pass, expression-for-expression the optax ``scale_by_adam`` +
    ``scale(-lr)`` chain so interpret mode is bit-identical to the
    discrete path. ``c_ref`` carries the two traced bias-correction
    scalars (they depend on the step count)."""
    g = g_ref[...]
    mu = mu_ref[...]
    nu = nu_ref[...]
    b1c = c_ref[0:1, :].astype(g.dtype)
    b2c = c_ref[1:2, :].astype(g.dtype)
    mu2 = (1 - b1) * g + b1 * mu
    nu2 = (1 - b2) * (g * g) + b2 * nu
    mu2_ref[...] = mu2
    nu2_ref[...] = nu2
    u_ref[...] = neg_lr * (
        (mu2 / b1c) / (jnp.sqrt(nu2 / b2c + eps_root) + eps))


def fused_adam_update(g, mu, nu, b1c, b2c, *, lr, b1, b2, eps,
                      eps_root=0.0):
    """One fused Adam step over a flat shard: returns ``(update, mu',
    nu')`` — bit-identical to optax's ``scale_by_adam`` →
    ``scale(-lr)`` chain. ``b1c``/``b2c`` are the traced bias
    corrections ``1 - b**count`` (they ride a two-row coefficient
    operand into the kernel).

    Works on any 1-D shard (zero-padded to whole tiles internally) and
    under ``jax.vmap`` — the form ``optim._zero_update`` applies over
    the per-bucket ``[N, shard_k]`` state buffers."""
    pl = _pl()
    L = g.shape[0]
    g2, tile = _as_rows(g)
    mu2, _ = _as_rows(mu)
    nu2, _ = _as_rows(nu)
    rows = g2.shape[0]
    vec = pl.BlockSpec((tile, _VEC_COLS), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(
            _adam_kernel, b1=b1, b2=b2, eps=eps, eps_root=eps_root,
            neg_lr=-lr),
        grid=(rows // tile,),
        in_specs=[_coef_spec(2), vec, vec, vec],
        out_specs=[vec, vec, vec],
        out_shape=[jax.ShapeDtypeStruct((rows, _VEC_COLS), g.dtype)] * 3,
        interpret=interpret(),
        name="hvd_fused_adam",
    )(_coef_rows(b1c, b2c), g2, mu2, nu2)
    return tuple(o.reshape(-1)[:L] for o in out)
