"""Gradient compression (reference ``horovod/torch/compression.py:20-73``,
``horovod/tensorflow/compression.py``): compress before the collective, decompress
after. On TPU fp16 compression maps to bfloat16 — same 2-byte wire size, far
better dynamic range on the MXU, and XLA fuses the casts into the collective's
pack/unpack copies.

Beyond the reference's fp16 cap, two low-bit compressors (both pair with
``error_feedback=True`` on :class:`horovod_tpu.optim.DistributedOptimizer`,
which keeps EF-SGD convergence guarantees — Karimireddy et al., ICML 2019):

- :class:`Int8Compressor` (``Compression.int8``): blockwise-scaled int8 —
  one bf16 max-abs scale per :data:`INT8_BLOCK` elements, ~4x fewer wire
  bytes than fp32 (25.8% incl. scale overhead). The *reduction* of int8
  values widens to f32 per shard inside the collective kernels
  (:mod:`horovod_tpu.ops.collective`), so int8 never overflows in the ring.
- :class:`PowerSGDCompressor` (``Compression.powersgd(rank=r)``): rank-r
  low-rank factorization of >=2-D gradient leaves (Vogels et al., NeurIPS
  2019) — only the small P/Q factors travel; 1-D leaves fall back to int8.
  Stateful (warm-started Q lives in the optimizer state), so it rides
  ``DistributedOptimizer`` rather than a bare ``allreduce``.

Every in-tree compressor exposes ``wire_bytes(shape, dtype)`` — the bytes
one leaf actually costs on the wire per transfer direction — which
``grad_sync_bytes_per_step`` accounting consumes (legacy compressors
without the hook fall back to a scalar compress probe's itemsize).
"""

from __future__ import annotations

import math
import os

import jax.numpy as jnp
import numpy as np
from jax import lax

#: elements per int8 quantization scale (one bf16 scale per block)
INT8_BLOCK = 256

#: bytes of one int8 scale on the wire (bfloat16)
_SCALE_BYTES = 2

#: smallest leaf the per-leaf int8 paths quantize. The quantized ring pads
#: every rank-pair message up to a whole scale block, so a tiny leaf (a
#: bias, a layernorm) would move MORE wire than its fp32 psum — below this
#: floor leaves pass through uncompressed and are billed dense, keeping
#: wire_bytes truthful. ~the crossover for rings up to ~32 ranks; the
#: ZeRO-1 flat-packed buffers amortize the padding and ignore this floor.
MIN_QUANT_ELEMS = 1024


def _quantizable(dtype) -> bool:
    """int8/PowerSGD compress only wide floats: f32/f64 leaves. Integer and
    already-16-bit (bf16/f16) leaves pass through uncompressed, exactly as
    fp16 compression passes integers through."""
    dt = jnp.dtype(dtype)
    return jnp.issubdtype(dt, jnp.floating) and dt.itemsize > 2


def _use_pallas(use_pallas) -> bool:
    """Resolve the per-call Pallas override against the
    ``HOROVOD_PALLAS`` knob (``None`` = knob decides)."""
    if use_pallas is not None:
        return bool(use_pallas)
    from horovod_tpu.ops import pallas_kernels as _pk

    return _pk.enabled()


def _pad_to_block(x, block: int):
    """Shared pad-to-scale-block helper: zero-pads a flat ``[L]`` vector
    (or the trailing axis of ``[n, s]`` destination-chunk rows) up to a
    multiple of ``block`` — the ONE place the wire's block alignment is
    spelled, shared by :func:`quantize_blockwise` tails,
    :func:`quantize_chunked`, the quantized collectives
    (:mod:`horovod_tpu.ops.collective`) and the serving delta encoder."""
    pad = (-x.shape[-1]) % block
    if not pad:
        return x
    if x.ndim == 1:
        return jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def quantize_blockwise(flat, block: int = INT8_BLOCK, *, use_pallas=None):
    """Blockwise-scaled int8 quantization of a flat float vector. A tail
    shorter than ``block`` is zero-padded internally (shared
    :func:`_pad_to_block` helper), so callers no longer pre-pad; ``q``
    comes back at the padded length and ``scales`` one per (padded)
    block.

    Returns ``(q, scales)``: ``q`` int8 in [-127, 127], ``scales`` bf16 —
    one max-abs/127 scale per block. The scale is rounded to bf16 *before*
    the divide so quantization and dequantization agree on the exact scale
    the wire carries (the receiver only ever sees the bf16 value).

    Under ``HOROVOD_PALLAS`` (``use_pallas=None`` consults the knob) the
    multi-op HLO sequence is replaced by the fused single-pass VMEM
    kernel :func:`horovod_tpu.ops.pallas_kernels.quantize_blockwise` —
    bit-identical output, pinned by interpret mode on CPU."""
    flat = _pad_to_block(flat, block)
    if _use_pallas(use_pallas):
        from horovod_tpu.ops import pallas_kernels as _pk

        return _pk.quantize_blockwise(flat, block)
    m = flat.reshape(-1, block)
    amax = jnp.max(jnp.abs(m), axis=1)
    # reduce_precision, not a bare astype pair: XLA may elide an
    # f32->bf16->f32 convert pair (excess precision — it does on TPU) and
    # divide by the unrounded scale, while the receiver multiplies by the
    # bf16 one; a reduce_precision rounding is never elided
    wire = lax.reduce_precision(
        amax / 127.0, exponent_bits=8, mantissa_bits=7)
    scales = wire.astype(jnp.bfloat16)
    s = wire[:, None]
    safe = jnp.where(s > 0, s, jnp.ones_like(s))
    q = jnp.where(s > 0, m / safe, jnp.zeros_like(m))
    q = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scales


def dequantize_blockwise(q, scales, dtype, block: int = INT8_BLOCK):
    """Inverse of :func:`quantize_blockwise`: int8 + bf16 scales back to a
    flat ``dtype`` vector (the f32 widening every accumulation uses)."""
    m = q.astype(dtype).reshape(-1, block)
    return (m * scales.astype(dtype)[:, None]).reshape(-1)


def dequantize_rows(qr, scr, dtype, block: int = INT8_BLOCK, *,
                    use_pallas=None):
    """Per-row dequantize of gathered int8 rows: ``qr [N, sp]`` + bf16
    scales ``scr [N, sp/block]`` → ``[N, sp]`` in ``dtype``. The ZeRO-3
    int8 parameter-gather epilogue (every row is a different rank's
    shard — NO accumulation, unlike the reduce-scatter's
    ``dequant_accumulate``). Under ``HOROVOD_PALLAS`` the multiply runs
    as one fused VMEM pass
    (:func:`horovod_tpu.ops.pallas_kernels.dequantize_rows` —
    bit-identical, pinned by interpret mode)."""
    if _use_pallas(use_pallas):
        from horovod_tpu.ops import pallas_kernels as _pk

        return _pk.dequantize_rows(qr, scr, dtype, block)
    n, sp = qr.shape
    m = qr.astype(dtype).reshape(n, sp // block, block)
    return (m * scr.astype(dtype)[:, :, None]).reshape(n, sp)


def int8_roundtrip(tensor, block: int = INT8_BLOCK):
    """What `tensor` looks like after one trip through the int8 wire
    (flat-block layout): dequant(quant(.)) — identity on non-quantizable
    dtypes and on leaves below the :data:`MIN_QUANT_ELEMS` floor (those
    ride uncompressed). vmap-safe (all shapes static), unlike the
    ``compress``/``decompress`` pair whose context carries python
    metadata."""
    if not _quantizable(getattr(tensor, "dtype", jnp.float32)) \
            or tensor.size < MIN_QUANT_ELEMS:
        return tensor
    shape, size = tensor.shape, tensor.size
    q, scales = quantize_blockwise(tensor.reshape(-1), block)
    return dequantize_blockwise(q, scales, tensor.dtype, block)[:size].reshape(
        shape)


def quantize_chunked(flat, n: int, block: int = INT8_BLOCK, *,
                     use_pallas=None):
    """The chunk-aligned wire image of a flat packed ``[Lp]`` buffer:
    ``(q, scales, rt)`` with the SAME block layout the quantized
    reduce-scatter puts on the wire — the ``[Lp]`` vector splits into
    ``n`` destination chunks, each chunk blockwise-quantized with its own
    zero-pad (shared :func:`_pad_to_block` helper, so the Pallas and HLO
    paths consume identical layouts). ``rt`` is the dequantized
    roundtrip sliced back to ``[Lp]``.

    Under Pallas the quantize and the roundtrip come out of ONE fused
    pass (:func:`horovod_tpu.ops.pallas_kernels.quantize_roundtrip`):
    error feedback's residual and the ``all_to_all`` payload share a
    single read of the corrected buffer, where the discrete path
    quantizes it twice. ``Lp`` must be a multiple of ``n``."""
    s = flat.shape[0] // n
    rows = _pad_to_block(flat.reshape(n, s), block)
    sp = rows.shape[1]
    if _use_pallas(use_pallas):
        from horovod_tpu.ops import pallas_kernels as _pk

        q, scales, deq = _pk.quantize_roundtrip(rows.reshape(-1), block)
    else:
        q, scales = quantize_blockwise(
            rows.reshape(-1), block, use_pallas=False)
        deq = dequantize_blockwise(q, scales, flat.dtype, block)
    rt = deq.reshape(n, sp)[:, :s].reshape(-1)
    return q, scales, rt


def quantize_roundtrip_chunked(flat, n: int, block: int = INT8_BLOCK):
    """Wire roundtrip of a flat packed buffer with the SAME block layout the
    quantized reduce-scatter puts on the wire (see
    :func:`quantize_chunked`). Error feedback measures its residual
    against exactly this, so the residual equals
    corrected-minus-what-the-ring-counted to the last ULP. ``Lp`` must be
    a multiple of ``n``."""
    return quantize_chunked(flat, n, block)[2]


class Compressor:
    """Interface (reference ``torch/compression.py:20-31``).

    Subclasses may additionally define ``wire_bytes(shape, dtype) -> int``
    (bytes one leaf costs per wire direction) for truthful
    ``grad_sync_bytes_per_step`` pricing; without it the accounting falls
    back to probing ``compress`` on a host scalar and billing the
    compressed itemsize per element — correct for elementwise casts only.
    """

    @staticmethod
    def compress(tensor):
        """Returns (compressed_tensor, context_for_decompress)."""
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor

    @staticmethod
    def wire_bytes(shape, dtype) -> int:
        return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize


class FP16Compressor(Compressor):
    """Casts float tensors to 16 bits for the wire (reference
    ``torch/compression.py:42-63``). bfloat16 rather than float16: TPU-native,
    no overflow scaling needed."""

    @staticmethod
    def compress(tensor):
        if jnp.issubdtype(tensor.dtype, jnp.floating):
            return tensor.astype(jnp.bfloat16), tensor.dtype
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor.astype(ctx) if ctx is not None else tensor

    @staticmethod
    def wire_bytes(shape, dtype) -> int:
        n = int(np.prod(shape, dtype=np.int64))
        dt = jnp.dtype(dtype)
        return n * (2 if jnp.issubdtype(dt, jnp.floating) else dt.itemsize)


class Int8Compressor(Compressor):
    """Blockwise-scaled int8 quantization: one bf16 max-abs scale per
    :data:`INT8_BLOCK` elements. f32/f64 leaves only; integer and 16-bit
    float leaves pass through untouched.

    ``compress``/``decompress`` are the *wire roundtrip* (what error
    feedback measures the residual against). The collectives themselves
    never sum int8: the kernels in :mod:`horovod_tpu.ops.collective`
    quantize per destination shard, move int8 + bf16 scales, widen to f32
    to accumulate, and requantize the reduced shard for the gather leg —
    the ``allreduce``/``DistributedOptimizer`` dispatch routes there
    automatically (``quantized = True``)."""

    #: marks this compressor for the quantized collective dispatch
    quantized = True
    block = INT8_BLOCK
    min_quant_elems = MIN_QUANT_ELEMS

    @classmethod
    def quantizes(cls, shape, dtype) -> bool:
        """Would a leaf of this shape/dtype ride the int8 wire? The single
        floor decision shared by ``compress``, the serving delta encoder
        (:mod:`horovod_tpu.serving.protocol`), and the analytic byte
        models — so wire accounting can never disagree with the wire."""
        n = int(np.prod(shape, dtype=np.int64))
        return _quantizable(dtype) and n >= cls.min_quant_elems

    @classmethod
    def compress(cls, tensor):
        if not _quantizable(getattr(tensor, "dtype", jnp.float32)) \
                or getattr(tensor, "size", 0) < cls.min_quant_elems:
            return tensor, None
        shape, dtype = tensor.shape, tensor.dtype
        q, scales = quantize_blockwise(tensor.reshape(-1), cls.block)
        return q, (scales, dtype, shape)

    @classmethod
    def decompress(cls, tensor, ctx):
        if ctx is None:
            return tensor
        scales, dtype, shape = ctx
        flat = dequantize_blockwise(tensor, scales, dtype, cls.block)
        size = int(np.prod(shape, dtype=np.int64))
        return flat[:size].reshape(shape)

    @classmethod
    def wire_bytes(cls, shape, dtype) -> int:
        n = int(np.prod(shape, dtype=np.int64))
        if not _quantizable(dtype) or n < cls.min_quant_elems:
            return n * jnp.dtype(dtype).itemsize
        return n + math.ceil(n / cls.block) * _SCALE_BYTES


class PowerSGDCompressor(Compressor):
    """Rank-``r`` low-rank gradient factorization (PowerSGD, Vogels et al.
    2019): a >=2-D leaf ``M`` (reshaped ``[d0, prod(rest)]``) syncs only
    ``P = M @ Q`` and ``Q_new = M^T @ P`` — ``(d0 + m) * r`` floats instead
    of ``d0 * m`` — with one Gram-Schmidt orthogonalization of the
    aggregated ``P`` per step and ``Q`` warm-started across steps.

    Stateful: the warm-started ``Q`` and the error-feedback residual live
    in the optimizer state, so this compressor only rides
    ``DistributedOptimizer(compression=Compression.powersgd(r),
    error_feedback=True)`` (a bare ``allreduce`` rejects it). 1-D (and
    integer/16-bit) leaves fall back to the int8 path. ``compress`` /
    ``decompress`` here are the stateless int8 fallback so legacy probes
    and the 1-D roundtrip work; the factorization itself is performed by
    :mod:`horovod_tpu.optim`."""

    #: marks this compressor as factorized/stateful for the optim dispatch
    factorized = True
    quantized = True  # the non-factorized leaves ride the int8 wire
    block = INT8_BLOCK
    #: the stateless compressor non-factorized leaves ride
    fallback = Int8Compressor

    def __init__(self, rank: int = 4):
        if rank < 1:
            raise ValueError(f"PowerSGD rank must be >= 1, got {rank}")
        self.rank = int(rank)

    def effective_rank(self, shape) -> int:
        d0 = int(shape[0])
        m = int(np.prod(shape[1:], dtype=np.int64))
        return min(self.rank, d0, m)

    def factorizes(self, shape, dtype) -> bool:
        """Factorize only when the P/Q factors actually cost less wire
        than the dense leaf: ``(d0 + m) * r < d0 * m``. A tiny matrix
        would otherwise pay TWO ring allreduces plus truncation error to
        move MORE bytes; it falls back to the int8/dense path instead."""
        if len(shape) < 2 or not _quantizable(dtype):
            return False
        r = self.effective_rank(shape)
        d0 = int(shape[0])
        m = int(np.prod(shape[1:], dtype=np.int64))
        return r >= 1 and (d0 + m) * r < d0 * m

    def compress(self, tensor):
        return Int8Compressor.compress(tensor)

    def decompress(self, tensor, ctx):
        return Int8Compressor.decompress(tensor, ctx)

    def wire_bytes(self, shape, dtype) -> int:
        if not self.factorizes(shape, dtype):
            return Int8Compressor.wire_bytes(shape, dtype)
        d0 = int(shape[0])
        m = int(np.prod(shape[1:], dtype=np.int64))
        r = self.effective_rank(shape)
        # P [d0, r] + Q [m, r], f32 factors on the wire
        return (d0 + m) * r * 4

    def __repr__(self):  # shows up in bench JSON / error messages
        return f"PowerSGD(rank={self.rank})"


class Compression:
    """Namespace mirroring ``hvd.Compression`` (reference
    ``torch/compression.py:66-73``), extended with the low-bit compressors."""

    none = NoneCompressor
    fp16 = FP16Compressor
    int8 = Int8Compressor

    @staticmethod
    def powersgd(rank: int = None) -> PowerSGDCompressor:
        """Rank-``r`` PowerSGD compressor (default: env
        ``HOROVOD_POWERSGD_RANK``, else 4)."""
        if rank is None:
            rank = int(os.environ.get("HOROVOD_POWERSGD_RANK", "4"))
        return PowerSGDCompressor(rank)

    @staticmethod
    def from_env(default=NoneCompressor):
        """Resolve ``HOROVOD_COMPRESSION`` (``none``/``fp16``/``int8``/
        ``powersgd``) — the env spelling of the ``compression=`` kwarg;
        ``DistributedOptimizer`` consults this when no compressor is passed
        explicitly."""
        name = os.environ.get("HOROVOD_COMPRESSION", "").strip().lower()
        if not name:
            return default
        if name in ("none", "off", "0"):
            return NoneCompressor
        if name in ("fp16", "bf16", "16bit"):
            return FP16Compressor
        if name == "int8":
            return Int8Compressor
        if name == "powersgd":
            return Compression.powersgd()
        raise ValueError(
            f"HOROVOD_COMPRESSION={name!r}: expected one of "
            "none|fp16|int8|powersgd"
        )
