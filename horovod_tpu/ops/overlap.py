"""Bucketed backward-pass gradient sync: comm/compute overlap.

The reference's defining perf trick is the background controller plus the
64 MB fusion buffer that overlaps allreduce with backprop
(``controller.cc:640-761``, ``operations.cc:550-600``): gradients are
reduced as backprop produces them, so step time approaches
``max(compute, comm)`` instead of ``compute + comm``. Every train-step
path here previously synced the whole gradient tree only after the full
backward pass. This module rebuilds the insight TPU-natively (the same
bucketing PyTorch DDP uses — Li et al., VLDB 2020):

- :class:`BucketPlan` partitions the flat per-dtype gradient packing into
  ~``HOROVOD_BUCKET_BYTES`` (default 64 MB, honoring the existing
  ``HOROVOD_FUSION_THRESHOLD`` knob) buckets in **reverse-topological
  (backprop-emission) order** — the last-declared parameters' gradients
  are produced first in the backward pass, so their bucket's collective
  can launch while the earlier layers' backward still runs.
- one collective per bucket instead of one per tree/dtype: each bucket's
  ``psum``/``psum_scatter`` depends only on ITS leaves' cotangents, so
  XLA's latency-hiding scheduler (plus the async-collective flags
  :func:`horovod_tpu.tuning.apply_xla_flags` sets) can hoist the launch
  into the backward — the data dependency, not the trace position, is
  what the scheduler honors.
- :func:`sync_hook` additionally *pins* the interleaving structurally: a
  ``custom_vjp`` hook on a layer block issues the block's bucket
  collectives inside its backward rule and threads the activation
  cotangent through :func:`barrier_after`
  (``lax.optimization_barrier``), so the remaining backward fragments
  *data-depend* on the issued collectives — no scheduler, CPU included,
  can sink them to the end of the step.

Used by ``DistributedOptimizer(overlap=True)`` (per-bucket reduce-scatter
under ZeRO-1 with a single trailing all-gather per dtype; per-bucket
quantize with error-feedback residuals keyed by bucket) and
``make_shardmap_train_step(..., overlap=True)``.
"""

from __future__ import annotations

import functools
import os
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import basics
from horovod_tpu.observability import metrics as _metrics

__all__ = [
    "DEFAULT_BUCKET_BYTES",
    "Segment",
    "Bucket",
    "BucketPlan",
    "plan_for",
    "bucket_bytes_from_env",
    "resolve_bucket_bytes",
    "barrier_enabled",
    "pack_group",
    "pack_group_rows",
    "assemble",
    "bucketed_allreduce",
    "barrier_after",
    "sync_hook",
]

#: default bucket capacity — the reference fusion buffer's 64 MB
DEFAULT_BUCKET_BYTES = 64 * 1024 * 1024

#: env knobs (documented in docs/performance.md's overlap knob table; the
#: CI guard in tests/test_overlap.py pins every HOROVOD_BUCKET_* /
#: HOROVOD_OVERLAP_* literal into that table)
BUCKET_BYTES_ENV = "HOROVOD_BUCKET_BYTES"
OVERLAP_ENV = "HOROVOD_OVERLAP"
OVERLAP_BARRIER_ENV = "HOROVOD_OVERLAP_BARRIER"


def _env_true(name: str, default: str = "0") -> bool:
    return os.environ.get(name, default).lower() in ("1", "true", "yes")


def bucket_bytes_from_env() -> int:
    """Bucket capacity in bytes: ``HOROVOD_BUCKET_BYTES`` when set, else
    the existing fusion-threshold knob ``HOROVOD_FUSION_THRESHOLD`` (the
    native core's bin size — one knob, one meaning), else 64 MB."""
    for name in (BUCKET_BYTES_ENV, "HOROVOD_FUSION_THRESHOLD"):
        v = os.environ.get(name)
        if v:
            return max(1, int(v))
    return DEFAULT_BUCKET_BYTES


def resolve_bucket_bytes(overlap=None, bucket_bytes: Optional[int] = None
                         ) -> Optional[int]:
    """Resolve the ``overlap=``/``bucket_bytes=`` kwarg pair to a bucket
    capacity, or ``None`` for the monolithic path.

    ``overlap=None`` consults ``HOROVOD_OVERLAP``; ``overlap=False``
    disables even with the env set (the explicit kwarg wins, matching
    every other knob here); ``bucket_bytes`` alone implies overlap."""
    if overlap is None:
        overlap = True if bucket_bytes is not None else _env_true(OVERLAP_ENV)
    if not overlap:
        return None
    if bucket_bytes is not None:
        return max(1, int(bucket_bytes))
    return bucket_bytes_from_env()


def barrier_enabled() -> bool:
    """``HOROVOD_OVERLAP_BARRIER`` (default on): thread
    ``lax.optimization_barrier`` tokens from each issued bucket collective
    into the remaining backward, pinning the interleaved order as a data
    dependency. Off, the schedule is left entirely to XLA's
    latency-hiding scheduler (maximum freedom, no ordering pin)."""
    return _env_true(OVERLAP_BARRIER_ENV, "1")


# --------------------------------------------------------------------------
# the plan


class Segment(NamedTuple):
    """One contiguous element range ``[start, stop)`` of raveled leaf
    ``idx`` — a bucket boundary may split a leaf, so a leaf can span
    several buckets via several segments."""

    idx: int
    start: int
    stop: int

    @property
    def size(self) -> int:
        return self.stop - self.start


class Bucket(NamedTuple):
    """One bucket: single-dtype (a collective moves one dtype), ordered
    segments, true packed length ``L`` and ``Lp`` padded to the axis
    size (ZeRO-1 reduce-scatter needs ``Lp % N == 0``; padding is zeros
    and inert through elementwise optimizers)."""

    key: str
    dtype: str
    segs: Tuple[Segment, ...]
    L: int
    Lp: int

    @property
    def idxs(self) -> Tuple[int, ...]:
        seen: List[int] = []
        for s in self.segs:
            if s.idx not in seen:
                seen.append(s.idx)
        return tuple(seen)


def _leaf_shape_dtype(leaf) -> Tuple[Tuple[int, ...], str]:
    shape = tuple(getattr(leaf, "shape", ()) or ())
    dt = getattr(leaf, "dtype", None)
    dt = jnp.dtype(dt) if dt is not None else jnp.result_type(leaf)
    return shape, str(dt)


class BucketPlan:
    """Partition of a gradient tree's leaves into reverse-emission-order
    buckets of ~``bucket_bytes`` each.

    The partition depends only on the leaf shapes/dtypes and
    ``bucket_bytes`` — NOT on the axis size ``n``, which only pads each
    bucket (``Lp``). Resharding a bucketed optimizer state across world
    sizes therefore re-derives the identical segment boundaries.
    """

    def __init__(self, buckets: Sequence[Bucket], *, n: int,
                 bucket_bytes: int):
        self.buckets: Tuple[Bucket, ...] = tuple(buckets)
        self.n = int(n)
        self.bucket_bytes = int(bucket_bytes)
        self.groups = {b.key: b for b in self.buckets}

    def __len__(self) -> int:
        return len(self.buckets)

    def describe(self) -> str:
        return "\n".join(
            f"{b.key}: L={b.L} Lp={b.Lp} segs="
            + ",".join(f"{s.idx}[{s.start}:{s.stop}]" for s in b.segs)
            for b in self.buckets
        )

    @classmethod
    def build(cls, leaves: Sequence, n: int,
              bucket_bytes: Optional[int] = None) -> "BucketPlan":
        """Build the plan from leaves (arrays or anything with
        ``.shape``/``.dtype``). Iteration runs over the leaves in
        REVERSE tree-flatten order: backprop produces the last-declared
        parameters' cotangents first, so the first bucket closed is the
        first whose gradients exist mid-backward."""
        bucket_bytes = int(bucket_bytes or bucket_bytes_from_env())
        n = max(1, int(n))
        open_segs: dict = {}    # dtype -> (segs list, bytes, elems)
        counters: dict = {}     # dtype -> next bucket ordinal
        buckets: List[Bucket] = []

        def close(dt: str) -> None:
            segs, _nbytes, elems = open_segs.pop(dt)
            if not segs:
                return
            k = counters.get(dt, 0)
            counters[dt] = k + 1
            L = elems
            buckets.append(Bucket(
                key=f"{dt}#{k}", dtype=dt, segs=tuple(segs),
                L=L, Lp=L + ((-L) % n),
            ))

        infos = [_leaf_shape_dtype(l) for l in leaves]
        for i in reversed(range(len(infos))):
            shape, dt = infos[i]
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            if size == 0:
                continue
            itemsize = int(jnp.dtype(dt).itemsize)
            pos = 0
            while pos < size:
                segs, nbytes, elems = open_segs.setdefault(dt, ([], 0, 0))
                # at least one element of progress per iteration, so a
                # bucket_bytes below one itemsize still terminates
                room = max(1, (bucket_bytes - nbytes) // itemsize)
                take = min(size - pos, room)
                segs.append(Segment(i, pos, pos + take))
                nbytes += take * itemsize
                elems += take
                open_segs[dt] = (segs, nbytes, elems)
                pos += take
                if nbytes >= bucket_bytes:
                    close(dt)
        for dt in list(open_segs):
            close(dt)
        return cls(buckets, n=n, bucket_bytes=bucket_bytes)


@functools.lru_cache(maxsize=256)
def _cached_plan(sig: tuple, n: int, bucket_bytes: int) -> BucketPlan:
    return BucketPlan.build(
        [jax.ShapeDtypeStruct(shape, jnp.dtype(dt)) for shape, dt in sig],
        n, bucket_bytes)


def plan_for(leaves: Sequence, n: int,
             bucket_bytes: Optional[int] = None) -> BucketPlan:
    """Cached :meth:`BucketPlan.build` keyed on the (shape, dtype)
    signature — the eager path rebuilds the plan every step, and the
    partition is pure in the signature."""
    bucket_bytes = int(bucket_bytes or bucket_bytes_from_env())
    sig = tuple(_leaf_shape_dtype(l) for l in leaves)
    return _cached_plan(sig, max(1, int(n)), bucket_bytes)


# --------------------------------------------------------------------------
# pack / unpack


def pack_group(leaves, bucket: Bucket):
    """Flatten + concatenate one bucket's segments, zero-padded to Lp."""
    parts = [
        jnp.ravel(jnp.asarray(leaves[s.idx]))[s.start:s.stop]
        for s in bucket.segs
    ]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if bucket.Lp > bucket.L:
        flat = jnp.concatenate(
            [flat, jnp.zeros((bucket.Lp - bucket.L,), flat.dtype)])
    return flat


def pack_group_rows(leaves, bucket: Bucket, stacked_flags, n: int):
    """``[N, Lp]`` matrix of per-rank flat contributions for one bucket:
    stacked ``[N, ...]`` leaves supply their own rows, replicated leaves
    tile (the eager-path analog of :func:`pack_group`)."""
    rows = []
    for s in bucket.segs:
        l = jnp.asarray(leaves[s.idx])
        if stacked_flags[s.idx]:
            rows.append(l.reshape(n, -1)[:, s.start:s.stop])
        else:
            rows.append(jnp.broadcast_to(
                jnp.ravel(l)[None, s.start:s.stop], (n, s.size)))
    m = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    if bucket.Lp > bucket.L:
        m = jnp.concatenate(
            [m, jnp.zeros((n, bucket.Lp - bucket.L), m.dtype)], axis=1)
    return m


def assemble(flats: dict, groups: dict, shapes: Sequence[Tuple[int, ...]],
             dtypes: Sequence) -> list:
    """Reassemble leaves from per-bucket flat buffers. ``flats[key]`` is
    the bucket's reduced flat buffer (length >= L; padding ignored);
    a leaf split across buckets is stitched from its segments in element
    order. Leaves no bucket covers (zero-size) come back as zeros."""
    pieces: dict = {}
    for key, b in groups.items():
        flat = flats[key]
        off = 0
        for s in b.segs:
            pieces.setdefault(s.idx, []).append((s.start, flat[off:off + s.size]))
            off += s.size
    out = []
    for i, shape in enumerate(shapes):
        ps = sorted(pieces.get(i, ()), key=lambda t: t[0])
        if not ps:
            out.append(jnp.zeros(shape, jnp.dtype(dtypes[i])))
            continue
        flat = (
            ps[0][1] if len(ps) == 1
            else jnp.concatenate([p for _, p in ps])
        )
        out.append(flat.reshape(shape))
    return out


# --------------------------------------------------------------------------
# bucketed tree sync (the non-sharded / allreduce mode)


def _record_buckets(mode: str, k: int) -> None:
    if not _metrics.enabled():
        return
    _metrics.gauge(
        "grad_sync_buckets",
        help="gradient-sync collectives (buckets) issued per step",
        mode=mode,
    ).set(k)


@jax.named_scope("hvd.sync/grads")  # the device trace's sync phase
def bucketed_allreduce(grads, op=None, *, axis=None, compression=None,
                       bucket_bytes: Optional[int] = None,
                       plan: Optional[BucketPlan] = None,
                       predivide: float = 1.0,
                       residual: Optional[dict] = None,
                       roundtrip=None):
    """Allreduce a gradient tree through reverse-emission-order buckets:
    one flat collective per bucket instead of one per leaf, each
    depending only on its own leaves' cotangents — the overlappable
    schedule. Trajectory-identical to the per-leaf path for ``none`` and
    ``fp16`` wire formats (packing is a permutation; the elementwise cast
    and the cross-rank sum commute with it); blockwise int8 scales are
    layout-dependent, so the int8 wire tracks within one quantization
    step per element (error feedback keeps it convergence-safe).

    With ``residual`` (a dict keyed by bucket key — the error-feedback
    state layout ``DistributedOptimizer(overlap=True)`` carries), returns
    ``(reduced_tree, new_residual)``; otherwise ``(reduced_tree, None)``.
    ``roundtrip`` models what one bucket's wire transfer preserves
    (default: the compressor's compress→decompress roundtrip).
    """
    from horovod_tpu.compression import Compression
    from horovod_tpu.ops import collective as _C

    op = _C.Average if op is None else op
    if op not in (_C.Average, _C.Sum):
        raise ValueError(
            "bucketed overlap supports op=Average/Sum (Adasum's pairwise "
            "projections are per-tensor scalars; bucket packing would mix "
            "them)"
        )
    compression = Compression.none if compression is None else compression
    if getattr(compression, "factorized", False):
        raise ValueError(
            "factorized compression (PowerSGD) syncs per-leaf rank-r "
            "factors; bucket-level overlap does not apply — drop "
            "overlap= or use the int8/fp16 wire"
        )
    ax = _C._axis(axis)
    n = _C._axis_size(ax)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    traced = any(_C._is_tracer(l) for l in leaves)
    stacked_flags = [
        (not traced) and _C._is_stacked(l, ax) for l in leaves
    ]
    shapes = [
        tuple(l.shape[1:]) if st else tuple(getattr(l, "shape", ()))
        for l, st in zip(leaves, stacked_flags)
    ]
    dtypes = [_leaf_shape_dtype(l)[1] for l in leaves]

    if plan is None:
        # n=1: the allreduce wire needs no shard padding (the quantized
        # ring pads internally), so L == Lp and the packing is exact
        plan = plan_for(
            [jax.ShapeDtypeStruct(s, jnp.dtype(d))
             for s, d in zip(shapes, dtypes)], 1, bucket_bytes)

    if roundtrip is None:
        def roundtrip(v):
            c, ctx = compression.compress(v)
            return compression.decompress(c, ctx)

    if basics.is_initialized():
        # byte-model accounting, priced per BUCKET through the
        # compressor's wire_bytes hook (the int8 floor applies to the
        # packed bucket, exactly what the wire below does)
        from horovod_tpu import optim as _optim

        _optim._record_sync_bytes("allreduce", n, sum(
            _optim._wire_bytes_leaf(
                (b.L,), jnp.dtype(b.dtype), compression)
            for b in plan.buckets
        ))

    reduced_flats = {}
    new_res: Optional[dict] = {} if residual is not None else None
    for key, b in plan.groups.items():
        if any(stacked_flags[i] for i in b.idxs):
            flat = pack_group_rows(leaves, b, stacked_flags, n)   # [N, L]
            flat = jax.device_put(
                flat, NamedSharding(basics.mesh(), P(ax)))
        else:
            flat = pack_group(leaves, b)                          # [L]
        if residual is not None:
            corrected = flat + residual[key]
            new_res[key] = (corrected - roundtrip(corrected)).astype(
                jnp.dtype(b.dtype))
            flat = corrected
        if op == _C.Average and predivide != 1.0:
            out = _C.allreduce(
                flat / predivide, _C.Sum, axis=ax, compression=compression,
            ) * (predivide / n)
        else:
            out = _C.allreduce(flat, op, axis=ax, compression=compression)
        reduced_flats[key] = out[:b.L]
    _record_buckets("allreduce", len(plan.groups))
    # eager stacked inputs reduce to the replicated per-rank shape — the
    # same contract allreduce() itself has
    out_leaves = assemble(reduced_flats, plan.groups, shapes, dtypes)
    return jax.tree_util.tree_unflatten(treedef, out_leaves), new_res


# --------------------------------------------------------------------------
# interleaving pins: barrier threading + the custom_vjp block hook


def barrier_after(x, dep):
    """Thread an ``optimization_barrier`` token derived from ``dep``
    (typically an issued bucket collective's output) into ``x`` (the
    activation cotangent the remaining backward consumes): every
    topological order — XLA's schedulers included — must now place the
    collective before the later backward fragments. One tiny (1-element)
    token per bucket; no math changes."""
    dep_leaves = [
        l for l in jax.tree_util.tree_leaves(dep)
        if hasattr(l, "dtype") and getattr(l, "size", 0)
    ]
    if not dep_leaves:
        return x
    tok = jnp.ravel(dep_leaves[0])[:1]
    flat, tdef = jax.tree_util.tree_flatten(x)
    if not flat:
        return x
    out = lax.optimization_barrier(tuple(flat) + (tok,))
    return jax.tree_util.tree_unflatten(tdef, list(out[:-1]))


def chain_barriers(values: list) -> list:
    """Pin issue order across a sequence of independent collectives'
    outputs: value k is barrier-tied to value k-1, so every schedule —
    XLA's latency-hiding scheduler included — issues them in list order.
    The ZeRO-3 gather-on-use leg chains its per-bucket parameter
    all-gathers this way: the forward consumes bucket k while bucket
    k+1's gather is still in flight, instead of all gathers racing (and
    all gathered buffers being live) at step start — the
    :func:`sync_hook`/:func:`barrier_after` trick run in the forward
    direction."""
    if len(values) <= 1:
        return list(values)
    out = [values[0]]
    for v in values[1:]:
        out.append(barrier_after(v, out[-1]))
    return out


def sync_hook(block_fn, sync_fn, *, barrier: Optional[bool] = None):
    """Wrap ``block_fn(params, x) -> y`` so its backward rule issues the
    block's gradient sync *inside* the backward pass — the ``custom_vjp``
    spelling of the reference's "reduce while backprop still runs".

    ``sync_fn(param_grads) -> synced_grads`` is typically a
    :func:`bucketed_allreduce` closure. With ``barrier`` (default: the
    ``HOROVOD_OVERLAP_BARRIER`` knob) the activation cotangent is
    barrier-tied to the issued collective, pinning bucket k's sync
    *between* block k's and block k-1's backward fragments in every
    schedule. ``jax.grad`` of a model composed of hooked blocks returns
    gradients that are ALREADY synced — pair with a plain optimizer, not
    ``DistributedOptimizer`` (which would reduce a second time)."""

    @jax.custom_vjp
    def blk(p, x):
        return block_fn(p, x)

    def fwd(p, x):
        y, vjp = jax.vjp(block_fn, p, x)
        return y, vjp

    def bwd(vjp, g):
        gp, gx = vjp(g)
        gp = sync_fn(gp)
        use_barrier = barrier_enabled() if barrier is None else barrier
        if use_barrier:
            gx = barrier_after(gx, gp)
        return gp, gx

    blk.defvjp(fwd, bwd)
    return blk
