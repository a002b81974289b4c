"""Distributed optimizer wrappers.

TPU-native analog of Horovod's ``DistributedOptimizer`` /
``DistributedGradientTape`` (reference ``horovod/tensorflow/__init__.py:270-535``,
``horovod/torch/__init__.py:67-222``): wrap a local optimizer so gradients are
averaged across the data axis before being applied. Here the local optimizer is
an ``optax.GradientTransformation`` and the allreduce lowers to ``lax.pmean``
inside the jitted step (XLA overlaps it with the backward pass, the role
Horovod's background cycle + fusion buffer play in the reference).
"""

from __future__ import annotations

import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import basics
from horovod_tpu import profiler as _profiler
from horovod_tpu.compression import (
    Compression,
    Int8Compressor,
    _quantizable,
    int8_roundtrip,
    quantize_chunked,
    quantize_roundtrip_chunked,
)
from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.ops import collective as _C
from horovod_tpu.ops import overlap as _ov
from horovod_tpu.ops.collective import (
    Average,
    Adasum,
    ReduceOp,
    Sum,
    allreduce,
    broadcast,
    broadcast_object,
)


def _fused_adasum_tree(grads, axis):
    """Adasum the whole gradient tree through the fused group butterfly —
    log2(ranks) collectives total (ops/adasum.py). Only for uncompressed
    gradients: the fused flat buffer is fp32, so compressing into it would
    add rounding error while saving zero wire bandwidth; compressed Adasum
    stays per-leaf where the 16-bit dtype rides end-to-end."""
    from horovod_tpu.ops.adasum import grouped_adasum_allreduce

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    return jax.tree_util.tree_unflatten(
        treedef, grouped_adasum_allreduce(leaves, axis=axis)
    )


class _EFState(NamedTuple):
    """State for error-feedback compression: the inner optimizer's state plus
    the per-rank residual tree (what lossy compression rounded away so far).

    The sharded (ZeRO-1) path reuses this composition: ``inner`` holds the
    per-rank shard states (every leaf carries a leading rank axis) and
    ``residual`` the per-rank flat residual buffers keyed by dtype — so
    error feedback shards through the same pytree the replicated path uses.
    """

    inner: Any
    residual: Any


class _PowerSGDState(NamedTuple):
    """PowerSGD optimizer state: the inner state, the error-feedback
    residual (param tree replicated, or the per-dtype flat ``[N, Lp]``
    buffers when sharded — the same packing :class:`_EFState` uses), and
    the warm-started ``Q`` factor tree — one ``[m, r]`` matrix per
    factorized (>=2-D float) leaf, ``None`` elsewhere; sharded states tile
    ``Q`` to ``[N, m, r]`` so EVERY leaf keeps the leading rank axis the
    ``shard_map`` specs rely on (the rows are identical by construction:
    ``Q`` comes out of an allreduce)."""

    inner: Any
    residual: Any
    q: Any


def _q_is_leaf(x) -> bool:
    return x is None


def _q_leaves(q_tree):
    """Flatten the Q tree keeping the ``None`` placeholders as leaves, so
    the list stays parallel to the gradient leaves."""
    return jax.tree_util.tree_flatten(q_tree, is_leaf=_q_is_leaf)[0]


def _powersgd_q_init(params, compression, n: Optional[int] = None):
    """Deterministic gaussian ``Q`` per factorized leaf (every rank runs the
    same program, so the seeds agree without a broadcast); ``n`` tiles a
    leading rank axis for the sharded state layout."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    qs = []
    for i, p in enumerate(leaves):
        shape = tuple(getattr(p, "shape", ()))
        if compression.factorizes(shape, _leaf_dtype(p)):
            m = int(np.prod(shape[1:], dtype=np.int64))
            r = compression.effective_rank(shape)
            q = jax.random.normal(
                jax.random.PRNGKey(0x9D5D + i), (m, r), jnp.float32)
            if n is not None:
                q = jnp.broadcast_to(q[None], (n, m, r))
            qs.append(q)
        else:
            qs.append(None)
    return jax.tree_util.tree_unflatten(treedef, qs)


def _orthonormalize(p, eps: float = 1e-8):
    """Single modified Gram-Schmidt pass over the (few, static) columns of
    ``P`` — the one orthogonalization PowerSGD performs per step."""
    cols = []
    for i in range(p.shape[1]):
        v = p[:, i]
        for u in cols:
            v = v - jnp.dot(u, v) * u
        v = v / (jnp.sqrt(jnp.sum(v * v)) + eps)
        cols.append(v)
    return jnp.stack(cols, axis=1)


def _sync_allreduce(x, op, *, axis, **kw):
    """``allreduce`` of one gradient-exchange buffer, under the device
    trace's ``hvd.sync/grads`` scope."""
    with _C._sync_scope("grads"):
        return allreduce(x, op, axis=axis, **kw)


def _psgd_factor_sync(m2d, qmat, reduce_mean):
    """One PowerSGD round on a 2-D per-rank matrix: ``P = M @ Q`` (mean
    across ranks), orthonormalize, ``Q' = M^T @ P`` (mean across ranks).
    Returns ``(P @ Q'^T, Q')`` — the rank-r approximation of the MEAN
    gradient plus the warm-start factor for the next step. Only the small
    ``P``/``Q'`` factors cross the wire."""
    p = reduce_mean(m2d @ qmat)
    p = _orthonormalize(p)
    qn = reduce_mean(m2d.T @ p)
    return p @ qn.T, qn


def _pallas_on() -> bool:
    from horovod_tpu.ops import pallas_kernels as _pk

    return _pk.enabled()


def fused_adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8, eps_root: float = 0.0):
    """Adam as a single fused Pallas kernel per (bucket) shard: moment
    update + bias correction + parameter step in one VMEM pass
    (:func:`horovod_tpu.ops.pallas_kernels.fused_adam_update`), instead
    of the ~10 elementwise HLO ops of ``optax.adam``.

    Drop-in for ``optax.adam`` as the inner optimizer of
    :class:`DistributedOptimizer` — the state pytree IS
    ``optax.adam``'s (``(ScaleByAdamState, EmptyState)``), so
    checkpoints are interchangeable across ``HOROVOD_PALLAS=0/1`` (the
    save→restore bit-stability the acceptance pins) and the ZeRO-1
    ``[N, shard_k]`` per-bucket state layout, ``reshard_optimizer_state``
    and ``broadcast_optimizer_state`` all behave identically. With the
    knob off (or on non-TPU backends under ``auto``) the update IS
    ``optax.adam``'s, bit for bit; with it on, the fused kernel mirrors
    the optax expressions exactly (interpret mode pins ≤1 ULP).

    The fused kernel composes with ``shard_optimizer=True``'s vmapped
    per-bucket update — under ``jax.vmap`` the Pallas call batches over
    the ``[N, shard_k]`` rank axis, one VMEM-resident bucket per
    invocation. Only static float learning rates are supported (a
    schedule would re-introduce the host-side count dependence the
    kernel folds in)."""
    if callable(learning_rate):
        raise ValueError(
            "fused_adam requires a static float learning_rate; wrap an "
            "optax schedule around optax.adam instead"
        )
    lr = float(learning_rate)
    ref = optax.adam(lr, b1=b1, b2=b2, eps=eps, eps_root=eps_root)

    def init_fn(params):
        return ref.init(params)

    def update_fn(updates, state, params=None):
        from horovod_tpu.ops import pallas_kernels as _pk

        if not _pk.enabled():
            return ref.update(updates, state, params)
        adam_st = state[0]
        count_inc = optax.safe_int32_increment(adam_st.count)
        # the traced bias corrections — the exact optax expressions
        b1c = 1 - b1 ** count_inc
        b2c = 1 - b2 ** count_inc
        g_leaves, treedef = jax.tree_util.tree_flatten(updates)
        mu_leaves = jax.tree_util.tree_leaves(adam_st.mu)
        nu_leaves = jax.tree_util.tree_leaves(adam_st.nu)
        us, mus, nus = [], [], []
        for g, m, v in zip(g_leaves, mu_leaves, nu_leaves):
            shape = tuple(g.shape)
            u1, m1, v1 = _pk.fused_adam_update(
                g.reshape(-1), m.reshape(-1), v.reshape(-1), b1c, b2c,
                lr=lr, b1=b1, b2=b2, eps=eps, eps_root=eps_root)
            us.append(u1.reshape(shape))
            mus.append(m1.reshape(shape))
            nus.append(v1.reshape(shape))
        new_adam = optax.ScaleByAdamState(
            count=count_inc,
            mu=jax.tree_util.tree_unflatten(treedef, mus),
            nu=jax.tree_util.tree_unflatten(treedef, nus),
        )
        return (
            jax.tree_util.tree_unflatten(treedef, us),
            (new_adam,) + tuple(state[1:]),
        )

    return optax.GradientTransformation(init_fn, update_fn)


# --------------------------------------------------------------------------
# ZeRO-1: sharded gradient sync + sharded optimizer state
#
# The reference (and the replicated path above) allreduces every gradient —
# ring cost 2(N-1)/N·B — and redundantly runs the full optimizer update on
# every rank. The sharded path decomposes the exchange (Li et al. 2020 DDP;
# Rajbhandari et al. 2020 ZeRO): flatten the gradient tree into one flat
# buffer per dtype (the `_eager_fused_allreduce_fn` packing discipline),
# pad to the data-axis size, reduce-scatter so each rank owns a 1/N shard
# ((N-1)/N·B gradient bytes — half the allreduce), update only that shard's
# optimizer state (moments HBM drops by N), then all-gather the update
# shards back ((N-1)/N·B parameter bytes).


def _env_true(name: str, default: str = "0") -> bool:
    return os.environ.get(name, default).lower() in ("1", "true", "yes")


def _leaf_dtype(x):
    dt = getattr(x, "dtype", None)
    return jnp.dtype(dt) if dt is not None else jnp.result_type(x)


def _zero_spec(leaves, n: int):
    """Per-dtype flat packing plan: ``{dtype_key: (idxs, sizes, shapes, L,
    Lp)}`` with leaf indices grouped by dtype in first-seen order (the same
    discipline as the eager flat fusion buffer), ``L`` the true packed
    length and ``Lp`` the length padded to a multiple of ``n``."""
    order, groups = [], {}
    for i, leaf in enumerate(leaves):
        k = str(_leaf_dtype(leaf))
        if k not in groups:
            groups[k] = []
            order.append(k)
        groups[k].append(i)
    spec = {}
    for k in order:
        idxs = groups[k]
        shapes = [tuple(getattr(leaves[i], "shape", ())) for i in idxs]
        sizes = [int(np.prod(s, dtype=np.int64)) for s in shapes]
        L = int(sum(sizes))
        Lp = L + ((-L) % n)
        spec[k] = (idxs, sizes, shapes, L, Lp)
    return spec


def _zero_pack(leaves, entry):
    """Flatten + concatenate one dtype group's leaves, zero-padded to Lp."""
    idxs, _, _, L, Lp = entry
    parts = [jnp.ravel(jnp.asarray(leaves[i])) for i in idxs]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if Lp > L:
        flat = jnp.concatenate([flat, jnp.zeros((Lp - L,), flat.dtype)])
    return flat


def _zero_unpack(flat, entry, out_leaves):
    """Split one dtype group's flat buffer back into `out_leaves` slots."""
    idxs, sizes, shapes, _, _ = entry
    off = 0
    for i, size, shape in zip(idxs, sizes, shapes):
        out_leaves[i] = flat[off:off + size].reshape(shape)
        off += size


def _wire_itemsize(dtype, compression) -> int:
    """Bytes per element the wire actually carries for this dtype under
    `compression` (probed on a host scalar — no device op). Legacy
    fallback only: a blockwise or low-rank compressor changes
    bytes-per-LEAF, not bytes-per-element — use :func:`_wire_bytes_leaf`."""
    try:
        c, _ = compression.compress(np.zeros((), dtype=np.dtype(dtype)))
        return int(np.dtype(c.dtype).itemsize)
    except Exception:
        return int(np.dtype(dtype).itemsize)


def _wire_bytes_leaf(shape, dtype, compression) -> int:
    """Wire bytes one leaf costs per transfer direction: the compressor's
    ``wire_bytes(shape, dtype)`` hook when it has one (truthful for
    blockwise scales and rank-r factors), else the scalar-probe itemsize
    times the element count (correct for elementwise casts only)."""
    shape = tuple(shape)
    hook = getattr(compression, "wire_bytes", None)
    if hook is not None:
        try:
            return int(hook(shape, dtype))
        except Exception as e:
            import logging

            logging.getLogger("horovod_tpu").debug(
                "compressor wire_bytes hook failed (%s); falling back to "
                "the itemsize probe", e)
    size = int(np.prod(shape, dtype=np.int64))
    return size * _wire_itemsize(dtype, compression)


def _record_sync_bytes(mode: str, n: int, wire_bytes: int,
                       gather_bytes: Optional[int] = None) -> None:
    """Trace-time gauge of the per-step gradient-sync wire volume under the
    standard ring model: allreduce moves ``2(N-1)/N·B`` gradient bytes,
    the sharded path ``(N-1)/N·B`` (reduce-scatter) plus an all-gather of
    the parameter updates reported separately — gradient bytes halve, the
    total stays ring-equal, and optimizer HBM drops by N."""
    if not _metrics.enabled():
        return
    ring = (n - 1) / n if n > 1 else 0.0
    factor = 2.0 * ring if mode == "allreduce" else ring
    _metrics.gauge(
        "grad_sync_bytes_per_step",
        help="ring-model gradient bytes exchanged per step",
        mode=mode,
    ).set(factor * wire_bytes)
    if gather_bytes is not None:
        _metrics.gauge(
            "param_gather_bytes_per_step",
            help="ring-model parameter/update bytes all-gathered per step "
                 "(sharded optimizer only)",
            mode=mode,
        ).set(ring * gather_bytes)


def _tree_sync_wire_bytes(grads, compression, *, axis=None) -> int:
    """Per-step wire bytes of one gradient exchange direction, priced
    per leaf through the compressor's ``wire_bytes`` hook. With ``axis``
    given, eager stacked ``[N, ...]`` leaves bill their per-rank shape —
    every rank sends ONE contribution, not N."""
    total = 0
    for g in jax.tree_util.tree_leaves(grads):
        shape = tuple(getattr(g, "shape", ()))
        if axis is not None and shape and _C._is_stacked(g, axis):
            shape = shape[1:]
        total += _wire_bytes_leaf(shape, _leaf_dtype(g), compression)
    return total


def _zero_pack_rows(leaves, entry, stacked_flags, n):
    """[N, Lp] matrix of per-rank flat contributions for one dtype group:
    stacked leaves supply their own rows, replicated leaves tile."""
    idxs, sizes, _, L, Lp = entry
    rows = []
    for i, size in zip(idxs, sizes):
        l = jnp.asarray(leaves[i])
        if stacked_flags[i]:
            rows.append(l.reshape(n, size))
        else:
            rows.append(jnp.broadcast_to(l.reshape(1, size), (n, size)))
    m = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    if Lp > L:
        m = jnp.concatenate([m, jnp.zeros((n, Lp - L), m.dtype)], axis=1)
    return m


def _zero_init(optimizer, params, n: int, *, error_feedback: bool,
               compression=None, bucket_bytes: Optional[int] = None):
    """Build the sharded optimizer state: per-dtype flat param buffers are
    padded and reshaped ``[N, shard]``, and the inner optimizer is
    ``jax.vmap``-initialized over the rank axis so EVERY state leaf —
    moments, counts, injected hyperparams — carries a leading rank dim.
    That uniform leading axis is what lets ``shard_map`` step builders spec
    the whole state ``P(data)`` (each rank holds only its own row).
    Factorized (PowerSGD) compression adds the warm-start Q tree, tiled
    ``[N, m, r]`` to keep the leading-axis contract.

    ``bucket_bytes`` (the overlap path) splits the per-dtype buffers into
    the reverse-emission bucket groups, one ``[N, shard_k]`` state buffer
    per bucket (error-feedback residuals keyed by bucket) — the exact
    layout :func:`_zero_update` exchanges per bucket."""
    leaves = jax.tree_util.tree_leaves(params)
    groups = _zero_groups(leaves, n, bucket_bytes)
    shards = {
        k: _ov.pack_group(leaves, g).reshape(n, -1)
        for k, g in groups.items()
    }
    inner = jax.vmap(optimizer.init)(shards)
    if compression is not None and getattr(compression, "factorized", False):
        residual = {
            k: jnp.zeros((n, g.Lp), dtype=jnp.dtype(g.dtype))
            for k, g in groups.items()
        }
        return _PowerSGDState(
            inner, residual, _powersgd_q_init(params, compression, n))
    if error_feedback:
        residual = {
            k: jnp.zeros((n, g.Lp), dtype=jnp.dtype(g.dtype))
            for k, g in groups.items()
        }
        return _EFState(inner, residual)
    return inner


def _maybe_place_sharded(state, ax):
    """Eagerly place a freshly built sharded state with its leading rank dim
    over the data axis, so the ZeRO-1 HBM saving is real from step 0 (and
    donation keeps the layout steady). No-op on tracers / before init."""
    if not basics.is_initialized():
        return state
    try:
        sh = NamedSharding(basics.mesh(), P(ax))
    except Exception:
        return state

    def place(x):
        if _C._is_tracer(x) or not getattr(x, "shape", ()):
            return x
        try:
            return jax.device_put(x, sh)
        except Exception:
            return x

    return jax.tree_util.tree_map(place, state)


def _zero_groups(shape_leaves, n: int, bucket_bytes: Optional[int]):
    """Exchange groups for the sharded update, all in the segment form of
    :mod:`horovod_tpu.ops.overlap`: without ``bucket_bytes`` one
    whole-leaf group per dtype (the monolithic flat packing, keys =
    dtype strings — the historical state layout); with it the
    reverse-emission :class:`~horovod_tpu.ops.overlap.BucketPlan`
    partition (~``bucket_bytes`` per group, leaf splitting allowed, keys
    ``dtype#k``) — one collective per bucket, the overlappable
    schedule."""
    if bucket_bytes:
        return _ov.plan_for(shape_leaves, n, bucket_bytes).groups
    groups = {}
    for k, (idxs, sizes, _shapes, L, Lp) in _zero_spec(
            shape_leaves, n).items():
        segs = tuple(
            _ov.Segment(i, 0, sz) for i, sz in zip(idxs, sizes)
        )
        groups[k] = _ov.Bucket(key=k, dtype=k, segs=segs, L=L, Lp=Lp)
    return groups


def _zero_update(grads, state, params, *, optimizer, compression,
                 error_feedback, op, predivide, ax, roundtrip, extra,
                 bucket_bytes: Optional[int] = None):
    """One sharded (ZeRO-1) update. Three dispatch modes, same math:

    - **bound axis** (inside ``shard_map``): the per-rank hot path —
      flat-pack, ``lax.psum_scatter`` the (compressed) buffer, update this
      rank's shard, ``lax.all_gather`` the update shards back.
    - **traced, unbound** (global jit / pjit): replicated semantics — XLA's
      sharding propagation plus the state's ``[N, shard]`` layout perform
      the reduce-scatter/all-gather placement; the rank axis is vmapped.
    - **eager**: dispatches the real eager ``reducescatter`` collective on
      the packed buffer (stacked ``[N, Lp]`` when error feedback makes the
      per-rank contributions differ), then vmaps the shard updates.

    Quantized (int8) compression swaps the reduce-scatter for the
    overflow-safe int8 ring (:func:`collective.quantized_psum_scatter`:
    int8 + bf16 scales on the wire, f32 accumulation per shard) on the
    f32/f64 dtype groups; integer and 16-bit groups ride uncompressed.
    Factorized (PowerSGD) compression dispatches to
    :func:`_zero_update_powersgd`.

    ``bucket_bytes`` (``DistributedOptimizer(overlap=True)``) swaps the
    per-dtype exchange for one reduce-scatter per reverse-emission
    bucket — each depending only on its own leaves' cotangents, so the
    collectives can launch while the remaining backward still runs —
    with error-feedback residuals keyed by bucket and the update shards
    still returned through a SINGLE trailing all-gather per dtype (the
    gather leg has nothing to overlap with and fuses best whole).
    """
    if getattr(compression, "factorized", False):
        return _zero_update_powersgd(
            grads, state, params, optimizer=optimizer,
            compression=compression, op=op, ax=ax, extra=extra)
    n = _C._axis_size(ax)
    quantized = getattr(compression, "quantized", False)
    qblock = int(getattr(compression, "block", 0) or 0)

    def _wire_rt(x):
        """Per-rank wire contribution of a quantized flat buffer — the
        chunk-aligned int8 roundtrip matching the reduce-scatter layout
        exactly, so EF residuals equal what the ring actually dropped."""
        one = lambda v: quantize_roundtrip_chunked(v, n, qblock)  # noqa: E731
        return one(x) if x.ndim == 1 else jax.vmap(one)(x)

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = jax.tree_util.tree_leaves(params) if params is not None else None
    inner = state.inner if error_feedback else state
    residual = state.residual if error_feedback else None
    traced = any(_C._is_tracer(l) for l in leaves)
    bound = traced and _C._axis_bound(ax)
    # eager per-rank (stacked [N, ...]) gradient leaves contribute their
    # per-rank shape to the packing plan — the update tree is param-shaped
    stacked_flags = [
        (not traced) and _C._is_stacked(l, ax) for l in leaves
    ]

    shape_leaves = [
        jax.ShapeDtypeStruct(tuple(l.shape[1:]), jnp.dtype(l.dtype)) if st
        else jax.ShapeDtypeStruct(
            tuple(getattr(l, "shape", ())), _leaf_dtype(l))
        for l, st in zip(leaves, stacked_flags)
    ]
    groups = _zero_groups(shape_leaves, n, bucket_bytes)

    def _pack_rows(g):
        """[N, Lp] matrix of per-rank flat contributions (eager path)."""
        return _ov.pack_group_rows(leaves, g, stacked_flags, n)

    gshards = {}
    pshards = {} if p_leaves is not None else None
    new_residual = {}
    wire_bytes = 0
    gather_bytes = 0
    idx = _C._flat_axis_index(basics.mesh(), ax) if bound else None

    for key, g in groups.items():
        Lp = g.Lp
        s = Lp // n
        # the quantized ring needs a single named axis for its all_to_all;
        # an axis pair falls back to shipping the roundtripped values
        # through the plain reduce-scatter (same math, modeled wire). A
        # flat buffer below the min-quantize floor rides uncompressed —
        # the per-chunk block padding would cost more than fp32.
        qgroup = (
            quantized and _quantizable(jnp.dtype(g.dtype))
            and Lp >= int(getattr(compression, "min_quant_elems", 0))
        )
        qkernel = qgroup and not isinstance(ax, tuple)
        flat = (
            None
            if any(stacked_flags[i] for i in g.idxs)
            else _ov.pack_group(leaves, g)  # [Lp]
        )
        if bound:
            pre = None
            if error_feedback:
                corrected = flat + residual[key][0]
                if qgroup and qkernel and _pallas_on() and not (
                        op == Average and predivide != 1.0):
                    # fused Pallas path: ONE quantize pass serves both the
                    # EF residual and the all_to_all payload (the wire
                    # image is of `corrected` itself, so reuse is exact;
                    # a predivide would rescale the wire and break it)
                    q_w, sc_w, rt = quantize_chunked(corrected, n, qblock)
                    pre = (q_w, sc_w)
                elif qgroup:
                    rt = _wire_rt(corrected)
                else:
                    rt = roundtrip(corrected)
                new_residual[key] = (corrected - rt)[None]
                send = corrected
            else:
                send = flat
            if op == Average and predivide != 1.0:
                send = send / predivide
            if qkernel:
                with _C._sync_scope("grads"):
                    shard = _C.quantized_psum_scatter(
                        send, ax, block=qblock, pre=pre)
                ctx = None
            else:
                comp, ctx = (
                    (_wire_rt(send), None) if qgroup
                    else compression.compress(send)
                )
                with _C._sync_scope("grads"):
                    shard = lax.psum_scatter(
                        comp, ax, scatter_dimension=0, tiled=True)
            if op == Average and predivide == 1.0:
                shard = _C._div(shard, n)
            if not qgroup:
                shard = compression.decompress(shard, ctx)
            if op == Average and predivide != 1.0:
                shard = shard * (predivide / n)
            gshards[key] = shard[None]
            if p_leaves is not None:
                pflat = _ov.pack_group(p_leaves, g)
                pshards[key] = lax.dynamic_slice(pflat, (idx * s,), (s,))[None]
        elif traced:
            # unbound global-jit: replicated semantics (XLA already placed
            # the cross-chip reduction); model the wire roundtrip exactly
            # as allreduce() does for global values
            if error_feedback:
                corrected = flat[None] + residual[key]       # [N, Lp]
                contrib = (
                    _wire_rt(corrected) if qgroup else roundtrip(corrected)
                )
                new_residual[key] = corrected - contrib
                reduced = (
                    contrib.mean(axis=0) if op == Average
                    else contrib.sum(axis=0)
                )
            else:
                r = _wire_rt(flat) if qgroup else roundtrip(flat)
                reduced = r if op == Average else r * n
            gshards[key] = reduced.reshape(n, s)
            if p_leaves is not None:
                pshards[key] = _ov.pack_group(p_leaves, g).reshape(n, s)
        else:
            # eager: the real reduce-scatter collective on the packed buffer
            per_rank = error_feedback or any(
                stacked_flags[i] for i in g.idxs
            )
            if error_feedback:
                corrected = _pack_rows(g) + residual[key]       # [N, Lp]
                rt = _wire_rt(corrected) if qgroup else roundtrip(corrected)
                new_residual[key] = corrected - rt
                send = corrected
            else:
                send = _pack_rows(g) if per_rank else flat
            if op == Average and predivide != 1.0:
                send = send / predivide
            if qkernel:
                if per_rank:
                    send = jax.device_put(
                        send, NamedSharding(basics.mesh(), P(ax)))
                shard = _C.quantized_reducescatter(
                    send, axis=ax, block=qblock)                # [N, s]
                ctx = None
            else:
                comp, ctx = (
                    (_wire_rt(send), None) if qgroup
                    else compression.compress(send)
                )
                if per_rank:
                    # per-rank rows: dispatch stacked over the data axis
                    comp = jax.device_put(
                        comp, NamedSharding(basics.mesh(), P(ax)))
                shard = _C.reducescatter(comp, Sum, axis=ax)    # [N, s]
            if op == Average and predivide == 1.0:
                shard = _C._div(shard, n)
            if not qgroup:
                shard = compression.decompress(shard, ctx)
            if op == Average and predivide != 1.0:
                shard = shard * (predivide / n)
            gshards[key] = shard
            if p_leaves is not None:
                pshards[key] = _ov.pack_group(p_leaves, g).reshape(n, s)
        wire_bytes += _wire_bytes_leaf(
            (Lp,), jnp.dtype(g.dtype), compression)
        gather_bytes += Lp * jnp.dtype(g.dtype).itemsize

    if error_feedback:
        for key, g in groups.items():
            new_residual[key] = new_residual[key].astype(jnp.dtype(g.dtype))

    # fence the vmapped optimizer into a self-contained fusion island:
    # with identical inputs its HLO (and therefore XLA's rounding — fma
    # vs separate mul/add) is the same in every program that embeds it,
    # which is what lets the ZeRO-3 step (optim._fsdp_update, fencing the
    # same subgraph the same way) pin its trajectory bit-identical to
    # this one
    if p_leaves is not None:
        def upd(g, st, p):
            return optimizer.update(g, st, p, **extra)

        gshards, inner, pshards = lax.optimization_barrier(
            (gshards, inner, pshards))
        upd_shards, new_inner = jax.vmap(upd)(gshards, inner, pshards)
    else:
        def upd(g, st):
            return optimizer.update(g, st, **extra)

        gshards, inner = lax.optimization_barrier((gshards, inner))
        upd_shards, new_inner = jax.vmap(upd)(gshards, inner)
    upd_shards, new_inner = lax.optimization_barrier(
        (upd_shards, new_inner))

    # gather leg: ONE trailing all-gather per dtype — the bucketed path
    # concatenates this rank's per-bucket update shards first (the gather
    # has nothing left to overlap with, and one fused transfer beats K),
    # then re-slices the gathered [N, sum(s_k)] blocks back per bucket
    full_flats = {}
    if bound:
        by_dtype: dict = {}
        for key, g in groups.items():
            by_dtype.setdefault(g.dtype, []).append(key)
        for keys in by_dtype.values():
            cats = [upd_shards[k][0] for k in keys]
            cat = cats[0] if len(cats) == 1 else jnp.concatenate(cats)
            S = cat.shape[0]
            with _C._sync_scope("updates"):
                gat = lax.all_gather(
                    cat, ax, axis=0, tiled=True).reshape(n, S)
            off = 0
            for k in keys:
                s_k = groups[k].Lp // n
                full_flats[k] = (
                    gat[:, off:off + s_k].reshape(-1)[:groups[k].L]
                )
                off += s_k
    else:
        for key, g in groups.items():
            full_flats[key] = upd_shards[key].reshape(-1)[:g.L]
    out_leaves = _ov.assemble(
        full_flats, groups,
        [s.shape for s in shape_leaves],
        [s.dtype for s in shape_leaves],
    )
    updates = jax.tree_util.tree_unflatten(treedef, out_leaves)

    _record_sync_bytes("sharded", n, wire_bytes, gather_bytes)
    _ov._record_buckets("sharded", len(groups))
    new_state = (
        _EFState(new_inner, new_residual) if error_feedback else new_inner
    )
    return updates, new_state


def _zero_update_powersgd(grads, state, params, *, optimizer, compression,
                          op, ax, extra):
    """ZeRO-1 update under PowerSGD: every >=2-D float leaf syncs only its
    rank-r P/Q factors (allreduce of two small matrices), 1-D float leaves
    ride the int8 wire, integer/16-bit leaves ride uncompressed — after
    which the MEAN gradient is known replicated, so each rank slices its
    own flat shard with no further collective, vmaps the shard update, and
    all-gathers the update shards exactly like :func:`_zero_update`.

    Error feedback stays in the per-dtype flat ``[N, Lp]`` residual
    packing (``residual_i = corrected_i - approx_mean`` for factorized
    leaves; the int8 wire roundtrip for fallback leaves), so the
    mass-preserving reshard path is unchanged.
    """
    n = _C._axis_size(ax)
    fallback = getattr(compression, "fallback", Int8Compressor)
    block = int(getattr(compression, "block", 0) or 0)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = jax.tree_util.tree_leaves(params) if params is not None else None
    inner, residual, q_tree = state.inner, state.residual, state.q
    q_leaves = _q_leaves(q_tree)
    traced = any(_C._is_tracer(l) for l in leaves)
    bound = traced and _C._axis_bound(ax)
    stacked_flags = [
        (not traced) and _C._is_stacked(l, ax) for l in leaves
    ]

    shapes = [
        tuple(l.shape[1:]) if st else tuple(getattr(l, "shape", ()))
        for l, st in zip(leaves, stacked_flags)
    ]
    spec = _zero_spec(
        [jax.ShapeDtypeStruct(s, _leaf_dtype(l))
         for s, l in zip(shapes, leaves)], n)

    # 1. per-rank corrected leaves: bound mode unpacks this rank's
    # corrected flat buffer; the others carry a leading rank axis [N, ...]
    corrected = [None] * len(leaves)
    for key, entry in spec.items():
        if bound:
            cflat = _zero_pack(leaves, entry) + residual[key][0]
            _zero_unpack(cflat, entry, corrected)
        else:
            rows = (
                _zero_pack_rows(leaves, entry, stacked_flags, n)
                + residual[key]
            )  # [N, Lp]
            off = 0
            for i, size, shape in zip(entry[0], entry[1], entry[2]):
                corrected[i] = rows[:, off:off + size].reshape((n,) + shape)
                off += size

    def _reduce_mean_bound(x):
        return _sync_allreduce(x, Average, axis=ax)

    # 2. per-leaf sync: factorized / int8 fallback / uncompressed
    reduced = [None] * len(leaves)
    res_leaves = [None] * len(leaves)
    new_q = [None] * len(leaves)
    wire_bytes = 0
    for i, (c, shape) in enumerate(zip(corrected, shapes)):
        dt = _leaf_dtype(leaves[i])
        wire_bytes += _wire_bytes_leaf(shape, dt, compression)
        if q_leaves[i] is not None:
            qmat = q_leaves[i][0]  # strip the (identical-rows) rank axis
            if bound:
                m2d = c.reshape(shape[0], -1)
                approx, qn = _psgd_factor_sync(m2d, qmat, _reduce_mean_bound)
                res_leaves[i] = (m2d - approx).reshape(shape)
                red = approx.reshape(shape)
                new_q[i] = qn[None]
            else:
                m2d = c.mean(axis=0).reshape(shape[0], -1)
                approx, qn = _psgd_factor_sync(m2d, qmat, lambda x: x)
                red = approx.reshape(shape)
                res_leaves[i] = c - red[None]
                new_q[i] = jnp.broadcast_to(qn[None], (n,) + qn.shape)
            reduced[i] = red * n if op == Sum else red
        elif _quantizable(dt):
            if bound:
                rt = int8_roundtrip(c, block)
                res_leaves[i] = c - rt
                reduced[i] = _sync_allreduce(
                    c, op, axis=ax, compression=fallback)
            else:
                rt = jax.vmap(lambda v: int8_roundtrip(v, block))(c)
                res_leaves[i] = c - rt
                red = rt.mean(axis=0)
                reduced[i] = red * n if op == Sum else red
        else:
            res_leaves[i] = jnp.zeros_like(c)
            if bound:
                reduced[i] = _sync_allreduce(c, op, axis=ax)
            else:
                red = c.sum(axis=0) if op == Sum else _C._div(c.sum(axis=0), n)
                reduced[i] = red.astype(dt)

    # 3. repack: the reduced tree is fully known (replicated), so shards
    # are slices — no further gradient collective
    gshards = {}
    pshards = {} if p_leaves is not None else None
    new_residual = {}
    gather_bytes = 0
    idx = _C._flat_axis_index(basics.mesh(), ax) if bound else None
    all_stacked = [True] * len(leaves)
    for key, entry in spec.items():
        Lp = entry[4]
        s = Lp // n
        red_flat = _zero_pack(reduced, entry)                   # [Lp]
        if bound:
            gshards[key] = lax.dynamic_slice(red_flat, (idx * s,), (s,))[None]
            new_residual[key] = _zero_pack(res_leaves, entry)[None]
            if p_leaves is not None:
                pflat = _zero_pack(p_leaves, entry)
                pshards[key] = lax.dynamic_slice(pflat, (idx * s,), (s,))[None]
        else:
            gshards[key] = red_flat.reshape(n, s)
            new_residual[key] = _zero_pack_rows(
                res_leaves, entry, all_stacked, n)              # [N, Lp]
            if p_leaves is not None:
                pshards[key] = _zero_pack(p_leaves, entry).reshape(n, s)
        new_residual[key] = new_residual[key].astype(jnp.dtype(key))
        gather_bytes += Lp * jnp.dtype(key).itemsize

    if p_leaves is not None:
        def upd(g, st, p):
            return optimizer.update(g, st, p, **extra)

        upd_shards, new_inner = jax.vmap(upd)(gshards, inner, pshards)
    else:
        def upd(g, st):
            return optimizer.update(g, st, **extra)

        upd_shards, new_inner = jax.vmap(upd)(gshards, inner)

    out_leaves = [None] * len(leaves)
    for key, entry in spec.items():
        L = entry[3]
        if bound:
            with _C._sync_scope("updates"):
                full = lax.all_gather(
                    upd_shards[key][0], ax, axis=0, tiled=True)
        else:
            full = upd_shards[key].reshape(-1)
        _zero_unpack(full[:L], entry, out_leaves)
    updates = jax.tree_util.tree_unflatten(treedef, out_leaves)

    # P/Q (and the int8-fallback leaves) ride full ring ALLREDUCES, i.e.
    # 2(N-1)/N per wire byte where _record_sync_bytes' sharded mode prices
    # (N-1)/N — double the wire sum so the gauge stays truthful
    _record_sync_bytes("sharded", n, 2 * wire_bytes, gather_bytes)
    new_state = _PowerSGDState(
        new_inner, new_residual,
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(q_tree, is_leaf=_q_is_leaf), new_q),
    )
    return updates, new_state


# --------------------------------------------------------------------------
# ZeRO-3 (FSDP): parameter shards + gather-on-use
#
# ZeRO-1 (above) shards gradients and optimizer state but keeps a full
# parameter replica on every chip. ZeRO-3 shards the parameters themselves
# in the SAME per-bucket flat [N, shard] packing (the segment-group
# machinery of ops/overlap.py): the step re-materializes the full tree with
# one all-gather per bucket just before the forward consumes it, discards
# it (``jax.checkpoint`` re-gathers in the backward), and the gradient
# arrives back as shards for free — the autodiff transpose of a tiled
# ``all_gather`` IS the tiled ``psum_scatter``, so differentiating through
# the gather performs the per-bucket gradient reduce-scatter ZeRO-1 issues
# explicitly, bit for bit. No code path duplicates the exchange: ZeRO-3 is
# a pack/gather stage over the ZeRO-1 group plan, and the vmapped shard
# update below is ZeRO-1's own.

FSDP_WIRE_ENV = "HOROVOD_FSDP_WIRE"


def _fsdp_wire() -> str:
    """Resolve the parameter-gather wire format (``HOROVOD_FSDP_WIRE``):
    ``none`` (full-precision gather) or ``int8`` (blockwise int8 + bf16
    scales — :func:`collective.quantized_all_gather`). Read at trace
    time; the SAME resolution prices the ``param_gather_bytes_per_step``
    gauge, so the model and the wire can never disagree."""
    wire = os.environ.get(FSDP_WIRE_ENV, "none").lower()
    if wire not in ("none", "int8"):
        raise ValueError(
            f"{FSDP_WIRE_ENV} must be 'none' or 'int8', got {wire!r}")
    return wire


class _FsdpMeta(NamedTuple):
    """Static (hashable) half of :class:`FsdpParams`: everything needed to
    re-derive the group plan and re-assemble the original tree."""
    treedef: Any
    shapes: tuple
    dtypes: tuple
    axis: Any
    bucket_bytes: Optional[int]


class FsdpParams:
    """ZeRO-3 parameter shards: ``{group_key: [N, shard]}`` flat buffers in
    the ZeRO-1 packing (per-dtype groups, or ``dtype#k`` bucket groups
    under ``bucket_bytes``) plus the static metadata to re-assemble the
    tree. Registered as a pytree node, so ``jax.grad`` w.r.t. one returns
    gradient shards of the same type, ``optax.apply_updates`` applies
    update shards shard-wise, and ``shard_map`` specs the whole thing
    ``P(axis)`` as a pytree prefix. Build with :func:`fsdp_pack_params`;
    re-materialize with :func:`fsdp_gather_params` (in-step, collective)
    or :func:`fsdp_unpack_params` (host-side)."""

    __slots__ = ("shards", "meta")

    def __init__(self, shards: dict, meta: _FsdpMeta):
        self.shards = dict(shards)
        self.meta = meta

    @property
    def num_shards(self) -> int:
        return next(iter(self.shards.values())).shape[0]

    def __repr__(self):
        return (f"FsdpParams(groups={sorted(self.shards)}, "
                f"axis={self.meta.axis!r})")


def _fsdp_flatten(fp):
    keys = tuple(sorted(fp.shards))
    return [fp.shards[k] for k in keys], (keys, fp.meta)


def _fsdp_unflatten(aux, children):
    keys, meta = aux
    return FsdpParams(dict(zip(keys, children)), meta)


jax.tree_util.register_pytree_node(FsdpParams, _fsdp_flatten, _fsdp_unflatten)


def _fsdp_groups(meta: _FsdpMeta, n: int):
    """Re-derive the exchange-group plan from the pack metadata. Group
    boundaries depend only on the leaf shapes and ``bucket_bytes`` — never
    on the world size (only the ``Lp`` padding does) — which is what makes
    :func:`fsdp_reshard_params` a pure re-pad."""
    shape_leaves = [
        jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
        for s, d in zip(meta.shapes, meta.dtypes)
    ]
    return _zero_groups(shape_leaves, n, meta.bucket_bytes)


def fsdp_pack_params(params, *, axis=None, bucket_bytes: Optional[int] = None):
    """Pack a parameter tree into ZeRO-3 shards (:class:`FsdpParams`).

    The flat packing is byte-identical to :func:`_zero_init`'s state
    layout (same ``_zero_groups`` plan), so
    ``DistributedOptimizer(shard_params=True).init(fp)`` produces
    optimizer state bit-identical to the ZeRO-1 state for the same tree —
    and :func:`reshard_optimizer_state` re-packs both with one plan.
    ``bucket_bytes`` sets the gather granularity (the overlap unit of the
    gather-on-use schedule); default is one group per dtype. The shard
    rows are eagerly placed ``P(axis)`` so the HBM saving is real from
    step 0."""
    ax = _C._axis(axis)
    n = _C._axis_size(ax)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    meta = _FsdpMeta(
        treedef=treedef,
        shapes=tuple(tuple(getattr(l, "shape", ())) for l in leaves),
        dtypes=tuple(str(_leaf_dtype(l)) for l in leaves),
        axis=ax,
        bucket_bytes=bucket_bytes,
    )
    groups = _fsdp_groups(meta, n)
    shards = {
        k: _ov.pack_group(leaves, g).reshape(n, -1)
        for k, g in groups.items()
    }
    return _maybe_place_sharded(FsdpParams(shards, meta), ax)


def fsdp_unpack_params(fp: FsdpParams):
    """Re-assemble the full parameter tree from ZeRO-3 shards, host-side
    (checkpoint consolidation, eval, publishing). Inside a traced step use
    :func:`fsdp_gather_params` — the collective gather-on-use leg."""
    n = fp.num_shards
    groups = _fsdp_groups(fp.meta, n)
    flats = {
        k: jnp.asarray(fp.shards[k]).reshape(-1)[:g.L]
        for k, g in groups.items()
    }
    leaves = _ov.assemble(
        flats, groups, [tuple(s) for s in fp.meta.shapes],
        [jnp.dtype(d) for d in fp.meta.dtypes],
    )
    return jax.tree_util.tree_unflatten(fp.meta.treedef, leaves)


def fsdp_gather_params(fp: FsdpParams, *, wire: Optional[str] = None):
    """The gather-on-use leg: re-materialize the full parameter tree from
    shards with ONE all-gather per group, issue-order pinned.

    Inside ``shard_map`` (bound axis) each group's ``[s]`` shard rides a
    tiled ``lax.all_gather`` — routed through the hierarchical ICI/DCN
    composition for a ``(cross, local)`` axis pair, or the int8 wire
    (``HOROVOD_FSDP_WIRE=int8`` /
    :func:`collective.quantized_all_gather`) for quantizable groups —
    then unpadded and re-assembled. Consecutive gathers are barrier-
    chained (``HOROVOD_OVERLAP_BARRIER``, default on) so every schedule
    issues them in pack order: the forward consumes bucket k while bucket
    k+1's gather is still in flight. Under ``jax.checkpoint`` the
    backward re-gathers instead of holding the gathered tree — the ZeRO-3
    memory deal — and the gather's transpose reduce-scatters the gradient
    shards back with no extra code.

    Unbound (global jit / eager) the shards are replicated ``[N, s]``
    rows: re-assembly is a reshape, with the int8 wire modeled as a
    per-row roundtrip so traced-unbound values match the bound wire."""
    from horovod_tpu.compression import (
        INT8_BLOCK, MIN_QUANT_ELEMS, dequantize_blockwise,
        quantize_blockwise,
    )

    meta = fp.meta
    ax = meta.axis
    vals = list(fp.shards.values())
    traced = any(_C._is_tracer(v) for v in vals)
    bound = traced and _C._axis_bound(ax)
    n = _C._axis_size(ax) if bound else fp.num_shards
    groups = _fsdp_groups(meta, n)
    if wire is None:
        wire = _fsdp_wire()

    def _roundtrip_row(row):
        q, sc = quantize_blockwise(row, INT8_BLOCK)
        return dequantize_blockwise(
            q, sc, row.dtype, INT8_BLOCK)[:row.shape[0]]

    keys, fulls = [], []
    for key, g in groups.items():
        qgroup = (
            wire == "int8" and _quantizable(jnp.dtype(g.dtype))
            and g.Lp >= MIN_QUANT_ELEMS
        )
        if bound:
            local = fp.shards[key][0]                          # [s]
            with _C._sync_scope("params"):
                if qgroup and not isinstance(ax, tuple):
                    full = _C.quantized_all_gather(
                        local, ax, block=INT8_BLOCK)
                else:
                    if qgroup:
                        # axis pair (hierarchical): the quantized kernel
                        # needs a single named axis — ship the
                        # roundtripped values through the routed gather
                        # (same math, modeled wire)
                        local = _roundtrip_row(local)
                    full = _C.allgather(local, axis=ax)        # [n*s]
        else:
            rows = jnp.asarray(fp.shards[key])                 # [N, s]
            if qgroup:
                rows = jax.vmap(_roundtrip_row)(rows)
            full = rows.reshape(-1)
        keys.append(key)
        fulls.append(full)
    if bound and len(fulls) > 1 and _ov.barrier_enabled():
        fulls = _ov.chain_barriers(fulls)
    flats = {k: f[:groups[k].L] for k, f in zip(keys, fulls)}
    leaves = _ov.assemble(
        flats, groups, [tuple(s) for s in meta.shapes],
        [jnp.dtype(d) for d in meta.dtypes],
    )
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def _fsdp_gather_wire_bytes(groups, n: int, wire: str) -> int:
    """Wire image of ONE parameter all-gather: fp32 groups move their full
    padded length; int8 groups move each rank's block-padded shard as int8
    plus one bf16 scale per block, times N ranks. The analytic twin is
    :func:`tools.scaling_projection.fsdp_gather_wire_bytes` — a test pins
    them equal."""
    from horovod_tpu.compression import (
        INT8_BLOCK, MIN_QUANT_ELEMS, _SCALE_BYTES,
    )

    total = 0
    for g in groups.values():
        dt = jnp.dtype(g.dtype)
        if (wire == "int8" and _quantizable(dt)
                and g.Lp >= MIN_QUANT_ELEMS):
            s = g.Lp // n
            sp = s + ((-s) % INT8_BLOCK)
            total += n * (sp + (sp // INT8_BLOCK) * _SCALE_BYTES)
        else:
            total += g.Lp * dt.itemsize
    return total


def _fsdp_update(grads, state, params, *, optimizer, op, ax, extra):
    """One ZeRO-3 update. The gradient already arrived REDUCED: inside
    ``shard_map`` the gather's transpose emitted
    ``psum_scatter(pack(local_grads))`` — the SUM over ranks of each
    rank's packed gradient shard, exactly the buffer ZeRO-1's explicit
    reduce-scatter produces — so this function only divides for Average,
    vmaps the inner update over the rank axis, and returns the update
    shards AS SHARDS (no trailing all-gather: the parameters stay
    sharded; the next step's gather-on-use sees ``shards + updates``,
    and gather distributes over the elementwise add, which is the whole
    bit-identity argument vs ZeRO-1)."""
    if not isinstance(grads, FsdpParams):
        raise TypeError(
            "DistributedOptimizer(shard_params=True) updates FsdpParams "
            "gradient shards — differentiate the loss w.r.t. the packed "
            "params from fsdp_pack_params (the gather's transpose returns "
            f"shards), got {type(grads).__name__}"
        )
    meta = grads.meta
    vals = list(grads.shards.values())
    traced = any(_C._is_tracer(v) for v in vals)
    bound = traced and _C._axis_bound(ax)
    n = _C._axis_size(ax) if bound else grads.num_shards
    groups = _fsdp_groups(meta, n)

    gshards = dict(grads.shards)
    if bound:
        if op == Average:
            gshards = {k: _C._div(v, n) for k, v in gshards.items()}
    elif op == Sum:
        # unbound/eager replicated semantics: every rank would contribute
        # the same global gradient (mirrors _zero_update's unbound mode)
        gshards = {k: v * n for k, v in gshards.items()}

    grad_wire = sum(
        g.Lp * jnp.dtype(g.dtype).itemsize for g in groups.values()
    )
    # the gather traces a data-dependent number of times under
    # jax.checkpoint (forward + backward re-gather), so the gauges are
    # recorded HERE, once per step: the gather leg bills 2x — its wire
    # runs twice per step by construction
    gather_wire = _fsdp_gather_wire_bytes(groups, n, _fsdp_wire())
    _record_sync_bytes("zero3", n, grad_wire, 2 * gather_wire)
    _ov._record_buckets("zero3", len(groups))

    # the same fusion fence as _zero_update around the same vmapped
    # subgraph: identical inputs → identical self-contained HLO →
    # identical XLA rounding (fma/rsqrt choices), the compiled half of
    # the ZeRO-3-vs-ZeRO-1 bit-identity argument
    pshards = params.shards if isinstance(params, FsdpParams) else None
    if pshards is not None:
        def upd(g, st, p):
            return optimizer.update(g, st, p, **extra)

        gshards, state, pshards = lax.optimization_barrier(
            (gshards, state, pshards))
        upd_shards, new_inner = jax.vmap(upd)(gshards, state, pshards)
    else:
        def upd(g, st):
            return optimizer.update(g, st, **extra)

        gshards, state = lax.optimization_barrier((gshards, state))
        upd_shards, new_inner = jax.vmap(upd)(gshards, state)
    upd_shards, new_inner = lax.optimization_barrier(
        (upd_shards, new_inner))
    if bound:
        # Materialization fence for the caller's `p + u` apply add. The
        # XLA CPU backend contracts the inner optimizer's trailing
        # `-lr * x` multiply into the consumer's add (a single-rounding
        # fma) even across optimization_barrier, which would put the new
        # params 1 ulp off ZeRO-1 — whose updates cross a real
        # all_gather and therefore materialize before the add. An
        # identity ppermute (every rank sends to itself: zero
        # cross-device bytes, so it is not billed to the sync gauges)
        # forces the update shards to materialize the same way,
        # completing the bitwise-equality argument.
        perm = [(i, i) for i in range(n)]
        upd_shards = {
            k: lax.ppermute(v, ax, perm) for k, v in upd_shards.items()
        }
    return FsdpParams(upd_shards, meta), new_inner


def fsdp_reshard_params(fp: FsdpParams, *, to_size: Optional[int] = None):
    """Re-pack ZeRO-3 parameter shards for a different world size (the
    parameter half of the elastic/checkpoint consolidation;
    :func:`reshard_optimizer_state` handles the state half and accepts
    the SAME :class:`FsdpParams` as its ``params`` argument). Group
    boundaries are world-size independent, so this is unpad-to-``L`` →
    re-pad for ``to_size`` → reshape ``[N', shard']`` per group — no
    collective, no device math."""
    n_new = int(to_size) if to_size is not None else basics.size()
    n_old = fp.num_shards
    if n_old == n_new:
        return fp
    old_groups = _fsdp_groups(fp.meta, n_old)
    new_groups = _fsdp_groups(fp.meta, n_new)
    shards = {}
    for k, g_new in new_groups.items():
        g_old = old_groups[k]
        flat = jnp.asarray(fp.shards[k]).reshape(-1)[:g_old.L]
        if g_new.Lp > g_new.L:
            flat = jnp.concatenate(
                [flat, jnp.zeros((g_new.Lp - g_new.L,), flat.dtype)])
        shards[k] = flat.reshape(n_new, -1)
    return _maybe_place_sharded(FsdpParams(shards, fp.meta), fp.meta.axis)


def reshard_optimizer_state(state, params, *, to_size: Optional[int] = None,
                            axis=None, bucket_bytes: Optional[int] = None):
    """Re-pack a sharded (ZeRO-1) optimizer state for a different data-axis
    size — the restore-side consolidation step after a world-size change.

    Two callers: checkpoint restore onto a differently-sized job
    (:func:`horovod_tpu.checkpoint.consolidate_opt_state`), and the elastic
    coordinator's *live* generation change
    (:mod:`horovod_tpu.resilience.elastic`), which calls this between mesh
    re-formation and the rebuilt step function's first replayed step.

    ``checkpoint.save`` persists the *consolidated* ``[N_old, shard]``
    arrays (rank 0 holds the addressable global view); on restore to
    ``to_size`` ranks (default: the current :func:`horovod_tpu.size`), each
    2-D leaf is unpadded back to its true flat length (derived from
    ``params`` — the same tree the state was initialized from), re-padded
    for the new size, and reshaped ``[N_new, shard']``. Per-rank vmapped
    scalars (e.g. Adam's ``count``, shape ``[N_old]``) are re-tiled from
    row 0; error-feedback residual buffers (``[N_old, Lp_old]``) are
    mass-preserving: the old per-rank residuals are summed — the total
    untransmitted gradient mass — and spread evenly over the new ranks.
    Leaves without a leading rank dim pass through untouched.

    Bucketed (overlap) states — dict keys ``dtype#k`` from
    ``DistributedOptimizer(overlap=True)`` — reshard too: the bucket
    boundaries depend only on the leaf shapes and the bucket size (never
    on the world size), so the plan is re-derived from ``params`` and
    ``bucket_bytes`` (default: the ``HOROVOD_BUCKET_BYTES`` /
    ``HOROVOD_FUSION_THRESHOLD`` env resolution — reshard with the same
    knob the state was trained with; a mismatch raises instead of
    silently mis-slicing)."""
    from horovod_tpu.resilience import numerics as _numerics

    if isinstance(state, _numerics.NumericsGuardState):
        # numerics-guard wrapper: re-pack the inner (possibly sharded)
        # state; the guard's EWMA/loss-scale scalars are replicated and
        # world-size independent, so they ride through untouched. The
        # per-rank fingerprint vector is diagnostic, one step deep —
        # re-init it at the new size rather than inventing values for
        # ranks that have not stepped yet.
        n = int(to_size) if to_size is not None else basics.size()
        rank_norms = state.rank_norms
        if getattr(rank_norms, "shape", (0,)) != (n,):
            rank_norms = jnp.zeros((n,), jnp.float32)
        return state._replace(
            inner=reshard_optimizer_state(
                state.inner, params, to_size=to_size, axis=axis,
                bucket_bytes=bucket_bytes),
            rank_norms=rank_norms,
        )
    if isinstance(params, FsdpParams):
        # ZeRO-3: the pack metadata carries the leaf shapes AND the bucket
        # granularity the state was laid out with — reshard with the same
        # plan, no live param tree needed (reshard the shards themselves
        # with fsdp_reshard_params)
        if bucket_bytes is None:
            bucket_bytes = params.meta.bucket_bytes
        params = [
            jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
            for s, d in zip(params.meta.shapes, params.meta.dtypes)
        ]
    n_new = int(to_size) if to_size is not None else basics.size()
    ax = _C._axis(axis) if basics.is_initialized() else axis
    leaves = jax.tree_util.tree_leaves(params)
    # the true flat length per dtype group is n-independent (padding is not)
    lengths = {k: e[3] for k, e in _zero_spec(leaves, max(n_new, 1)).items()}
    is_ef = isinstance(state, (_EFState, _PowerSGDState))
    inner = state.inner if is_ef else state

    def _dict_str_keys(tree) -> set:
        keys: set = set()
        stack = [tree]
        while stack:
            node = stack.pop()
            if isinstance(node, dict):
                for k, v in node.items():
                    if isinstance(k, str):
                        keys.add(k)
                    stack.append(v)
            elif isinstance(node, (list, tuple)):  # NamedTuples included
                stack.extend(node)
        return keys

    def _is_bucket_key(k) -> bool:
        """Exactly the generated `dtype#index` form — a user param tree
        whose names merely contain '#' must NOT trip bucket handling
        (reshard stays safe on arbitrary plain states)."""
        if not isinstance(k, str) or "#" not in k:
            return False
        dt, _, idx = k.rpartition("#")
        if not idx.isdigit():
            return False
        try:
            jnp.dtype(dt)
        except TypeError:
            return False
        return True

    # bucketed (overlap) states carry `dtype#k` group keys: re-derive the
    # bucket plan (boundaries are n-independent) and validate the keys
    group_keys = {k for k in _dict_str_keys(inner) if _is_bucket_key(k)}
    if is_ef and isinstance(state.residual, dict):
        group_keys |= {
            k for k in state.residual if _is_bucket_key(k)
        }
    if group_keys:
        plan = _ov.plan_for(
            leaves, max(n_new, 1),
            bucket_bytes or _ov.bucket_bytes_from_env())
        exact = {b.key: b.L for b in plan.buckets}
        unknown = sorted(group_keys - set(exact))

        def _bucket_mismatch(detail):
            raise ValueError(
                "bucketed (overlap) optimizer state does not match the "
                f"re-derived BucketPlan ({detail}); reshard with the "
                "SAME HOROVOD_BUCKET_BYTES (or pass bucket_bytes=) the "
                "state was trained with"
            )

        if unknown:
            _bucket_mismatch(f"unknown bucket keys {unknown}")
        # a plan rebuilt with the wrong bucket size can still COVER the
        # state's keys (fewer, larger buckets subset finer ones) — pin
        # every bucket-keyed 2-D buffer's row length to the re-derived
        # bucket's padded length (residuals: Lp; shard buffers: Lp/n)
        for tree in (inner, state.residual if is_ef else None):
            if tree is None:
                continue
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                gk = [
                    getattr(p, "key", None) for p in path
                    if _is_bucket_key(getattr(p, "key", None))
                ]
                if not gk or getattr(leaf, "ndim", 0) != 2:
                    continue
                L = exact[gk[-1]]
                rows = leaf.shape[0]
                Lp_old = L + ((-L) % rows)
                if leaf.shape[1] not in (Lp_old, Lp_old // rows):
                    _bucket_mismatch(
                        f"buffer {gk[-1]} has row length {leaf.shape[1]}, "
                        f"expected {Lp_old} or {Lp_old // rows}")
        cands: dict = {}
        for b in plan.buckets:
            cands.setdefault(b.dtype, []).append(b.L)
    else:
        exact = dict(lengths)
        cands = {dt: [L] for dt, L in lengths.items()}

    def _match_shard(x) -> Optional[tuple]:
        """(n_old, L) when `x` is a [n_old, shard] flat buffer of one of
        this param tree's packing groups, else None."""
        shape = tuple(getattr(x, "shape", ()))
        if len(shape) != 2:
            return None
        n_old, s_old = shape
        if n_old < 1:
            return None
        matches = [
            L for L in cands.get(str(_leaf_dtype(x)), ())
            if n_old * s_old == L + ((-L) % n_old)
        ]
        if not matches:
            return None
        unpadded = [L for L in matches if L == n_old * s_old]
        return n_old, (unpadded[0] if unpadded else max(matches))

    # Infer the source world size from the actual shard buffers. A state
    # with none is not a sharded state from this param tree — pass it
    # through untouched (consolidate_opt_state must be safe on plain
    # optimizer states, whose 1-D moment leaves would otherwise be
    # misread as per-rank vmapped scalars).
    olds = {
        m[0] for m in (
            _match_shard(x) for x in jax.tree_util.tree_leaves(inner)
        ) if m is not None
    }
    if not olds and is_ef \
            and isinstance(state.residual, dict) and state.residual:
        # stateless inner (e.g. plain sgd): the sharded signature lives in
        # the residual dict — group-string keys, [n_old, pad(L, n_old)]
        # rows. A replicated-path _EFState carries a param-tree residual
        # instead and never matches.
        if all(
            isinstance(k, str) and k in exact
            and getattr(v, "ndim", 0) == 2 and v.shape[0] >= 1
            and v.shape[1] == exact[k] + ((-exact[k]) % v.shape[0])
            for k, v in state.residual.items()
        ):
            olds = {v.shape[0] for v in state.residual.values()}
    if not olds:
        return state
    n_old_global = max(olds)
    if n_old_global == n_new and len(olds) == 1:
        return state  # same world size: a strict no-op, residuals included

    def _repad(flat, L):
        Lp_new = L + ((-L) % n_new)
        if Lp_new > L:
            flat = jnp.concatenate(
                [flat, jnp.zeros((Lp_new - L,), flat.dtype)])
        return flat

    def _path_group_key(path) -> Optional[str]:
        """The innermost dict key along `path` that names a packing
        group — authoritative for the buffer's true length, where the
        shape-based `_match_shard` can be ambiguous (a tail bucket whose
        ZeRO padding makes it the same padded size as a sibling)."""
        key = None
        for p in path:
            k = getattr(p, "key", None)
            if isinstance(k, str) and k in exact:
                key = k
        return key

    def one(path, x):
        shape = tuple(getattr(x, "shape", ()))
        gk = _path_group_key(path)
        if gk is not None and len(shape) == 2 and shape[0] >= 1:
            L = exact[gk]
            n_old = shape[0]
            if n_old * shape[1] == L + ((-L) % n_old):
                if n_old == n_new:
                    return x
                flat = jnp.asarray(x).reshape(-1)[:L]
                return _repad(flat, L).reshape(n_new, -1)
        m = _match_shard(x)
        if m is not None:
            n_old, L = m
            if n_old == n_new:
                return x
            flat = jnp.asarray(x).reshape(-1)[:L]
            return _repad(flat, L).reshape(n_new, -1)
        if len(shape) == 1 and shape[0] == n_old_global:
            # per-rank vmapped scalar (identical across ranks by
            # construction, e.g. Adam's count): re-tile from row 0
            if shape[0] == n_new:
                return x
            return jnp.broadcast_to(jnp.asarray(x)[0], (n_new,))
        return x

    def one_residual(x, key=None):
        # [n_old, Lp_old] per-rank full residuals: the summed rows are the
        # total untransmitted gradient mass; spread it evenly so the next
        # steps transmit exactly what the old ranks still owed
        L = exact.get(key, lengths.get(str(_leaf_dtype(x)), x.shape[1]))
        total = jnp.asarray(x).sum(axis=0)[:L] / n_new
        return jnp.broadcast_to(_repad(total, L), (n_new, L + ((-L) % n_new)))

    def one_q(x):
        # warm-start Q factors, tiled [n_old, m, r] with identical rows
        # (each comes out of an allreduce): re-tile row 0 for the new size
        if x is None:
            return None
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] == n_old_global:
            if x.shape[0] == n_new:
                return x
            return jnp.broadcast_to(jnp.asarray(x)[0], (n_new,) + x.shape[1:])
        return x

    if isinstance(state, _PowerSGDState):
        out = _PowerSGDState(
            jax.tree_util.tree_map_with_path(one, state.inner),
            {k: one_residual(v, k) for k, v in state.residual.items()},
            jax.tree_util.tree_map(one_q, state.q, is_leaf=_q_is_leaf),
        )
    elif isinstance(state, _EFState):
        out = _EFState(
            jax.tree_util.tree_map_with_path(one, state.inner),
            {k: one_residual(v, k) for k, v in state.residual.items()},
        )
    else:
        out = jax.tree_util.tree_map_with_path(one, state)
    return _maybe_place_sharded(out, ax) if basics.is_initialized() else out


def _powersgd_update(grads, state, params, *, optimizer, compression, op,
                     ax, extra):
    """Replicated-state PowerSGD update (the non-ZeRO path): every >=2-D
    float leaf syncs rank-r P/Q factors with warm-started Q and the EF
    residual in the param-tree layout; 1-D float leaves ride the int8
    wire; integer/16-bit leaves pass through uncompressed. Works in all
    three dispatch modes of the plain optimizer: bound (inside shard_map —
    explicit P/Q allreduces), traced-unbound (replicated semantics), and
    eager (stacked ``[N, ...]`` or replicated leaves)."""
    n = _C._axis_size(ax)
    fallback = getattr(compression, "fallback", Int8Compressor)
    block = int(getattr(compression, "block", 0) or 0)
    inner, residual, q_tree = state.inner, state.residual, state.q
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    r_leaves = jax.tree_util.tree_flatten(residual)[0]
    q_leaves = _q_leaves(q_tree)
    traced = any(_C._is_tracer(g) for g in g_leaves)
    bound = traced and _C._axis_bound(ax)

    reduced = [None] * len(g_leaves)
    new_res = [None] * len(g_leaves)
    new_q = [None] * len(g_leaves)
    wire_bytes = 0
    for i, g in enumerate(g_leaves):
        dt = _leaf_dtype(g)
        stacked = (not traced) and _C._is_stacked(g, ax)
        shape = tuple(g.shape[1:]) if stacked else tuple(
            getattr(g, "shape", ()))
        c = jnp.asarray(g) + r_leaves[i]
        # the residual itself may carry the per-rank axis after an earlier
        # stacked eager step; detect the layout on the corrected value
        per_rank = (
            not bound
            and getattr(c, "ndim", 0) == len(shape) + 1
            and c.shape[0] == n
            and tuple(c.shape[1:]) == shape
        )
        wire_bytes += _wire_bytes_leaf(shape, dt, compression)
        if q_leaves[i] is not None:
            qmat = q_leaves[i]
            if bound:
                m2d = c.reshape(shape[0], -1)
                approx, qn = _psgd_factor_sync(
                    m2d, qmat,
                    lambda x: _sync_allreduce(x, Average, axis=ax))
                new_res[i] = (m2d - approx).reshape(shape)
                red = approx.reshape(shape)
            else:
                m2d = (c.mean(axis=0) if per_rank else c).reshape(
                    shape[0], -1)
                approx, qn = _psgd_factor_sync(m2d, qmat, lambda x: x)
                red = approx.reshape(shape)
                new_res[i] = c - (red[None] if per_rank else red)
            new_q[i] = qn
            reduced[i] = red * n if op == Sum else red
        elif _quantizable(dt):
            if bound:
                rt = int8_roundtrip(c, block)
                new_res[i] = c - rt
                reduced[i] = _sync_allreduce(
                    c, op, axis=ax, compression=fallback)
            else:
                if per_rank:
                    rt = jax.vmap(lambda v: int8_roundtrip(v, block))(c)
                    red = rt.mean(axis=0)
                else:
                    rt = int8_roundtrip(c, block)
                    red = rt
                new_res[i] = c - rt
                reduced[i] = red * n if op == Sum else red
        else:
            new_res[i] = jnp.zeros_like(c)
            if bound:
                reduced[i] = _sync_allreduce(c, op, axis=ax)
            elif per_rank:
                red = c.sum(axis=0) if op == Sum else _C._div(c.sum(axis=0), n)
                reduced[i] = red.astype(dt)
            else:
                reduced[i] = c * n if op == Sum else c

    if basics.is_initialized():
        _record_sync_bytes("allreduce", n, wire_bytes)
    reduced_tree = jax.tree_util.tree_unflatten(treedef, reduced)
    updates, new_inner = optimizer.update(reduced_tree, inner, params, **extra)
    return updates, _PowerSGDState(
        new_inner,
        jax.tree_util.tree_unflatten(treedef, new_res),
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(q_tree, is_leaf=_q_is_leaf), new_q),
    )


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: ReduceOp = Average,
    compression=None,
    backward_passes_per_step: int = 1,
    axis: Optional[str] = None,
    gradient_predivide_factor: float = 1.0,
    error_feedback: bool = False,
    shard_optimizer: Optional[bool] = None,
    shard_params: Optional[bool] = None,
    overlap: Optional[bool] = None,
    bucket_bytes: Optional[int] = None,
    numerics_guard: Optional[bool] = None,
    loss_scale=None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so each ``update`` first allreduces gradients
    across ranks (reference ``_DistributedOptimizer.compute_gradients``,
    ``tensorflow/__init__.py:270-315``; torch hook-based variant
    ``torch/__init__.py:67-222``).

    ``backward_passes_per_step > 1`` accumulates that many gradient
    applications locally before communicating (reference
    ``torch/__init__.py:72-96``) via ``optax.MultiSteps``.

    ``gradient_predivide_factor`` splits the averaging divisor between
    pre/post-scale as the reference does for numerical headroom
    (upstream semantics: pre-divide by f, post-divide by size/f).

    ``compression`` defaults to the env spelling
    (``HOROVOD_COMPRESSION=none|fp16|int8|powersgd``) when not passed.
    Beyond fp16, ``Compression.int8`` rides the overflow-safe quantized
    ring (int8 + bf16 blockwise scales on the wire, f32 accumulation) and
    ``Compression.powersgd(r)`` syncs only rank-r P/Q factors per >=2-D
    leaf with the warm-started Q carried in this optimizer's state
    (requires ``error_feedback=True``; 1-D leaves fall back to int8).

    ``error_feedback=True`` (beyond the reference; EF-SGD, Karimireddy et
    al. 2019) makes lossy ``compression`` convergence-safe: each rank keeps
    the rounding error the compressor discarded and adds it back into the
    next step's gradient, so systematic bias (components smaller than a
    bfloat16 ULP vanishing every step) accumulates until it transmits
    instead of being lost. All elementwise — XLA fuses it into the step.
    Requires a lossy compressor; pair with Average/Sum (Adasum's scalar
    projections would mix into the residual bookkeeping).

    ``shard_optimizer=True`` (env ``HOROVOD_SHARD_OPTIMIZER=1``) switches
    the exchange to the ZeRO-1 decomposition: the gradient tree is
    flat-packed per dtype, reduce-scattered so each rank owns a 1/N shard,
    the inner update runs on only that shard's moments, and the update
    shards are all-gathered back — gradient-sync bytes halve
    (``(N-1)/N·B`` vs the allreduce ring's ``2(N-1)/N·B``) and
    optimizer-state HBM drops by N. The state pytree changes shape: every
    leaf carries a leading rank axis (``init`` on 8 ranks gives Adam
    moments ``[8, ceil(P/8)]`` per dtype). Use with
    ``make_shardmap_train_step(..., shard_optimizer=True)`` (which specs
    the state ``P(data)``), plain global jit (the layout does the
    sharding), or eagerly. Single-controller SPMD only; composes with
    ``compression`` and ``error_feedback`` (residuals ride the same flat
    packing); not with ``op=Adasum``.

    ``shard_params=True`` (env ``HOROVOD_SHARD_PARAMS=1``) is the ZeRO-3
    extension of ``shard_optimizer``: the PARAMETERS are sharded too.
    ``init`` takes the packed shards from :func:`fsdp_pack_params`
    (raising on a plain tree) and builds the same ``[N, shard]`` state
    layout as ZeRO-1; ``update`` takes :class:`FsdpParams` gradient
    shards — produced for free by differentiating the loss through
    :func:`fsdp_gather_params` (the gather's transpose reduce-scatters)
    — divides for ``Average``, vmaps the inner update per shard, and
    returns update shards with NO trailing all-gather: params stay
    sharded, and the next step's gather-on-use re-materializes them
    (``make_shardmap_train_step(shard_params=True)`` wires all of this).
    Per-chip param + optimizer HBM both drop by N; the wire cost is the
    per-step parameter gather, twice (forward + the ``jax.checkpoint``
    backward re-gather) — ``HOROVOD_FSDP_WIRE=int8`` quantizes that leg.
    The gradient leg is exact by construction, so gradient
    ``compression``/``error_feedback`` are rejected (nothing lossy to
    feed back); ``op`` must be Average/Sum and the numerics guard does
    not compose yet (its global-norm reduction assumes full gradients).
    ``bucket_bytes`` must match the value given to ``fsdp_pack_params``
    — the pack defines the exchange granularity.

    ``overlap=True`` (env ``HOROVOD_OVERLAP=1``; implied by
    ``bucket_bytes=``) switches the gradient exchange to **bucketed
    backward-pass sync** — the reference's fusion-buffer overlap trick,
    TPU-native: the flat per-dtype packing is partitioned into
    ~``bucket_bytes`` (``HOROVOD_BUCKET_BYTES``, default 64 MB, honoring
    ``HOROVOD_FUSION_THRESHOLD``) buckets in reverse-topological
    (backprop-emission) order, and ONE collective is issued per bucket
    instead of one per tree/dtype. Each bucket's
    ``psum``/``psum_scatter`` depends only on its own leaves'
    cotangents, so XLA's latency-hiding scheduler (pin the flags with
    :func:`horovod_tpu.tuning.apply_xla_flags`) launches it while the
    remaining backward still runs — step time approaches
    ``max(compute, comm)`` instead of ``compute + comm``. Composes with
    ``shard_optimizer=True`` (per-bucket reduce-scatter, state buffers
    ``[N, shard_k]`` per bucket, a single trailing all-gather per dtype)
    and the fp16/int8 wire formats (per-bucket compress; error-feedback
    residuals keyed by bucket). Trajectories are bit-identical to the
    monolithic path for none/fp16 (packing is a permutation and the
    elementwise wire commutes with it); int8's blockwise scales are
    layout-dependent, so that wire tracks within one quantization step
    per element (EF keeps it convergence-safe). Not with ``op=Adasum``
    or PowerSGD (per-tensor/per-leaf math that bucket packing would
    mix).

    ``numerics_guard=True`` (env ``HOROVOD_NUMERICS_GUARD=1``; implied by
    ``loss_scale``) wraps the whole optimizer in the in-jit numerics
    guard (:func:`horovod_tpu.resilience.numerics.guard`): every step's
    gradient finiteness + EWMA global-norm spike verdict is computed in
    one fused reduction inside the step, and a BAD step's update —
    moments, EF residuals, PowerSGD ``Q`` warm-starts — is discarded
    atomically. ``loss_scale`` enables dynamic bf16/fp16 loss scaling
    (``"dynamic"`` or an initial float; grow/backoff carried in the guard
    state). The ``make_*_train_step`` builders detect the guard and
    thread the loss + scale automatically.
    """
    if shard_optimizer is None:
        shard_optimizer = _env_true("HOROVOD_SHARD_OPTIMIZER")
    if shard_params is None:
        shard_params = _env_true("HOROVOD_SHARD_PARAMS")
    ov_bytes = _ov.resolve_bucket_bytes(overlap, bucket_bytes)
    if compression is None:
        # unset -> the env spelling (HOROVOD_COMPRESSION=fp16|int8|powersgd)
        compression = Compression.from_env()
        if getattr(compression, "factorized", False) and not error_feedback:
            # the env knob must work on call sites that never opted into
            # compression kwargs: env-resolved PowerSGD implies the error
            # feedback it cannot converge without
            error_feedback = True
    factorized = getattr(compression, "factorized", False)
    quantized = getattr(compression, "quantized", False)
    if shard_params:
        if op not in (Average, Sum):
            raise ValueError(
                "shard_params=True (ZeRO-3) supports op=Average/Sum only "
                "(Adasum's pairwise projections have no reduce-scatter "
                "formulation)"
            )
        if compression is not Compression.none:
            raise ValueError(
                "gradient compression does not compose with "
                "shard_params=True: the ZeRO-3 gradient leg is the "
                "parameter gather's transpose — exact full precision by "
                "construction. Compress the parameter GATHER instead "
                "(HOROVOD_FSDP_WIRE=int8)"
            )
        if error_feedback:
            raise ValueError(
                "error_feedback needs a lossy gradient wire; the ZeRO-3 "
                "gradient leg is exact (see shard_params). The int8 "
                "GATHER wire perturbs only forward parameter values — "
                "there is no gradient rounding to feed back"
            )
        if gradient_predivide_factor != 1.0:
            raise ValueError(
                "gradient_predivide_factor is not supported with "
                "shard_params=True (the reduced shards arrive through "
                "the gather transpose; there is no pre-wire scale point)"
            )
    if factorized and not error_feedback:
        raise ValueError(
            "PowerSGD compression is biased low-rank truncation; it is "
            "only convergence-safe with error_feedback=True (EF-SGD, "
            "Karimireddy et al. 2019)"
        )
    if factorized and op not in (Average, Sum):
        raise ValueError("PowerSGD compression supports op=Average/Sum only")
    if (factorized or quantized) and gradient_predivide_factor != 1.0:
        raise ValueError(
            "gradient_predivide_factor is a headroom trick for plain "
            "16-bit casts; blockwise int8 scaling / PowerSGD factors "
            "normalize per block and do not support it"
        )
    if error_feedback and compression is Compression.none:
        raise ValueError(
            "error_feedback=True needs a lossy compression "
            "(e.g. Compression.fp16); with Compression.none there is no "
            "rounding error to feed back"
        )
    if error_feedback and op == Adasum:
        raise ValueError("error_feedback is not supported with op=Adasum")
    if quantized and op == Adasum:
        raise ValueError(
            "quantized compression is not supported with op=Adasum (the "
            "scalar projections have no low-bit reduction formulation)"
        )
    if shard_optimizer and op == Adasum:
        raise ValueError(
            "shard_optimizer=True is not supported with op=Adasum (the "
            "pairwise projections have no reduce-scatter formulation)"
        )
    if ov_bytes and factorized:
        raise ValueError(
            "overlap/bucket_bytes is not supported with PowerSGD "
            "compression: the rank-r P/Q factors are per-leaf matrices "
            "that bucket packing would mix; use the int8/fp16 wire with "
            "overlap, or PowerSGD without it"
        )
    if ov_bytes and op == Adasum:
        raise ValueError(
            "overlap/bucket_bytes is not supported with op=Adasum (the "
            "pairwise projections are per-tensor scalars; bucket packing "
            "would mix them)"
        )

    @_C._sync_scope("grads")
    def _allreduce_grads(grads):
        if op == Adasum and compression is Compression.none:
            return _fused_adasum_tree(grads, axis)

        def one(g):
            if op == Average and gradient_predivide_factor != 1.0:
                g = g / gradient_predivide_factor
                out = allreduce(g, Sum, axis=axis, compression=compression)
                return out * (gradient_predivide_factor / basics.size())
            return allreduce(g, op, axis=axis, compression=compression)

        if op != Adasum and basics.is_initialized():
            ax = _C._axis(axis)
            _record_sync_bytes(
                "allreduce", _C._axis_size(ax),
                _tree_sync_wire_bytes(grads, compression, axis=ax),
            )
        return jax.tree_util.tree_map(one, grads)

    def _roundtrip(g):
        """The value g effectively contributes through the wire. With a
        predivide the wire carries compress(g/f) (scaled back by f at the
        receiver), so the residual must be measured against THAT — rounding
        introduced by the divide is exactly the bias EF exists to track."""
        if op == Average and gradient_predivide_factor != 1.0:
            c, ctx = compression.compress(g / gradient_predivide_factor)
            return compression.decompress(c, ctx) * gradient_predivide_factor
        c, ctx = compression.compress(g)
        return compression.decompress(c, ctx)

    def init_fn(params):
        if shard_params:
            if not isinstance(params, FsdpParams):
                raise TypeError(
                    "DistributedOptimizer(shard_params=True).init expects "
                    "the packed FsdpParams shards — build them with "
                    "fsdp_pack_params(params) (and gather back with "
                    "fsdp_unpack_params)"
                )
            if ov_bytes and params.meta.bucket_bytes != ov_bytes:
                raise ValueError(
                    "bucket_bytes mismatch: params were packed with "
                    f"bucket_bytes={params.meta.bucket_bytes} but this "
                    f"optimizer resolved {ov_bytes}; pass the same value "
                    "to fsdp_pack_params — the pack defines the exchange "
                    "granularity"
                )
            state = jax.vmap(optimizer.init)(params.shards)
            return _maybe_place_sharded(state, _C._axis(axis))
        if shard_optimizer:
            ax = _C._axis(axis)
            state = _zero_init(
                optimizer, params, _C._axis_size(ax),
                error_feedback=error_feedback,
                compression=compression if factorized else None,
                bucket_bytes=ov_bytes,
            )
            return _maybe_place_sharded(state, ax)
        inner = optimizer.init(params)
        if factorized:
            residual = jax.tree_util.tree_map(jax.numpy.zeros_like, params)
            return _PowerSGDState(
                inner, residual, _powersgd_q_init(params, compression))
        if error_feedback:
            if ov_bytes:
                # overlap: error-feedback residuals keyed by bucket — the
                # flat layout each bucket's wire roundtrip is measured in
                plan = _ov.plan_for(
                    jax.tree_util.tree_leaves(params), 1, ov_bytes)
                residual = {
                    b.key: jnp.zeros((b.L,), dtype=jnp.dtype(b.dtype))
                    for b in plan.buckets
                }
            else:
                residual = jax.tree_util.tree_map(
                    jax.numpy.zeros_like, params)
            return _EFState(inner, residual)
        return inner

    def update_fn(grads, state, params=None, **extra):
        if shard_params:
            return _fsdp_update(
                grads, state, params,
                optimizer=optimizer, op=op, ax=_C._axis(axis), extra=extra,
            )
        if shard_optimizer:
            return _zero_update(
                grads, state, params,
                optimizer=optimizer, compression=compression,
                error_feedback=error_feedback, op=op,
                predivide=gradient_predivide_factor, ax=_C._axis(axis),
                roundtrip=_roundtrip, extra=extra,
                bucket_bytes=ov_bytes,
            )
        if factorized:
            return _powersgd_update(
                grads, state, params, optimizer=optimizer,
                compression=compression, op=op, ax=_C._axis(axis),
                extra=extra,
            )
        if ov_bytes:
            # non-sharded overlap: K bucket allreduces (reverse emission
            # order), each depending only on its own leaves' cotangents;
            # EF residuals ride the bucket-keyed flat layout
            reduced, new_res = _ov.bucketed_allreduce(
                grads, op, axis=axis, compression=compression,
                bucket_bytes=ov_bytes,
                predivide=gradient_predivide_factor,
                residual=state.residual if error_feedback else None,
                roundtrip=_roundtrip,
            )
            if error_feedback:
                updates, inner = optimizer.update(
                    reduced, state.inner, params, **extra)
                return updates, _EFState(inner, new_res)
            return optimizer.update(reduced, state, params, **extra)
        if error_feedback:
            corrected = jax.tree_util.tree_map(
                lambda g, r: g + r, grads, state.residual
            )
            # residual = what the wire will round away; the allreduce below
            # compresses `corrected` itself (single compression pass), which
            # is exactly the transform _roundtrip models
            residual = jax.tree_util.tree_map(
                lambda c: c - _roundtrip(c), corrected
            )
            reduced = _allreduce_grads(corrected)
            updates, inner = optimizer.update(
                reduced, state.inner, params, **extra
            )
            return updates, _EFState(inner, residual)
        grads = _allreduce_grads(grads)
        return optimizer.update(grads, state, params, **extra)

    tx = optax.GradientTransformationExtraArgs(init_fn, update_fn)
    if backward_passes_per_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
    if numerics_guard is None:
        numerics_guard = (
            _env_true("HOROVOD_NUMERICS_GUARD") or loss_scale is not None
        )
    elif not numerics_guard and loss_scale is not None:
        raise ValueError(
            "loss_scale is carried in the numerics guard's state (the "
            "guard unscales the gradients and backs the scale off on bad "
            "steps); numerics_guard=False with loss_scale set would "
            "silently train UNSCALED — drop loss_scale or the explicit "
            "numerics_guard=False"
        )
    if numerics_guard and shard_params:
        raise ValueError(
            "numerics_guard does not compose with shard_params=True yet: "
            "the guard's fused global-norm/finiteness reduction assumes "
            "full (or ZeRO-1 replicated) gradients, and per-rank verdicts "
            "over FsdpParams shards could diverge. Guard ZeRO-1 "
            "(shard_optimizer=True) instead, or train ZeRO-3 unguarded"
        )
    if numerics_guard:
        # outermost, so a BAD verdict freezes EVERYTHING this optimizer
        # owns — inner moments, EF residuals, PowerSGD Q, MultiSteps
        # accumulators — in one atomic where-select
        from horovod_tpu.resilience import numerics as _numerics

        tx = _numerics.guard(tx, loss_scale=loss_scale, axis=axis)
    return tx


class DistributedGradientTape:
    """Analog of ``hvd.DistributedGradientTape`` (reference
    ``tensorflow/__init__.py:478-535``): wraps a gradient-producing function
    (e.g. ``jax.grad(loss)`` or ``jax.value_and_grad(loss)``) so its gradients
    are allreduced.

    Example::

        tape = hvd.DistributedGradientTape(jax.value_and_grad(loss_fn))
        (loss, grads) = tape(params, batch)   # grads are rank-averaged
    """

    def __init__(
        self,
        grad_fn: Callable,
        *,
        op: ReduceOp = Average,
        compression=Compression.none,
        axis: Optional[str] = None,
        has_aux_value: Optional[bool] = None,
    ):
        self._fn = grad_fn
        self._op = op
        self._compression = compression
        self._axis = axis
        self._has_aux_value = has_aux_value

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        has_value = self._has_aux_value
        if has_value is None:
            # value_and_grad returns (scalar_loss, grads). Require the first
            # element to actually look like a scalar loss so a 2-tuple of
            # gradients (jax.grad with argnums=(0, 1)) is not misclassified;
            # pass has_aux_value explicitly for ambiguous cases.
            has_value = (
                isinstance(out, tuple)
                and len(out) == 2
                and not isinstance(out[0], (list, dict))
                and getattr(out[0], "ndim", None) == 0
            )
        if has_value:
            value, grads = out
        else:
            grads = out
        if self._op == Adasum and self._compression is Compression.none:
            grads = _fused_adasum_tree(grads, self._axis)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: _sync_allreduce(
                    g, self._op, axis=self._axis,
                    compression=self._compression,
                ),
                grads,
            )
        self._record(grads)
        return (value, grads) if has_value else grads

    @staticmethod
    def _record(grads):
        """Per-step accounting for the tape path. Eager calls only: under
        jit this __call__ body runs once at trace time, so recording there
        would freeze a single count into the compiled step."""
        if not _metrics.enabled():
            return
        leaves = jax.tree_util.tree_leaves(grads)
        if any(isinstance(g, jax.core.Tracer) for g in leaves):
            return
        _metrics.counter(
            "tape_steps", help="DistributedGradientTape gradient exchanges"
        ).inc()
        _metrics.counter(
            "tape_grad_bytes", help="gradient bytes exchanged by the tape"
        ).inc(sum(getattr(g, "nbytes", 0) or 0 for g in leaves))


@_profiler.annotate("hvd.broadcast_parameters", record=True)
def broadcast_parameters(params: Any, root_rank: int = 0, *, axis=None):
    """Broadcast a pytree of parameters from root (reference
    ``torch/__init__.py:451-469``, ``tensorflow/__init__.py:126-152``
    ``broadcast_variables``). Under single-controller SPMD parameters are
    born synchronized; this is the multi-process resync primitive and the
    checkpoint-restore pattern (SURVEY.md §5.4)."""
    _metrics.counter(
        "broadcast_parameters_calls",
        help="parameter-tree broadcasts (init sync / checkpoint restore)",
    ).inc()
    return jax.tree_util.tree_map(
        lambda p: broadcast(p, root_rank, axis=axis)
        if isinstance(p, (jax.Array,)) or hasattr(p, "dtype")
        else broadcast_object(p, root_rank),
        params,
    )


broadcast_variables = broadcast_parameters


def is_sharded_state_leaf(x, *, axis=None) -> bool:
    """Is `x` a ZeRO-1 sharded optimizer-state leaf (leading rank dim laid
    out over the data axis)? Such leaves are per-rank data: broadcasting
    root's value over them would blow each rank's 1/N moment shard back up
    to root's copy and destroy the sharding."""
    ax = _C._axis(axis)
    return _C._is_stacked(x, ax)


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0, *, axis=None):
    """Broadcast optimizer state (reference ``torch/__init__.py:471-607``:
    scalars are wrapped into tensors and broadcast; here the optax state is
    already a pytree of arrays/scalars).

    Leaves sharded over the data axis (ZeRO-1 moment shards, see
    ``DistributedOptimizer(shard_optimizer=True)``) are detected and left
    in place: each rank's shard IS its own authoritative state, and
    stuffing root's row into every rank would both corrupt the other
    ranks' moments and re-replicate the very state the sharding un-replicated.
    """
    ax = _C._axis(axis)
    skipped = [0]

    def one(x):
        if is_sharded_state_leaf(x, axis=ax):
            skipped[0] += 1
            return x
        if isinstance(x, (jax.Array,)) or hasattr(x, "dtype"):
            return broadcast(x, root_rank, axis=ax)
        return broadcast_object(x, root_rank)

    out = jax.tree_util.tree_map(one, opt_state)
    if skipped[0]:
        _metrics.counter(
            "broadcast_optimizer_state_sharded_skipped",
            help="ZeRO-1 sharded state leaves left un-broadcast",
        ).inc(skipped[0])
    return out
