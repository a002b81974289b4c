"""XLA-native collective ops.

Semantics follow Horovod 0.19.2's op layer (reference
``horovod/tensorflow/mpi_ops.py:104-201``, ``horovod/torch/mpi_ops.py:94-524``,
dispatch in ``horovod/common/ops/``), but execution is pure XLA:

- **in-jit path** — inside a ``shard_map``/``pjit`` region the ops are thin
  wrappers over ``lax.psum``/``lax.all_gather``/``lax.all_to_all`` on the named
  mesh axis. This is the hot path: XLA fuses, schedules, and overlaps the
  collectives with compute (the role NCCL streams + the fusion buffer play in
  the reference, ``nccl_operations.cc:109-159``).
- **eager path** — on concrete ``jax.Array``s we compile (and cache) a tiny
  ``shard_map`` program per (op, shape, dtype). Dispatch is asynchronous, so the
  returned array doubles as Horovod's async handle: ``synchronize`` is
  ``block_until_ready`` (the reference's handle manager + finalizer-thread
  machinery, ``torch/handle_manager.cc``, ``gpu_operations.h:101-112``, is
  subsumed by XLA's async runtime).

Per-rank values in the eager single-controller world are represented as a
*stacked* leading rank axis sharded over the data axis (shape ``[size, ...]``);
arrays without that sharding are treated as replicated (every rank holds the
same tensor), which matches running the same program on every Horovod rank.
"""

from __future__ import annotations

import enum
import functools
import os
import pickle
import threading
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from horovod_tpu import basics
from horovod_tpu.analysis import sanitizer as _sanitizer
from horovod_tpu.observability import (
    flight as _flight,
    metrics as _metrics,
    straggler as _straggler,
    trace as _trace,
)
from horovod_tpu.resilience import chaos as _chaos, retry as _retry


class ReduceOp(enum.IntEnum):
    """Reduction ops (reference ``horovod_reduce_op_{average,sum,adasum}``,
    ``common/operations.cc:770-799``)."""

    AVERAGE = 0
    SUM = 1
    ADASUM = 2


Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM


class Handle:
    """Async-op handle (reference ``torch/handle_manager.{h,cc}``; poll/wait
    semantics ``torch/mpi_ops.py:475-524``). JAX dispatch is already async, so
    the handle just owns the in-flight arrays and its registered name."""

    __slots__ = ("_values", "_name")

    def __init__(self, values, name=None):
        self._values = values if isinstance(values, (list, tuple)) else [values]
        self._name = name

    def done(self) -> bool:
        return all(_array_ready(v) for v in self._values)

    def wait(self, timeout=None):
        """Block until the op completes and return its value(s).

        ``timeout`` exists for signature parity with ``CoreHandle.wait`` but
        is NOT enforced on this path: XLA's ``block_until_ready`` has no
        interruptible form, so the call blocks until completion regardless.
        Callers relying on the timeout for stall detection get a one-time
        warning so the silent divergence is visible.
        """
        if timeout is not None:
            warnings.warn(
                "Handle.wait(timeout=...) is not enforced on the XLA path "
                "(block_until_ready is uninterruptible); the call blocks "
                "until completion. Attach the native core for bounded waits.",
                RuntimeWarning,
                stacklevel=2,
            )
        for v in self._values:
            v.block_until_ready()
        _release_name(self._name)
        if len(self._values) == 1:
            return self._values[0]
        return list(self._values)


_outstanding_lock = threading.Lock()
_outstanding_names = set()


def _register_name(name: Optional[str]):
    """Duplicate outstanding names are an error, as in the reference
    (``DUPLICATE_NAME_ERROR``, ``common/common.h:161-164``)."""
    if name is None:
        return
    with _outstanding_lock:
        if name in _outstanding_names:
            raise ValueError(
                f"Duplicate tensor name '{name}' in outstanding collective; "
                "synchronize the previous op first (reference DUPLICATE_NAME_ERROR)."
            )
        _outstanding_names.add(name)


def _release_name(name: Optional[str]):
    if name is None:
        return
    with _outstanding_lock:
        _outstanding_names.discard(name)


def _async(op_fn, name):
    """Register `name`, run the op, and release the name if the op itself
    fails (otherwise the name would be poisoned forever)."""
    _register_name(name)
    try:
        out = op_fn()
    except BaseException:
        _release_name(name)
        raise
    return Handle(out, name=name)


def _array_ready(v) -> bool:
    try:
        return v.is_ready()
    except AttributeError:  # pragma: no cover
        return True


def synchronize(handle: Handle):
    """Block until the handle's op completed and return its output
    (reference ``torch/mpi_ops.py:491-508``)."""
    return handle.wait()


def poll(handle: Handle) -> bool:
    """Nonblocking completion check (reference ``torch/mpi_ops.py:475-489``)."""
    return handle.done()


def join() -> int:
    """Uneven-data join (reference ``torch/mpi_ops.py:511-524``,
    ``controller.cc:219-307``): a joined rank keeps participating in the
    other ranks' collectives with zero contributions until every rank joins;
    returns the last rank to join.

    With the native core attached this blocks on the controller's JOIN
    response while the background cycle zero-backfills negotiated reductions
    (``core.py::_execute_backfilled``). Under single-controller SPMD every
    chip executes the same program, so there is no raggedness to repair and
    join degenerates to a no-op returning ``rank()``."""
    basics._require_init()
    core = basics._state.core
    if core is not None:
        from horovod_tpu.core import JOIN_TENSOR_NAME, REQUEST_JOIN

        h = core.enqueue(
            JOIN_TENSOR_NAME, np.zeros((0,), np.float32), REQUEST_JOIN
        )
        return int(h.wait())
    return basics.rank()


# --------------------------------------------------------------------------
# helpers


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _sync_scope(part: str):
    """``hvd.sync/<part>``: the scope of an explicit exchange inside a
    compiled step (``grads``, ``stats``, ``loss``; ZeRO's ``params`` and
    ``updates`` gathers). What runs under it is the ``sync`` phase of
    :func:`horovod_tpu.profiler.scope_of`."""
    return jax.named_scope(f"hvd.sync/{part}")


def _jit_scoped(fn):
    """The in-jit branch of a public collective runs under ``hvd.<op>``
    (``/<name>`` where the caller gave ``name=``), so a user's own
    ``hvd.allreduce`` under ``jit`` is named in the device trace. A scope
    is HLO metadata: no jaxpr equation, nothing at run time. The eager
    branch pays one ``isinstance``."""
    scope = "hvd." + fn.__name__

    @functools.wraps(fn)
    def scoped(tensor, *args, **kwargs):
        tensors = tensor if isinstance(tensor, (list, tuple)) else (tensor,)
        if not any(map(_is_tracer, tensors)):
            return fn(tensor, *args, **kwargs)
        name = kwargs.get("name")
        with jax.named_scope(f"{scope}/{name}" if name else scope):
            return fn(tensor, *args, **kwargs)

    return scoped


def _hier_enabled() -> bool:
    from horovod_tpu.ops import hierarchical

    return hierarchical.enabled()


def _hier_allgather_enabled() -> bool:
    from horovod_tpu.ops import hierarchical

    return hierarchical.allgather_enabled()


def _axis_bound(ax) -> bool:
    """True iff `ax` is a bound collective axis in the current trace (i.e. we
    are inside a shard_map/pmap region over it). Outside such a region a traced
    value is *global*: under jit + input sharding XLA inserts the cross-chip
    reductions itself, so collectives degrade to their replicated semantics
    (the TPU-native analog of Horovod's single-rank degenerate mode)."""
    if isinstance(ax, tuple):
        return all(_axis_bound(a) for a in ax)
    try:
        lax.axis_index(ax)
        return True
    except NameError:
        return False


def _axis(axis):
    """Normalize the axis arg: default data axis, lists → tuples. A 2-tuple
    ``(cross, local)`` selects the host-hierarchy pair (see
    :mod:`horovod_tpu.ops.hierarchical`)."""
    if axis is None:
        return basics.data_axis()
    return tuple(axis) if isinstance(axis, (list, tuple)) else axis


def _mesh_axis_size(mesh, axis) -> int:
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _axis_size(axis) -> int:
    return _mesh_axis_size(basics.mesh(), axis)


def _hostlocal_mode(x) -> bool:
    """True iff we are multi-process and `x` is this process's host-local
    contribution (the Horovod per-worker model) rather than a global array."""
    from horovod_tpu.ops import hostlocal

    return basics.process_size() > 1 and not hostlocal.is_global_array(x)


def _named_sharding(x) -> Optional[NamedSharding]:
    """x's ``NamedSharding``, or None: for a NumPy array, a scalar, another
    kind of sharding, and a tracer. A tracer is answered by its type — its
    ``.sharding`` raises, and JAX builds that error by walking every
    equation traced so far, so a probe per leaf grew with the program."""
    if _is_tracer(x):
        return None
    sharding = getattr(x, "sharding", None)
    return sharding if isinstance(sharding, NamedSharding) else None


def _is_stacked(x, axis) -> bool:
    """True iff x's leading dim is the per-rank axis sharded over `axis`
    (any member of it, for a multi-axis tuple)."""
    sharding = _named_sharding(x)
    if sharding is None:
        return False
    spec = sharding.spec
    if not spec or spec[0] is None:
        return False
    first = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
    axes = axis if isinstance(axis, tuple) else (axis,)
    return any(a in first for a in axes)


def _as_array(x):
    if isinstance(x, (jnp.ndarray, jax.Array)):
        return x
    return jnp.asarray(np.asarray(x))


def _div(x, n):
    if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
        return (x / n).astype(x.dtype)
    return x / jnp.asarray(n, dtype=x.dtype)



def _smap(fn, mesh, in_specs, out_specs):
    """shard_map with the static replication check disabled: collectives like
    all_gather/ppermute produce values the checker cannot prove replicated."""
    return _shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

# --------------------------------------------------------------------------
# compiled eager kernels (cached per mesh/shape/dtype/op)

#: XLA:CPU's in-process communicator rendezvouses per-device partition
#: threads with NO ordering across concurrently-launched programs: two
#: collective programs in flight (e.g. the core's cycle thread + a user
#: thread's eager hostlocal op) can each capture part of the thread pool and
#: abort on the fixed rendezvous timeout. On CPU every eager collective
#: launch therefore serializes through this lock and completes before the
#: next starts. TPU orders launches on the per-device stream — no wrapping.
_cpu_collective_lock = threading.Lock()


def _flat_axis_index(mesh, axis):
    """Row-major rank within `axis` (a name or a tuple of names) — the
    in-shard_map analog of the flattened data-axis coordinate."""
    if not isinstance(axis, tuple):
        return lax.axis_index(axis)
    idx = lax.axis_index(axis[0])
    for a in axis[1:]:
        idx = idx * mesh.shape[a] + lax.axis_index(a)
    return idx


def _cpu_serialized(jitfn):
    if jax.default_backend() != "cpu":
        return jitfn

    def locked(*args):
        with _cpu_collective_lock:
            out = jitfn(*args)
            jax.block_until_ready(out)
            return out

    return locked


#: substrings marking an eager-dispatch failure as transient — deliberately
#: narrow: only the XLA:CPU in-process communicator's rendezvous-abort
#: class (surfaces as DEADLINE_EXCEEDED mentioning the rendezvous), where a
#: re-dispatch genuinely succeeds. Broad markers like UNAVAILABLE/CANCELLED
#: retried permanent failures (device loss, interpreter shutdown) and
#: delayed their surfacing.
_TRANSIENT_DISPATCH_MARKERS = (
    "deadline exceeded",
    "deadline_exceeded",
    "rendezvous",
)

_dispatch_policy: Optional[_retry.RetryPolicy] = None


def _get_dispatch_policy() -> _retry.RetryPolicy:
    """Shared policy for eager launch retries, built lazily on first
    dispatch so ``HOROVOD_RETRY_COLLECTIVE_DISPATCH_*`` set by user code
    after ``import horovod_tpu`` is still honored (the KV and
    worker-restart policies read the env at use time too)."""
    global _dispatch_policy
    if _dispatch_policy is None:
        _dispatch_policy = _retry.policy_from_env(
            "collective_dispatch", max_attempts=3, base_delay=0.05,
            max_delay=1.0,
        )
    return _dispatch_policy


def _transient_dispatch_error(e: BaseException) -> bool:
    """Is this eager-launch failure worth re-dispatching? Only when every
    participant aborted together: chaos injections and, single-process, the
    XLA:CPU rendezvous-timeout class. Multi-process failures are never
    retried unilaterally — a rank relaunching a collective its peers
    completed would desynchronize the job."""
    if isinstance(e, _retry.TransientError):
        return True
    if basics.is_initialized() and basics.process_size() > 1:
        return False
    msg = str(e).lower()
    return any(m in msg for m in _TRANSIENT_DISPATCH_MARKERS)


def _guarded(jitfn, donated: bool = False):
    """Wrap one compiled eager kernel with the fault-tolerance guard:
    chaos injection (``collective_delay``/``collective_fail``) ahead of the
    launch, and the shared retry/backoff policy around transient dispatch
    failures. This is the dispatch-timeout path of the eager layer — the
    reference's answer was "stall, then die"; ours is classify-and-retry.
    CPU backends additionally serialize through :func:`_cpu_serialized`.

    ``donated=True`` marks a kernel whose launch consumes its input
    buffers: a failure raised DURING the launch must not be re-dispatched
    (the rerun would read already-donated arrays). Chaos injections stay
    retriable — they fire before the launch touches its arguments."""
    inner = _cpu_serialized(jitfn)
    retriable = (
        (lambda e: isinstance(e, _retry.TransientError))
        if donated else _transient_dispatch_error
    )

    def _launch(*args):
        if _chaos.enabled():
            _chaos.maybe_delay("collective_delay")

            def attempt():
                if _chaos.enabled():
                    _chaos.inject_failure("collective_fail")
                return inner(*args)

            return _get_dispatch_policy().call(
                attempt, retriable=retriable
            )
        # happy path: one chaos check, a bare launch, no retry machinery —
        # the backoff schedule is only built once a launch actually fails
        try:
            return inner(*args)
        except BaseException as e:
            if donated or not _transient_dispatch_error(e):
                raise
            # hand the policy the failure that already happened as its
            # first attempt: total launches stay within max_attempts and
            # the first re-dispatch waits out base_delay (re-entering a
            # rendezvous abort immediately tends to hit the same window)
            first = [e]

            def rerun():
                if first:
                    raise first.pop()
                return inner(*args)

            return _get_dispatch_policy().call(
                rerun, retriable=_transient_dispatch_error
            )

    def launch(*args):
        out = _launch(*args)
        # flight-ring end marker for the begin _record_eager_op logged
        # (once per correlation key): a rank that reached here made host
        # progress — the hang watchdog's progress signal
        _flight.collective_end()
        return out

    return launch


def _eager_cache_size() -> Optional[int]:
    """``HOROVOD_EAGER_CACHE_SIZE`` (default 128): LRU capacity of each
    compiled-eager-kernel cache. Shape-polymorphic workloads (ragged batch
    tails, growing gather sizes) mint a new (shape, dtype) signature per
    variant; unbounded, the caches held every compiled program forever.
    ``0``/negative/``none`` disables the cap (the old behavior)."""
    v = os.environ.get("HOROVOD_EAGER_CACHE_SIZE", "128").strip().lower()
    if v in ("none", ""):
        return None
    n = int(v)
    return n if n > 0 else None


def _counted_lru_cache(builder):
    """Capped ``functools.lru_cache`` that also counts hits/misses/evictions
    into the metrics registry. Every compiled-eager-kernel lookup goes
    through one of these, so ``eager_compile_cache_{hits,misses,evictions}``
    is the in-tree answer to "is steady-state training replaying cached
    programs or recompiling every step?" (the eager analog of the
    reference's cycle observability). Labeled by kernel kind
    (``_eager_allreduce_fn`` -> ``kind=allreduce``). The underlying cache is
    built lazily so ``cache_clear()`` re-reads ``HOROVOD_EAGER_CACHE_SIZE``."""
    kind = builder.__name__.replace("_eager_", "").replace("_fn", "")
    box = {}

    def _cached():
        if "c" not in box:
            box["c"] = functools.lru_cache(maxsize=_eager_cache_size())(builder)
        return box["c"]

    @functools.wraps(builder)
    def lookup(*key):
        cached = _cached()
        if not _metrics.enabled():
            return cached(*key)
        before = cached.cache_info()
        fn = cached(*key)
        after = cached.cache_info()
        missed = after.misses > before.misses
        name = "eager_compile_cache_misses" if missed \
            else "eager_compile_cache_hits"
        _metrics.counter(
            name, help="eager shard_map program-cache lookups", kind=kind
        ).inc()
        if (
            missed
            and after.maxsize is not None
            and before.currsize == after.maxsize
            and after.currsize == after.maxsize
        ):
            # a miss that did not grow a full cache displaced its LRU entry
            _metrics.counter(
                "eager_compile_cache_evictions",
                help="compiled eager kernels displaced by the LRU cap",
                kind=kind,
            ).inc()
        return fn

    lookup.cache_info = lambda: _cached().cache_info()
    lookup.cache_clear = lambda: box.pop("c", None)
    return lookup


def _record_eager_op(op_name: str, tensors, axis=None) -> None:
    """Count one dispatched eager collective and its payload bytes, and
    assign the op its fleet correlation key — ``(step, elastic
    generation, per-op seq)`` via
    :func:`horovod_tpu.observability.straggler.collective_begin`, which
    also records per-rank arrival timestamps and applies any
    ``HOROVOD_CHAOS=rank_slow`` charge. The correlation hook runs even
    with metrics disabled: chaos charges and the seq discipline must not
    depend on the metrics switch (ranks disagreeing on seq would
    mis-correlate every later collective). With ``HOROVOD_SANITIZE=1``
    the op's signature (name, axis, per-tensor shape/dtype) is also
    appended to the schedule sanitizer's per-step ring
    (:mod:`horovod_tpu.analysis.sanitizer`) — the cross-rank schedule
    hash rank 0 verifies each step."""
    try:
        world = basics.size()
        prank = basics.process_rank()
        psize = basics.process_size()
    except RuntimeError:  # before init: eager ops will fail later anyway
        world, prank, psize = 1, 0, 1
    key = _straggler.collective_begin(
        op_name, world=world, process_rank=prank, process_size=psize,
    )
    # flight ring: the crash-durable record of this dispatch (begin; the
    # _guarded launch wrapper records the matching end). Also the hook the
    # rank_hang chaos charge fires through.
    _flight.collective_begin(
        op_name, key, world=world, process_rank=prank, process_size=psize,
    )
    _sanitizer.record(op_name, tensors, axis=axis)
    if not _metrics.enabled():
        return
    nbytes = 0
    for t in tensors:
        nbytes += getattr(t, "nbytes", 0) or 0
    _metrics.counter(
        f"{op_name}_count", help="eager collectives dispatched"
    ).inc()
    _metrics.counter(
        f"{op_name}_bytes", help="payload bytes through eager collectives"
    ).inc(nbytes)
    _metrics.counter(
        f"{op_name}_tensors", help="tensors through eager collectives"
    ).inc(len(tensors) if hasattr(tensors, "__len__") else 1)


@_counted_lru_cache
def _eager_allreduce_fn(mesh, axis, stacked, n_tensors):
    in_spec = P(axis) if stacked else P()

    def fn(*tensors):
        outs = []
        for v in tensors:
            s = lax.psum(v, axis)
            outs.append(s)
        return tuple(outs)

    sm = _smap(fn, mesh, (in_spec,) * n_tensors, (P(),) * n_tensors)
    return _guarded(jax.jit(sm))


_donate_fused: Optional[bool] = None


def _donate_fused_enabled() -> bool:
    """``HOROVOD_DONATE_FUSED``: donate the flat fused-buffer inputs of the
    eager fused allreduce / reduce-scatter programs so XLA aliases the
    output into the input's HBM instead of holding both live across the
    collective — on a 64 MB bin that is 64 MB of transient HBM back.
    Default: on for accelerator backends, OFF on CPU — the CPU/test path is
    where ``_guarded`` may legitimately re-dispatch a launch (XLA:CPU
    rendezvous aborts), and a retry must never replay already-donated
    buffers. Donation is safe with the chaos/retry guard because chaos
    failure injection fires *before* the launch consumes its arguments."""
    global _donate_fused
    if _donate_fused is None:
        env = os.environ.get("HOROVOD_DONATE_FUSED")
        if env is not None:
            _donate_fused = env.lower() not in ("0", "false")
        else:
            _donate_fused = jax.default_backend() != "cpu"
    return _donate_fused


def _maybe_donated_jit(sm, n_args: int, donate: bool):
    """jit with all collective inputs donated when enabled; unusable
    donations (shape-changing outputs, e.g. stacked inputs) surface as a
    one-line XLA warning, filtered here so opting in stays quiet."""
    if not donate:
        return jax.jit(sm)
    jitted = jax.jit(sm, donate_argnums=tuple(range(n_args)))

    def first_call_quiet(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*donated.*", category=UserWarning
            )
            return jitted(*args)

    return first_call_quiet


_flat_fusion: Optional[bool] = None


def _flat_fusion_enabled() -> bool:
    """``HOROVOD_FUSION_FLAT`` (default on): fuse a grouped bin into one
    flat buffer per dtype (one collective each). Off = one psum per tensor
    inside the single launch, leaving the merge to XLA's all-reduce
    combiner. Measured on the 8-device CPU mesh (161-tensor 5.9 MB bin):
    flat 34.6 ms vs per-tensor 27.2 ms — host memcpy makes pack/unpack a
    net cost THERE; on TPU one DMA-scheduled collective per dtype is the
    fusion the reference's 64 MB buffer exists to get."""
    global _flat_fusion
    if _flat_fusion is None:
        _flat_fusion = os.environ.get(
            "HOROVOD_FUSION_FLAT", "1").lower() not in ("0", "false")
    return _flat_fusion


@_counted_lru_cache
def _eager_fused_allreduce_fn(mesh, axis, stacked, sig):
    """Flat fusion-buffer allreduce: the true analog of the reference's
    ``MemcpyInFusionBuffer`` → one reduction → ``MemcpyOutFusionBuffer``
    (``common/ops/collective_operations.cc``). Every same-dtype member of the
    fused response is flattened and concatenated into ONE buffer, reduced
    with ONE ``psum`` per dtype, and split back — so a 100-tensor bin costs
    #dtypes collectives instead of 100. XLA lowers the concat/split to fused
    HBM copies around the collective.

    ``sig`` is the trace signature: a tuple of per-tensor (shape, dtype-str)
    pairs (the lru key; shapes are per-shard shapes as seen inside
    shard_map). Non-stacked inputs are donated when
    :func:`_donate_fused_enabled` (each output aliases its same-shaped
    input buffer); stacked inputs change shape through the reduce, so
    donation would never alias and is skipped.
    """
    in_spec = P(axis) if stacked else P()
    n_tensors = len(sig)

    def fn(*tensors):
        by_dtype: dict = {}
        for i, t in enumerate(tensors):
            by_dtype.setdefault(t.dtype, []).append(i)
        outs = [None] * len(tensors)
        for idxs in by_dtype.values():
            if len(idxs) == 1:
                i = idxs[0]
                outs[i] = lax.psum(tensors[i], axis)
                continue
            flat = jnp.concatenate([tensors[i].reshape(-1) for i in idxs])
            red = lax.psum(flat, axis)
            off = 0
            for i in idxs:
                sz = tensors[i].size
                outs[i] = red[off:off + sz].reshape(tensors[i].shape)
                off += sz
        return tuple(outs)

    sm = _smap(fn, mesh, (in_spec,) * n_tensors, (P(),) * n_tensors)
    donate = _donate_fused_enabled() and not stacked
    return _guarded(_maybe_donated_jit(sm, n_tensors, donate), donated=donate)


@_counted_lru_cache
def _eager_allgather_fn(mesh, axis, stacked, n_tensors):
    in_spec = P(axis) if stacked else P()

    def fn(*tensors):
        return tuple(
            lax.all_gather(v, axis, axis=0, tiled=True) for v in tensors
        )

    return _guarded(jax.jit(
        _smap(fn, mesh, (in_spec,) * n_tensors, (P(),) * n_tensors)
    ))


@_counted_lru_cache
def _eager_broadcast_fn(mesh, axis, root):
    def fn(v):
        idx = _flat_axis_index(mesh, axis)
        masked = jnp.where(idx == root, v, jnp.zeros_like(v))
        return lax.psum(masked, axis)

    return _guarded(jax.jit(
        _smap(fn, mesh, (P(axis),), P())
    ))


@_counted_lru_cache
def _eager_alltoall_fn(mesh, axis):
    n = _mesh_axis_size(mesh, axis)

    def fn(v):
        # v: [1, rows, ...] -> per-rank [rows, ...]
        v = jnp.squeeze(v, axis=0)
        rows = v.shape[0]
        v = v.reshape((n, rows // n) + v.shape[1:])
        r = lax.all_to_all(v, axis, split_axis=0, concat_axis=0)
        r = r.reshape((rows,) + r.shape[2:])
        return r[None]

    return _guarded(jax.jit(
        _smap(fn, mesh, (P(axis),), P(axis))
    ))


# --------------------------------------------------------------------------
# int8 quantized collectives (Compression.int8 / the PowerSGD int8 fallback)
#
# int8 values must never be summed in int8 — a ring hop would overflow at
# the second addition. The kernels below keep the wire low-bit while the
# arithmetic stays wide: quantize per destination shard → move int8 + bf16
# scales (all_to_all = the scatter half of a ring reduce-scatter) →
# dequantize and ACCUMULATE IN f32 on the owning rank → requantize the
# reduced shard → all-gather int8 + scales → dequantize. The HLO carries
# s8/bf16 collectives, so the compiled program's wire bytes are the real
# ~4x saving, not a simulation.


def _quant_block(compression) -> int:
    from horovod_tpu.compression import INT8_BLOCK

    return int(getattr(compression, "block", INT8_BLOCK))


def _quant_exchange(flat, axis, block, pre=None):
    """The wire half of the quantized reduce-scatter: split this rank's
    ``[Lp]`` vector into N destination-chunk rows, blockwise-quantize
    (shared ``compression._pad_to_block`` layout), and ``all_to_all`` the
    int8 values + bf16 scales. ``pre=(q, scales)`` reuses an already
    computed wire image with the SAME layout (the fused EF path quantizes
    once for both the residual and the wire). Returns ``(qr [N, sp],
    scr [N, sp/block], n, s, sp)``."""
    from horovod_tpu.compression import _pad_to_block, quantize_blockwise

    n = lax.psum(1, axis)  # static axis size
    s = flat.shape[0] // n
    rows = _pad_to_block(flat.reshape(n, s), block)
    sp = rows.shape[1]
    if pre is not None:
        q, scales = pre
    else:
        # sp % block == 0, so flat blocks align to destination-chunk rows;
        # quantize_blockwise itself dispatches to the fused Pallas kernel
        # under HOROVOD_PALLAS
        q, scales = quantize_blockwise(rows.reshape(-1), block)
    qr = lax.all_to_all(
        q.reshape(n, sp), axis, split_axis=0, concat_axis=0)
    scr = lax.all_to_all(
        scales.reshape(n, sp // block), axis, split_axis=0, concat_axis=0)
    return qr, scr, n, s, sp


def quantized_psum_scatter(flat, axis, *, block=None, pre=None):
    """In-jit (bound axis) int8 reduce-scatter of a flat per-rank vector.

    ``flat``: this rank's ``[Lp]`` contribution, ``Lp`` a multiple of the
    axis size N. Each rank's vector is split into N destination chunks,
    each chunk blockwise-quantized (internal zero-pad up to the scale
    block), exchanged as int8 + bf16 scales via ``all_to_all``, and the N
    received chunks are dequantized and summed in f32. Returns this rank's
    f32(-dtype) SUM shard ``[Lp // N]``. ``pre=(q, scales)`` supplies a
    precomputed wire image (see :func:`_quant_exchange`).

    Under ``HOROVOD_PALLAS`` the dequant-accumulate epilogue runs as ONE
    fused VMEM kernel (no ``[N, sp]`` f32 dequant matrix in HBM); the
    ``all_to_all`` signatures are identical either way, so the collective
    schedule fingerprints are invariant."""
    from horovod_tpu.compression import INT8_BLOCK, dequantize_blockwise
    from horovod_tpu.ops import pallas_kernels as _pk

    block = int(block or INT8_BLOCK)
    qr, scr, n, s, sp = _quant_exchange(flat, axis, block, pre=pre)
    if _pk.enabled():
        return _pk.dequant_accumulate(qr, scr, flat.dtype, block)[:s]
    deq = dequantize_blockwise(
        qr.reshape(-1), scr.reshape(-1), flat.dtype, block).reshape(n, sp)
    return deq.sum(axis=0)[:s]


def _quantized_all_gather_fwd(flat, axis, block):
    from horovod_tpu.compression import dequantize_rows, quantize_blockwise

    n = lax.psum(1, axis)  # static axis size
    s = flat.shape[0]
    q, scales = quantize_blockwise(flat, block)       # [sp], [sp/block]
    sp = q.shape[0]
    qg = lax.all_gather(q, axis, axis=0, tiled=True).reshape(n, sp)
    scg = lax.all_gather(
        scales, axis, axis=0, tiled=True).reshape(n, sp // block)
    deq = dequantize_rows(qg, scg, flat.dtype, block)  # [n, sp]
    return deq[:, :s].reshape(-1), None


def _quantized_all_gather_bwd(axis, block, _res, ct):
    # the gradient leg stays EXACT full precision: the transpose of the
    # plain tiled all_gather — only the forward's parameter values ride
    # the int8 wire
    del block
    return (lax.psum_scatter(ct, axis, scatter_dimension=0, tiled=True),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _quantized_all_gather(flat, axis, block):
    return _quantized_all_gather_fwd(flat, axis, block)[0]


_quantized_all_gather.defvjp(
    _quantized_all_gather_fwd, _quantized_all_gather_bwd)


def quantized_all_gather(flat, axis, *, block=None):
    """In-jit (bound axis) int8 all-gather of a flat per-rank shard — the
    ZeRO-3 parameter gather-on-use wire (``HOROVOD_FSDP_WIRE=int8``).

    This rank's ``[s]`` shard is blockwise-quantized (internal zero-pad
    up to the scale block), the int8 values + bf16 scales ride the tiled
    all-gather, and every rank dequantizes the N received rows back to
    ``[N*s]`` — ~4x less gather wire than fp32, with the fused per-row
    dequant epilogue under ``HOROVOD_PALLAS``
    (:func:`horovod_tpu.ops.pallas_kernels.dequantize_rows`).

    Differentiable by design: the backward is the transpose of the PLAIN
    tiled all-gather — an exact full-precision ``lax.psum_scatter`` of
    the cotangent — so a ZeRO-3 step under this wire trains on
    int8-rounded weights but exact gradients (the trajectory deviation
    is bounded by the forward rounding alone)."""
    from horovod_tpu.compression import INT8_BLOCK

    return _quantized_all_gather(flat, axis, int(block or INT8_BLOCK))


def _quant_allreduce_bound(v, axis, *, op, block):
    """In-jit (bound axis) int8 allreduce: quantized reduce-scatter, f32
    accumulate, requantize the reduced shard, int8 all-gather, dequantize.
    ``op`` Average divides the f32 shard before the requantize so the
    gather leg quantizes at the final magnitude.

    Under ``HOROVOD_PALLAS`` dequantize → accumulate → divide →
    requantize runs as ONE fused kernel between the ``all_to_all`` and
    the ``all_gather`` (the reduced shard never round-trips HBM); the
    collective signatures are unchanged."""
    from horovod_tpu.compression import (
        dequantize_blockwise, quantize_blockwise,
    )
    from horovod_tpu.ops import pallas_kernels as _pk

    n = lax.psum(1, axis)
    shape, size, dtype = v.shape, v.size, v.dtype
    flat = v.reshape(-1)
    pad = (-size) % (n * block)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
    if _pk.enabled():
        qr, scr, n, _s, _sp = _quant_exchange(flat, axis, block)
        # s == sp here: Lp is a multiple of N*block, so the rows need no pad
        q2, sc2 = _pk.dequant_accumulate_requantize(
            qr, scr, dtype, block, divisor=(n if op == Average else None))
    else:
        shard = quantized_psum_scatter(flat, axis, block=block)  # [Lp//n]
        if op == Average:
            shard = shard / n
        # shard length is a multiple of block (Lp % n*block == 0)
        q2, sc2 = quantize_blockwise(shard, block)
    qg = lax.all_gather(q2, axis, axis=0, tiled=True)
    scg = lax.all_gather(sc2, axis, axis=0, tiled=True)
    out = dequantize_blockwise(qg, scg, dtype, block)
    return out[:size].reshape(shape)


@_counted_lru_cache
def _eager_quant_allreduce_fn(mesh, axis, stacked, shape, dtype_str, block,
                              avg, pallas_key=(False, False)):
    """Compiled eager int8 allreduce (one program per mesh/shape/dtype,
    LRU-capped + hit/miss counted like every eager kernel). Stacked
    ``[N, ...]`` inputs contribute one per-rank row each; replicated inputs
    contribute the same value from every rank. ``pallas_key`` carries the
    resolved ``HOROVOD_PALLAS`` state into the cache key — the traced body
    consults the knob, so flipping it must never replay a stale program."""
    in_spec = P(axis) if stacked else P()

    def fn(v):
        if stacked:
            v = jnp.squeeze(v, axis=0)
        return _quant_allreduce_bound(
            v, axis, op=Average if avg else Sum, block=block)

    return _guarded(jax.jit(_smap(fn, mesh, (in_spec,), P())))


@_counted_lru_cache
def _eager_quant_reducescatter_fn(mesh, axis, stacked, shape, dtype_str,
                                  block, pallas_key=(False, False)):
    """Compiled eager int8 SUM reduce-scatter on a flat packed buffer
    (the ZeRO-1 exchange): input ``[Lp]`` replicated or ``[N, Lp]``
    stacked per-rank rows; output ``[N, Lp // N]`` f32 shards, one row per
    owning rank (sharded ``P(axis)`` like :func:`_eager_reducescatter_fn`).
    ``pallas_key`` keys the compiled program on the resolved
    ``HOROVOD_PALLAS`` state (the traced body consults the knob)."""
    in_spec = P(axis) if stacked else P()

    def fn(v):
        if stacked:
            v = jnp.squeeze(v, axis=0)
        return quantized_psum_scatter(v, axis, block=block)[None]

    sm = _smap(fn, mesh, (in_spec,), P(axis))
    # same donation discipline as _eager_reducescatter_fn: the flat packed
    # buffer is consumed by the launch, releasing its HBM during the
    # exchange (never aliasable — the output is the 1/N f32 shard)
    donate = _donate_fused_enabled()
    return _guarded(_maybe_donated_jit(sm, 1, donate), donated=donate)


def quantized_reducescatter(tensor, *, axis=None, block=None):
    """SUM reduce-scatter with the int8 wire on a flat packed buffer.

    In-jit (bound axis): per-rank ``[Lp]`` → this rank's f32 shard
    ``[Lp//N]``. Eager: ``[Lp]`` replicated or ``[N, Lp]`` stacked →
    ``[N, Lp//N]`` stacked shards; the input buffer is donated to the
    launch when ``HOROVOD_DONATE_FUSED`` is on (accelerator default) —
    treat it as consumed. ``Lp`` must be a multiple of the axis size (the
    ZeRO-1 flat packing guarantees it)."""
    from horovod_tpu.compression import INT8_BLOCK

    block = int(block or INT8_BLOCK)
    ax = _axis(axis)
    if _is_tracer(tensor):
        if not _axis_bound(ax):
            raise ValueError(
                "quantized_reducescatter is rank-dependent and requires a "
                "bound mesh axis; call it inside shard_map over the data "
                "axis."
            )
        return quantized_psum_scatter(tensor, ax, block=block)
    from horovod_tpu.ops import pallas_kernels as _pk

    tensor = _as_array(tensor)
    stacked = _is_stacked(tensor, ax)
    fn = _eager_quant_reducescatter_fn(
        basics.mesh(), ax, stacked,
        tuple(tensor.shape), str(tensor.dtype), block, _pk.cache_key())
    _record_eager_op("reducescatter", (tensor,), axis=ax)
    return fn(tensor)


def _quantizes_dtype(compression, tensor) -> bool:
    """Does `compression` actually quantize this tensor? Integer and
    already-16-bit leaves pass through the regular path untouched, as do
    leaves below the compressor's ``min_quant_elems`` floor — the ring
    pads every rank-pair message to a whole scale block, so quantizing a
    small bias would move MORE wire than its fp32 psum."""
    from horovod_tpu.compression import _quantizable

    dt = getattr(tensor, "dtype", None)
    if dt is None:
        t = np.asarray(tensor)
        dt, size = t.dtype, t.size
    else:
        size = int(np.prod(getattr(tensor, "shape", ()), dtype=np.int64))
    return _quantizable(dt) and \
        size >= int(getattr(compression, "min_quant_elems", 0))


def _roundtrip_compressed(tensor, compression):
    c, ctx = compression.compress(tensor)
    return compression.decompress(c, ctx)


def _quantized_allreduce(tensor, op, ax, compression, *, name=None,
                         prescale_factor=1.0, postscale_factor=1.0):
    """allreduce() body for quantized (int8-family) compression. The bound
    single-axis path runs the real int8 ring; a bound two-axis hierarchy
    compresses ONLY the cross (DCN) hop while the local (ICI) legs stay
    full-width; everything else models the wire as a quantize roundtrip of
    the contribution (exact error-feedback semantics either way)."""
    if op == Adasum:
        raise ValueError("quantized compression does not support op=Adasum")
    block = _quant_block(compression)
    if prescale_factor != 1.0:
        tensor = tensor * prescale_factor
    if _is_tracer(tensor):
        if _axis_bound(ax):
            if isinstance(ax, tuple) and len(ax) == 2 and _hier_enabled():
                from horovod_tpu.ops import hierarchical

                out = hierarchical.hier_allreduce(
                    tensor, cross_axis=ax[0], local_axis=ax[1],
                    compression=compression)
                if op == Average:
                    out = _div(out, lax.psum(1, ax[0]) * lax.psum(1, ax[1]))
            elif isinstance(ax, tuple):
                # flat multi-axis: model the wire as the roundtrip of the
                # contribution; the reduction itself stays a plain psum
                out = lax.psum(_roundtrip_compressed(tensor, compression), ax)
                if op == Average:
                    out = _div(out, lax.psum(1, ax))
            else:
                out = _quant_allreduce_bound(tensor, ax, op=op, block=block)
        else:
            # global value under jit: replicated semantics + wire roundtrip
            rt = _roundtrip_compressed(tensor, compression)
            out = rt * _axis_size(ax) if op == Sum else rt
    elif _hostlocal_mode(tensor):
        from horovod_tpu.ops import hostlocal

        rt = _roundtrip_compressed(_as_array(tensor), compression)
        _record_eager_op("allreduce", (rt,), axis=ax)
        with _trace.span("eager", f"allreduce:{name or ''}",
                         **_straggler.span_args()):
            out = hostlocal.allreduce(rt, op, ax)
    elif isinstance(ax, tuple):
        # eager multi-axis: roundtrip + the regular eager dispatch
        out = allreduce(
            _roundtrip_compressed(_as_array(tensor), compression), op, axis=ax)
    else:
        from horovod_tpu.ops import pallas_kernels as _pk

        tensor = _as_array(tensor)
        stacked = _is_stacked(tensor, ax)
        fn = _eager_quant_allreduce_fn(
            basics.mesh(), ax, stacked, tuple(tensor.shape),
            str(tensor.dtype), block, op == Average, _pk.cache_key())
        _record_eager_op("allreduce", (tensor,), axis=ax)
        with _trace.span("eager", f"allreduce:{name or ''}",
                         **_straggler.span_args()):
            out = fn(tensor)
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


@_counted_lru_cache
def _eager_reducescatter_fn(mesh, axis, stacked):
    in_spec = P(axis) if stacked else P()

    def fn(v):
        if stacked:
            v = jnp.squeeze(v, axis=0)
        r = lax.psum_scatter(v, axis, scatter_dimension=0, tiled=True)
        return r[None]

    sm = _smap(fn, mesh, (in_spec,), P(axis))
    # donation frees the (padded) input buffer during the scatter — never
    # aliasable (the output is the 1/N shard) but the early release is the
    # point on large flat gradient buffers
    donate = _donate_fused_enabled()
    return _guarded(_maybe_donated_jit(sm, 1, donate), donated=donate)


def clear_outstanding_names() -> None:
    """Forget every outstanding async-collective name: an op left in
    flight when a run died must not poison the next ``hvd.init`` on this
    live process with DUPLICATE_NAME. ``basics.shutdown`` calls this."""
    with _outstanding_lock:
        _outstanding_names.clear()


def clear_eager_caches() -> None:
    """Drop every compiled-eager-kernel cache and the outstanding-name set.

    The caches are keyed by mesh; ``basics.init`` calls this when a
    live-process re-init builds a *different* mesh (the elastic resize):
    the old mesh's entries can never hit again, but they pin compiled
    programs (and through them device buffers) for devices the new mesh
    may no longer own. A re-init on an equal mesh keeps the caches — they
    are warm hits, and recompiling every eager collective per init cycle
    would be pure waste."""
    for fn in (
        _eager_allreduce_fn,
        _eager_fused_allreduce_fn,
        _eager_allgather_fn,
        _eager_broadcast_fn,
        _eager_alltoall_fn,
        _eager_reducescatter_fn,
        _eager_quant_allreduce_fn,
        _eager_quant_reducescatter_fn,
    ):
        fn.cache_clear()
    for mod_name, names in (
        ("horovod_tpu.ops.adasum",
         ("_eager_adasum_fn", "_eager_grouped_adasum_fn")),
        ("horovod_tpu.ops.hierarchical",
         ("_eager_hier_allreduce_fn", "_eager_hier_allgather_fn")),
    ):
        import sys as _sys

        mod = _sys.modules.get(mod_name)
        if mod is None:
            continue  # never imported: nothing cached
        for n in names:
            getattr(mod, n).cache_clear()
    with _outstanding_lock:
        _outstanding_names.clear()


# --------------------------------------------------------------------------
# allreduce


@_jit_scoped
def allreduce(tensor, op: ReduceOp = Average, *, axis=None, name: Optional[str] = None,
              compression=None, prescale_factor: float = 1.0,
              postscale_factor: float = 1.0):
    """Sum/average `tensor` across ranks.

    In-jit: `tensor` is a per-shard value; lowers to ``lax.psum``/``pmean``
    over ``axis`` (default: the data axis). Eager: `tensor` is either stacked
    ``[size, ...]`` (per-rank values) or replicated; returns the reduced tensor
    replicated across the mesh. Mirrors reference
    ``tensorflow/__init__.py:43-122`` (Average divides by size after summing).
    """
    ax = _axis(axis)
    if compression is not None and getattr(compression, "factorized", False):
        raise ValueError(
            "factorized compression (PowerSGD) is stateful (warm-started Q "
            "+ error feedback) and cannot ride a stateless allreduce; use "
            "DistributedOptimizer(compression=Compression.powersgd(r), "
            "error_feedback=True)"
        )
    if (
        compression is not None
        and getattr(compression, "quantized", False)
        and _quantizes_dtype(compression, tensor)
    ):
        return _quantized_allreduce(
            tensor, op, ax, compression, name=name,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    if compression is not None:
        tensor, ctx = compression.compress(tensor)
    if prescale_factor != 1.0:
        tensor = tensor * prescale_factor
    if op == Adasum:
        from horovod_tpu.ops import adasum as _adasum

        out = _adasum.adasum_allreduce(tensor, axis=ax, name=name)
    elif _is_tracer(tensor):
        if _axis_bound(ax):
            if isinstance(ax, tuple) and len(ax) == 2 and _hier_enabled():
                from horovod_tpu.ops import hierarchical

                # reference HOROVOD_HIERARCHICAL_ALLREDUCE: explicit
                # local RS -> cross AR -> local AG decomposition
                out = hierarchical.hier_allreduce(
                    tensor, cross_axis=ax[0], local_axis=ax[1])
            else:
                out = lax.psum(tensor, ax)
            if op == Average:
                out = _div(out, lax.psum(1, ax))
        else:
            # global value under jit: XLA's sharding propagation already did
            # the cross-chip reduction; replicated semantics apply.
            out = tensor * _axis_size(ax) if op == Sum else tensor
    elif _hostlocal_mode(tensor):
        from horovod_tpu.ops import hostlocal

        _record_eager_op("allreduce", (_as_array(tensor),), axis=ax)
        with _trace.span("eager", f"allreduce:{name or ''}",
                         **_straggler.span_args()):
            out = hostlocal.allreduce(tensor, op, ax)
    elif isinstance(ax, tuple) and len(ax) == 2 and _hier_enabled():
        from horovod_tpu.ops import hierarchical

        out = hierarchical.hierarchical_allreduce(
            tensor, op, cross_axis=ax[0], local_axis=ax[1])
    else:
        tensor = _as_array(tensor)
        stacked = _is_stacked(tensor, ax)
        n = _axis_size(ax)
        fn = _eager_allreduce_fn(basics.mesh(), ax, stacked, 1)
        _record_eager_op("allreduce", (tensor,), axis=ax)
        with _trace.span("eager", f"allreduce:{name or ''}",
                         **_straggler.span_args()):
            (out,) = fn(tensor)
        if stacked:
            out = jnp.squeeze(out, axis=0)
        if op == Average:
            out = _div(out, n)
    if postscale_factor != 1.0:
        out = out * postscale_factor
    if compression is not None:
        out = compression.decompress(out, ctx)
    return out


def allreduce_(tensor, op: ReduceOp = Average, *, axis=None, name=None):
    """In-place spelling for torch parity (reference
    ``torch/mpi_ops.py:182-240``); JAX arrays are immutable so this is
    ``allreduce``."""
    return allreduce(tensor, op, axis=axis, name=name)


def _core_enqueue(name, tensor, request_type, **kw):
    """Route a named async op through the native core when one is attached
    (init(native_core=True)); returns None when the direct path should run."""
    core = basics._state.core
    if core is None or name is None:
        return None
    return core.enqueue(name, _as_array(tensor), request_type, **kw)


def allreduce_async(tensor, op: ReduceOp = Average, *, axis=None, name=None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0):
    """Async allreduce returning a handle
    (reference ``torch/mpi_ops.py:94-129``).

    With the native core attached and a tensor `name` given, the op goes
    through the background negotiation cycle (fusion + response cache +
    stall detection); otherwise it dispatches directly (XLA's async runtime
    is the handle)."""
    from horovod_tpu.core import REQUEST_ADASUM, REQUEST_ALLREDUCE

    h = _core_enqueue(
        name, tensor, REQUEST_ADASUM if op == Adasum else REQUEST_ALLREDUCE,
        op=op, axis=axis, prescale=prescale_factor, postscale=postscale_factor,
    )
    if h is not None:
        return h
    return _async(
        lambda: allreduce(tensor, op, axis=axis,
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor),
        name,
    )


allreduce_async_ = allreduce_async


@_jit_scoped
def grouped_allreduce(tensors: Sequence, op: ReduceOp = Average, *, axis=None,
                      name=None):
    """Fused allreduce of a list of tensors in one collective.

    This is the eager-layer analog of the reference's tensor fusion
    (``FuseResponses`` bin-packing, ``controller.cc:640-761`` +
    ``MemcpyInFusionBuffer``, ``collective_operations.cc``): tensors are
    flattened into one buffer, reduced with a single ``psum``, and split back.
    XLA performs the pack/unpack as fused copies in HBM.
    """
    ax = _axis(axis)
    if op == Adasum:
        # fused Adasum: one flat-concat buffer, per-tensor dot/norm scalars
        # via segment reductions inside the combine, ONE butterfly for the
        # whole group -> O(log n) collectives per step (reference
        # adasum.h:194-398 FusedPairwiseReduceWithComm over fusion-buffer
        # offsets).
        from horovod_tpu.ops.adasum import grouped_adasum_allreduce

        return grouped_adasum_allreduce(tensors, axis=ax)
    if not any(_is_tracer(t) for t in tensors) and any(
        _hostlocal_mode(t) for t in tensors
    ):
        from horovod_tpu.ops import hostlocal

        # mixed host-local/global lists dispatch per tensor, like allreduce
        # (global tensors record inside their own allreduce() call)
        _record_eager_op(
            "allreduce",
            [_as_array(t) for t in tensors if _hostlocal_mode(t)],
            axis=ax,
        )
        return [
            hostlocal.allreduce(_as_array(t), op, ax)
            if _hostlocal_mode(t)
            else allreduce(t, op, axis=ax)
            for t in tensors
        ]
    tensors = [_as_array(t) for t in tensors]
    if any(_is_tracer(t) for t in tensors):
        if not _axis_bound(ax):
            n = _axis_size(ax)
            return [t * n if op == Sum else t for t in tensors]
        outs = [lax.psum(t, ax) for t in tensors]
        if op == Average:
            n = lax.psum(1, ax)
            outs = [_div(o, n) for o in outs]
        return outs

    n = _axis_size(ax)
    stacked = [_is_stacked(t, ax) for t in tensors]
    if all(stacked) or not any(stacked):
        st = bool(stacked and stacked[0])
        if len(tensors) > 1 and _flat_fusion_enabled():
            # flat fusion-buffer path: one psum per dtype for the whole bin
            sig = tuple((tuple(t.shape), str(t.dtype)) for t in tensors)
            fn = _eager_fused_allreduce_fn(basics.mesh(), ax, st, sig)
        else:
            fn = _eager_allreduce_fn(basics.mesh(), ax, st, len(tensors))
        _record_eager_op("allreduce", tensors, axis=ax)
        with _trace.span("eager", f"grouped_allreduce:{name or ''}",
                         **_straggler.span_args()):
            outs = list(fn(*tensors))
        if st:
            outs = [jnp.squeeze(o, axis=0) for o in outs]
    else:
        outs = [allreduce(t, Sum, axis=ax) for t in tensors]
    if op == Average:
        outs = [_div(o, n) for o in outs]
    return outs


def grouped_allreduce_async(tensors, op: ReduceOp = Average, *, axis=None,
                            name=None):
    return _async(lambda: grouped_allreduce(tensors, op, axis=axis), name)


# --------------------------------------------------------------------------
# allgather


@_jit_scoped
def allgather(tensor, *, axis=None, name=None):
    """Concatenate per-rank tensors along dim 0 (reference
    ``MPIAllgather``/``NCCL`` path, ``mpi_operations.cc:83+``;
    ``tensorflow/mpi_ops.py:110-143``). All ranks must agree on trailing dims;
    equal dim-0 is required in the XLA (static-shape) path — ragged gather is
    available eagerly via :func:`allgather_object`."""
    ax = _axis(axis)
    if _is_tracer(tensor):
        if not _axis_bound(ax):
            # global value: replicated semantics (every rank contributed the
            # same tensor) -> tile along dim 0.
            return jnp.concatenate([tensor] * _axis_size(ax), axis=0)
        if isinstance(ax, tuple) and len(ax) == 2 and _hier_allgather_enabled():
            from horovod_tpu.ops import hierarchical

            # reference HOROVOD_HIERARCHICAL_ALLGATHER: intra-host gather
            # (ICI) then inter-host (DCN); rank order preserved
            return hierarchical.hier_allgather(
                tensor, cross_axis=ax[0], local_axis=ax[1])
        return lax.all_gather(tensor, ax, axis=0, tiled=True)
    if _hostlocal_mode(tensor):
        from horovod_tpu.ops import hostlocal

        _record_eager_op("allgather", (_as_array(tensor),), axis=ax)
        return hostlocal.allgather(tensor, ax)
    if isinstance(ax, tuple) and len(ax) == 2 and _hier_allgather_enabled():
        from horovod_tpu.ops import hierarchical

        return hierarchical.hierarchical_allgather(
            tensor, cross_axis=ax[0], local_axis=ax[1])
    tensor = _as_array(tensor)
    stacked = _is_stacked(tensor, ax)
    fn = _eager_allgather_fn(basics.mesh(), ax, stacked, 1)
    _record_eager_op("allgather", (tensor,), axis=ax)
    (out,) = fn(tensor)
    if stacked:
        # [size, rows, ...] -> [size*rows, ...]
        out = out.reshape((out.shape[0] * out.shape[1],) + out.shape[2:])
    return out


@_jit_scoped
def grouped_allgather(tensors: Sequence, *, axis=None, name=None):
    """Fused allgather of a tensor list in one XLA launch (the reference
    fuses allgather responses too, ``controller.cc:700-755``; here the
    grouped program holds one ``all_gather`` per tensor — mixed dtypes
    welcome — and XLA schedules them together)."""
    ax = _axis(axis)
    tensors = list(tensors)
    if not tensors:
        return []
    if any(_is_tracer(t) for t in tensors) or any(
        _hostlocal_mode(t) for t in tensors
    ):
        # in-jit and multi-process host paths dispatch per tensor (the
        # hostlocal exchange stages host-side regardless)
        return [allgather(t, axis=ax, name=name) for t in tensors]
    tensors = [_as_array(t) for t in tensors]
    stacked = [_is_stacked(t, ax) for t in tensors]
    if any(stacked) != all(stacked):
        return [allgather(t, axis=ax) for t in tensors]
    st = bool(stacked and stacked[0])
    fn = _eager_allgather_fn(basics.mesh(), ax, st, len(tensors))
    _record_eager_op("allgather", tensors, axis=ax)
    outs = list(fn(*tensors))
    if st:
        outs = [
            o.reshape((o.shape[0] * o.shape[1],) + o.shape[2:]) for o in outs
        ]
    return outs


def allgather_async(tensor, *, axis=None, name=None):
    from horovod_tpu.core import REQUEST_ALLGATHER

    h = _core_enqueue(name, tensor, REQUEST_ALLGATHER, axis=axis)
    if h is not None:
        return h
    return _async(lambda: allgather(tensor, axis=axis), name)


def allgather_object(obj, *, name=None):
    """Gather arbitrary picklable objects from every rank (reference uses
    cloudpickle + allgather of byte tensors, ``torch/__init__.py:609-648``
    pattern). Single-controller: every rank runs this same program, so the
    result is simply ``[obj] * size``; multi-process gathers over the
    controller."""
    basics._require_init()
    if basics.process_size() == 1:
        return [pickle.loads(pickle.dumps(obj))] * basics.size()
    from horovod_tpu.ops import hostlocal

    return hostlocal.allgather_object(obj, basics.data_axis())


# --------------------------------------------------------------------------
# broadcast


@_jit_scoped
def broadcast(tensor, root_rank: int = 0, *, axis=None, name=None):
    """Broadcast root's value to all ranks (reference
    ``NCCLBroadcast``, ``nccl_operations.cc:366-396``;
    ``tensorflow/mpi_ops.py:145-174``)."""
    ax = _axis(axis)
    if not 0 <= root_rank < _axis_size(ax):
        # reference validates root across ranks and returns an ERROR response
        # (controller.cc:378-611)
        raise ValueError(
            f"broadcast root_rank {root_rank} out of range [0, {_axis_size(ax)})"
        )
    if _is_tracer(tensor):
        if not _axis_bound(ax):
            return tensor  # global value: all ranks already hold root's value
        return _inner_broadcast(tensor, root_rank, ax)
    if _hostlocal_mode(tensor):
        # multi-process: root_rank is a *process* index (the Horovod rank)
        from horovod_tpu.ops import hostlocal

        _record_eager_op("broadcast", (_as_array(tensor),), axis=ax)
        return hostlocal.broadcast(tensor, root_rank, ax)
    tensor = _as_array(tensor)
    if not _is_stacked(tensor, ax):
        # replicated: every rank already holds root's value
        return tensor
    was_bool = tensor.dtype == jnp.bool_
    if was_bool:
        tensor = tensor.astype(jnp.int8)
    fn = _eager_broadcast_fn(basics.mesh(), ax, int(root_rank))
    _record_eager_op("broadcast", (tensor,), axis=ax)
    out = jnp.squeeze(fn(tensor), axis=0)
    if was_bool:
        out = out.astype(jnp.bool_)
    return out


def _inner_broadcast(v, root, ax):
    idx = _flat_axis_index(basics.mesh(), ax)
    was_bool = v.dtype == jnp.bool_
    if was_bool:
        v = v.astype(jnp.int8)
    out = lax.psum(jnp.where(idx == root, v, jnp.zeros_like(v)), ax)
    return out.astype(jnp.bool_) if was_bool else out


def broadcast_(tensor, root_rank: int = 0, *, axis=None, name=None):
    return broadcast(tensor, root_rank, axis=axis, name=name)


def broadcast_async(tensor, root_rank: int = 0, *, axis=None, name=None):
    from horovod_tpu.core import REQUEST_BROADCAST

    h = _core_enqueue(
        name, tensor, REQUEST_BROADCAST, axis=axis, root_rank=root_rank
    )
    if h is not None:
        return h
    return _async(lambda: broadcast(tensor, root_rank, axis=axis), name)


broadcast_async_ = broadcast_async


def broadcast_object(obj, root_rank: int = 0, *, name=None):
    """Broadcast a picklable object (reference ``torch/__init__.py:609-648``)."""
    basics._require_init()
    if basics.process_size() == 1:
        return pickle.loads(pickle.dumps(obj))
    from horovod_tpu.ops import hostlocal

    return hostlocal.broadcast_object(obj, root_rank, basics.data_axis())


# --------------------------------------------------------------------------
# TPU-native extensions (beyond the 0.19.2 surface; used by
# horovod_tpu.parallel for sequence/expert parallelism)


@_jit_scoped
def alltoall(tensor, *, axis=None, name=None):
    """All-to-all: rank i sends chunk j of its tensor to rank j. Not in the
    0.19.2 reference (added upstream in 0.20); first-class here because
    sequence/expert parallelism needs it. dim0 must be divisible by size."""
    ax = _axis(axis)
    if _is_tracer(tensor):
        if not _axis_bound(ax):
            raise ValueError(
                "alltoall is rank-dependent and requires a bound mesh axis; "
                "call it inside shard_map over the data axis."
            )
        k = tensor.shape[0]
        n = _axis_size(ax)
        g = tensor.reshape((n, k // n) + tensor.shape[1:])
        r = lax.all_to_all(g, ax, split_axis=0, concat_axis=0)
        return r.reshape((k,) + r.shape[2:])
    if _hostlocal_mode(tensor):
        from horovod_tpu.ops import hostlocal

        _record_eager_op("alltoall", (_as_array(tensor),), axis=ax)
        return hostlocal.alltoall(tensor, ax)
    tensor = _as_array(tensor)
    if not _is_stacked(tensor, ax):
        raise ValueError("eager alltoall requires a stacked [size, ...] array")
    fn = _eager_alltoall_fn(basics.mesh(), ax)
    _record_eager_op("alltoall", (tensor,), axis=ax)
    return fn(tensor)


def alltoall_async(tensor, *, axis=None, name=None):
    from horovod_tpu.core import REQUEST_ALLTOALL

    h = _core_enqueue(name, tensor, REQUEST_ALLTOALL, axis=axis)
    if h is not None:
        return h
    return _async(lambda: alltoall(tensor, axis=axis), name)


def handle_average_backwards_compatibility(op, average):
    """Resolve the deprecated ``average=`` kwarg against ``op=`` (reference
    ``horovod/common/util.py`` ``handle_average_backwards_compatibility``):
    exactly one may be given; ``average`` defaults to True -> Average."""
    if op is not None:
        if average is not None:
            raise ValueError(
                "The op parameter supersedes average; provide only one."
            )
        return op
    return Average if (average is None or average) else Sum


def reducescatter_async(tensor, op: ReduceOp = Average, *, axis=None,
                        name=None):
    """Async reduce-scatter returning a handle; with the native core
    attached and a `name`, rides the negotiation cycle as
    REQUEST_REDUCESCATTER (the dispatch in ``core.py`` was previously
    reachable only in principle)."""
    from horovod_tpu.core import REQUEST_REDUCESCATTER

    _check_rs_op(op)

    h = _core_enqueue(name, tensor, REQUEST_REDUCESCATTER, axis=axis, op=op)
    if h is not None:
        return h
    return _async(lambda: reducescatter(tensor, op, axis=axis), name)


def _check_rs_op(op):
    if op not in (Average, Sum):
        raise ValueError(
            f"reducescatter supports Average/Sum, got {op!r} (Adasum's "
            "pairwise projections have no scatter formulation)"
        )


def _pad_rows(tensor, n: int, dim: int = 0):
    """Zero-pad `dim` up to the next multiple of `n` (the reduce-scatter
    padding path: SPMD shapes are static, so Horovod's "first ranks get one
    extra row" uneven split cannot be expressed — the XLA-native spelling
    pads with zero rows that land in the tail ranks' shards)."""
    rows = tensor.shape[dim]
    pad = (-rows) % n
    if not pad:
        return tensor
    widths = [(0, 0)] * tensor.ndim
    widths[dim] = (0, pad)
    return jnp.pad(tensor, widths)


@_jit_scoped
def reducescatter(tensor, op: ReduceOp = Average, *, axis=None, name=None):
    """Reduce-scatter along dim 0 (upstream 0.21 feature; here it is also the
    building block of hierarchical allreduce, reference
    ``nccl_operations.cc:162-354``, and of the ZeRO-1 sharded optimizer).

    On the single-controller paths (in-jit and eager) a leading dim not
    divisible by the axis size is zero-padded up to the next multiple
    before the scatter (each rank then holds ``ceil(rows/N)`` rows; the
    pad rows — all zeros — land in the tail ranks' shards). The
    multi-process host-local path still requires dim 0 divisible by the
    process count (its shard exchange is row-exact across hosts). On the
    eager path the (padded) input buffer is donated to the launch when
    ``HOROVOD_DONATE_FUSED`` is on (accelerator default) — treat the input
    as consumed, as with every Horovod collective."""
    _check_rs_op(op)
    ax = _axis(axis)
    n = _axis_size(ax)
    if _is_tracer(tensor):
        if not _axis_bound(ax):
            raise ValueError(
                "reducescatter is rank-dependent and requires a bound mesh "
                "axis; call it inside shard_map over the data axis."
            )
        tensor = _pad_rows(tensor, n)
        out = lax.psum_scatter(tensor, ax, scatter_dimension=0, tiled=True)
        return _div(out, n) if op == Average else out
    if _hostlocal_mode(tensor):
        from horovod_tpu.ops import hostlocal

        _record_eager_op("reducescatter", (_as_array(tensor),), axis=ax)
        return hostlocal.reducescatter(tensor, op, ax)
    tensor = _as_array(tensor)
    stacked = _is_stacked(tensor, ax)
    # stacked [size, rows, ...]: the per-rank tensor's dim 0 is dim 1 here
    tensor = _pad_rows(tensor, n, dim=1 if stacked else 0)
    fn = _eager_reducescatter_fn(basics.mesh(), ax, stacked)
    _record_eager_op("reducescatter", (tensor,), axis=ax)
    out = fn(tensor)
    return _div(out, n) if op == Average else out
