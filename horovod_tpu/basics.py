"""Process/mesh bootstrap and identity queries.

TPU-native analog of Horovod's ``HorovodBasics`` (reference
``horovod/common/basics.py:22-131`` + the C side ``horovod_init/_rank/_size/...``
``horovod/common/operations.cc:661-799``).

Identity model
--------------
Horovod runs one process per accelerator; ``rank`` is the process index. On TPU
the natural unit of data parallelism is the *chip*, and a single process owns
several chips (or, single-controller, all of them). We therefore define:

- ``size()``    — number of mesh slices along the **data axis** (the DP degree);
                  equals total chips for the default 1-D mesh. This is what
                  Horovod calls ``size`` (``basics.py:100-106``).
- ``rank()``    — data-axis coordinate of this process's first local device.
                  Single-controller: always 0. Multi-host process-major meshes:
                  process_index * chips_per_process, matching Horovod's
                  rank-major allocation (``run/gloo_run.py:54-112``).
- ``local_size()/local_rank()`` — processes on this host / this process's
  slot index, from launcher env when exported (Horovod ``basics.py:108-122``);
  single-process default: chips owned / 0. ``local_chip_count()`` is always
  the chips-owned figure (hostlocal tiling).
- ``cross_rank()/cross_size()`` — host-level coordinates (Horovod's CROSS
  communicator, ``common/common.h:111-115``).

Build/feature queries (`*_built`) mirror ``horovod_*_built`` in
``operations.cc:713-746``: the only data-plane backend here is XLA.
"""

from __future__ import annotations

import atexit
import dataclasses
import logging
import os
import threading
from typing import Optional, Sequence

import jax
import numpy as np

from horovod_tpu import profiler as _profiler
from horovod_tpu.parallel.mesh import build_mesh, DATA_AXIS

logger = logging.getLogger("horovod_tpu")


@dataclasses.dataclass
class _GlobalState:
    """Python-side analog of HorovodGlobalState (reference
    ``horovod/common/global_state.h:42-122``). Device-side state (fusion
    buffers) lives in the core/ops modules; control-plane state (tensor queue,
    controller) lives in the native core once attached."""

    initialized: bool = False
    mesh: Optional[jax.sharding.Mesh] = None
    #: mesh of the previous init, kept across shutdown: a re-init whose
    #: mesh differs (elastic resize) must drop the compiled-eager-kernel
    #: caches keyed by the old one; a re-init on the SAME mesh keeps them
    #: (meshes over identical devices/axes compare equal — the caches are
    #: warm hits, and clearing would recompile every eager collective)
    prev_mesh: Optional[jax.sharding.Mesh] = None
    #: axis name, or a (cross, local) tuple on host-hierarchy meshes
    data_axis: "str | tuple" = DATA_AXIS
    # process-level identity (multi-host)
    process_index: int = 0
    process_count: int = 1
    local_device_count: int = 0
    local_process_rank: int = 0
    local_slot_count: int = 0  # launcher slots on this host (0 = not launched)
    homogeneous: bool = True
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    core: object = None  # native core handle (attached by horovod_tpu.core)


_state = _GlobalState()
_atexit_registered = False


@_profiler.annotate("hvd.init", record=True)
def init(
    mesh: Optional[jax.sharding.Mesh] = None,
    *,
    axes: Optional[dict] = None,
    devices: Optional[Sequence] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    comm=None,
    native_core: Optional[bool] = None,
) -> None:
    """Initialize the framework. Analog of ``hvd.init()`` (reference
    ``horovod/common/basics.py:33-65`` -> ``operations.cc:604-650``).

    Where Horovod spawns the C++ background negotiation thread and rendezvouses
    via Gloo/MPI, we (a) optionally wire up multi-host JAX via
    ``jax.distributed.initialize`` (the TPU-native rendezvous; coordinates read
    from args or ``HVD_COORDINATOR_ADDR``/``HVD_NUM_PROCESSES``/``HVD_PROCESS_ID``
    env set by the launcher, mirroring ``HOROVOD_GLOO_RENDEZVOUS_ADDR`` et al.,
    reference ``run/gloo_run.py:152-163``), and (b) build the device mesh that
    every collective lowers onto.

    Args:
      mesh: pre-built ``jax.sharding.Mesh`` to adopt. Must contain the data
        axis (default ``"data"``).
      axes: mesh axes spec passed to :func:`build_mesh`, e.g.
        ``{"data": -1}`` (default) or ``{"data": -1, "model": 4}``.
      devices: subset of devices to use (Horovod's ``init(ranks)`` subset,
        ``basics.py:33-42``).
      coordinator_address/num_processes/process_id: multi-host wire-up.
      comm: unsupported (MPI communicator in the reference); raises if not None.
    """
    # set-up's compile pipeline into the metrics registry, once a process
    _profiler.book_compiles()
    # HOROVOD_XLA_FLAGS_PRESET: arm the async-collective/latency-hiding
    # XLA flags BEFORE the first backend touch below (XLA reads XLA_FLAGS
    # exactly once, at backend creation) — the env-knob spelling of
    # horovod_tpu.tuning.apply_xla_flags, a no-op when unset
    from horovod_tpu import tuning as _tuning

    _tuning.maybe_apply_from_env()
    if comm is not None:
        if not isinstance(comm, (list, tuple)):
            raise ValueError(
                "horovod_tpu does not speak MPI; pass a device subset via "
                "`devices=`/`comm=[ranks]` or a prebuilt `mesh=` instead of "
                "an MPI communicator."
            )
        # reference init(ranks) subset (basics.py:33-42): rank i -> chip i
        if devices is not None:
            raise ValueError("pass either `comm` (rank subset) or `devices`")
        all_devices = jax.devices()
        devices = [all_devices[i] for i in comm]
    with _state.lock:
        if _state.initialized:
            return

        coord = coordinator_address or os.environ.get("HVD_COORDINATOR_ADDR")
        nproc = num_processes or _env_int("HVD_NUM_PROCESSES")
        pid = process_id if process_id is not None else _env_int("HVD_PROCESS_ID")
        if coord and nproc and nproc > 1:
            # Must run before anything initializes the XLA backend (so no
            # jax.process_count() guard here — that call itself would
            # initialize the backend and make this fail).
            try:
                # CPU multi-process needs gloo collectives to federate device
                # views across processes (TPU runtimes federate natively; the
                # flag only affects CPU-client creation, so set it whenever
                # multi-process — the default platform may resolve to cpu).
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            except Exception as e:
                import logging

                logging.getLogger("horovod_tpu").warning(
                    "could not enable gloo CPU collectives (%s); "
                    "multi-process CPU collectives may fail", e
                )
            try:
                kw = {}
                start_timeout = _env_int("HVD_START_TIMEOUT")
                if start_timeout:
                    kw["initialization_timeout"] = start_timeout
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=nproc,
                    process_id=pid or 0,
                    **kw,
                )
            except RuntimeError as e:  # already initialized by the caller
                if "already" not in str(e).lower():
                    raise

        if mesh is not None and axes is not None:
            raise ValueError("pass either `mesh` or `axes`, not both")
        if mesh is None:
            mesh = build_mesh(axes=axes, devices=devices)
        if _state.prev_mesh is not None and _state.prev_mesh != mesh:
            # live-process re-init onto a DIFFERENT mesh (elastic resize):
            # the compiled-eager-kernel caches are keyed by the old mesh —
            # unreachable hits that pin stale programs and device buffers
            try:
                from horovod_tpu.ops import collective as _C

                _C.clear_eager_caches()
            except Exception as e:
                logger.debug("eager-cache clear on re-init failed: %s", e)
        _state.prev_mesh = mesh
        _state.mesh = mesh
        from horovod_tpu.parallel.mesh import CROSS_AXIS, LOCAL_AXIS

        if DATA_AXIS in mesh.axis_names:
            _state.data_axis = DATA_AXIS
        elif {CROSS_AXIS, LOCAL_AXIS} <= set(mesh.axis_names):
            # host-hierarchy mesh: the Horovod GLOBAL communicator is BOTH
            # axes — defaulting to just one would silently reduce over
            # hosts (or chips) only
            _state.data_axis = (CROSS_AXIS, LOCAL_AXIS)
        else:
            _state.data_axis = mesh.axis_names[0]
        _state.process_index = jax.process_index()
        _state.process_count = jax.process_count()
        _state.local_device_count = len(
            [d for d in mesh.devices.flat if d.process_index == _state.process_index]
        ) or jax.local_device_count()
        counts = _per_process_device_counts(mesh)
        _state.homogeneous = len(set(counts)) <= 1
        # Launcher-assigned slot coordinates within the host: -H host:2 puts
        # two processes on one host, so these cannot be hardwired (reference
        # derives them per slot, ``basics.py:108-122``, ``run/gloo_run.py:54-112``).
        # local_slot_count (HOROVOD_LOCAL_SIZE) is the number of *processes*
        # on this host — distinct from local_device_count (chips owned by
        # this process, which hostlocal tiling uses) — so that
        # local_rank() < local_size() always holds.
        _state.local_process_rank = _env_int("HOROVOD_LOCAL_RANK") or 0
        _state.local_slot_count = _env_int("HOROVOD_LOCAL_SIZE") or 0

        # Optionally attach the native control-plane core (csrc/): named
        # async collectives then go through the background negotiation cycle
        # (tensor fusion, response cache, stall detection, timeline) instead
        # of direct dispatch. Mandatory for multi-process named ops.
        use_core = native_core
        if use_core is None:
            use_core = os.environ.get("HOROVOD_NATIVE_CORE", "0") == "1"
        if use_core:
            from horovod_tpu.core import NativeCore

            _state.core = NativeCore(
                rank=_state.process_index,
                size=_state.process_count,
                coordinator_host=os.environ.get("HVD_CORE_COORD_ADDR"),
                coordinator_port=int(
                    os.environ.get("HVD_CORE_COORD_PORT", "29500")
                ),
            )
        _state.initialized = True

        # Opt-in metrics endpoint (HOROVOD_METRICS_PORT), rank 0 only —
        # the same coordinator-only convention as the reference Timeline.
        # Never let observability take down init.
        try:
            from horovod_tpu.observability import exporters, trace

            # every rank records for the fleet merge (the span ring bounds
            # memory; ranks != 0 flush to a per-rank sidecar at shutdown).
            # HOROVOD_TRACE_ALL_RANKS=0 restores the PR-1 coordinator-only
            # mode: ranks != 0 never record (no append cost, no sidecar).
            all_ranks = os.environ.get(
                "HOROVOD_TRACE_ALL_RANKS", "1"
            ).lower() not in ("0", "false")
            trace.set_recording(_state.process_index == 0 or all_ranks)
            if _state.process_index == 0:
                exporters.maybe_start_http_server()
            # hang watchdog: armed iff HOROVOD_HANG_TIMEOUT > 0 (the
            # flight ring itself is always-on and needs no arming)
            from horovod_tpu.observability import flight

            flight.maybe_arm_watchdog()
        except Exception as e:
            # observability must never take down init — but it should
            # say why it is missing
            logger.debug("observability bring-up skipped: %s", e)
    global _atexit_registered
    if not _atexit_registered:
        # once per process, not once per init: a shutdown() → init() cycle
        # (elastic re-init) must not stack a new atexit entry each
        # generation — the old handles would otherwise accumulate forever
        atexit.register(shutdown)
        _atexit_registered = True


def flush_timeline() -> None:
    """Flush the host trace ring: process rank 0 merges into the
    ``HOROVOD_TIMELINE`` file the native core wrote; every other rank
    writes its per-rank sidecar (``<HOROVOD_TIMELINE>.rank<r>.json``) for
    the skew-corrected fleet merge. Shared by :func:`shutdown` and the
    SIGTERM drain in :mod:`horovod_tpu.resilience.loop` — a preempted run
    must keep its spans, not only its weights."""
    from horovod_tpu.observability import trace

    idx = _state.process_index
    if idx == 0:
        trace.flush()
    else:
        base = os.environ.get("HOROVOD_TIMELINE")
        if base:
            trace.flush(f"{base}.rank{idx}.json")


def shutdown() -> None:
    """Analog of ``hvd.shutdown()`` (reference ``basics.py:67-73``).

    Safe to follow with a fresh :func:`init` on the same live process (the
    elastic world-size path re-forms the mesh this way): the native core
    handle is released and the outstanding-collective name set is cleared
    (an async op left in flight at death must not poison the next init
    with DUPLICATE_NAME). The compiled-eager-kernel caches survive — a
    re-init on an equal mesh reuses them warm; :func:`init` drops them
    only when the new mesh actually differs (elastic resize).
    """
    with _state.lock:
        if not _state.initialized:
            return
        if _state.core is not None:
            try:
                _state.core.shutdown()
            except Exception as e:
                logger.debug("native core shutdown failed: %s", e)
            _state.core = None
        # Merge buffered host spans into the (now closed) native timeline
        # file — rank 0, the rank whose file the core wrote; every other
        # rank flushes its buffer to a per-rank sidecar
        # (<HOROVOD_TIMELINE>.rank<r>.json) for the skew-corrected fleet
        # merge (observability.clock.merge_rank_traces).
        try:
            flush_timeline()
        except Exception as e:
            logger.debug("timeline flush at shutdown failed: %s", e)
        # flight ring: disarm the hang watchdog (a re-init re-arms it for
        # the new generation) and push any pending events to the sidecar
        try:
            from horovod_tpu.observability import flight

            flight.disarm_watchdog()
            flight.flush()
        except Exception as e:
            logger.debug("flight flush at shutdown failed: %s", e)
        # The LAST step's schedule record only publishes at the next step
        # boundary — which never comes. Flush it here so a divergence at
        # the final step (the crash-adjacent case) is still named.
        try:
            from horovod_tpu.analysis import sanitizer as _sanitize

            _sanitize.flush()
        except Exception as e:
            logger.debug("sanitizer flush at shutdown failed: %s", e)
        # same last-boundary problem for the numerics guard's lagged
        # standalone verdict: the final step has no next boundary
        try:
            from horovod_tpu.resilience import numerics as _numerics

            _numerics.flush_staged()
        except Exception as e:
            logger.debug("numerics flush at shutdown failed: %s", e)
        try:
            from horovod_tpu.ops import collective as _C

            _C.clear_outstanding_names()
        except Exception as e:
            logger.debug("outstanding-name clear at shutdown failed: %s", e)
        _state.mesh = None
        _state.initialized = False


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _GlobalState:
    if not _state.initialized:
        # Horovod raises "Horovod has not been initialized; use hvd.init()."
        # (common/operations.cc checks initialization_done).
        raise RuntimeError(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first."
        )
    return _state


def mesh() -> jax.sharding.Mesh:
    """The global device mesh all collectives run over."""
    return _require_init().mesh


def core():
    """The attached native control-plane core, or None when running without
    it (``init(native_core=True)`` / ``hvdrun --native-core`` attach it)."""
    return _require_init().core


def data_axis() -> "str | tuple":
    """Name of the data-parallel mesh axis."""
    return _require_init().data_axis


def size() -> int:
    """DP degree: chips along the data axis (Horovod ``size()``). On a
    host-hierarchy mesh the data axis is the ``(cross, local)`` pair and
    size() is their product — the GLOBAL communicator size."""
    st = _require_init()
    if isinstance(st.data_axis, tuple):
        n = 1
        for a in st.data_axis:
            n *= st.mesh.shape[a]
        return n
    return st.mesh.shape[st.data_axis]


def rank() -> int:
    """Data-axis coordinate of this process's first local device."""
    st = _require_init()
    if st.process_count == 1:
        return 0
    devs = st.mesh.devices
    names = st.mesh.axis_names
    axes = st.data_axis if isinstance(st.data_axis, tuple) else (st.data_axis,)
    coords = np.argwhere(
        np.vectorize(lambda d: d.process_index)(devs) == st.process_index
    )
    if coords.size == 0:
        return 0
    # row-major flatten of each local device's (possibly multi-axis) data
    # coordinate; report the smallest (the process's first device)
    idxs = [names.index(a) for a in axes]
    best = None
    for row in coords:
        r = 0
        for a, i in zip(axes, idxs):
            r = r * st.mesh.shape[a] + int(row[i])
        best = r if best is None else min(best, r)
    return best


def local_size() -> int:
    """Processes on this host when the launcher exported slot coordinates
    (HOROVOD_LOCAL_SIZE); otherwise chips owned by this process (the
    TPU-native unit when one process spans a host's chips). Either way
    ``local_rank() < local_size()`` holds (reference ``basics.py:108-122``)."""
    st = _require_init()
    return st.local_slot_count or st.local_device_count


def local_chip_count() -> int:
    """Chips this process owns on the mesh — the hostlocal tiling factor.
    Distinct from :func:`local_size` under multi-slot launches (two
    one-chip processes on a host: local_size()==2, local_chip_count()==1)."""
    return _require_init().local_device_count


def local_rank() -> int:
    """Index of this process within its host's processes (reference
    ``basics.py:108-122``). 0 in the one-process-per-host TPU-native layout;
    the launcher exports ``HOROVOD_LOCAL_RANK`` per slot
    (:func:`horovod_tpu.run.hosts.slot_env`) so ``-H host:2`` style
    multi-slot hosts get distinct values."""
    return _require_init().local_process_rank


def cross_rank() -> int:
    return _require_init().process_index


def cross_size() -> int:
    return _require_init().process_count


def process_rank() -> int:
    return _require_init().process_index


def process_size() -> int:
    return _require_init().process_count


def is_homogeneous() -> bool:
    """All processes own the same number of chips (reference
    ``mpi_controller.cc:25-81`` homogeneity check)."""
    return _require_init().homogeneous


# --- health (resilience state machine) -------------------------------------


def health_state():
    """This process's :class:`~horovod_tpu.resilience.HealthState`
    (``HEALTHY → SUSPECT → DEGRADED → FATAL``), fed by the native core's
    cycle/stall signals and the retry layer. Readable before :func:`init`
    (always ``HEALTHY`` until something feeds the monitor)."""
    from horovod_tpu.resilience import health as _health

    return _health.health_state()


def health() -> dict:
    """JSON-able health snapshot (state, reason, strike count, last-beat
    age) — what the rank-0 metrics endpoint serves at ``/health``."""
    from horovod_tpu.resilience import health as _health

    return _health.snapshot()


# --- build/feature queries (reference operations.cc:713-760) ---------------


def mpi_threads_supported() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def xla_built() -> bool:
    """The one true data plane here."""
    return True


def num_rank_is_power_2(num: int) -> bool:
    """Reference ``common/util.py:163-171`` — the Adasum precondition check
    user scripts call before opting into ``op=hvd.Adasum``."""
    return num != 0 and (num & (num - 1)) == 0


def gpu_available(ext_base_name: str = None, verbose: bool = False) -> bool:
    """Reference ``common/util.py:125-128`` compat shim: is a GPU driving
    this job? Never — the accelerator here is TPU (query
    ``jax.devices()[0].device_kind`` for what is actually attached)."""
    del ext_base_name, verbose
    return False


def mpi_enabled() -> bool:
    """Runtime controller query (reference ``basics.py:151-160``): is MPI
    driving coordination? Never — no MPI exists here by design."""
    return False


def gloo_enabled() -> bool:
    """Runtime controller query (reference ``basics.py:170-179``). The TCP
    controller + KV rendezvous fill the role the reference calls gloo mode
    (its no-MPI configuration), so this answers True — consistent with
    ``hvdrun --gloo`` being an accepted no-op."""
    return True


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def _per_process_device_counts(mesh: jax.sharding.Mesh):
    counts = {}
    for d in mesh.devices.flat:
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return list(counts.values())
