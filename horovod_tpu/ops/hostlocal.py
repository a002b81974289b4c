"""Multi-process eager collectives on host-local values.

This is the Horovod programming model proper (reference
``horovod/torch/mpi_ops.py``: every *process* passes its own tensor and
receives the cross-process result): under multi-controller JAX each process
owns ``local_chip_count()`` chips of the global mesh, and a host-local (numpy /
single-device) array is that process's contribution.

Mapping onto the chip-level data axis: the local value is tiled over the
process's local chips and assembled into a global ``[n_chips, ...]`` array via
``multihost_utils.host_local_array_to_global_array``; a chip-level ``psum``
then yields ``local_size * (sum over processes)``, so process-level Sum
divides by ``local_chip_count`` and process-level Average by ``n_chips`` — both
exact. Broadcast/allgather slice the tiling back out. This keeps one mesh and
one collective implementation for both the SPMD in-jit path and the
process-eager path.

Device order is process-major (JAX orders ``jax.devices()`` by process
index), matching the reference's rank-major slot allocation
(``run/gloo_run.py:54-112``).
"""

from __future__ import annotations

import pickle
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as P

from horovod_tpu import basics


def is_global_array(x) -> bool:
    """True iff x is a jax.Array already placed on the global mesh (the SPMD
    path); host-local numpy/scalars and single-device arrays are 'mine'."""
    from horovod_tpu.ops import collective as C

    return C._named_sharding(x) is not None


def _stack_local(x, ax: str):
    """Tile this process's value over its local chips and build the global
    stacked [n_chips, ...] array sharded over `ax`."""
    mesh = basics.mesh()
    ls = basics.local_chip_count()
    local = np.repeat(np.asarray(x)[None], ls, axis=0)
    return multihost_utils.host_local_array_to_global_array(local, mesh, P(ax))


def allreduce(x, op, ax: str):
    """Process-level allreduce; returns the reduced value replicated.

    The collective runs on the *flattened* tensor: a join()ed process
    zero-backfills from response metadata that only records element counts
    (``core.py::_execute_backfilled``), so flat-by-construction contributions
    from joined ranks always shape-match the live ranks' here.
    """
    from horovod_tpu.ops import collective as C

    mesh = basics.mesh()
    x = jnp.asarray(x)
    shape = x.shape
    g = _stack_local(jnp.reshape(x, (-1,)), ax)
    fn = C._eager_allreduce_fn(mesh, ax, True, 1)
    (out,) = fn(g)
    out = jnp.squeeze(out, axis=0)
    if op == C.Sum:
        out = C._div(out, basics.local_chip_count())
    elif op == C.Average:
        out = C._div(out, C._axis_size(ax))  # product for tuple axes
    else:
        raise ValueError(f"unsupported op for host-local allreduce: {op}")
    return jnp.reshape(out, shape)


def _allgather_equal(x, ax: str):
    """Allgather of same-shaped per-process tensors (concat along dim 0)."""
    from horovod_tpu.ops import collective as C

    mesh = basics.mesh()
    ls = basics.local_chip_count()
    g = _stack_local(x, ax)
    fn = C._eager_allgather_fn(mesh, ax, True, 1)
    (out,) = fn(g)  # [n_chips, *shape]; every ls-th row is one process
    out = out[::ls]  # [n_procs, *shape]
    return out.reshape((out.shape[0] * out.shape[1],) + out.shape[2:])


def allgather(x, ax: str):
    """Process-level allgather: concat per-process tensors along dim 0.

    Leading dims may DIFFER per process (reference semantics: allgather
    negotiates per-rank first-dim sizes and computes receive displacements,
    ``MPI_Allgatherv`` in ``mpi_operations.cc``): a tiny equal-shape count
    gather first, then ragged contributions are padded to the max row count
    and sliced back out after the gather."""
    x = jnp.asarray(x)
    if x.ndim == 0:
        x = x[None]
    nproc = basics.process_size()
    counts = np.asarray(
        _allgather_equal(jnp.asarray([x.shape[0]], jnp.int32), ax)
    ).reshape(nproc)
    if (counts == counts[0]).all():
        return _allgather_equal(x, ax)
    m = int(counts.max())
    pad = jnp.zeros((m - x.shape[0],) + x.shape[1:], x.dtype)
    out = np.asarray(_allgather_equal(jnp.concatenate([x, pad], axis=0), ax))
    out = out.reshape((nproc, m) + x.shape[1:])
    return jnp.concatenate(
        [jnp.asarray(out[i, : counts[i]]) for i in range(nproc)], axis=0
    )


def broadcast(x, root_proc: int, ax: str):
    """Process-level broadcast from `root_proc` (process index)."""
    from horovod_tpu.ops import collective as C

    mesh = basics.mesh()
    nproc = basics.process_size()
    if not 0 <= root_proc < nproc:
        raise ValueError(
            f"broadcast root rank {root_proc} out of range [0, {nproc})"
        )
    g = _stack_local(x, ax)
    was_bool = g.dtype == jnp.bool_
    if was_bool:
        g = g.astype(jnp.int8)
    root_coord = root_proc * basics.local_chip_count()  # process-major device order
    fn = C._eager_broadcast_fn(mesh, ax, int(root_coord))
    out = jnp.squeeze(fn(g), axis=0)
    return out.astype(jnp.bool_) if was_bool else out


def alltoall(x, ax: str):
    """Process-level alltoall: process ``r`` receives block ``r`` of every
    process's tensor, concatenated in process order (dim 0 split into
    ``process_size`` blocks).

    ``local_chip_count == 1`` runs a chip-level ``all_to_all`` directly.
    Multi-chip processes run the chip-level ``all_to_all`` on the tiled
    array when dim 0 divides the chip count: each chip then *receives* only
    ``rows`` elements (vs ``n_chips x rows`` for an allgather), and this
    process's chips collectively hold every process's block-``r`` chunk —
    duplicated ``local_chip_count`` times on the send side by the tiling,
    deduplicated in the host-side reassembly below. Falls back to
    allgather + local slice when dim 0 does not divide the chip count. The
    bandwidth-optimal path remains the in-jit SPMD ``all_to_all``.
    """
    from horovod_tpu.ops import collective as C

    mesh = basics.mesh()
    nproc = basics.process_size()
    ls = basics.local_chip_count()
    n_chips = C._axis_size(ax)
    rows = np.asarray(x).shape[0]
    if rows % nproc != 0:
        raise ValueError(
            f"alltoall dim 0 ({rows}) must be divisible by the number of "
            f"processes ({nproc})"
        )
    if ls == 1:
        g = _stack_local(x, ax)
        fn = C._eager_alltoall_fn(mesh, ax)
        out = fn(g)
        return jnp.asarray(np.asarray(out.addressable_data(0))[0])
    if rows % n_chips == 0:
        # chip-level exchange on the tiled array: chip c receives chip-chunk
        # c of every chip's (tiled) value. Process p owns chips
        # [p*ls, (p+1)*ls) (process-major device order), whose chunks
        # p*ls..(p+1)*ls-1 concatenate to exactly process-block p; sources
        # j and j+1.. within one process carry identical tiles, so one
        # source chip per process (j = q*ls) suffices.
        chunk = rows // n_chips
        g = _stack_local(x, ax)
        fn = C._eager_alltoall_fn(mesh, ax)
        out = fn(g)
        flat_devices = list(mesh.devices.reshape(-1))
        my_shards = {
            flat_devices.index(s.device): np.asarray(s.data)[0]
            for s in out.addressable_shards
        }
        p = basics.process_rank()
        blocks = []
        for q in range(nproc):
            j = q * ls  # dedup tiled sources: one chip per source process
            for m in range(ls):
                rec = my_shards[p * ls + m]
                blocks.append(rec[j * chunk:(j + 1) * chunk])
        return jnp.asarray(np.concatenate(blocks, axis=0))
    gathered = allgather(x, ax)  # [nproc * rows, ...]
    gathered = gathered.reshape((nproc, nproc, rows // nproc) + gathered.shape[1:])
    r = basics.process_rank()
    return gathered[:, r].reshape((rows,) + gathered.shape[3:])


def reducescatter(x, op, ax: str):
    """Process-level reduce-scatter: process ``r`` receives block ``r`` of
    the cross-process reduction (dim 0 split into ``process_size`` blocks).

    Multi-chip processes use the chip-level ``psum_scatter`` when dim 0
    divides the chip count — the device order is process-major, so a
    process's chips hold exactly the contiguous chip-blocks forming its
    process block; the tiling multiplies the sum by ``local_chip_count``, divided
    back out. Otherwise it falls back to allreduce + local slice.
    """
    from horovod_tpu.ops import collective as C

    mesh = basics.mesh()
    nproc = basics.process_size()
    ls = basics.local_chip_count()
    n_chips = C._axis_size(ax)
    rows = np.asarray(x).shape[0]
    if rows % nproc != 0:
        raise ValueError(
            f"reducescatter dim 0 ({rows}) must be divisible by the number "
            f"of processes ({nproc})"
        )
    if ls == 1 or rows % n_chips == 0:
        g = _stack_local(x, ax)
        fn = C._eager_reducescatter_fn(mesh, ax, True)
        out = fn(g)
        # this process's chips hold consecutive chip-blocks; concatenated
        # they are its process-level shard (process-major device order)
        flat_devices = list(mesh.devices.reshape(-1))
        shards = sorted(
            ((flat_devices.index(s.device), np.asarray(s.data))
             for s in out.addressable_shards),
            key=lambda t: t[0],
        )
        shard = jnp.concatenate([jnp.asarray(v)[0] for _, v in shards], axis=0)
        if ls > 1:
            shard = C._div(shard, ls)  # tiling contributed ls copies
        if op == C.Average:
            shard = C._div(shard, nproc)
        return shard
    reduced = allreduce(x, C.Sum, ax)  # [rows, ...] full reduction
    block = rows // nproc
    r = basics.process_rank()
    shard = reduced[r * block:(r + 1) * block]
    if op == C.Average:
        shard = C._div(shard, nproc)
    return shard


# ----------------------------------------------------------- object shuttle


def _obj_to_padded(obj):
    blob = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    return blob


def allgather_object(obj, ax: str) -> list:
    """Gather arbitrary picklable objects from every process (reference
    pattern ``torch/__init__.py:609-648``: length-allgather + padded
    byte-tensor allgather)."""
    from horovod_tpu.ops import collective as C

    blob = _obj_to_padded(obj)
    # both gathers are equal-shaped by construction — skip the ragged
    # size negotiation allgather() would prepend
    lengths = np.asarray(_allgather_equal(np.array([len(blob)], np.int32), ax))
    max_len = int(lengths.max())
    padded = np.zeros((max_len,), np.uint8)
    padded[: len(blob)] = blob
    gathered = np.asarray(_allgather_equal(padded, ax))
    gathered = gathered.reshape(basics.process_size(), max_len)
    per_process = [
        pickle.loads(gathered[i, : int(lengths[i])].tobytes())
        for i in range(basics.process_size())
    ]
    # one entry per *chip* ("rank" = chip, so len == hvd.size() regardless of
    # process count; chips of the same process hold that process's object)
    out = []
    for obj_i in per_process:
        out.extend([obj_i] * basics.local_chip_count())
    return out


def broadcast_object(obj, root_proc: int, ax: str):
    """Broadcast a picklable object from `root_proc`."""
    blob = _obj_to_padded(obj)
    length = np.asarray(
        broadcast(np.array([len(blob)], np.int32), root_proc, ax)
    )
    n = int(length[0])
    buf = np.zeros((n,), np.uint8)
    buf[: min(len(blob), n)] = blob[:n]  # non-root values are masked anyway
    out = np.asarray(broadcast(buf, root_proc, ax))
    return pickle.loads(out.tobytes())
