"""Checkpoint/resume helpers.

In the reference, checkpointing is a documented *pattern*, not a subsystem
(SURVEY §5.4): rank 0 writes (``examples/pytorch_imagenet_resnet50.py``,
``examples/tensorflow2_keras_mnist.py``), and on restart everyone restores
rank 0's state via ``broadcast_parameters``/``broadcast_optimizer_state``
(reference ``torch/__init__.py:451-648``, ``tensorflow/__init__.py:126-152``).

This module packages that pattern TPU-natively:

- :func:`save` — rank-0-only write (every process holds the replicated
  global state, so one writer suffices); ``.npz`` + pickled treedef, with
  an atomic rename so a died-mid-write checkpoint is never loaded.
- :func:`restore` — read on every process + broadcast from root so all ranks
  resume bit-identically even if their local filesystems disagree.
- :func:`latest_step` — resume discovery, skipping corrupt or incomplete
  step directories (missing treedef, truncated ``.npz``) so resume falls
  back to the newest *valid* checkpoint instead of dying on the newest
  directory (the resilience layer's emergency-checkpoint path depends on
  this: a host killed mid-``rename`` must not poison the restart).
- :func:`attach_data_state` / :func:`detach_data_state` — the input
  pipeline's ``(epoch, step)`` cursors ride the payload
  (``"data_cursor"``): ``resilience.run``'s periodic and emergency
  checkpoints attach the registered loaders' cursors, and resume restores
  them, so a kill/resume mid-epoch reproduces the exact remaining sample
  stream (``docs/data.md``).
"""

from __future__ import annotations

import logging
import os
import pickle
import re
import tempfile
import zipfile
from typing import Any, Optional

import jax
import numpy as np

from horovod_tpu import basics
from horovod_tpu.ops import collective as C

_STEP_RE = re.compile(r"^step_(\d+)$")

logger = logging.getLogger("horovod_tpu.checkpoint")


def _is_writer() -> bool:
    """Process rank 0 writes. Before ``hvd.init`` the launcher's identity
    env decides (a launched-but-uninitialized worker must not multi-write a
    shared directory); a standalone uninitialized process is its own
    rank 0 (``resilience.run`` checkpoints without ``hvd.init``)."""
    if basics.is_initialized():
        return basics.process_rank() == 0
    return int(
        os.environ.get(
            "HVD_PROCESS_ID", os.environ.get("HOROVOD_RANK", "0")
        )
    ) == 0


def _step_dir(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step}")


def attach_data_state(payload: dict, cursors: Optional[dict] = None
                      ) -> dict:
    """Return `payload` with the input plane's loader cursors attached
    under ``"data_cursor"`` (verbatim `cursors` when given — the elastic
    driver passes its COMMITTED cursors, which may trail the live ones;
    otherwise the live registry export). Unchanged when no loader is
    registered, so states that never touch the data plane round-trip
    byte-identically."""
    if cursors is None:
        from horovod_tpu.data import sampler as _sampler

        cursors = _sampler.export_state()
    if not cursors:
        return payload
    out = dict(payload)
    out["data_cursor"] = cursors
    return out


def detach_data_state(payload: Any) -> Any:
    """Restore any ``"data_cursor"`` riding `payload` into the loader
    registry (pending until the loader registers, on a cold restart) and
    return the payload without it. Non-dict payloads pass through."""
    if not isinstance(payload, dict) or "data_cursor" not in payload:
        return payload
    payload = dict(payload)
    cursors = payload.pop("data_cursor")
    try:
        from horovod_tpu.data import sampler as _sampler

        # npz round-trips ints as 0-d arrays: coerce back
        _sampler.restore_state({
            str(name): {str(k): int(v) for k, v in cur.items()}
            for name, cur in dict(cursors).items()
        })
    except Exception:
        logger.warning("data-cursor restore failed", exc_info=True)
    return payload


def save(directory: str, step: int, state: Any, *, force: bool = False,
         fence: bool = True) -> str:
    """Write `state` (any pytree of arrays + picklable leaves) for `step`.

    Only process rank 0 writes (reference pattern: ``hvd.rank() == 0`` guard
    in every example script). With ``fence=True`` (default) all ranks then
    synchronize on the writer's status — a writer-side failure raises on
    EVERY rank instead of leaving the others hung in a barrier; that makes
    the call collective, so every rank must reach it. ``fence=False`` skips
    the status broadcast for callers that cannot assume their peers are
    still participating (the emergency checkpoint on an asymmetric
    preemption: one SIGTERMed rank must not block on ranks that are still
    training). The write is atomic either way: staged into a temp dir,
    renamed into place."""
    path = _step_dir(directory, step)
    err: Optional[BaseException] = None
    if _is_writer():
        try:
            _write_checkpoint(directory, path, step, state, force)
        except BaseException as e:
            err = e
    err_msg = repr(err) if err is not None else None
    status = _sync_status(err_msg) if fence else err_msg
    if err is not None:
        raise err
    if status is not None:
        raise RuntimeError(f"checkpoint write failed on rank 0: {status}")
    return path


def _write_checkpoint(directory, path, step, state, force):
    if os.path.exists(path):
        if not force:
            raise FileExistsError(f"checkpoint already exists: {path}")
        import shutil

        shutil.rmtree(path)
    os.makedirs(directory, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=directory, prefix=f".tmp_step_{step}_")
    try:
        leaves, treedef = jax.tree_util.tree_flatten(state)
        arrays = {}
        meta = []
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, (jax.Array, np.ndarray, np.generic)):
                arrays[f"a{i}"] = np.asarray(leaf)
                meta.append(("array", f"a{i}"))
            else:
                meta.append(("obj", leaf))
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "tree.pkl"), "wb") as f:
            pickle.dump({"treedef": treedef, "meta": meta}, f)
        os.rename(tmp, path)
    except BaseException:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
        raise


def restore(directory: str, step: Optional[int] = None, *,
            broadcast_root: int = 0) -> Any:
    """Load a checkpoint on `broadcast_root` ONLY and broadcast it, so every
    rank resumes from identical state even when the checkpoint exists solely
    on the root host's filesystem (the reference's restore-then-broadcast
    pattern, ``tensorflow/__init__.py:126-152`` docstring)."""
    multi = basics.is_initialized() and basics.process_size() > 1
    i_am_root = not multi or basics.process_rank() == broadcast_root

    d = None
    arrays = None
    err = None
    if i_am_root:
        try:
            if step is None:
                step = latest_step(directory)
                if step is None:
                    raise FileNotFoundError(
                        f"no checkpoints under {directory}"
                    )
            path = _step_dir(directory, step)
            with open(os.path.join(path, "tree.pkl"), "rb") as f:
                d = pickle.load(f)
            arrays = np.load(os.path.join(path, "arrays.npz"))
        except BaseException as e:
            err = e
    if not multi:
        if err is not None:
            raise err
    else:
        # ship structure + object leaves + array specs from root; non-root
        # never touches its local filesystem
        if i_am_root and err is None:
            spec = {
                "treedef": d["treedef"],
                "meta": d["meta"],
                "shapes": {
                    k: (arrays[k].shape, arrays[k].dtype.str)
                    for kind, k in d["meta"]
                    if kind == "array"
                },
            }
            payload = {"ok": True, "spec": spec}
        elif i_am_root:
            payload = {"ok": False, "error": repr(err)}
        else:
            payload = None
        payload = C.broadcast_object(payload, broadcast_root)
        if not payload["ok"]:
            if err is not None:
                raise err
            raise RuntimeError(
                f"checkpoint restore failed on rank {broadcast_root}: "
                f"{payload['error']}"
            )
        d = payload["spec"]

    leaves = []
    for kind, v in d["meta"]:
        if kind != "array":
            leaves.append(v)
            continue
        if multi:
            shape, dtype = d["shapes"][v]
            local = (
                np.asarray(arrays[v])
                if i_am_root
                else np.zeros(shape, np.dtype(dtype))
            )
            leaves.append(np.asarray(C.broadcast(local, broadcast_root)))
        else:
            leaves.append(arrays[v])
    return jax.tree_util.tree_unflatten(d["treedef"], leaves)


def consolidate_opt_state(opt_state, params, *, to_size: Optional[int] = None,
                          axis=None):
    """Re-pack a restored ZeRO-1 sharded optimizer state for the current
    world size.

    :func:`save` already persists the *consolidated* view of sharded
    moments — every ``[N, shard]`` leaf is materialized as the full global
    array on the writer (rank 0 owns the addressable single-controller
    view), so the checkpoint is world-size-portable by construction. What
    changes across world sizes is the *packing*: the flat per-dtype buffers
    are padded to a multiple of N, so an 8-way state does not reshape onto
    4 ranks. Call this after :func:`restore` with the freshly restored
    ``params`` (the same tree the state was initialized from)::

        state = checkpoint.restore(ckpt_dir)
        opt_state = checkpoint.consolidate_opt_state(
            state["opt_state"], state["params"])

    Delegates to :func:`horovod_tpu.optim.reshard_optimizer_state`; leaves
    without a rank axis (replicated/non-sharded state) pass through, so the
    call is safe on any optimizer state.

    ZeRO-3: when ``params`` is a :class:`horovod_tpu.optim.FsdpParams`
    (param-sharded training), pass it here *as restored* — the re-pack
    derives shapes/dtypes and the bucket plan from its metadata, so a
    param-sharded state moves across world sizes the same way (re-shard
    the params themselves with
    :func:`horovod_tpu.optim.fsdp_reshard_params` first, then consolidate
    the state against the re-packed tree)."""
    from horovod_tpu.optim import reshard_optimizer_state

    return reshard_optimizer_state(
        opt_state, params, to_size=to_size, axis=axis)


def state_nbytes(state: Any) -> int:
    """Raw array bytes a full checkpoint of `state` persists (the ``.npz``
    member payload, before zip framing) — the denominator of the serving
    layer's delta-vs-full-checkpoint wire comparison
    (``tools/scaling_projection.py::publish_bytes``)."""
    return sum(
        np.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(state)
        if isinstance(leaf, (jax.Array, np.ndarray, np.generic))
    )


def is_valid_checkpoint(path: str) -> bool:
    """Is `path` a loadable ``step_N`` directory? ``tree.pkl`` must
    unpickle, every ``.npz`` member must read back intact (zipfile
    CRC-checks each member as it is decompressed — a truncated write,
    power loss after the atomic rename, or a torn copy fails here
    instead of at ``restore``), and no float leaf may carry NaN/Inf — a
    checkpoint of numerically poisoned state is skipped exactly like a
    corrupt one, so resume/rollback can never land training (or the
    weight publisher's consolidation) back on poison. One full read of
    the archive covers both checks; a resume pays roughly one extra read
    of the newest checkpoint — the price of never dying on (or resuming
    into) a bad one. States that legitimately carry non-finite leaves
    (additive ``-inf`` mask buffers, ``inf`` best-loss trackers) opt out
    of the poison sweep with ``HOROVOD_CHECKPOINT_FINITE_CHECK=0`` —
    CRC validation still runs."""
    return _checkpoint_invalid_reason(path) is None


def _checkpoint_invalid_reason(path: str) -> Optional[str]:
    """None when `path` is a valid checkpoint; otherwise ``"corrupt"``
    (unreadable/torn/CRC failure) or ``"nonfinite"`` (intact archive
    rejected only by the finiteness sweep) — resume uses the distinction
    to tell a config problem (a model that legitimately stores non-finite
    leaves) apart from real corruption."""
    import zlib

    from horovod_tpu.resilience.numerics import (
        array_finite, checkpoint_finite_check_enabled)

    finite_check = checkpoint_finite_check_enabled()

    tree = os.path.join(path, "tree.pkl")
    npz = os.path.join(path, "arrays.npz")
    if not (os.path.isfile(tree) and os.path.isfile(npz)):
        return "corrupt"
    try:
        with open(tree, "rb") as f:
            pickle.load(f)
    except Exception:
        return "corrupt"
    if not finite_check:
        # no poison sweep wanted: stream every member through zipfile's
        # decompress-time CRC check instead of np.load-materializing the
        # arrays — validation of a multi-GB checkpoint must not allocate
        # its largest member on a small-RAM resume host
        try:
            with zipfile.ZipFile(npz) as zf:
                for name in zf.namelist():
                    with zf.open(name) as m:
                        while m.read(1 << 20):
                            pass
        except (zipfile.BadZipFile, zlib.error, EOFError, OSError,
                ValueError) as e:
            logger.warning("checkpoint %s is corrupt (%s)", path, e)
            return "corrupt"
        return None
    try:
        with np.load(npz) as z:
            for k in z.files:
                try:
                    a = z[k]  # full member read: zipfile verifies the CRC
                except (zipfile.BadZipFile, zlib.error, EOFError,
                        OSError) as e:
                    logger.warning(
                        "checkpoint %s member %s is corrupt (%s)",
                        path, k, e)
                    return "corrupt"
                except Exception as e:
                    # a member np.load cannot materialize (object dtype
                    # under allow_pickle=False, exotic custom dtypes)
                    # must still be CRC-verified — stream the raw member
                    # (zipfile checks the CRC as it decompresses), the
                    # coverage the old testzip() gave — without failing
                    # an intact archive over the dtype itself
                    logger.debug(
                        "finiteness sweep skipped member %s: %s", k, e)
                    try:
                        zf = getattr(z, "zip", None)
                        if zf is not None:
                            name = (
                                k if k in zf.namelist() else k + ".npy"
                            )
                            with zf.open(name) as m:
                                while m.read(1 << 20):
                                    pass
                    except Exception as e2:
                        logger.warning(
                            "checkpoint %s member %s is corrupt (%s)",
                            path, k, e2)
                        return "corrupt"
                    continue
                if not array_finite(a):
                    logger.warning(
                        "checkpoint %s carries non-finite values in %s; "
                        "treating it as invalid", path, k,
                    )
                    return "nonfinite"
    except (zipfile.BadZipFile, OSError, ValueError, EOFError):
        return "corrupt"
    return None


def _step_listing(directory: str) -> list:
    if not os.path.isdir(directory):
        return []
    return sorted(
        int(m.group(1))
        for name in os.listdir(directory)
        if (m := _STEP_RE.match(name))
    )


def _warn_all_nonfinite(directory: str, reasons: list) -> None:
    """Every candidate was rejected and ONLY by the finiteness sweep: that
    is a config problem (a model that legitimately stores non-finite
    leaves invalidates every checkpoint it writes), not corruption — and
    silently restarting from step 0 would be how the operator finds out.
    Name the escape hatch loudly."""
    if reasons and all(r == "nonfinite" for r in reasons):
        logger.error(
            "ALL %d checkpoints under %s were rejected solely by the "
            "non-finite sweep — resume will restart from scratch. If your "
            "model legitimately stores non-finite leaves (additive -inf "
            "mask buffers, inf best-loss trackers), set "
            "HOROVOD_CHECKPOINT_FINITE_CHECK=0.",
            len(reasons), directory,
        )


def valid_steps(directory: str) -> list:
    """Ascending step numbers of the *valid* checkpoints under `directory`;
    corrupt/incomplete ones are skipped with a warning. Validates every
    directory — use :func:`latest_step` when only the newest is needed."""
    steps = []
    reasons = []
    for s in _step_listing(directory):
        reason = _checkpoint_invalid_reason(_step_dir(directory, s))
        if reason is None:
            steps.append(s)
        else:
            reasons.append(reason)
            logger.warning(
                "skipping %s checkpoint %s",
                reason, _step_dir(directory, s),
            )
    if not steps:
        _warn_all_nonfinite(directory, reasons)
    return steps


def latest_step(directory: str) -> Optional[int]:
    """Highest step with a complete, *valid* checkpoint (corrupt or
    incomplete ``step_N`` directories are skipped, so resume falls back to
    the newest checkpoint that can actually be loaded). Validation walks
    newest-first and stops at the first loadable one — a directory of N
    retained checkpoints costs one CRC sweep, not N."""
    reasons = []
    for s in reversed(_step_listing(directory)):
        reason = _checkpoint_invalid_reason(_step_dir(directory, s))
        if reason is None:
            return s
        reasons.append(reason)
        logger.warning(
            "skipping %s checkpoint %s",
            reason, _step_dir(directory, s),
        )
    _warn_all_nonfinite(directory, reasons)
    return None


def _sync_status(err_msg: Optional[str]) -> Optional[str]:
    """Cross-process fence carrying the writer's status: every rank learns
    whether the write succeeded (None) or failed (the error string), so a
    writer-side exception can never strand the other ranks in a barrier."""
    if basics.is_initialized() and basics.process_size() > 1:
        return C.broadcast_object(err_msg, 0)
    return err_msg


class CheckpointManager:
    """Keep-last-N rotation over :func:`save`/:func:`restore` — the
    convenience layer orbax users expect, on the rank-0-writer pattern.

    ``save(..., asynchronous=True)`` overlaps the disk write with training
    (the orbax async pattern, idiomatic on TPU where the step loop should
    never stall on host IO): the device→host snapshot is taken synchronously
    — the state the checkpoint captures is the state at the call — and the
    serialize+write+rotate runs on a background thread. The writer's status
    is fenced across ranks in :meth:`wait_until_finished`, which the next
    ``save``/``restore`` calls implicitly; like every fence here it is a
    collective when ``process_size() > 1``, so all ranks must reach it in
    the same order (never call it from only one rank)."""

    def __init__(self, directory: str, *, max_to_keep: int = 3):
        self.directory = directory
        self.max_to_keep = max_to_keep
        self._pending = None  # (thread | None, [err]) of the in-flight save

    def save(self, step: int, state: Any, *, force: bool = False,
             asynchronous: bool = False) -> str:
        self.wait_until_finished()
        if not asynchronous:
            path = save(self.directory, step, state, force=force)
            self._rotate()
            return path

        path = _step_dir(self.directory, step)
        thread = None
        err_box: list = []
        if _is_writer():
            # Snapshot errors go through err_box + the fence too (never raise
            # before _pending is set): a writer that raised here while the
            # other ranks queued up for the status broadcast would strand
            # them in the collective.
            try:
                # np.array copies: a np.asarray view would let later in-place
                # mutation of host arrays leak into the background write
                snapshot = jax.tree_util.tree_map(
                    lambda x: np.array(x)
                    if isinstance(x, (jax.Array, np.ndarray, np.generic))
                    else x,
                    state,
                )
            except BaseException as e:
                err_box.append(e)
            else:

                def _work():
                    try:
                        _write_checkpoint(
                            self.directory, path, step, snapshot, force)
                        self._rotate()
                    except BaseException as e:  # surfaced at the fence
                        err_box.append(e)

                import threading

                # non-daemon: an interpreter exiting without an explicit
                # wait_until_finished still joins the thread, so the final
                # checkpoint's atomic rename lands instead of being lost
                thread = threading.Thread(
                    target=_work, name=f"hvd-ckpt-save-{step}", daemon=False)
                thread.start()
        self._pending = (thread, err_box)
        return path

    def wait_until_finished(self) -> None:
        """Block until the in-flight async save (if any) completes, then
        fence the writer's status across ranks — a writer-side failure
        raises on every rank. Collective when ``process_size() > 1``."""
        if self._pending is None:
            return
        thread, err_box = self._pending
        self._pending = None
        if thread is not None:
            thread.join()
        err = err_box[0] if err_box else None
        status = _sync_status(repr(err) if err is not None else None)
        if err is not None:
            raise err
        if status is not None:
            raise RuntimeError(f"checkpoint write failed on rank 0: {status}")

    def _rotate(self) -> None:
        if not (_is_writer() and self.max_to_keep):
            return
        import shutil

        steps = sorted(
            s
            for name in os.listdir(self.directory)
            if (m := _STEP_RE.match(name)) and (s := int(m.group(1))) >= 0
        )
        for old in steps[: -self.max_to_keep]:
            shutil.rmtree(_step_dir(self.directory, old), ignore_errors=True)

    def restore(self, step: Optional[int] = None) -> Any:
        self.wait_until_finished()
        return restore(self.directory, step)

    def latest_step(self) -> Optional[int]:
        self.wait_until_finished()
        return latest_step(self.directory)
