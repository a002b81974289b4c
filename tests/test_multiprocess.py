"""Real 2-process distributed tests through the launcher: jax.distributed
wire-up + cross-process collectives on host-local values — the reference's
``horovodrun -np 2 pytest`` pattern (SURVEY.md §4) done TPU-native (gloo CPU
collectives stand in for ICI)."""

import os

import numpy as np
import pytest

from horovod_tpu.run import runner

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TESTS_DIR)


def _worker_env():
    """Workers unpickle functions from this module by reference, so both the
    repo root and the tests dir must be importable there."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT, _TESTS_DIR, env.get("PYTHONPATH", "")]
    )
    return env


def _two_proc_collectives():
    # runs inside each launched worker process
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    results = {}
    results["size"] = hvd.size()
    results["process_size"] = hvd.process_size()
    rank = hvd.process_rank()
    results["rank"] = rank

    # allreduce: each process contributes rank+1 -> sum=3, avg=1.5
    x = np.full((2, 3), float(rank + 1), np.float32)
    results["sum"] = np.asarray(hvd.allreduce(x, hvd.Sum)).tolist()
    results["avg"] = np.asarray(hvd.allreduce(x, hvd.Average)).tolist()

    # allgather: concat per-process rows
    g = np.full((1, 2), float(rank), np.float32)
    results["gathered"] = np.asarray(hvd.allgather(g)).tolist()

    # broadcast from process 1
    b = np.array([float(rank * 10)], np.float32)
    results["bcast"] = np.asarray(hvd.broadcast(b, root_rank=1)).tolist()

    # grouped allreduce rides the same host-local path
    ga = hvd.grouped_allreduce(
        [np.array([float(rank)]), np.array([float(rank * 2)])], hvd.Sum
    )
    results["grouped"] = [np.asarray(t).tolist() for t in ga]

    # object collectives
    results["objs"] = hvd.allgather_object({"r": rank, "msg": "x" * (rank + 1)})
    results["obj_b"] = hvd.broadcast_object({"from": rank}, root_rank=0)

    # alltoall: process r sends row j to process j
    a2a = np.array([[rank, 0.0], [rank, 1.0]], np.float32)
    results["alltoall"] = np.asarray(hvd.alltoall(a2a)).tolist()

    # reducescatter: each gets its reduced shard
    rs = np.arange(4, dtype=np.float32).reshape(4, 1) + rank
    results["rs"] = np.asarray(hvd.reducescatter(rs, hvd.Sum)).tolist()

    # every worker's registry saw its own traffic (ISSUE 1 acceptance:
    # eager multi-process run -> nonzero op counts/bytes + compile-cache
    # accounting, queried through hvd.metrics, not ad hoc probes)
    results["metrics"] = {
        "allreduce_count": hvd.metrics.value("allreduce_count"),
        "allreduce_bytes": hvd.metrics.value("allreduce_bytes"),
        "allgather_count": hvd.metrics.value("allgather_count"),
        "compile_lookups": sum(
            sum(fam["samples"].values())
            for name, fam in hvd.metrics.snapshot().items()
            if name.startswith("eager_compile_cache_")
        ),
    }
    return results


def test_two_process_collectives_end_to_end():
    out = runner.run(
        _two_proc_collectives, np=2, env=_worker_env(), timeout_s=240
    )
    for rank, r in enumerate(out):
        assert r["rank"] == rank
        assert r["size"] == 2  # one CPU device per process
        assert r["process_size"] == 2
        assert r["sum"] == [[3.0] * 3] * 2
        assert r["avg"] == [[1.5] * 3] * 2
        assert r["gathered"] == [[0.0, 0.0], [1.0, 1.0]]
        assert r["bcast"] == [10.0]
        assert r["grouped"] == [[1.0], [2.0]]
        assert r["objs"] == [
            {"r": 0, "msg": "x"},
            {"r": 1, "msg": "xx"},
        ]
        assert r["obj_b"] == {"from": 0}
        # alltoall: row j of every process's tensor lands on process j
        assert r["alltoall"] == [[0.0, float(rank)], [1.0, float(rank)]]
        # reducescatter: sum_p(arange(4)+p) = [1,3,5,7]; rank r gets rows
        # [2r, 2r+2)
        assert r["rs"] == [[4.0 * rank + 1.0], [4.0 * rank + 3.0]]
        m = r["metrics"]
        # 2 allreduce calls + 1 grouped (2 tensors); sizes: 2x3 f32 twice
        # + [1]+[1] f32 grouped
        assert m["allreduce_count"] == 3
        assert m["allreduce_bytes"] == 2 * (2 * 3 * 4) + 2 * 4
        assert m["allgather_count"] >= 1  # object collectives ride it too
        assert m["compile_lookups"] >= 3


def _two_proc_train_step():
    """Full DP train step over the 2-process global mesh (SPMD jit path)."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import MLP
    from horovod_tpu.training import (
        init_model,
        make_shardmap_train_step,
        replicate,
    )

    hvd.init()
    rank = hvd.process_rank()
    model = MLP(features=(8, 4))
    tx = optax.sgd(0.1)
    rng = jax.random.PRNGKey(0)
    params, batch_stats = init_model(
        model, rng, jnp.zeros((1, 6), jnp.float32)
    )
    params = replicate(params)
    batch_stats = replicate(batch_stats)
    opt_state = replicate(tx.init(params))
    step = make_shardmap_train_step(model, tx)

    mesh = hvd.mesh()
    # per-process local batch -> global [2, 6] array sharded over data
    local_x = np.random.RandomState(rank).rand(1, 6).astype(np.float32)
    local_y = np.array([rank % 4], np.int32)
    gx = multihost_utils.host_local_array_to_global_array(
        local_x, mesh, P("data")
    )
    gy = multihost_utils.host_local_array_to_global_array(
        local_y, mesh, P("data")
    )
    params, batch_stats, opt_state, loss = step(
        params, batch_stats, opt_state, gx, gy
    )
    return float(np.asarray(loss))


def _two_proc_multichip_collectives():
    """2 processes x 2 local chips: exercises the host-local tiling math for
    local_size > 1 (one process per TPU host owning several chips)."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.process_rank()
    results = {
        "size": hvd.size(),
        "local_size": hvd.local_size(),
        "process_size": hvd.process_size(),
    }
    x = np.full((3,), float(rank + 1), np.float32)
    results["sum"] = np.asarray(hvd.allreduce(x, hvd.Sum)).tolist()
    results["avg"] = np.asarray(hvd.allreduce(x, hvd.Average)).tolist()
    g = np.full((1, 2), float(rank), np.float32)
    results["gathered"] = np.asarray(hvd.allgather(g)).tolist()
    b = np.array([float(rank * 10 + 5)], np.float32)
    results["bcast"] = np.asarray(hvd.broadcast(b, root_rank=1)).tolist()
    # alltoall / reducescatter with local_size > 1 (the TPU-native layout):
    # process r sends row j to process j / receives its reduced shard
    a2a = np.array([[rank, 0.0], [rank, 1.0]], np.float32)
    results["alltoall"] = np.asarray(hvd.alltoall(a2a)).tolist()
    # dim0 divisible by the 4 chips -> chip-level tiled exchange path
    # (each chip receives rows elements, not n_chips*rows)
    a2a4 = np.array(
        [[rank, 0.0], [rank, 1.0], [rank, 2.0], [rank, 3.0]], np.float32
    )
    results["alltoall4"] = np.asarray(hvd.alltoall(a2a4)).tolist()
    rs = np.arange(4, dtype=np.float32).reshape(4, 1) + rank
    results["rs_sum"] = np.asarray(hvd.reducescatter(rs, hvd.Sum)).tolist()
    results["rs_avg"] = np.asarray(
        hvd.reducescatter(rs, hvd.Average)
    ).tolist()
    # odd leading dim: not divisible by the 4 chips -> allreduce+slice path
    rs3 = np.full((2, 3), float(rank + 1), np.float32)
    results["rs_odd"] = np.asarray(hvd.reducescatter(rs3, hvd.Sum)).tolist()
    # adasum over host-local values: pair-combine of ones vs twos
    results["adasum"] = np.asarray(
        hvd.allreduce(np.full((4,), float(rank + 1), np.float32), hvd.Adasum)
    ).tolist()
    # grouped (fused) adasum over host-local values: one flat-concat
    # butterfly across processes with PER-TENSOR dot/norm scalars. The
    # second tensor flips sign on rank 1 so its combine coefficients differ
    # from the first's — concat-level (single-segment) scalars would give a
    # different answer, pinning the segmentation.
    sign = 1.0 if rank == 0 else -1.0
    ga, gb = hvd.grouped_allreduce(
        [
            np.full((4,), float(rank + 1), np.float32),
            np.full((2, 3), sign * float(rank + 1), np.float32),
        ],
        op=hvd.Adasum,
    )
    results["adasum_grouped"] = [
        np.asarray(ga).tolist(),
        np.asarray(gb).tolist(),
    ]
    return results


def test_two_process_multichip_collectives():
    out = runner.run(
        _two_proc_multichip_collectives, np=2, env=_worker_env(), timeout_s=240
    )
    for rank, r in enumerate(out):
        assert r["size"] == 4  # 2 processes x 2 chips
        assert r["local_size"] == 2
        assert r["process_size"] == 2
        # process-level semantics: sum over the 2 processes, not the 4 chips
        assert r["sum"] == [3.0, 3.0, 3.0]
        assert r["avg"] == [1.5, 1.5, 1.5]
        assert r["gathered"] == [[0.0, 0.0], [1.0, 1.0]]
        assert r["bcast"] == [15.0]
        # row j of every process's tensor lands on process j
        assert r["alltoall"] == [[0.0, float(rank)], [1.0, float(rank)]]
        # block p of every process's 4-row tensor, in process order
        # (chip-level tiled exchange path: dim0 % n_chips == 0)
        assert r["alltoall4"] == [
            [0.0, float(2 * rank)], [0.0, float(2 * rank + 1)],
            [1.0, float(2 * rank)], [1.0, float(2 * rank + 1)],
        ]
        # sum_p(arange(4)+p) = [1,3,5,7]; process r gets rows [2r, 2r+2)
        assert r["rs_sum"] == [[4.0 * rank + 1.0], [4.0 * rank + 3.0]]
        assert r["rs_avg"] == [
            [2.0 * rank + 0.5], [2.0 * rank + 1.5]
        ]
        # full reduce [2,3] of 1s+2s = 3s; process r gets row r
        assert r["rs_odd"] == [[3.0, 3.0, 3.0]]
        # VHDD combine of a=1s, b=2s (d=4): dot=8, |a|^2=4, |b|^2=16
        # -> ca = 1-8/8 = 0, cb = 1-8/32 = 0.75 -> 1.5s
        assert r["adasum"] == [1.5, 1.5, 1.5, 1.5]
        # per-tensor VHDD scalars: tensor A (1s vs 2s): ca=0, cb=0.75 ->
        # 1.5s; tensor B (1s vs -2s): dot=-12, |a|^2=6, |b|^2=24 -> ca=2,
        # cb=1.25 -> 2*1 + 1.25*(-2) = -0.5. Concat-level scalars would
        # yield 3.3/... instead, so this distinguishes the segmentation.
        ga, gb = r["adasum_grouped"]
        assert ga == [1.5] * 4
        assert gb == [[-0.5] * 3] * 2


def test_two_process_train_step():
    out = runner.run(
        _two_proc_train_step, np=2, env=_worker_env(), timeout_s=240
    )
    assert len(out) == 2
    # identical global loss on both processes
    assert np.isfinite(out[0])
    assert out[0] == pytest.approx(out[1])


def _two_proc_torch_and_checkpoint():
    """Regression coverage for cross-process torch state broadcast and
    checkpoint save/restore: fresh-optimizer broadcast_optimizer_state must
    not deadlock, restore must work when only rank 0 has the files, and a
    writer-side save failure must raise on every rank."""
    import os
    import shutil
    import tempfile

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import torch

    import horovod_tpu.torch as hvd
    from horovod_tpu import checkpoint as ckpt

    hvd.init()
    r = hvd.process_rank()
    results = {}

    # 1. fresh optimizer (no state): dummy step must run on EVERY rank
    torch.manual_seed(3)
    model = torch.nn.Linear(4, 2)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9),
        named_parameters=model.named_parameters(),
    )
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(opt, root_rank=0)
    results["opt_lr"] = opt.state_dict()["param_groups"][0]["lr"]

    # 2. checkpoint written by rank 0 into a rank-PRIVATE dir: non-root has
    # no files at all and must restore via broadcast
    d = os.path.join(tempfile.gettempdir(), f"hvdckpt_rank{r}")
    shutil.rmtree(d, ignore_errors=True)
    state = {"w": np.full((3,), float(r + 1), np.float32), "step": 4}
    ckpt.save(d, 4, state)
    out = ckpt.restore(d)
    results["restored_w"] = np.asarray(out["w"]).tolist()
    results["restored_step"] = out["step"]

    # 3. duplicate save without force: FileExistsError on EVERY rank
    try:
        ckpt.save(d, 4, state)
        results["dup_save"] = "no-error"
    except FileExistsError:
        results["dup_save"] = "file-exists"
    except RuntimeError as e:
        results["dup_save"] = (
            "runtime-file-exists"
            if "FileExistsError" in str(e)
            else f"runtime-other: {e}"
        )
    shutil.rmtree(d, ignore_errors=True)
    return results


def test_two_process_torch_and_checkpoint():
    out = runner.run(
        _two_proc_torch_and_checkpoint, np=2, env=_worker_env(), timeout_s=240
    )
    for r, res in enumerate(out):
        assert res["opt_lr"] == pytest.approx(0.1)
        # rank 0's state everywhere (non-root had no checkpoint files)
        assert res["restored_w"] == [1.0, 1.0, 1.0]
        assert res["restored_step"] == 4
    assert out[0]["dup_save"] == "file-exists"
    assert out[1]["dup_save"] == "runtime-file-exists"


def _two_proc_tensorflow():
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.tensorflow as hvd

    hvd.init()
    r = hvd.process_rank()
    out = {}
    out["avg"] = hvd.allreduce(
        tf.constant([float(r + 1)] * 3), op=hvd.Average).numpy().tolist()
    out["gathered"] = hvd.allgather(
        tf.constant([[float(r)]])).numpy().tolist()
    out["bcast"] = hvd.broadcast(
        tf.constant([float(r + 10)]), root_rank=0).numpy().tolist()
    # variable sync: non-root starts different, ends equal to root
    v = tf.Variable([float(r), 1.0])
    hvd.broadcast_variables([v], root_rank=0)
    out["var"] = v.numpy().tolist()
    return out


def test_two_process_tensorflow_frontend():
    results = runner.run(
        _two_proc_tensorflow, np=2, env=_worker_env(), timeout_s=600.0)
    for r in results:
        np.testing.assert_allclose(r["avg"], [1.5] * 3)
        np.testing.assert_allclose(r["gathered"], [[0.0], [1.0]])
        np.testing.assert_allclose(r["bcast"], [10.0])
        np.testing.assert_allclose(r["var"], [0.0, 1.0])


def _four_proc_collectives():
    """np=4: more ranks than any other e2e — distinct code paths in the
    bitvector AND sync (more proposer patterns), the VHDD butterfly
    (log2(4)=2 levels), and allgather displacement math."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.process_rank()
    out = {"rank": r, "size": hvd.size()}
    x = np.full((3,), float(r + 1), np.float32)
    out["sum"] = np.asarray(hvd.allreduce(x, hvd.Sum)).tolist()
    out["avg"] = np.asarray(hvd.allreduce(x, hvd.Average)).tolist()
    g = np.full((1, 2), float(r), np.float32)
    out["gathered"] = np.asarray(hvd.allgather(g)).tolist()
    out["bcast"] = np.asarray(
        hvd.broadcast(np.array([float(r)], np.float32), root_rank=2)
    ).tolist()
    # 4-rank VHDD butterfly: 2 levels (1^2, then pairs of pairs)
    out["adasum"] = np.asarray(
        hvd.allreduce(np.full((4,), float(r + 1), np.float32), hvd.Adasum)
    ).tolist()
    a2a = np.arange(4, dtype=np.float32).reshape(4, 1) + 10 * r
    out["alltoall"] = np.asarray(hvd.alltoall(a2a)).tolist()
    # ISSUE 1 acceptance: a 4-process eager allreduce run shows nonzero
    # op counters and compile-cache accounting via hvd.metrics
    out["metrics"] = {
        "allreduce_count": hvd.metrics.value("allreduce_count"),
        "allreduce_bytes": hvd.metrics.value("allreduce_bytes"),
        "cache_misses": sum(
            sum(fam["samples"].values())
            for name, fam in hvd.metrics.snapshot().items()
            if name == "eager_compile_cache_misses"
        ),
    }
    return out


@pytest.mark.slow
def test_four_process_collectives():
    out = runner.run(
        _four_proc_collectives, np=4, env=_worker_env(), timeout_s=300
    )
    import numpy as np

    for r, res in enumerate(out):
        assert res["rank"] == r and res["size"] == 4
        assert res["sum"] == [10.0] * 3  # 1+2+3+4
        assert res["avg"] == [2.5] * 3
        assert res["gathered"] == [[0.0, 0.0], [1.0, 1.0], [2.0, 2.0],
                                   [3.0, 3.0]]
        assert res["bcast"] == [2.0]
        # row j of every process's tensor lands on process j, process order
        assert res["alltoall"] == [[float(r)], [10.0 + r], [20.0 + r],
                                   [30.0 + r]]
    # adasum vs the NumPy VHDD oracle over 4 rank vectors
    from tests.test_ops import _vhdd_oracle  # noqa

    expect = _vhdd_oracle([np.full((4,), float(i + 1)) for i in range(4)])
    for res in out:
        np.testing.assert_allclose(res["adasum"], expect, rtol=1e-4)
        # sum + avg on (3,) f32 through the instrumented eager path
        # (Adasum rides its own VHDD kernels, not counted under allreduce)
        assert res["metrics"]["allreduce_count"] >= 2
        assert res["metrics"]["allreduce_bytes"] >= 2 * 3 * 4
        assert res["metrics"]["cache_misses"] >= 1


def _two_proc_async_checkpoint():
    """Async save + fence + restore across 2 processes: the writer's status
    broadcast must release both ranks, and the restore broadcast must hand
    rank 1 the state even though only rank 0's directory has files."""
    import os
    import tempfile

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import checkpoint as ckpt

    hvd.init()
    r = hvd.process_rank()
    # rank-PRIVATE dir: non-root never sees the files, restore must broadcast
    d = os.path.join(tempfile.gettempdir(), f"hvd_async_ck_rank{r}")
    import shutil

    shutil.rmtree(d, ignore_errors=True)
    mgr = ckpt.CheckpointManager(d)
    state = {"w": np.full((3,), 7.0, np.float32), "step": 4}
    mgr.save(4, state, asynchronous=True)
    mgr.wait_until_finished()

    out = {"rank": r, "has_files": os.path.isdir(os.path.join(d, "step_4"))}
    restored = mgr.restore()
    out["w"] = np.asarray(restored["w"]).tolist()
    out["step"] = restored["step"]

    # writer-side failure (step_4 exists, no force) must raise on BOTH ranks
    mgr.save(4, state, asynchronous=True)
    try:
        mgr.wait_until_finished()
        out["err"] = None
    except (FileExistsError, RuntimeError) as e:
        out["err"] = type(e).__name__
    shutil.rmtree(d, ignore_errors=True)
    return out


def test_two_process_async_checkpoint():
    out = runner.run(
        _two_proc_async_checkpoint, np=2, env=_worker_env(), timeout_s=240
    )
    for r, res in enumerate(out):
        assert res["rank"] == r
        assert res["has_files"] == (r == 0)  # rank-0-writer pattern
        assert res["w"] == [7.0, 7.0, 7.0]
        assert res["step"] == 4
        # failure fenced to every rank: writer re-raises the original,
        # non-writers get the wrapped status error
        assert res["err"] == ("FileExistsError" if r == 0 else "RuntimeError")


def _two_proc_torch_ef():
    """Error-feedback compression cross-process: each rank sees a DIFFERENT
    data half (so residuals genuinely differ per rank), gradients exchange
    compressed, and both ranks stay bit-identical in parameters — the
    invariant that proves the residual is per-rank local state while the
    wire carries the same reduced values everywhere."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import torch

    import horovod_tpu as hvd
    import horovod_tpu.torch as thvd

    hvd.init()
    r = hvd.process_rank()
    torch.manual_seed(0)  # identical init on both ranks
    model = torch.nn.Sequential(
        torch.nn.Linear(8, 16), torch.nn.Tanh(), torch.nn.Linear(16, 2)
    )
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters(),
        compression=thvd.Compression.fp16, error_feedback=True,
    )
    rng = np.random.RandomState(100 + r)  # rank-dependent data
    losses = []
    for _ in range(8):
        x = torch.from_numpy(rng.randn(16, 8).astype(np.float32))
        y = torch.from_numpy(rng.randint(0, 2, 16))
        opt.zero_grad()
        loss = torch.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        losses.append(float(loss))
    # parameter fingerprint must agree across ranks (same reduced updates)
    fp = float(sum(p.detach().abs().sum() for p in model.parameters()))
    n_resid = len(opt._ef_residual)
    # residual fingerprint must DIFFER across ranks (per-rank local error
    # of per-rank gradients) — zeroed or allreduced residuals would match
    resid_fp = float(sum(t.abs().sum() for t in opt._ef_residual.values()))
    return {"rank": r, "fp": fp, "n_resid": n_resid, "resid_fp": resid_fp,
            "finite": all(np.isfinite(losses))}


def test_two_process_torch_error_feedback():
    out = runner.run(
        _two_proc_torch_ef, np=2, env=_worker_env(), timeout_s=300
    )
    assert all(res["finite"] for res in out)
    assert all(res["n_resid"] == 4 for res in out)  # 2 weights + 2 biases
    np.testing.assert_allclose(out[0]["fp"], out[1]["fp"], rtol=1e-5)
    assert all(res["resid_fp"] > 0 for res in out)
    assert abs(out[0]["resid_fp"] - out[1]["resid_fp"]) > 1e-9


def _two_proc_ragged_gather():
    """Variable-leading-dim allgather across dtypes/ranks (the Allgatherv
    displacement semantics added for hostlocal arrays)."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.process_rank()
    out = {"rank": r}
    # rank r contributes r+1 rows; 2-D f32, 3-D f32, 1-D int32, 1-D bool
    f2 = np.full((r + 1, 2), float(r), np.float32)
    out["f2"] = np.asarray(hvd.allgather(f2)).tolist()
    f3 = np.full((r + 2, 2, 2), float(10 + r), np.float32)
    out["f3_shape"] = list(np.asarray(hvd.allgather(f3)).shape)
    i1 = np.arange(r + 1, dtype=np.int32) + 100 * r
    out["i1"] = np.asarray(hvd.allgather(i1)).tolist()
    b1 = np.array([bool(r)] * (r + 1))
    out["b1"] = np.asarray(hvd.allgather(b1)).astype(int).tolist()
    return out


@pytest.mark.slow
def test_two_process_ragged_allgather():
    out = runner.run(
        _two_proc_ragged_gather, np=2, env=_worker_env(), timeout_s=300
    )
    r0, r1 = out
    assert r0["f2"] == [[0.0, 0.0]] + [[1.0, 1.0]] * 2
    assert r0["f3_shape"] == [5, 2, 2]  # 2 + 3 rows
    assert r0["i1"] == [0, 100, 101]
    assert r0["b1"] == [0, 1, 1]
    assert r1 == r0 | {"rank": 1}


def _two_proc_fleet_observability():
    """ISSUE 7 fleet plane across REAL processes: both ranks publish metric
    snapshots (+ arrival rings + clock sync) to the launcher's KV, rank 0
    aggregates fleet stats and rank-labeled series, a short-TTL snapshot
    shows the rank as DEAD (not absent), and the two ranks' trace sidecars
    merge into one skew-corrected timeline with correlated spans."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    rank_env = int(os.environ["HOROVOD_RANK"])
    trace_dir = os.environ["HVD_FLEET_TRACE_DIR"]
    timeline = os.path.join(trace_dir, f"tl_rank{rank_env}.json")
    os.environ["HOROVOD_TIMELINE"] = timeline
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.observability import aggregate, clock, straggler, trace
    from horovod_tpu.run.rendezvous import KVStoreClient

    hvd.init()
    r = hvd.process_rank()
    client = KVStoreClient(
        os.environ["HVD_RUN_KV_ADDR"], int(os.environ["HVD_RUN_KV_PORT"])
    )
    off, err = clock.refresh_from_kv(client, rank=r)
    out = {"rank": r, "clock_err": err, "clock_off": off}

    for step in range(2):
        straggler.set_step(step)
        hvd.allreduce(np.full((4,), float(r + 1), np.float32), hvd.Sum)

    # first lease is short-lived: after it expires rank 1 must show DEAD;
    # the generous republish below is what the live fleet view aggregates
    pub = aggregate.MetricsPublisher(
        client, rank=r, interval=60.0, ttl=(0.5 if r == 1 else 60.0)
    )
    pub.publish_once()
    trace.flush(timeline)
    client.put(f"/obs/trace_ready/{r}", timeline.encode())

    if r == 1:
        # wait for rank 0's dead-rank observation, then republish (alive
        # again) so the final aggregation sees both ranks
        client.wait_for("/obs/saw_dead", timeout=60)
        pub2 = aggregate.MetricsPublisher(
            client, rank=r, interval=60.0, ttl=60.0)
        pub2.publish_once()
        client.wait_for("/obs/done", timeout=60)
        return out

    # ---- rank 0: the aggregator ----
    agg = aggregate.FleetAggregator(client, world=2, register=False)
    client.wait_for("/obs/snap/1", timeout=60)
    first = agg.collect()
    out["first_ranks"] = first["ranks"]
    deadline = time.time() + 30
    dead = []
    while time.time() < deadline:
        view = agg.collect()
        if view["dead_ranks"]:
            dead = view["dead_ranks"]
            break
        time.sleep(0.2)
    out["dead_ranks"] = dead
    client.put("/obs/saw_dead", b"1")
    # rank 1 republishes with a generous lease: both ranks live again
    deadline = time.time() + 30
    while time.time() < deadline:
        fleet = agg.collect()
        if fleet["ranks"] == [0, 1]:
            break
        time.sleep(0.2)
    out["final_ranks"] = fleet["ranks"]
    s = fleet["metrics"]["allreduce_count"]["samples"][""]
    out["count_ranks"] = s["ranks"]
    out["count_stats"] = {
        "min": s["min"], "max": s["max"], "mean": s["mean"], "p99": s["p99"]
    }
    prom = aggregate.to_prometheus_fleet(fleet)
    out["rank_series"] = (
        'allreduce_count{rank="0"} 2' in prom
        and 'allreduce_count{rank="1"} 2' in prom
    )
    out["p99_series"] = 'fleet_allreduce_count{stat="p99"} 2' in prom

    # ---- merged skew-corrected trace across both ranks' sidecars ----
    other = client.wait_for("/obs/trace_ready/1", timeout=60).decode()
    merged = clock.merge_rank_traces(
        [timeline, other], os.path.join(trace_dir, "merged.json"))
    by_key = {}
    for e in merged:
        a = e.get("args") or {}
        pid = str(e.get("pid", ""))
        if "seq" in a and pid.startswith("rank") and "-host" not in pid:
            by_key.setdefault(
                (a["step"], a["gen"], a["seq"]), set()).add(pid)
    out["correlated_keys"] = sorted(
        [list(k) for k, pids in by_key.items()
         if pids >= {"rank0", "rank1"}]
    )
    client.put("/obs/done", b"1")
    return out


def test_two_process_fleet_observability(tmp_path):
    env = _worker_env()
    env["HVD_FLEET_TRACE_DIR"] = str(tmp_path)
    out = runner.run(
        _two_proc_fleet_observability, np=2, env=env, timeout_s=240
    )
    r0 = next(r for r in out if r["rank"] == 0)
    # clock sync happened on both ranks with a sane (local-loopback) bound
    assert all(r["clock_err"] is not None and r["clock_err"] < 1.0
               for r in out)
    # both ranks' snapshots aggregated; the short-lease rank showed DEAD
    # (surfaced, not silently absent) and came back on republish
    assert r0["first_ranks"] == [0, 1]
    assert r0["dead_ranks"] == [1]
    assert r0["final_ranks"] == [0, 1]
    # fleet stats + rank-labeled raw series served by rank 0
    assert r0["count_ranks"] == {"0": 2.0, "1": 2.0}
    assert r0["count_stats"]["min"] == 2.0
    assert r0["count_stats"]["p99"] == 2.0
    assert r0["rank_series"] and r0["p99_series"]
    # the merged timeline holds BOTH ranks' spans for the same collectives,
    # tied by (step, gen, seq): 2 steps, seq resetting at each boundary
    assert r0["correlated_keys"] == [[0, 0, 0], [1, 0, 0]]


def _two_proc_flight_sidecars():
    """Each worker records real eager collectives into its own flight
    sidecar (the crash-durable per-rank record) and flushes on shutdown."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.observability import flight, straggler

    hvd.init()
    rank = hvd.process_rank()
    for step in range(3):
        straggler.set_step(step)
        flight.step_boundary(step)
        for _ in range(2):
            hvd.allreduce(np.full((2,), float(rank + 1), np.float32))
    path = flight.flush()
    hvd.shutdown()
    return {"rank": rank, "sidecar": path}


def test_two_process_flight_sidecar_merge(tmp_path):
    """Satellite (ISSUE 14): a real 2-process run leaves one sidecar per
    rank; the offline merge assigns each stream to its rank, skew-corrects
    onto one timebase, finds both ranks at the same frontier, and returns
    the no-hang verdict."""
    from horovod_tpu.observability import flight

    d = str(tmp_path / "flight")
    env = _worker_env()
    env["HOROVOD_FLIGHT_DIR"] = d
    out = runner.run(
        _two_proc_flight_sidecars, np=2, env=env, timeout_s=240
    )
    assert sorted(r["rank"] for r in out) == [0, 1]
    assert {os.path.basename(r["sidecar"]) for r in out} == {
        "flight-rank0.jsonl", "flight-rank1.jsonl",
    }
    rank_events, meta = flight.load_dir(d)
    assert sorted(rank_events) == [0, 1]
    assert meta["world"] == 2
    # both ranks recorded the SAME correlation keys (the cross-process
    # agreement everything downstream leans on), each with begin AND end
    def keys(r, ph):
        return [
            (e["step"], e["gen"], e["seq"]) for e in rank_events[r]
            if e["kind"] == "collective" and e["ph"] == ph
        ]

    assert keys(0, "b") == keys(1, "b")
    assert keys(0, "e") == keys(1, "e")
    assert len(keys(0, "b")) == 6  # 3 steps x 2 collectives
    # merged streams are time-sorted on the corrected timebase
    for r in (0, 1):
        ts = [e["t"] for e in rank_events[r]]
        assert ts == sorted(ts)
    v = flight.analyze(rank_events, expected=[0, 1])
    assert v["verdict"] == "progressing"
    assert v["key"] == [2, 0, 1]  # frontier: last collective of step 2


def _two_proc_loader_streams():
    """Each worker drives its own per-rank ResumableLoader over the same
    global stream: first half of the epoch at world 2, cursor saved, a
    FRESH loader restored (the cold-restart path), and — on rank 0 — a
    mid-epoch reshard to world 1 consuming the remainder alone (the
    repartition drill)."""
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.data import ResumableLoader, sampler

    hvd.init()
    rank = hvd.process_rank()
    n, bs = 64, 16  # 4 steps/epoch
    rng = np.random.RandomState(0)
    x = rng.rand(n, 3).astype(np.float32)
    y = np.arange(n, dtype=np.int32)

    def make(name):
        return ResumableLoader(
            (x, y), bs, seed=21, rank=rank, size=2, prefetch=2,
            name=name,
        )

    out = {"rank": rank}
    ld = make("mp")
    # first half of the epoch at world 2
    out["head"] = [
        np.asarray(ld.next_batch()[1]).tolist() for _ in range(2)
    ]
    cursor = ld.state()
    ld.close()
    # cold restart: fresh loader + restored cursor must continue exactly
    sampler.reset()
    ld2 = make("mp")
    ld2.restore(cursor)
    out["resumed"] = [
        np.asarray(ld2.next_batch()[1]).tolist() for _ in range(2)
    ]
    # resharding drill: rank 0 re-binds to world 1 at the SAME cursor
    # and consumes the remaining epoch alone with full batches
    if rank == 0:
        ld3 = make("mp-reshard")
        ld3.restore(cursor)
        ld3.reshard(rank=0, size=1, generation=2)
        tail = []
        for _ in range(2):
            _, yb = ld3.next_batch()
            tail.append(np.asarray(yb).tolist())
        out["reshard_tail"] = tail
        out["reshard_state"] = ld3.state()
        ld3.close()
    ld2.close()
    hvd.shutdown()
    return out


def test_two_process_loader_determinism_and_resharding():
    """Satellite (ISSUE 15): 2 real processes drive per-rank loaders —
    both ranks' sample streams are disjoint, their union is exactly the
    epoch, a killed-and-restored loader continues identically, and a
    mid-epoch 2→1 repartition covers the remainder exactly once."""
    from horovod_tpu.data import GlobalSampleIndex

    out = runner.run(
        _two_proc_loader_streams, np=2, env=_worker_env(), timeout_s=240
    )
    by_rank = {r["rank"]: r for r in out}
    assert sorted(by_rank) == [0, 1]
    n, bs = 64, 16
    gsi = GlobalSampleIndex(n, bs, seed=21)
    # per-rank streams match the pure index function
    for rank in (0, 1):
        ref = [
            gsi.rank_indices(0, s, rank, 2).tolist() for s in range(4)
        ]
        stream = by_rank[rank]["head"] + by_rank[rank]["resumed"]
        assert stream == ref, f"rank {rank} stream diverged"
    # disjoint, union == epoch
    flat0 = [v for b in by_rank[0]["head"] + by_rank[0]["resumed"]
             for v in b]
    flat1 = [v for b in by_rank[1]["head"] + by_rank[1]["resumed"]
             for v in b]
    assert not set(flat0) & set(flat1)
    assert sorted(flat0 + flat1) == list(range(n))
    # the reshard: steps 2..3 consumed alone are the FULL global batches
    tail = by_rank[0]["reshard_tail"]
    assert tail == [gsi.batch_indices(0, s).tolist() for s in (2, 3)]
    # half-epoch under world 2 + remainder under world 1 == the epoch,
    # exactly once
    first_half = [v for r in (0, 1) for b in by_rank[r]["head"]
                  for v in b]
    # (head was steps 0..1; resumed re-drew the same steps after the
    # simulated kill — use head for the exactly-once ledger)
    assert sorted(first_half + [v for b in tail for v in b]) == \
        list(range(n))
    assert by_rank[0]["reshard_state"]["generation"] == 2


def _kv_failover_drill_worker():
    """Runs inside each launched worker: publish step-keyed records to
    the EXTERNAL control plane (primary + standby endpoint list), with
    rank 0 delivering a real SIGKILL to the primary process at step 3.
    No jax needed — this is a pure control-plane drill."""
    import os
    import signal
    import time

    from horovod_tpu.resilience.retry import RetryPolicy
    from horovod_tpu.run.rendezvous import KVStoreClient, parse_endpoints

    eps = parse_endpoints(os.environ["HVD_TEST_EXT_KV"])
    primary_pid = int(os.environ["HVD_TEST_EXT_KV_PID"])
    rank = int(os.environ["HOROVOD_RANK"])
    pol = RetryPolicy(
        scope="kv", max_attempts=120, base_delay=0.1, max_delay=0.5,
        multiplier=2.0, jitter=0.1, deadline=60.0,
    )
    client = KVStoreClient(endpoints=eps, retry_policy=pol)
    for step in range(6):
        if step == 3:
            # the two workers start unsynchronised, and one that ran all
            # six steps before the kill would never meet the failover:
            # rank 0 kills once rank 1 is mid-run, and rank 1 goes on once
            # rank 0's step 3 exists, which only the promoted standby can
            # have taken
            if rank == 0:
                client.wait_for("/drill/rank1/step2", timeout=120.0)
                os.kill(primary_pid, signal.SIGKILL)  # the real kill drill
            else:
                client.wait_for("/drill/rank0/step3", timeout=120.0)
        client.put(f"/drill/rank{rank}/step{step}", str(step).encode())
        time.sleep(0.05)
    # re-read the whole publication record through the (now promoted)
    # control plane: every step key must still be there, same values
    seen = {
        step: (client.get(f"/drill/rank{rank}/step{step}") or b"").decode()
        for step in range(6)
    }
    return {
        "rank": rank,
        "seen": seen,
        "epoch_seen": client.fencing_epoch_seen,
        "failovers": client.failovers,
    }


def test_two_process_kv_failover_drill(tmp_path):
    """Control-plane HA (ISSUE 19): the primary rendezvous KV runs as a
    REAL separate process replicating to a warm standby; mid-run a worker
    SIGKILLs it. The lease monitor promotes the standby, and both
    workers' step-keyed publications continue under the same keys with
    nothing lost — the client auto-reconnect path, end to end."""
    import signal
    import subprocess
    import sys

    from horovod_tpu.run import replication
    from horovod_tpu.run.rendezvous import KVStoreServer

    standby = KVStoreServer(
        wal_path=str(tmp_path / "standby.wal"), role="standby")
    standby.start()
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run.replication",
         "--role", "primary", "--port", "0",
         "--wal", str(tmp_path / "primary.wal"),
         "--replicas", f"127.0.0.1:{standby.port}", "--quorum", "1"],
        stdout=subprocess.PIPE, text=True, env=_worker_env(),
        cwd=_REPO_ROOT,
    )
    monitor = None
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("KV primary ready on port "), line
        pport = int(line.rsplit(" ", 1)[1])
        monitor = replication.FailoverMonitor(
            standby, ("127.0.0.1", pport), lease=0.5, poll=0.1)
        monitor.start()

        wenv = _worker_env()
        wenv["HVD_TEST_EXT_KV"] = (
            f"127.0.0.1:{pport},127.0.0.1:{standby.port}")
        wenv["HVD_TEST_EXT_KV_PID"] = str(proc.pid)
        out = runner.run(
            _kv_failover_drill_worker, np=2, env=wenv, timeout_s=240
        )

        assert proc.wait(timeout=10) == -signal.SIGKILL
        assert standby.role == "primary"  # promoted, not just surviving
        assert standby.fencing_epoch == 1
        assert monitor.result is not None
        by_rank = {r["rank"]: r for r in out}
        assert sorted(by_rank) == [0, 1]
        for rank in (0, 1):
            # publications continued across the failover under the SAME
            # step keys, none lost or replayed
            assert by_rank[rank]["seen"] == {
                s: str(s) for s in range(6)}, by_rank[rank]
            assert by_rank[rank]["epoch_seen"] >= 1
        # the killing rank provably failed over at least once
        assert by_rank[0]["failovers"] >= 1
        # and the promoted standby's own store holds every record
        for rank in (0, 1):
            for step in range(6):
                assert standby.get(
                    f"/drill/rank{rank}/step{step}") == str(step).encode()
    finally:
        if monitor is not None:
            monitor.stop()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)
        standby.close()
