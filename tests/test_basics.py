"""Identity/bootstrap tests (reference ``test/test_tensorflow.py`` rank/size
checks + ``horovod/common/basics.py`` surface)."""

import numpy as np
import pytest


def test_init_idempotent(hvd):
    hvd.init()
    hvd.init()
    assert hvd.is_initialized()


def test_size_rank(hvd):
    assert hvd.size() == 8
    assert hvd.rank() == 0
    assert hvd.local_size() == 8
    assert hvd.local_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.is_homogeneous()


def test_local_rank_from_launcher_env(monkeypatch):
    """Two slots on one host (-H host:2) must get distinct local ranks from
    the launcher-exported HOROVOD_LOCAL_RANK (reference ``basics.py:108-122``,
    ``run/gloo_run.py:54-112``)."""
    import horovod_tpu as hvd
    from horovod_tpu.run.hosts import get_host_assignments, slot_env

    slots = get_host_assignments("localhost:2", None, 2)
    envs = [slot_env(s) for s in slots]
    assert [e["HOROVOD_LOCAL_RANK"] for e in envs] == ["0", "1"]

    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", envs[1]["HOROVOD_LOCAL_RANK"])
    monkeypatch.setenv("HOROVOD_LOCAL_SIZE", envs[1]["HOROVOD_LOCAL_SIZE"])
    hvd.init()
    assert hvd.local_rank() == 1
    assert hvd.local_size() == 2  # processes on host, not chips
    assert hvd.local_rank() < hvd.local_size()
    assert hvd.local_chip_count() == 8  # tiling factor unchanged
    hvd.shutdown()


def test_install_sigterm_exit_runs_finalizers():
    """Benchmark/tool children convert a watchdog's SIGTERM into
    SystemExit(143) so ``finally`` blocks (and the JAX client teardown)
    actually run — the kernel default would terminate with no cleanup."""
    import os
    import signal
    import subprocess
    import sys

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from horovod_tpu.run.env_util import install_sigterm_exit\n"
        "install_sigterm_exit()\n"
        "import time\n"
        "try:\n"
        "    print('READY', flush=True)\n"
        "    time.sleep(60)\n"
        "finally:\n"
        "    print('FINALLY-RAN', flush=True)\n"
    ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    assert "READY" in proc.stdout.readline()
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=30)
    assert proc.returncode == 143
    assert "FINALLY-RAN" in out


def test_builds(hvd):
    assert hvd.xla_built()
    assert not hvd.mpi_built()
    assert not hvd.nccl_built()
    assert not hvd.gloo_built()
    assert not hvd.mpi_threads_supported()


def test_uninitialized_raises():
    import horovod_tpu as hvd

    hvd.shutdown()
    with pytest.raises(RuntimeError, match="not been initialized"):
        hvd.size()


def test_mesh_axes(hvd):
    m = hvd.mesh()
    assert hvd.data_axis() in m.axis_names
    assert m.shape[hvd.data_axis()] == 8


def test_custom_mesh_axes():
    import horovod_tpu as hvd
    from horovod_tpu.parallel import build_mesh

    hvd.shutdown()
    m = build_mesh(axes={"data": -1, "model": 2})
    hvd.init(mesh=m)
    assert hvd.size() == 4
    assert hvd.mesh().shape["model"] == 2
    hvd.shutdown()


def test_build_mesh_errors():
    from horovod_tpu.parallel import build_mesh

    with pytest.raises(ValueError, match="at most one"):
        build_mesh(axes={"data": -1, "model": -1})
    with pytest.raises(ValueError, match="not divisible"):
        build_mesh(axes={"data": -1, "model": 3})
    with pytest.raises(ValueError, match="!= device count"):
        build_mesh(axes={"data": 3})


def test_mesh_and_axes_mutually_exclusive():
    import jax
    import numpy as np
    import horovod_tpu as hvd

    hvd.shutdown()
    m = jax.sharding.Mesh(np.asarray(jax.devices()), ("model",))
    with pytest.raises(ValueError, match="not both"):
        hvd.init(mesh=m, axes={"data": -1})
    # a custom mesh without a 'data' axis falls back to its first axis
    hvd.init(mesh=m)
    assert hvd.data_axis() == "model"
    assert hvd.size() == 8
    hvd.shutdown()


def test_controller_enabled_flags(hvd):
    """Runtime controller queries (reference basics.py:151-179): gloo mode
    (the no-MPI TCP-controller role) answers enabled, MPI never."""
    assert hvd.gloo_enabled() is True
    assert hvd.mpi_enabled() is False
    thvd = pytest.importorskip("horovod_tpu.torch")
    assert thvd.gloo_enabled() and not thvd.mpi_enabled()


def test_compat_utils(hvd):
    assert hvd.num_rank_is_power_2(8) and not hvd.num_rank_is_power_2(6)
    assert not hvd.num_rank_is_power_2(0)
    assert hvd.gpu_available() is False  # TPU framework, honestly
    assert hvd.gpu_available("tensorflow") is False  # reference signature
