"""Stall-detection failure-mode test — analog of reference
``test/test_stall.py`` (rank>0 withholds a tensor; the coordinator must warn
within ``HOROVOD_STALL_CHECK_TIME_SECONDS``, listing the missing ranks)."""

import os
import socket
import subprocess
import sys
import textwrap

WORKER = textwrap.dedent(
    """
    import logging, os, sys, time
    logging.basicConfig(level=logging.DEBUG, stream=sys.stderr)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.core import NativeCore, REQUEST_ALLREDUCE

    rank = int(sys.argv[1])
    port = int(sys.argv[2])
    os.environ["HOROVOD_CYCLE_TIME"] = "2"
    os.environ["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "1"
    hvd.init()
    core = NativeCore(rank=rank, size=2, coordinator_host="127.0.0.1",
                      coordinator_port=port)
    x = np.ones((4,), np.float32)

    # both ranks agree on 'warm'; only rank 0 submits 'missing'
    h = core.enqueue("warm", x, REQUEST_ALLREDUCE, op=1)
    h.wait(timeout=20)
    if rank == 0:
        hm = core.enqueue("missing", x, REQUEST_ALLREDUCE, op=1)
        time.sleep(3.5)   # > stall warning interval; rank 1 never joins in
        print("RANK0-WAITED", flush=True)
    else:
        time.sleep(3.5)
        hm = core.enqueue("missing", x, REQUEST_ALLREDUCE, op=1)
    hm.wait(timeout=20)
    print(f"rank{rank}: recovered after stall", flush=True)
    core.shutdown()
    """
)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_stall_warning_and_recovery(tmp_path):
    script = tmp_path / "stall_worker.py"
    script.write_text(WORKER)
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-u", str(script), str(r), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    # the coordinator (rank 0) must have warned, naming the missing rank,
    # and the job must still complete once rank 1 catches up
    assert "Stalled collective" in outs[0], outs[0]
    assert "missing" in outs[0]
    assert "missing ranks: 1" in outs[0], outs[0]
    for r, out in enumerate(outs):
        assert f"rank{r}: recovered after stall" in out, out
    assert all(p.returncode == 0 for p in procs), outs


DEATH_WORKER = textwrap.dedent(
    """
    import logging, os, sys, time
    logging.basicConfig(level=logging.DEBUG, stream=sys.stderr)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.core import NativeCore, REQUEST_ALLREDUCE

    rank = int(sys.argv[1])
    port = int(sys.argv[2])
    os.environ["HOROVOD_CYCLE_TIME"] = "2"
    os.environ["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "1"
    os.environ["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = "3"
    hvd.init()
    core = NativeCore(rank=rank, size=2, coordinator_host="127.0.0.1",
                      coordinator_port=port)
    x = np.ones((4,), np.float32)
    h = core.enqueue("warm", x, REQUEST_ALLREDUCE, op=1)
    h.wait(timeout=120)
    pending = sys.argv[3]  # rank 0 makes it once 'orphan' is enqueued
    if rank == 1:
        # die only when the survivor's collective IS pending: a peer that
        # died first was noticed within a cycle, the loop was gone before
        # 'orphan' arrived, and nothing was left to abort it
        deadline = time.monotonic() + 120
        while not os.path.exists(pending) and time.monotonic() < deadline:
            time.sleep(0.01)
        os._exit(7)  # die abruptly mid-job: no shutdown, no socket close

    def aborted(name, handle):
        try:
            # a client-side TimeoutError must FAIL the test: only the
            # core's own abort (RuntimeError from the shutdown error
            # response) counts
            handle.wait(timeout=120)
            print(f"RANK0-UNEXPECTED-COMPLETION {name}", flush=True)
        except TimeoutError as e:
            # still a test failure (no ABORTED line) but diagnosable
            print(f"RANK0-CLIENT-TIMEOUT {name}: {e}", flush=True)
        except RuntimeError as e:
            print(f"RANK0-ABORTED {name}: {type(e).__name__}: {e}",
                  flush=True)

    hm = core.enqueue("orphan", x, REQUEST_ALLREDUCE, op=1)
    open(pending, "w").close()
    aborted("orphan", hm)
    # and the other order: the loop is gone now, so what is enqueued next
    # must be aborted by the enqueue itself, not wait for a drain that
    # never comes
    aborted("late", core.enqueue("late", x, REQUEST_ALLREDUCE, op=1))
    core.shutdown()
    print("rank0: exited cleanly", flush=True)
    """
)


def test_worker_death_aborts_survivor(tmp_path):
    """Abrupt peer death mid-job (reference failure semantics, SURVEY §5.3):
    the survivor's pending collective must ABORT — never hang until an
    external timeout kills the job — and so must one it enqueues after
    the core's loop has shut itself down."""
    script = tmp_path / "death_worker.py"
    script.write_text(DEATH_WORKER)
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-u", str(script), str(r), str(port),
             str(tmp_path / "orphan_pending")],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=400)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    assert procs[1].returncode == 7  # the deliberate death
    assert "RANK0-ABORTED orphan" in outs[0], outs[0]
    assert "RANK0-ABORTED late" in outs[0], outs[0]
    assert "rank0: exited cleanly" in outs[0], outs[0]
    assert procs[0].returncode == 0, outs[0]


COORD_DEATH_WORKER = textwrap.dedent(
    """
    import logging, os, sys, time
    logging.basicConfig(level=logging.DEBUG, stream=sys.stderr)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.core import NativeCore, REQUEST_ALLREDUCE

    rank = int(sys.argv[1])
    port = int(sys.argv[2])
    os.environ["HOROVOD_CYCLE_TIME"] = "2"
    # stall shutdown deliberately FAR above the pass deadline: the abort must
    # come from closed-socket detection, not the stall timeout
    os.environ["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "30"
    os.environ["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = "120"
    hvd.init()
    core = NativeCore(rank=rank, size=2, coordinator_host="127.0.0.1",
                      coordinator_port=port)
    x = np.ones((4,), np.float32)
    h = core.enqueue("warm", x, REQUEST_ALLREDUCE, op=1)
    h.wait(timeout=20)
    if rank == 0:
        os._exit(7)  # coordinator dies abruptly: no shutdown, no goodbye
    t0 = time.monotonic()
    hm = core.enqueue("orphan", x, REQUEST_ALLREDUCE, op=1)
    try:
        hm.wait(timeout=45)
        print("RANK1-UNEXPECTED-COMPLETION", flush=True)
    except TimeoutError as e:
        print(f"RANK1-CLIENT-TIMEOUT: {e}", flush=True)
    except RuntimeError as e:
        dt = time.monotonic() - t0
        print(f"RANK1-ABORTED after {dt:.1f}s: {e}", flush=True)
    core.shutdown()
    print("rank1: exited cleanly", flush=True)
    """
)


def test_coordinator_death_fails_fast(tmp_path):
    """Coordinator (process rank 0) death must abort workers promptly via
    closed-socket detection with a cause naming the coordinator — NOT via the
    stall timeout (set to 120s here; the reference relies on launcher-side
    kill instead, ``run/gloo_run.py:294-304``)."""
    script = tmp_path / "coord_death_worker.py"
    script.write_text(COORD_DEATH_WORKER)
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-u", str(script), str(r), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    assert procs[0].returncode == 7  # the deliberate coordinator death
    assert "RANK1-ABORTED" in outs[1], outs[1]
    assert "coordinator" in outs[1], outs[1]  # cause names the coordinator
    assert "rank1: exited cleanly" in outs[1], outs[1]
    assert procs[1].returncode == 0, outs[1]
