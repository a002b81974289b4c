"""Launcher unit tests (reference ``test/test_run.py``: arg→env translation,
config-file merging, slot allocation, command construction with mocked exec,
process-tree kill semantics) plus a real 2-process localhost job."""

import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
from unittest import mock

import pytest

from horovod_tpu.run import config_parser, hosts, runner, safe_exec
from horovod_tpu.run.rendezvous import KVStoreClient, KVStoreServer


# ---------------------------------------------------------------- hosts


def test_parse_hosts():
    infos = hosts.parse_hosts("h1:4,h2:2,h3")
    assert [(h.hostname, h.slots) for h in infos] == [
        ("h1", 4), ("h2", 2), ("h3", 1)
    ]


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("h1 slots=4\n# comment\nh2 slots=2\nh3\n")
    infos = hosts.parse_hostfile(str(f))
    assert [(h.hostname, h.slots) for h in infos] == [
        ("h1", 4), ("h2", 2), ("h3", 1)
    ]


def test_allocate_coordinates():
    # 2 hosts x 2 slots: the reference's rank/local/cross math
    # (gloo_run.py:54-112)
    infos = hosts.parse_hosts("h1:2,h2:2")
    slots = hosts.allocate(infos, 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.local_rank for s in slots] == [0, 1, 0, 1]
    assert all(s.local_size == 2 for s in slots)
    assert [s.cross_rank for s in slots] == [0, 0, 1, 1]
    assert all(s.cross_size == 2 for s in slots)
    assert all(s.size == 4 for s in slots)


def test_allocate_oversubscribe_rejected():
    with pytest.raises(ValueError, match="exceeds available slots"):
        hosts.allocate(hosts.parse_hosts("h1:2"), 3)


def test_slot_env():
    slots = hosts.allocate(hosts.parse_hosts("h1:2"), 2)
    env = hosts.slot_env(slots[1])
    assert env["HOROVOD_RANK"] == "1"
    assert env["HOROVOD_SIZE"] == "2"
    assert env["HOROVOD_LOCAL_RANK"] == "1"
    assert env["HVD_PROCESS_ID"] == "1"
    assert env["HVD_NUM_PROCESSES"] == "2"


# ---------------------------------------------------------------- args/env


def test_args_to_env():
    args = runner.parse_args(
        [
            "-np", "2",
            "--fusion-threshold-mb", "32",
            "--cycle-time-ms", "3.5",
            "--cache-capacity", "2048",
            "--timeline-filename", "/tmp/t.json",
            "--timeline-mark-cycles",
            "--stall-check-warning-time-seconds", "120",
            "--stall-check-shutdown-time-seconds", "240",
            "--autotune",
            "--autotune-log-file", "/tmp/a.csv",
            "--log-level", "INFO",
            "--native-core",
            "python", "train.py",
        ]
    )
    env = {}
    config_parser.set_env_from_args(env, args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "3.5"
    assert env["HOROVOD_CACHE_CAPACITY"] == "2048"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "1"
    assert float(env["HOROVOD_STALL_CHECK_TIME_SECONDS"]) == 120
    assert float(env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"]) == 240
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_AUTOTUNE_LOG"] == "/tmp/a.csv"
    assert env["HOROVOD_LOG_LEVEL"] == "INFO"
    assert env["HOROVOD_NATIVE_CORE"] == "1"
    assert args.command == ["python", "train.py"]


def test_hierarchical_flags():
    # tri-state: unset -> no env; --x -> "1"; --no-x -> "0" (reference
    # horovodrun's mutually-exclusive group pairs, runner.py:295)
    args = runner.parse_args(["-np", "1", "x"])
    env = {}
    config_parser.set_env_from_args(env, args)
    assert "HOROVOD_HIERARCHICAL_ALLREDUCE" not in env
    assert "HOROVOD_HIERARCHICAL_ALLGATHER" not in env

    args = runner.parse_args(
        ["-np", "1", "--hierarchical-allreduce",
         "--no-hierarchical-allgather", "x"])
    env = {}
    config_parser.set_env_from_args(env, args)
    assert env["HOROVOD_HIERARCHICAL_ALLREDUCE"] == "1"
    assert env["HOROVOD_HIERARCHICAL_ALLGATHER"] == "0"

    with pytest.raises(SystemExit):
        runner.parse_args(["-np", "1", "--hierarchical-allreduce",
                           "--no-hierarchical-allreduce", "x"])


def test_no_stall_check_flag():
    args = runner.parse_args(["-np", "1", "--no-stall-check", "x"])
    env = {}
    config_parser.set_env_from_args(env, args)
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
    assert "HOROVOD_STALL_CHECK_TIME_SECONDS" not in env


def test_validate_args():
    with pytest.raises(ValueError, match="cycle-time-ms"):
        runner.parse_args(["-np", "1", "--cycle-time-ms", "0", "x"])


# ---------------------------------------------------------------- config file


CONFIG_YAML = textwrap.dedent(
    """
    fusion_threshold_mb: 16
    cycle_time_ms: 2.5
    cache_capacity: 512
    timeline:
        filename: /tmp/conf_timeline.json
        mark_cycles: true
    stall_check:
        warning_time_seconds: 99
    autotune:
        enable: true
        log_file: /tmp/conf_autotune.csv
    library_options:
        log_level: DEBUG
    """
)


def test_config_file_applies(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(CONFIG_YAML)
    args = runner.parse_args(["-np", "1", "--config-file", str(cfg), "x"])
    assert args.fusion_threshold_mb == 16
    assert args.cycle_time_ms == 2.5
    assert args.cache_capacity == 512
    assert args.timeline_filename == "/tmp/conf_timeline.json"
    assert args.timeline_mark_cycles is True
    assert args.stall_check_warning_time_seconds == 99
    assert args.autotune is True
    assert args.autotune_log_file == "/tmp/conf_autotune.csv"
    assert args.log_level == "DEBUG"


def test_cli_overrides_config(tmp_path):
    # explicit CLI flags beat the config file (reference test_run.py:168-226)
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(CONFIG_YAML)
    args = runner.parse_args(
        ["-np", "1", "--config-file", str(cfg), "--cycle-time-ms", "7", "x"]
    )
    assert args.cycle_time_ms == 7
    assert args.fusion_threshold_mb == 16  # still from config


def test_config_unknown_key_rejected(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("nonsense_key: 1\n")
    with pytest.raises(ValueError, match="unknown config keys"):
        runner.parse_args(["-np", "1", "--config-file", str(cfg), "x"])


# ---------------------------------------------------------------- commands


def test_build_command_local():
    slot = hosts.allocate(hosts.parse_hosts("localhost:2"), 2)[1]
    argv, env = runner.build_command_for_slot(
        slot, ["python", "train.py"], {"A": "1"}, "127.0.0.1", 1234, 5678
    )
    assert argv == ["python", "train.py"]
    assert env["HVD_COORDINATOR_ADDR"] == "127.0.0.1:1234"
    assert env["HVD_CORE_COORD_ADDR"] == "127.0.0.1"
    assert env["HVD_CORE_COORD_PORT"] == "5678"
    assert env["HOROVOD_RANK"] == "1"


def test_build_command_remote_ssh():
    slot = hosts.allocate(hosts.parse_hosts("far-host:1"), 1)[0]
    argv, _ = runner.build_command_for_slot(
        slot, ["python", "train.py"], {}, "far-host", 1234, 5678, ssh_port=2222
    )
    assert argv[0] == "ssh"
    assert "-p" in argv and "2222" in argv
    assert argv[-2] == "far-host"
    remote = argv[-1]
    assert "HOROVOD_RANK=0" in remote
    assert "HVD_CORE_COORD_PORT=5678" in remote
    assert "python train.py" in remote


def test_launch_job_mocked_failure_kills_job():
    # one rank failing must terminate the whole job
    # (reference gloo_run.py:294-304)
    slots = hosts.allocate(hosts.parse_hosts("localhost:2"), 2)
    calls = []

    def fake_execute(argv, env=None, stdout_handler=None, stderr_handler=None,
                     event=None, shell=False):
        rank = int(env["HOROVOD_RANK"])
        calls.append(rank)
        if rank == 0:
            return 3  # fail fast
        assert event.wait(10), "rank 1 was never told to stop"
        return -signal.SIGTERM

    with mock.patch.object(runner.safe_exec, "execute", fake_execute):
        codes = runner.launch_job(
            slots, ["python", "train.py"], {"JAX_PLATFORMS": "cpu"})
    assert sorted(calls) == [0, 1]
    assert codes[0] == 3
    assert codes[1] == -signal.SIGTERM


# ---------------------------------------------------------------- safe_exec


def test_safe_exec_basic():
    rc = safe_exec.execute([sys.executable, "-c", "print('hi')"])
    assert rc == 0


def test_safe_exec_kills_process_tree():
    # parent spawns a grandchild; event-triggered kill must take down both
    # (reference safe_shell_exec.py middleman semantics)
    script = (
        "import subprocess, sys, time\n"
        "p = subprocess.Popen([sys.executable, '-c', "
        "'import time; print(\"G\", flush=True); time.sleep(60)'])\n"
        "print('child pid', p.pid, flush=True)\n"
        "time.sleep(60)\n"
    )
    lines = []
    event = threading.Event()

    def on_out(line):
        lines.append(line)
        if line.startswith("child pid"):
            event.set()  # kill as soon as the grandchild exists

    t0 = time.monotonic()
    rc = safe_exec.execute(
        [sys.executable, "-u", "-c", script],
        stdout_handler=on_out,
        event=event,
    )
    elapsed = time.monotonic() - t0
    assert rc == -signal.SIGTERM
    assert elapsed < 30
    # grandchild must be gone: its pid was printed
    pid = None
    for line in lines:
        if line.startswith("child pid"):
            pid = int(line.split()[-1])
    assert pid is not None
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        os.kill(pid, signal.SIGKILL)
        pytest.fail("grandchild survived tree kill")


# ---------------------------------------------------------------- KV store


def test_kv_store_roundtrip():
    server = KVStoreServer()
    port = server.start()
    try:
        client = KVStoreClient("127.0.0.1", port)
        assert client.get("missing") is None
        client.put("k1", b"v1")
        assert client.get("k1") == b"v1"
        assert server.get("k1") == b"v1"
        server.put("k2", b"v2")
        assert client.wait_for("k2", timeout=5) == b"v2"
    finally:
        server.stop()


def test_kv_store_auth():
    server = KVStoreServer(secret="s3cret")
    port = server.start()
    try:
        good = KVStoreClient("127.0.0.1", port, secret="s3cret")
        bad = KVStoreClient("127.0.0.1", port, secret="wrong")
        good.put("k", b"v")
        with pytest.raises(RuntimeError, match="403"):
            bad.put("k", b"x")
        with pytest.raises(RuntimeError, match="403"):
            bad.get("k")
        assert good.get("k") == b"v"
    finally:
        server.stop()


# ---------------------------------------------------------------- end-to-end


def test_programmatic_run_two_processes():
    # nested fn: cloudpickle serializes it by value, so the worker process
    # does not need this test module importable
    def worker_fn(x):
        import os

        rank = int(os.environ["HOROVOD_RANK"])
        size = int(os.environ["HOROVOD_SIZE"])
        return {"rank": rank, "size": size, "x2": x * 2}

    results = runner.run(worker_fn, args=(21,), np=2, timeout_s=120)
    assert results == [
        {"rank": 0, "size": 2, "x2": 42},
        {"rank": 1, "size": 2, "x2": 42},
    ]


def test_programmatic_run_propagates_worker_error():
    def boom():
        raise RuntimeError("worker exploded")

    with pytest.raises(RuntimeError, match="worker exploded"):
        runner.run(boom, np=1, timeout_s=120)


def test_cli_end_to_end(tmp_path):
    script = tmp_path / "job.py"
    script.write_text(
        "import os\n"
        "print('R', os.environ['HOROVOD_RANK'], 'of',"
        " os.environ['HOROVOD_SIZE'])\n"
    )
    out_dir = tmp_path / "logs"
    rc = runner.run_commandline(
        [
            "-np", "2",
            "--output-filename", str(out_dir),
            sys.executable, str(script),
        ]
    )
    assert rc == 0
    assert (out_dir / "rank.0.out").read_text().strip() == "R 0 of 2"
    assert (out_dir / "rank.1.out").read_text().strip() == "R 1 of 2"


def test_multihost_aliased_run(tmp_path):
    """-H localhost:1,127.0.0.1:1 — a 2-"host" aliased job (both resolve
    locally, like reference ``test/test_interactiverun.py:1-77``): distinct
    global ranks, per-host local/cross coordinates, and a real cross-process
    collective over the launcher-wired rendezvous."""
    def worker_fn():
        import os

        import jax

        jax.config.update("jax_platforms", "cpu")
        import numpy as np

        import horovod_tpu as hvd

        hvd.init()
        r = hvd.process_rank()
        out = np.asarray(hvd.allreduce(np.full((3,), float(r + 1)), hvd.Sum))
        return {
            "rank": int(os.environ["HOROVOD_RANK"]),
            "local_rank": hvd.local_rank(),
            "local_size": hvd.local_size(),
            "cross_rank": int(os.environ["HOROVOD_CROSS_RANK"]),
            "cross_size": int(os.environ["HOROVOD_CROSS_SIZE"]),
            "sum": out.tolist(),
        }

    results = runner.run(
        worker_fn, np=2, hosts="localhost:1,127.0.0.1:1", timeout_s=180
    )
    assert [r["rank"] for r in results] == [0, 1]
    # one slot per aliased "host": local 0-of-1 on each, cross 2 hosts
    assert all(r["local_rank"] == 0 and r["local_size"] == 1 for r in results)
    assert [r["cross_rank"] for r in results] == [0, 1]
    assert all(r["cross_size"] == 2 for r in results)
    # the collective really crossed both processes: 1 + 2 = 3
    assert all(r["sum"] == [3.0, 3.0, 3.0] for r in results)


def test_cli_failure_exit_code(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(7)\n")
    rc = runner.run_commandline(["-np", "1", sys.executable, str(script)])
    assert rc == 1


# ---------------------------------------------------------------- scheduler


def test_hosts_from_lsf_env(tmp_path):
    from horovod_tpu.run.hosts import hosts_from_scheduler_env

    hf = tmp_path / "lsb_hosts"
    hf.write_text("node1\nnode1\nnode2\nnode2\n")
    infos = hosts_from_scheduler_env({"LSB_DJOB_HOSTFILE": str(hf)})
    assert [(i.hostname, i.slots) for i in infos] == [
        ("node1", 2), ("node2", 2)]

    infos = hosts_from_scheduler_env({"LSB_HOSTS": "a a a b"})
    assert [(i.hostname, i.slots) for i in infos] == [("a", 3), ("b", 1)]


def test_hosts_from_slurm_env():
    from horovod_tpu.run.hosts import hosts_from_scheduler_env

    infos = hosts_from_scheduler_env({
        "SLURM_JOB_NODELIST": "tpu[01-03],gpu7",
        "SLURM_NTASKS_PER_NODE": "4",
    })
    assert [(i.hostname, i.slots) for i in infos] == [
        ("tpu01", 4), ("tpu02", 4), ("tpu03", 4), ("gpu7", 4)]


def test_hosts_env_empty_falls_back():
    from horovod_tpu.run.hosts import hosts_from_scheduler_env

    assert hosts_from_scheduler_env({}) is None


def test_hosts_slurm_tasks_per_node_format():
    from horovod_tpu.run.hosts import hosts_from_scheduler_env

    infos = hosts_from_scheduler_env({
        "SLURM_JOB_NODELIST": "n[1-3],m5",
        "SLURM_TASKS_PER_NODE": "2(x3),1",
    })
    assert [(i.hostname, i.slots) for i in infos] == [
        ("n1", 2), ("n2", 2), ("n3", 2), ("m5", 1)]


def test_hosts_lsf_unreadable_hostfile_falls_through(tmp_path):
    from horovod_tpu.run.hosts import hosts_from_scheduler_env

    infos = hosts_from_scheduler_env({
        "LSB_DJOB_HOSTFILE": str(tmp_path / "does_not_exist"),
        "LSB_HOSTS": "x x y",
    })
    assert [(i.hostname, i.slots) for i in infos] == [("x", 2), ("y", 1)]


def test_check_build_summary(capsys):
    """--check-build mirrors reference horovodrun --check-build
    (runner.py:115-151): honest availability flags, exit 0."""
    rc = runner.run_commandline(["--check-build"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[X] JAX / optax (native)" in out
    assert "[X] XLA" in out
    assert "[ ] NCCL" in out and "[ ] MPI" in out  # honest negatives


def test_mpi_flag_rejected(capsys):
    rc = runner.run_commandline(["--mpi", "-np", "1", "--", "python", "x.py"])
    assert rc == 2
    assert "no MPI by design" in capsys.readouterr().err


def test_gloo_flag_accepted():
    """--gloo parses as a compat no-op (the TCP controller fills the role)."""
    args = runner.parse_args(["--gloo", "-np", "2", "--", "python", "x.py"])
    assert args.use_gloo is True and args.np == 2
