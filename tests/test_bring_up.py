"""The small repairs that keep a missing or unknown device from hiding:
entry points that fail off-chip instead of printing a CPU number, a peak
table that raises on a TPU it does not know, a compile cache that can be
placed from outside, and a launcher that refuses to start two processes
on one TPU host."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from horovod_tpu import profiler, tuning
from horovod_tpu.run import hosts, runner

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_off_chip(script, *args, cwd=_REPO, pythonpath=_REPO):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


# ------------------------------------------------------------ compile cache


@pytest.fixture()
def cache_updates(monkeypatch):
    """Record jax.config.update calls instead of switching the suite's
    own process onto a persistent cache."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_env_set_means_code_sets_nothing(
        monkeypatch, cache_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert tuning.enable_compile_cache() is None
    assert cache_updates == []


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = tuning.enable_compile_cache()
    second = tuning.enable_compile_cache()
    assert first == second == os.path.join(_REPO, ".jax_cache")
    assert cache_updates == [("jax_compilation_cache_dir", first)] * 2
    ignored = open(os.path.join(_REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


# --------------------------------------------------------------- peak table


def test_peak_lookup_raises_for_unknown_kind_on_tpu(monkeypatch):
    assert profiler.device_peak_flops("TPU v5 lite") == 197e12
    assert profiler.device_peak_hbm_bytes("TPU v5 lite") == 819e9
    assert profiler.device_peak_flops("cpu") is None  # CPU backend here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="no peak entry.*TPU v9 hyper"):
        profiler.device_peak_flops("TPU v9 hyper")
    with pytest.raises(ValueError, match="no peak entry"):
        profiler.device_peak_hbm_bytes("TPU v9 hyper")
    # v5p reports "TPU v5": it must not fall to the v5e row, nor v5e to it
    assert profiler.device_peak_flops("TPU v5") == 459e12
    assert profiler.device_peak_flops("TPU v5p") == 459e12
    assert profiler.device_peak_hbm_bytes("TPU v5e") == 819e9


# ------------------------------------------------- entry points off the chip


def test_benchmark_cell_exits_nonzero_off_chip():
    proc = _run_off_chip(os.path.join("benchmarks", "run.py"),
                         "--workload", "resnet50_train_1chip")
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stderr
    assert "{" not in proc.stdout  # no metrics line, null or otherwise


def test_chip_smoke_exits_nonzero_off_chip_before_any_phase():
    proc = _run_off_chip("chip_smoke.py")
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stderr
    assert proc.stdout == ""  # no phase started, no result printed


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script proves nothing and must
    say so with its exit status."""
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = _run_off_chip("chip_smoke.py", cwd=tmp_path, pythonpath=None)
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_runs_every_phase():
    proc = _run_off_chip("chip_smoke.py", "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines[0].startswith("platform=cpu")
    for name in ("kernels", "allreduce", "train", "serve"):
        assert any(ln.startswith(f"[{name}] ok") for ln in lines), name
    assert lines[-1].startswith('{"ok": true, "device": {"platform": "cpu"')


# ----------------------------------------------------------------- launcher


def test_launcher_refuses_two_local_slots_unless_cpu_pinned():
    two = hosts.allocate(hosts.parse_hosts("localhost:2"), 2)
    with pytest.raises(ValueError, match="one process per TPU host"):
        runner.require_one_process_per_tpu_host(two, {})
    with pytest.raises(ValueError, match="one process per TPU host"):
        runner.launch_job(two, ["true"], {"JAX_PLATFORMS": "tpu"})
    runner.require_one_process_per_tpu_host(two, {"JAX_PLATFORMS": "cpu"})
    spread = hosts.allocate(hosts.parse_hosts("h1:1,h2:1"), 2)
    runner.require_one_process_per_tpu_host(spread, {})


def test_hvdrun_refuses_two_local_slots_up_front(monkeypatch, capsys):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_PLATFORM_NAME", raising=False)
    assert runner.run_commandline(["-np", "2", "--", "true"]) == 2
    assert "one process per TPU host" in capsys.readouterr().err
