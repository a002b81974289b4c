"""Test harness: force an 8-device virtual CPU mesh so collective semantics are
exercised without TPU hardware — the analog of the reference running every test
file under a 2-process localhost launcher (SURVEY.md §4,
``.buildkite/gen-pipeline.sh:124,232``). Must run before jax is imported."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Repo root on sys.path: tests import from examples/ (e.g. the Adasum
# steps-to-threshold helper), which a bare ``pytest`` invocation does not
# provide (only ``python -m pytest`` from the root does).
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import subprocess  # noqa: E402
import pathlib  # noqa: E402

import pytest  # noqa: E402

# always (incrementally) rebuild the native core: the binary is git-ignored,
# so one left on disk by an older checkout would otherwise be used silently
subprocess.run(
    ["make", "-C", str(pathlib.Path(__file__).resolve().parents[1] / "csrc")],
    check=True, stdout=subprocess.DEVNULL)


def pytest_configure(config):
    # Tier-1 brushes the 870 s verify timeout, so every run reports its
    # slowest tests: regressions in runtime are visible in the log the
    # moment they land, not when the suite first times out. An explicit
    # --durations on the command line wins.
    if getattr(config.option, "durations", None) is None:
        config.option.durations = 15
        config.option.durations_min = 5.0


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture()
def mesh8(hvd):
    return hvd.mesh()
