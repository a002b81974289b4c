"""The benchmark's own tests (``benchmarks/tests/``: the manifest rules, the
planted faults and the precision control at a tiny size) as cases of
tier-1, so a change that breaks ``correct`` is found before the chip is.

The functions and their fixture are imported, not copied: each case is
collected here under its own name. ``benchmarks/tests/conftest.py`` asks for
four virtual devices and this suite's for eight; a cell takes
``jax.devices()[:chips]``, which the last test pins."""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_check",
                               "benchmarks.tests.test_correct",
                               "benchmarks.tests.test_mellum",
                               "benchmarks.tests.test_laguna",
                               "benchmarks.tests.test_qwen3next",
                               "benchmarks.tests.test_nemotron_h")

from benchmarks.tests.test_check import (  # noqa: E402,F401
    test_a_second_four_chip_cell_needs_eight_cells,
    test_committed_manifest_is_sound,
    test_pr22_manifest_is_refused,
    test_rules_the_driver_refuses_by,
    test_trace_reduction_on_the_hand_made_trace,
)
from benchmarks.tests.test_correct import (  # noqa: E402,F401
    no_exchange,
    test_broken_timed_path_is_not_correct,
    test_control_is_not_correct,
    test_unbroken_run_is_correct,
)
from benchmarks.tests.test_laguna import (  # noqa: E402,F401
    test_broken_laguna_timed_path_is_not_correct,
    test_laguna_control_is_not_correct,
    test_unbroken_laguna_run_is_correct,
)
from benchmarks.tests.test_mellum import (  # noqa: E402,F401
    test_broken_mellum_timed_path_is_not_correct,
    test_mellum_control_is_not_correct,
    test_unbroken_mellum_run_is_correct,
)
from benchmarks.tests.test_nemotron_h import (  # noqa: E402,F401
    test_broken_nemotron_h_timed_path_is_not_correct,
    test_nemotron_h_control_is_not_correct,
    test_unbroken_nemotron_h_run_is_correct,
)
from benchmarks.tests.test_qwen3next import (  # noqa: E402,F401
    test_broken_qwen3next_timed_path_is_not_correct,
    test_qwen3next_control_is_not_correct,
    test_unbroken_qwen3next_run_is_correct,
)


@pytest.fixture(autouse=True)
def _no_cache_left_on_the_worker(monkeypatch, tmp_path):
    """A run places the persistent compile cache and keeps every entry
    (``common.place_compile_cache``), which would stay with this xdist
    worker for the files it runs next. JAX read
    ``JAX_COMPILATION_CACHE_DIR`` when it was imported, so naming a
    directory now places none, and the two thresholds are put back."""
    import jax

    kept = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    yield
    for k, v in kept.items():
        jax.config.update(k, v)


def test_four_chip_tiny_cell_takes_four_of_the_eight_devices():
    import jax

    from benchmarks import common
    from benchmarks.tests import tiny

    job = common.load_module("jobs", "train")
    opened = job.open_cell(tiny.cell("gpt2m_train_dp4", "gpt2", 4),
                           require_chip=False)
    try:
        assert len(jax.devices()) == 8
        assert opened["hvd"].size() == 4
        assert (list(opened["hvd"].mesh().devices.flat)
                == jax.devices()[:4] == opened["devices"])
    finally:
        opened["hvd"].shutdown()
