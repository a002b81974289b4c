"""What decides ``correct``, at a size a test run can hold.

* The control — the plain reference put in the program's place, computed in
  the precision below the configuration's — comes out as not correct under
  the cell's own limits.
* A run of the harness itself, with its look for a chip skipped and the
  timed path broken underneath, comes out as not correct: once for each
  fault a training cell can have.
* The unbroken run comes out correct.
"""

import json

import pytest

from benchmarks import common, compare
from benchmarks.reference import steps as ref_steps
from benchmarks.tests import tiny

CELLS = [("gpt2m_train_1chip", "gpt2", 1), ("gpt2m_train_dp4", "gpt2", 4),
         ("resnet50_train_1chip", "resnet", 1)]


def _committed(name):
    with open(common.MANIFEST) as f:
        return any(w["name"] == name for w in json.load(f)["workloads"])


def _run(cell, break_program=None, seed=7):
    job = common.load_module("jobs", "train")
    try:
        return job.run(cell, tiny.args(seed=seed), require_chip=False,
                       break_program=break_program)
    finally:
        import horovod_tpu as hvd

        hvd.shutdown()


def _state_unchanged(program):
    import jax
    import jax.numpy as jnp

    step = program.step_fn

    def broken(p, s, o, x, y):
        copy = jax.tree_util.tree_map(jnp.copy, (p, s, o))
        return (p, s, o, step(*copy, x, y)[3])

    program.step_fn = broken


def _half_batch(program):
    step = program.step_fn
    program.step_fn = lambda p, s, o, x, y: step(
        p, s, o, x[:x.shape[0] // 2], y[:y.shape[0] // 2])


@pytest.fixture
def no_exchange(monkeypatch):
    """The gradient allreduce of the explicit step left out: every chip
    keeps its own shard's gradient (the loss is still averaged)."""
    from horovod_tpu import training

    real = training.allreduce

    def local(x, *a, **kw):
        return x if x.ndim else real(x, *a, **kw)

    return lambda program: monkeypatch.setattr(training, "allreduce", local)


@pytest.mark.parametrize("name,family,chips", CELLS)
def test_unbroken_run_is_correct(name, family, chips):
    if not _committed(name):
        pytest.skip(f"{name} is not a cell of BENCHMARK.json")
    result, checks = _run(tiny.cell(name, family, chips))
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_examples_per_s_per_chip",
                                      "setup_s"}


@pytest.mark.parametrize("name,family,chips", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_broken_timed_path_is_not_correct(name, family, chips, fault,
                                          no_exchange):
    if not _committed(name):
        pytest.skip(f"{name} is not a cell of BENCHMARK.json")
    if fault == "no_exchange" and chips == 1:
        pytest.skip("one chip exchanges nothing")
    breaker = {"state_unchanged": _state_unchanged,
               "half_batch": _half_batch, "no_exchange": no_exchange}[fault]
    result, checks = _run(tiny.cell(name, family, chips), breaker)
    assert not result["correct"], checks
    failed = [k for k, c in checks.items() if not c["ok"]]
    assert failed and "compilations_in_window" not in failed, checks


@pytest.mark.parametrize("name,family,chips", CELLS)
def test_control_is_not_correct(name, family, chips):
    if not _committed(name):
        pytest.skip(f"{name} is not a cell of BENCHMARK.json")
    cell = tiny.cell(name, family, chips)
    cfg, wl = cell.config, cell.workload
    ref = cell.module("reference")
    gen = common.load_module("traffic", cell.traffic["generator"])
    for seed in (3, 4, 5):
        halves = common.split_seed(seed)
        pool = gen.make(cell.traffic, cfg, seed,
                        cell.traffic["per_chip_batch"] * chips)[:3]
        reference = ref_steps.first_steps(ref, cfg, wl, halves, pool)
        control = ref_steps.first_steps(ref, cfg, wl, halves, pool,
                                        precision=ref.CONTROL)
        checks = compare.checks(control, reference, wl["limits"])
        assert not all(c["ok"] for c in checks.values()), (seed, checks)
