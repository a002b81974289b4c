"""What decides ``correct`` in the ``nemotron_h`` cell, at a size a test run
can hold (hidden 64, the pattern ``MEM*E``: two Mamba-2 layers of four
heads of 8 on two groups of state 16, chunks of 8; two LatentMoE layers of
4 of 16 relu² experts held, top-6, in a latent of 32, beside a relu² shared
expert; an attention layer of two query heads of 16 on one K/V head, no
positions; at T 32): the unbroken run comes out correct, with the cell's
forced choice of experts and with the router's own; the control (the plain
reference in the precision below bfloat16, in the program's place) does
not, and neither does a run whose timed path is broken underneath.
"""

import json
import os

import pytest

from benchmarks import common, compare
from benchmarks.reference import steps as ref_steps
from benchmarks.tests import test_correct, tiny

CELL = "nemotron3super_train_1chip"
_TRAFFIC = {"generator": "packed_tokens", "per_chip_batch": 2, "seq_len": 32,
            "pool": 4, "doc_len_median": 12, "doc_len_sigma": 1.0,
            "doc_len_clip": [4, 64], "zipf_exponent": 0.0}
#: limits that hold at the tiny size only, from readings on the CPU (seeds
#: 3-7 under both selections, the control and ``half_batch`` on seeds 3-6):
#: the program reads 0.0021-0.0150 by the worst gradient leaf and
#: 0.00010-0.00037 by the median one, the fp8 control 0.041-0.134 and
#: 0.0016-0.0040; the worst leaf's change reads 0.0012-0.0115 for the
#: program and 0.23-0.25 under ``half_batch``
_LIMITS = {"grad_norm_gap": 0.03, "grad_norm_gap_median": 0.0008,
           "update_norm_gap": 0.05}


def tiny_cell(selection=None):
    """The cell's workload file and manifest entries over the tiny
    configuration (with the cell's ``router_selection``, or the one given)
    and a traffic mix of its kind."""
    real = common.Cell.load(CELL)
    with open(os.path.join(tiny.HERE, "tiny_nemotron_h.json")) as f:
        config = dict(json.load(f), flops=real.config["flops"],
                      router_selection=selection
                      or real.config["router_selection"])
    workload = dict(real.workload, trace_steps=2, limits=_LIMITS)
    return common.Cell(CELL, 1, "tiny_nemotron_h", config, workload,
                       _TRAFFIC, real.manifest)


@pytest.mark.parametrize("selection", ["forced_uniform", "top_k"])
def test_unbroken_nemotron_h_run_is_correct(selection):
    result, checks = test_correct._run(tiny_cell(selection))
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_examples_per_s_per_chip",
                                      "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_nemotron_h_timed_path_is_not_correct(fault):
    breaker = {"state_unchanged": test_correct._state_unchanged,
               "half_batch": test_correct._half_batch}[fault]
    result, checks = test_correct._run(tiny_cell(), breaker)
    assert not result["correct"], checks
    failed = [k for k, c in checks.items() if not c["ok"]]
    assert failed and "compilations_in_window" not in failed, checks


def test_nemotron_h_control_is_not_correct():
    cell = tiny_cell()
    cfg, wl = cell.config, cell.workload
    ref = cell.module("reference")
    gen = common.load_module("traffic", cell.traffic["generator"])
    for seed in (3, 4, 5):
        halves = common.split_seed(seed)
        pool = gen.make(cell.traffic, cfg, seed,
                        cell.traffic["per_chip_batch"])[:3]
        reference = ref_steps.first_steps(ref, cfg, wl, halves, pool)
        control = ref_steps.first_steps(ref, cfg, wl, halves, pool,
                                        precision=ref.CONTROL)
        checks = compare.checks(control, reference, wl["limits"])
        assert not all(c["ok"] for c in checks.values()), (seed, checks)
