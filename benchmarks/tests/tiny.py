"""Tiny stand-ins for the cells: the cell's own workload file (builder,
optimizer, limits) over a configuration and a traffic mix that a test run
can hold."""

import json
import os
import types

from benchmarks import common

HERE = os.path.dirname(os.path.abspath(__file__))

_TRAFFIC = {
    "gpt2": {"generator": "packed_tokens", "per_chip_batch": 4, "seq_len": 64,
             "pool": 4, "doc_len_median": 20, "doc_len_sigma": 1.0,
             "doc_len_clip": [4, 200], "zipf_exponent": 1.0},
    "resnet": {"generator": "synthetic_images", "per_chip_batch": 64,
               "pool": 3},
}


#: limits that hold at the tiny size only, from readings on the CPU: the tiny
#: ResNet's median leaf reads 0.005-0.011 for the program and 0.015-0.028 for
#: the int8 control, where the cell's own size reads 0.004-0.007 and
#: 0.014-0.017 and leaves the control to the worst leaf (PERF.md section 2)
_LIMITS = {"resnet": {"grad_norm_gap_median": 0.013,
                      "update_norm_gap_median": 0.013}}


def cell(real_cell, family, chips):
    """``real_cell``'s workload file and manifest entries at a tiny size."""
    real = common.Cell.load(real_cell)
    with open(os.path.join(HERE, f"tiny_{family}.json")) as f:
        config = json.load(f)
    workload = dict(real.workload, trace_steps=2)
    workload["limits"] = dict(workload["limits"], **_LIMITS.get(family, {}))
    return common.Cell(real_cell, chips, f"tiny_{family}", config, workload,
                       _TRAFFIC[family], real.manifest)


def args(seed=7, seconds=0.5, trace=0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
