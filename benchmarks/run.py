#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once, in this process, on the chips of
the machine it is started on, and prints one JSON line. Everything that
belongs to one configuration, traffic mix, job kind or per-layer metric is a
file of its own under ``benchmarks/``, found by the name in the manifest
(``benchmarks/README.md``).

    python3 benchmarks/run.py --check

validates the manifest and the trace reduction, with no chip and no JAX
device.
"""

import argparse
import os
import sys
import time

_T_START = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import check, common

    if args.check:
        check.main()
        return
    if not args.workload:
        ap.error("--workload is required (or --check)")
    cell = common.Cell.load(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])
    job = common.load_module("jobs", cell.workload["job"])
    result, checks = job.run(cell, args, t_start=_T_START)
    common.print_result(result, checks)


if __name__ == "__main__":
    main()
