#!/usr/bin/env python3
"""The readings a training cell's limits are set from, in one process on
the chip: for each seed the program's first three steps against the
reference (the lower readings), and for the control seeds the reference in
the precision below (``fp8``) and with each fault planted, against the
reference (the upper readings). One JSON line per seed goes to stdout and
to ``chiprun_out/readings_<cell>.jsonl``.

    python3 benchmarks/tools/readings.py <cell> --seeds 1,2,3 --control-seeds 1,2,3
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--program-dtype", default=None,
                    help="witness: run the program in this compute dtype "
                         "(float32 runs at highest matmul precision)")
    ap.add_argument("--leaves", type=int, default=0,
                    help="also print the N worst leaves of each gap of norms")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal off the chip; proves nothing")
    args = ap.parse_args()

    from benchmarks import common, compare
    from benchmarks.reference import steps as ref_steps

    cell = common.Cell.load(args.cell)
    train = common.load_module("jobs", "train")
    import jax

    cfg, wl = cell.config, cell.workload
    if args.program_dtype:
        cell.config = dict(cfg, compute_dtype=args.program_dtype)
        if args.program_dtype == "float32":
            jax.config.update("jax_default_matmul_precision", "highest")
    opened = train.open_cell(cell, require_chip=not args.cpu)
    cell.config = cfg
    hvd, ref, program = opened["hvd"], opened["ref"], opened["program"]
    generator = opened["generator"]
    global_batch = cell.traffic["per_chip_batch"] * cell.chips
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out",
                            f"readings_{args.cell}.jsonl")

    def flat(numbers):
        return {k: v for k, (v, _) in numbers.items()}

    for seed in dict.fromkeys(seeds + control):
        t0 = time.perf_counter()
        halves = common.split_seed(seed)
        pool = generator.make(cell.traffic, cfg, seed,
                              global_batch)[:train.checked_steps(wl)]
        line = {"cell": args.cell, "seed": seed}
        reference = ref_steps.first_steps(ref, cfg, wl, halves, pool)
        line["reference_s"] = time.perf_counter() - t0
        line["reference_losses"] = reference["losses"]
        if seed in seeds:
            program.load(ref.make_weights(cfg, halves))
            observed = train.first_steps(program, pool, ref, cfg, wl, halves)
            program.free()
            numbers = compare.numbers(observed, reference)
            line["program"] = flat(numbers)
            line["program_leaves"] = {k: w for k, (_, w) in numbers.items()
                                      if w}
            line["program_losses"] = observed["losses"]
            if args.leaves:
                for key in ("grad_norms", "update_norms"):
                    line["worst_" + key] = [
                        [leaf, gap, rel, p, r] for gap, leaf, rel, p, r in
                        compare.leaf_gaps(observed[key], reference[key])
                        [:args.leaves]]
                gaps = sorted(g for g, *_ in compare.leaf_gaps(
                    observed["grad_norms"], reference["grad_norms"]))
                line["grad_gap_quantiles"] = [
                    gaps[int(q * (len(gaps) - 1))]
                    for q in (0.5, 0.9, 0.99, 1.0)]
        if seed in control:
            runs = {"control": dict(precision=ref.CONTROL),
                    "fault_half_batch": dict(fault="half_batch")}
            if cell.chips > 1:
                runs["fault_no_exchange"] = dict(fault="no_exchange",
                                                 chips=cell.chips)
            for name, kw in runs.items():
                try:
                    got = ref_steps.first_steps(ref, cfg, wl, halves, pool,
                                                **kw)
                    line[name] = flat(compare.numbers(got, reference))
                except Exception as e:   # a control that crashes has failed
                    line[name] = {"crashed": repr(e)[:300]}
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")
    hvd.shutdown()


if __name__ == "__main__":
    main()
