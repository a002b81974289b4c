#!/usr/bin/env python3
"""Look at a cell's last trace in the program's terms: the split by phase,
then the operations that took most device time with the phase and kernel
``profiler.scope_of`` gives each, then the ones it gives none. Run the cell
with ``--trace 1`` first: the run leaves its scope table beside the trace.

    python3 benchmarks/tools/scope_look.py <cell> [operations per list]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from benchmarks import scope_reduce, trace_reduce
    from horovod_tpu import profiler

    cell = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    trace_dir = os.path.join(ROOT, ".bench_trace", cell)
    with open(os.path.join(trace_dir, "scope_table.json")) as f:
        tables = scope_reduce.tables_from_json(json.load(f))
    trace = scope_reduce.load_xplane(trace_dir)
    print(json.dumps(scope_reduce.reduce(trace, tables, profiler.scope_of),
                     indent=1))
    names = {scope_reduce.instruction_of(n)
             for ev in trace["devices"].values() for n, _, _ in ev}
    table = scope_reduce.pick_tables(tables, trace["modules"], names)
    plane, events = sorted(trace["devices"].items())[0]
    sums = {}
    for (name, _, dur), (phase, kernel, kind, _, _) in zip(
            events, scope_reduce.classify(events, table, profiler.scope_of)):
        inst = scope_reduce.instruction_of(name)
        key = (phase, kernel, trace_reduce.op_group(name),
               table.get(inst, ("not in the table",))[0][-90:])
        sums[key] = sums.get(key, 0.0) + dur
    ranked = sorted(sums.items(), key=lambda x: -x[1])
    for title, rows in (
            (f"{plane}: most device time (summed, containers included)",
             ranked[:top]),
            ("no phase", [r for r in ranked if r[0][0] is None][:top])):
        print(title)
        for (phase, kernel, group, op_name), ns in rows:
            print(f"  {ns * 1e-6:10.3f} ms  {phase or '-':9} "
                  f"{kernel or '-':14} {group[:70]:70} {op_name}")


if __name__ == "__main__":
    main()
