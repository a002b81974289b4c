"""The comparison that decides ``correct`` for a training cell: the timed
step's first three steps against the plain reference's, number by number,
each under a limit of its own from the cell's workload file.

A gap of norms is taken by the worst leaf: the distance between the
program's norm of a leaf and the reference's, over the reference's norm of
that leaf or of the median leaf, whichever is larger (some gradients are
all but zero). Leaves whose reference gradient is under a thousandth of the
median leaf's move under Adam by round-off alone and are left out of the
change's comparison.
"""

import math
import statistics


def leaf_gaps(program, reference, leaves=None):
    """Every leaf's gap of norms, worst first, with the reference's norm as
    a multiple of the median leaf's: for a look at where a gap sits."""
    leaves = sorted(reference) if leaves is None else leaves
    floor = statistics.median(reference[k] for k in leaves)
    rows = []
    for k in leaves:
        gap = abs(program[k] - reference[k]) / max(reference[k], floor, 1e-30)
        rows.append((gap if math.isfinite(gap) else float("inf"), k,
                     reference[k] / floor, program[k], reference[k]))
    return sorted(rows, reverse=True)


def numbers(program, reference):
    """name -> (value, where): the gaps a training cell is held to."""
    if set(program["grad_norms"]) != set(reference["grad_norms"]):
        raise ValueError("program and reference name different leaves: "
                         f"{sorted(set(program['grad_norms']) ^ set(reference['grad_norms']))[:6]}")
    out = {}
    for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"]),
                                 start=1):
        gap = abs(lp - lr) / abs(lr)
        out[f"loss{i}_gap"] = (gap if math.isfinite(gap) else float("inf"),
                               None)
    leaves = sorted(reference["grad_norms"])
    g_med = statistics.median(reference["grad_norms"].values())
    moved = [k for k in leaves if reference["grad_norms"][k] >= 1e-3 * g_med]
    for name, key, among in (("grad_norm_gap", "grad_norms", leaves),
                             ("update_norm_gap", "update_norms", moved)):
        # the median leaf's gap beside the worst one's: steady where single
        # small leaves are noise (PERF.md section 2)
        gaps = leaf_gaps(program[key], reference[key], among)
        out[name] = gaps[0][:2]
        out[name + "_median"] = gaps[len(gaps) // 2][:2]
    return out


def checks(program, reference, limits):
    """The ``checks`` of a result line. Only numbers the workload file gives
    a limit are compared; the others are printed with ``limit`` null."""
    out = {}
    for name, (value, where) in numbers(program, reference).items():
        limit = limits.get(name)
        entry = {"value": value, "limit": limit,
                 "ok": limit is None or value <= limit}
        if where:
            entry["leaf"] = where
        out[name] = entry
    return out
