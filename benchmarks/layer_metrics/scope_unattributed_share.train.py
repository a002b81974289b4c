"""The share of the busy device time whose instruction carries no ``hvd.*``
scope, or is not in ``profiler.scope_table()``, and runs inside no
operation that does (busiest device)."""

from benchmarks import scope_reduce


def read(ctx):
    red = scope_reduce.for_cell(ctx)
    if not red or not red["busy_s"]:
        return None
    return 100.0 * red["unattributed_s"] / red["busy_s"]
