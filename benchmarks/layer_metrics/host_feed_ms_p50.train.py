"""Median per step of the host time inside the program's own spans:
``hvd.step`` (``InstrumentedStep.__call__``: hooks, dispatch, metrics) plus
the ``hvd.shard_batch`` calls that fed it. A step cannot get shorter than
this before the host sets the pace. Needs host tracer level >= 1."""

from benchmarks import scope_reduce


def read(ctx):
    red = scope_reduce.for_cell(ctx)
    if not red or "host_feed_s_p50" not in red:
        return None
    return 1e3 * red["host_feed_s_p50"]
