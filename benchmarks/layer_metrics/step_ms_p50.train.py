"""Median host time between consecutive steps seen complete, before the
profiler went on."""

import statistics


def read(ctx):
    gaps = ctx["step_gaps_s"]
    return 1e3 * statistics.median(gaps) if gaps else None
