"""Device time per traced step under ``jvp(hvd.forward)``: the differentiated loss function
(busiest device; scope_reduce partitions the busy time by phase)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, lambda r: r["phases_s"]["forward"])
