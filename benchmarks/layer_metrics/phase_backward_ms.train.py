"""Device time per traced step under ``transpose(jvp(hvd.forward))``: the loss function's transpose
(busiest device; scope_reduce partitions the busy time by phase)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, lambda r: r["phases_s"]["backward"])
