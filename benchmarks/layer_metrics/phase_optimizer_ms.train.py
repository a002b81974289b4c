"""Device time per traced step under ``hvd.optimizer`` (``tx.update`` + ``apply_updates``), less what
``hvd.sync`` and the collectives take inside it
(busiest device; scope_reduce partitions the busy time by phase)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, lambda r: r["phases_s"]["optimizer"])
