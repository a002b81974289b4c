"""Device time per traced step under ``hvd.moe_shared``: the shared expert
that every token of a routed layer passes through beside its routed ones,
three plain matrix products and the activation between them, forward and
backward (busiest device). Nothing where the program has no such scope."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(
        ctx, lambda r: r["kernels_s"].get("hvd.moe_shared"))
