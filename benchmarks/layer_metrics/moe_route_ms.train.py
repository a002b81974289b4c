"""Device time per traced step under ``hvd.moe_route``: the routed layers'
router, top-k, sort, the gathers into the sorted buffer and back, forward
and backward (busiest device)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(
        ctx, lambda r: r["kernels_s"].get("hvd.moe_route"))
