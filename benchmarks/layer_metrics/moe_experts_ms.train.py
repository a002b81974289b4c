"""Device time per traced step under ``hvd.moe_experts``: the routed
layers' grouped matrix products and the activation between them, forward
and backward (busiest device). The products' ``pallas_call``s carry names
of their own (``hvd_moe_gmm``, ``hvd_moe_tgmm``), which
``profiler.scope_of`` keys apart from the scope around them: their time is
added in."""

from benchmarks import scope_reduce

SCOPE, KERNELS = "hvd.moe_experts", "hvd_moe_"


def seconds(reduction):
    """Seconds under the scope in the traced steps, kernels included; None
    where the trace has neither."""
    parts = [v for k, v in reduction["kernels_s"].items()
             if k == SCOPE or k.startswith(KERNELS)]
    return sum(parts) if parts else None


def read(ctx):
    return scope_reduce.per_step_ms(ctx, seconds)
