"""Device time per traced step under ``hvd.ssm``: the Mamba-2 layers' work
between their in- and out-projections — the causal convolution, the step
sizes and decays, the chunked state-space recurrence and the gated group
norm — forward and backward (busiest device). Nothing where the program
has no such scope."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(
        ctx, lambda r: r["kernels_s"].get("hvd.ssm"))
