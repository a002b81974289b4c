"""Device time per traced step under ``hvd.moe_latent``: a latent routed
layer's two projections, the tokens down to the experts' width and the
routed sum back to the hidden width, forward and backward (busiest device).
Nothing where the program has no such scope."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(
        ctx, lambda r: r["kernels_s"].get("hvd.moe_latent"))
