"""Device time per traced step under ``hvd.gdn``: the Gated DeltaNet
layers' work between their in- and out-projections — the causal
convolution, the normalisations of q and k, beta and the decays, the
chunked delta rule and the gated output norm — forward and backward
(busiest device). Nothing where the program has no such scope."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(
        ctx, lambda r: r["kernels_s"].get("hvd.gdn"))
