"""The flash backward's share of its roofline: the least time the chip
could take for the backward's FLOPs and bytes over the device time under
``hvd.flash_bwd`` in one traced step (the union of the scan's ``while`` and
whatever else runs under the scope; the device that takes longest).

The backward does five block products (scores again, dV, dP, dQ, dK)
against the forward's two: 2.5 x ``flops.flash_fwd_cost``'s FLOPs. It reads
q, k, v, the output and its cotangent and writes dq, dk, dv, each
``[B, T, n_embd]`` in bfloat16, once per layer."""

from benchmarks import scope_reduce


def read(ctx):
    cell, peaks = ctx["cell"], ctx["peaks"]
    red = scope_reduce.for_cell(ctx)
    if not red or not peaks or not ctx["traced_steps"]:
        return None
    seconds = red["kernels_s"].get("hvd.flash_bwd")
    if not seconds:
        return None
    cfg, traffic = cell.config, cell.traffic
    batch = traffic["per_chip_batch"]
    flops = 2.5 * ctx["flops"].flash_fwd_cost(cfg, traffic, batch)[0]
    bytes_ = batch * cfg["n_layer"] * 8 * traffic["seq_len"] \
        * cfg["n_embd"] * 2
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / seconds
