"""The Mamba-2 layers' share of their roofline: the least time the chip
could take for the FLOPs and bytes of the work under ``hvd.ssm``
(``flops.ssm_cost``: the recurrence and the tensors in and out, the same
work whatever chunk or kernel computes it) over the device time under that
scope in one traced step. Nothing where the program has no such scope."""

from benchmarks import scope_reduce


def read(ctx):
    cell, peaks = ctx["cell"], ctx["peaks"]
    red = scope_reduce.for_cell(ctx)
    cost = getattr(ctx["flops"], "ssm_cost", None)
    if not red or not peaks or not ctx["traced_steps"] or cost is None:
        return None
    seconds = red["kernels_s"].get("hvd.ssm")
    if not seconds:
        return None
    flops, bytes_ = cost(cell.config, cell.traffic,
                         cell.traffic["per_chip_batch"])
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / seconds
