"""The routed layers' grouped products' share of their roofline: the least
time the chip could take for their FLOPs and bytes
(``flops.moe_experts_cost``: 3 x 3 products a layer over the rows a
balanced router sends here, the same work whatever implements it) over the
device time under ``hvd.moe_experts`` in one traced step."""

from benchmarks import common, scope_reduce


def read(ctx):
    cell, peaks = ctx["cell"], ctx["peaks"]
    red = scope_reduce.for_cell(ctx)
    cost = getattr(ctx["flops"], "moe_experts_cost", None)
    if not red or not peaks or not ctx["traced_steps"] or cost is None:
        return None
    seconds = common.load_module(
        "layer_metrics", "moe_experts_ms.train").seconds(red)
    if not seconds:
        return None
    flops, bytes_ = cost(cell.config, cell.traffic,
                         cell.traffic["per_chip_batch"])
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / seconds
