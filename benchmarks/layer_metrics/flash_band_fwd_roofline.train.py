"""The flash forward's share of its roofline where layers are windowed:
the least time the chip could take for the FLOPs of the visible pairs only
(the band of a sliding layer, the triangle of a full one) and the bytes
(``flops.flash_band_cost``) over the device time of the ``hvd_flash_fwd``
calls in one traced step. The calls are found by the kernel's name through
the program's scope table, never by a result's shape."""

from benchmarks import scope_reduce


def read(ctx, kernel="hvd_flash_fwd", work=lambda f, fwd, bwd: (f, fwd)):
    cell, peaks = ctx["cell"], ctx["peaks"]
    red = scope_reduce.for_cell(ctx)
    cost = getattr(ctx["flops"], "flash_band_cost", None)
    if not red or not peaks or not ctx["traced_steps"] or cost is None:
        return None
    seconds = red["kernels_s"].get(kernel)
    if not seconds:
        return None
    flops, bytes_ = work(*cost(cell.config, cell.traffic,
                               cell.traffic["per_chip_batch"]))
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / seconds
