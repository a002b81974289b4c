"""The flash backward's share of its roofline where layers are windowed:
2.5 x the forward's FLOPs over the visible pairs (five block products for
two) and the backward's bytes (``flops.flash_band_cost``) over the device
time under ``hvd.flash_bwd`` in one traced step: the kernels and the layout
glue around them, as ``flash_bwd_roofline.train`` takes it."""

from benchmarks import common


def read(ctx):
    forward = common.load_module("layer_metrics",
                                 "flash_band_fwd_roofline.train")
    return forward.read(ctx, kernel="hvd.flash_bwd",
                        work=lambda f, fwd, bwd: (2.5 * f, bwd))
