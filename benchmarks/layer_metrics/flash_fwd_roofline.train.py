"""The flash forward's share of its roofline: the least time the chip
could take for the forward's FLOPs and bytes (from shapes, ``flops/``)
over the summed device time of the Mosaic calls in one traced step.

The program gives its ``pallas_call`` no name, so the calls are told from
other operations by what only they produce: a tuple of the attention
output ``bf16[B*H, T, d]`` and the log-sum-exp ``f32[B*H, T, 1]``. A trace
in which nothing matches returns nothing."""

import re

from benchmarks import trace_reduce


def read(ctx):
    cell, peaks = ctx["cell"], ctx["peaks"]
    if not peaks or not ctx["traced_steps"] or not ctx["trace"]["devices"]:
        return None
    cfg, traffic = cell.config, cell.traffic
    rows = traffic["per_chip_batch"] * cfg["n_head"]
    t, hd = traffic["seq_len"], cfg["n_embd"] // cfg["n_head"]
    pattern = re.compile(r"= \(bf16\[%d,%d,%d\], f32\[%d,%d,1\]\) custom-call"
                         % (rows, t, hd, rows, t))
    seconds = trace_reduce.ops_matching(
        ctx["trace"],
        lambda name: bool(pattern.search(trace_reduce.short_name(name))))
    if not seconds:
        return None
    flops, bytes_ = ctx["flops"].flash_fwd_cost(
        cfg, traffic, traffic["per_chip_batch"])
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced_steps"] / seconds
