"""The whole step's share of the chip's bfloat16 peak: model FLOPs per
example from shapes x examples a second over the untraced part of the
window, over chips x peak."""


def read(ctx):
    if not ctx["peaks"] or not ctx["steady_steps"]:
        return None
    cell = ctx["cell"]
    rate = ctx["steady_steps"] * ctx["global_batch"] / ctx["steady_s"]
    flops = ctx["flops"].model_flops_per_example(cell.config, cell.traffic)
    return 100.0 * flops * rate / (
        cell.chips * ctx["peaks"]["bf16_flops_per_s"])
