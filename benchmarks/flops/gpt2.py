"""Model FLOPs of a ``gpt2``-family training step, from shapes alone:
6 x the parameters that sit in matrix products (forward 2, backward 4) per
token, plus causal attention's two products (scores and values) at half the
square. Recomputation is never counted; the embedding look-ups do no FLOPs.
"""


def matmul_params(cfg):
    d = cfg["n_embd"]
    per_layer = d * 3 * d + d * d + 2 * d * 4 * d
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def attention_flops_forward(cfg, seq_len):
    """Causal scores + values for one sequence, every layer: 2 products of
    2 T^2 d FLOPs each, half of them under the mask."""
    return cfg["n_layer"] * 2 * seq_len * seq_len * cfg["n_embd"]


def model_flops_per_example(cfg, traffic):
    """One sequence of ``seq_len`` tokens, forward and backward."""
    t = traffic["seq_len"]
    return 6 * matmul_params(cfg) * t + 3 * attention_flops_forward(cfg, t)


def flash_fwd_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the flash forward needs per step on one chip:
    every layer's causal scores and values; q, k, v read and the output
    written once in bfloat16, the log-sum-exp in float32."""
    t, d = traffic["seq_len"], cfg["n_embd"]
    flops = per_chip_batch * attention_flops_forward(cfg, t)
    bytes_ = per_chip_batch * cfg["n_layer"] * (
        4 * t * d * 2 + t * cfg["n_head"] * 4)
    return flops, bytes_
