"""Model FLOPs of a ``mellum``-family training step on one chip's share,
from shapes alone: 6 x the parameters that sit in a token's matrix products
(forward 2, backward 4), with the experts counted at the assignments a
balanced router sends here (``num_experts_per_tok x num_experts /
num_experts_routed`` a token, not the experts held), plus attention's two
products over the pairs its mask lets through: the triangle for a full
layer, the band for a sliding one. Recomputation is never counted; the
embedding look-ups and the sort do no FLOPs.
"""


def local_assignments_per_token(cfg):
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["num_experts_routed"])


def matmul_params_per_token(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attention = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    router = d * cfg["num_experts_routed"]
    experts = (local_assignments_per_token(cfg)
               * 3 * d * cfg["moe_intermediate_size"])
    return (cfg["num_layers"] * (attention + router + experts)
            + d * cfg["vocab_size"])


def visible_pairs(kind, cfg, seq_len):
    """(row, column) pairs a layer's mask lets through in one sequence."""
    if kind == "sliding_attention":
        w = min(cfg["sliding_window"], seq_len)
        return w * (w + 1) // 2 + (seq_len - w) * w
    return seq_len * (seq_len + 1) // 2


def attention_flops_forward(cfg, seq_len):
    """Scores and values for one sequence, every layer: two products of
    2 x head_dim FLOPs a visible pair and query head."""
    pairs = sum(visible_pairs(kind, cfg, seq_len)
                for kind in cfg["layer_types"])
    return 2 * 2 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]


def model_flops_per_example(cfg, traffic):
    """One sequence of ``seq_len`` tokens, forward and backward."""
    t = traffic["seq_len"]
    return (6 * matmul_params_per_token(cfg) * t
            + 3 * attention_flops_forward(cfg, t))


def flash_band_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the attention forward needs per step on one
    chip, over every layer: the visible pairs only; q and the output
    ``[T, heads x head_dim]`` and k, v ``[T, kv_heads x head_dim]`` once
    each in bfloat16, the log-sum-exp in float32. Returns ``(flops, forward
    bytes, backward bytes)``; the backward does 2.5 x the FLOPs (five block
    products for two) and reads q, k, v, the output and its cotangent and
    writes dq, dk, dv."""
    t = traffic["seq_len"]
    q = t * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    kv = t * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    n = per_chip_batch * cfg["num_layers"]
    forward = n * (2 * q + 2 * kv + t * cfg["num_attention_heads"] * 4)
    backward = n * (4 * q + 4 * kv)
    return (per_chip_batch * attention_flops_forward(cfg, t), forward,
            backward)


def moe_experts_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the routed layers' grouped products need per
    step on one chip, forward and backward, whatever implements them: in
    each layer 3 x 3 products (gate, up, down: the product, its input's
    gradient, its matrix's) of 2 x rows x hidden x expert-width FLOPs over
    the ``rows`` a balanced router sends here; each product's operands and
    result once, activations and matrices in bfloat16, the matrices'
    gradients in float32."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = per_chip_batch * traffic["seq_len"] * local_assignments_per_token(
        cfg)
    flops = cfg["num_layers"] * 9 * 2 * rows * d * f
    matrix = cfg["num_experts"] * d * f
    wide, narrow = rows * d * 2, rows * f * 2
    one_layer = (
        # forward: gate, up (read rows x hidden, the matrix; write rows x
        # width), down (the other way round)
        3 * (wide + narrow + 2 * matrix)
        # backward: three input gradients, the same traffic
        + 3 * (wide + narrow + 2 * matrix)
        # and three matrix gradients: both activations read, float32 out
        + 3 * (wide + narrow + 4 * matrix))
    return flops, cfg["num_layers"] * one_layer
