"""Model FLOPs of a ``nemotron_h``-family training step on one chip's share,
from shapes alone: 6 x the parameters that sit in a token's matrix products
(forward 2, backward 4) — the Mamba-2 layers' in- and out-projections, the
attention layer's, the router, the latent projections, the routed experts
counted at the assignments a balanced router sends here
(``num_experts_per_tok x n_routed_experts / num_experts_routed`` a token,
not the experts held), the shared expert, the head — plus the state-space
recurrence, ``3 x 4 P N`` a token and a held head of a Mamba-2 layer
(``dt x B^T`` into the state and ``S C``, 2 P N each forward, twice that
backward), plus the attention layer's two products over the causal
triangle. Recomputation is never counted; the convolution, the norms, the
decays, the embedding look-ups and the sort do no FLOPs worth counting.
"""


def _count(cfg, kind):
    return cfg["hybrid_override_pattern"].count(kind)


def local_assignments_per_token(cfg):
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["num_experts_routed"])


def _in_proj_width(cfg):
    """``[z | x | B | C | dt]`` of a Mamba-2 layer's in-projection."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return (2 * inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
            + cfg["mamba_num_heads"])


def matmul_params_per_token(cfg):
    d = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    lat = cfg["moe_latent_size"]
    mamba = d * _in_proj_width(cfg) + inner * d
    attention = 2 * d * nq * hd + 2 * d * nkv * hd
    moe = (d * cfg["num_experts_routed"] + 2 * d * lat
           + local_assignments_per_token(cfg)
           * 2 * lat * cfg["moe_intermediate_size"]
           + 2 * d * cfg["moe_shared_expert_intermediate_size"])
    return (_count(cfg, "M") * mamba + _count(cfg, "*") * attention
            + _count(cfg, "E") * moe + d * cfg["vocab_size"])


def recurrence_flops_forward(cfg, seq_len):
    """The recurrence's forward over one sequence, every Mamba-2 layer."""
    return (4 * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
            * cfg["mamba_num_heads"] * seq_len * _count(cfg, "M"))


def attention_flops_forward(cfg, seq_len):
    """Scores and values for one sequence, every attention layer: two
    products of 2 x head_dim FLOPs a visible pair and query head."""
    return (2 * 2 * cfg["head_dim"] * seq_len * (seq_len + 1) // 2
            * cfg["num_attention_heads"] * _count(cfg, "*"))


def model_flops_per_example(cfg, traffic):
    """One sequence of ``seq_len`` tokens, forward and backward."""
    t = traffic["seq_len"]
    return (6 * matmul_params_per_token(cfg) * t
            + 3 * attention_flops_forward(cfg, t)
            + 3 * recurrence_flops_forward(cfg, t))


def flash_band_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the attention forward needs per step on one
    chip, over the attention layers: the causal triangle only; q and the
    output ``[T, heads x head_dim]`` and k, v ``[T, kv_heads x head_dim]``
    once each in bfloat16, the log-sum-exp in float32. Returns ``(flops,
    forward bytes, backward bytes)``; the backward does 2.5 x the FLOPs and
    reads q, k, v, the output and its cotangent and writes dq, dk, dv."""
    t, n = traffic["seq_len"], _count(cfg, "*")
    heads = cfg["num_attention_heads"] * n
    q = t * heads * cfg["head_dim"] * 2
    kv = t * n * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    forward = per_chip_batch * (2 * q + 2 * kv + t * heads * 4)
    backward = per_chip_batch * (4 * q + 4 * kv)
    return (per_chip_batch * attention_flops_forward(cfg, t), forward,
            backward)


def moe_experts_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the routed layers' grouped products need per
    step on one chip, forward and backward, whatever implements them: in
    each layer 2 x 3 products (up and down, relu² having no gate: the
    product, its input's gradient, its matrix's) of 2 x rows x latent x
    expert-width FLOPs over the ``rows`` a balanced router sends here; each
    product's operands and result once, activations and matrices in
    bfloat16, the matrices' gradients in float32."""
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    rows = per_chip_batch * traffic["seq_len"] * local_assignments_per_token(
        cfg)
    layers = _count(cfg, "E")
    flops = layers * 6 * 2 * rows * lat * f
    matrix = cfg["n_routed_experts"] * lat * f
    wide, narrow = rows * lat * 2, rows * f * 2
    one_layer = (
        # forward: up (read rows x latent, the matrix; write rows x width),
        # down (the other way round)
        2 * (wide + narrow + 2 * matrix)
        # backward: two input gradients, the same traffic
        + 2 * (wide + narrow + 2 * matrix)
        # and two matrix gradients: both activations read, float32 out
        + 2 * (wide + narrow + 4 * matrix))
    return flops, layers * one_layer


def ssm_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes of the work under ``hvd.ssm`` per step on one
    chip, forward and backward, over every Mamba-2 layer, from the
    recurrence and the shapes of what goes in and out, whatever chunk or
    kernel computes it: the recurrence's ``3 x 4 P N`` a token and head;
    forward, the in-projection ``[z | x | B | C | dt]`` read in bfloat16 and
    the output ``[T, heads x P]`` written in float32; backward, the
    in-projection and the output's cotangent read and the in-projection's
    cotangent written. The convolution, the decays and the norm are
    elementwise work on the same tensors and add no bytes."""
    t = traffic["seq_len"] * per_chip_batch
    ins = t * _in_proj_width(cfg) * 2
    out = t * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * 4
    flops = 3 * per_chip_batch * recurrence_flops_forward(
        cfg, traffic["seq_len"])
    return flops, _count(cfg, "M") * ((ins + out) + (2 * ins + out))
