"""Model FLOPs of a ``laguna``-family training step on one chip's share,
from shapes alone: 6 x the parameters that sit in a token's matrix products
(forward 2, backward 4), each layer with its own head count and its gate,
the routed experts counted at the assignments a balanced router sends here
(``num_experts_per_tok x num_experts / num_experts_routed`` a token, not the
experts held), the shared expert and the dense MLP whole, plus attention's
two products over the pairs its mask lets through: the triangle for a full
layer, the band for a sliding one. Recomputation is never counted; the
embedding look-ups and the sort do no FLOPs.
"""

from benchmarks import common

_mellum = common.load_module("flops", "mellum")
local_assignments_per_token = _mellum.local_assignments_per_token
visible_pairs = _mellum.visible_pairs


def _sparse_layers(cfg):
    return sum(mlp == "sparse" for mlp in cfg["mlp_layer_types"])


def matmul_params_per_token(cfg):
    d, hd, nkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    gate = 1 if cfg["gating"] else 0
    attention = sum(2 * d * nq * hd + 2 * d * nkv * hd + gate * d * nq
                    for nq in cfg["num_attention_heads_per_layer"])
    dense = 3 * d * cfg["intermediate_size"]
    sparse = (d * cfg["num_experts_routed"]
              + local_assignments_per_token(cfg)
              * 3 * d * cfg["moe_intermediate_size"]
              + 3 * d * cfg["shared_expert_intermediate_size"])
    n_sparse = _sparse_layers(cfg)
    return (attention + (cfg["num_layers"] - n_sparse) * dense
            + n_sparse * sparse + d * cfg["vocab_size"])


def attention_flops_forward(cfg, seq_len):
    """Scores and values for one sequence, every layer: two products of
    2 x head_dim FLOPs a visible pair and query head of that layer."""
    return 2 * 2 * cfg["head_dim"] * sum(
        visible_pairs(kind, cfg, seq_len) * nq for kind, nq in zip(
            cfg["layer_types"], cfg["num_attention_heads_per_layer"]))


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
    heads = sum(cfg["num_attention_heads_per_layer"])
    q = t * heads * cfg["head_dim"] * 2
    kv = (t * cfg["num_layers"] * cfg["num_key_value_heads"]
          * cfg["head_dim"] * 2)
    forward = per_chip_batch * (2 * q + 2 * kv + t * heads * 4)
    backward = per_chip_batch * (4 * q + 4 * kv)
    return (per_chip_batch * attention_flops_forward(cfg, t), forward,
            backward)


def moe_experts_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the routed layers' grouped products need per
    step on one chip, forward and backward, whatever implements them (the
    shared expert is not among them: it has a scope and a metric of its
    own): ``flops/mellum.moe_experts_cost``'s count a routed layer, over
    the sparse layers only."""
    return _mellum.moe_experts_cost(
        dict(cfg, num_layers=_sparse_layers(cfg)), traffic, per_chip_batch)
