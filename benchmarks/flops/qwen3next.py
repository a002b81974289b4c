"""Model FLOPs of a ``qwen3_next``-family training step on one chip's
share, from shapes alone: 6 x the parameters that sit in a token's matrix
products (forward 2, backward 4) — the linear layers' in- and
out-projections, the full layer's with its gate, the router, the routed
experts counted at the assignments a balanced router sends here
(``num_experts_per_tok x num_experts / num_experts_routed`` a token, not
the experts held), the shared expert with its gate, the head — plus the
delta rule's recurrence, ``3 x 6 d_k d_v`` a token and a held value head of
a linear layer (``S'^T k``, the rank-one update and ``S^T q``, 2 d_k d_v
each forward, twice that backward), plus the full layer's two attention
products over the causal triangle. Recomputation is never counted; the
convolution, the norms, the embedding look-ups and the sort do no FLOPs
worth counting.
"""

from benchmarks import common

_mellum = common.load_module("flops", "mellum")
local_assignments_per_token = _mellum.local_assignments_per_token


def _kinds(cfg):
    """(linear layers, full layers)."""
    full = cfg["num_layers"] // cfg["full_attention_interval"]
    return cfg["num_layers"] - full, full


def matmul_params_per_token(cfg):
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    linear = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    full = d * 2 * nq * hd + 2 * d * nkv * hd + nq * hd * d
    ffn = (d * cfg["num_experts_routed"]
           + local_assignments_per_token(cfg)
           * 3 * d * cfg["moe_intermediate_size"]
           + 3 * d * cfg["shared_expert_intermediate_size"] + d)
    n_linear, n_full = _kinds(cfg)
    return (n_linear * linear + n_full * full + cfg["num_layers"] * ffn
            + d * cfg["vocab_size"])


def recurrence_flops_forward(cfg, seq_len):
    """The delta rule's forward over one sequence, every linear layer."""
    n_linear, _ = _kinds(cfg)
    return (6 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
            * cfg["linear_num_value_heads"] * seq_len * n_linear)


def attention_flops_forward(cfg, seq_len):
    """Scores and values for one sequence, every full layer: two products
    of 2 x head_dim FLOPs a visible pair and query head."""
    _, n_full = _kinds(cfg)
    return (2 * 2 * cfg["head_dim"] * seq_len * (seq_len + 1) // 2
            * cfg["num_attention_heads"] * n_full)


def model_flops_per_example(cfg, traffic):
    """One sequence of ``seq_len`` tokens, forward and backward."""
    t = traffic["seq_len"]
    return (6 * matmul_params_per_token(cfg) * t
            + 3 * attention_flops_forward(cfg, t)
            + 3 * recurrence_flops_forward(cfg, t))


def flash_band_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the attention forward needs per step on one
    chip, over the full layers: the causal triangle only; q and the output
    ``[T, heads x head_dim]`` and k, v ``[T, kv_heads x head_dim]`` once
    each in bfloat16, the log-sum-exp in float32. Returns ``(flops, forward
    bytes, backward bytes)``; the backward does 2.5 x the FLOPs and reads
    q, k, v, the output and its cotangent and writes dq, dk, dv."""
    t = traffic["seq_len"]
    _, n_full = _kinds(cfg)
    heads = cfg["num_attention_heads"] * n_full
    q = t * heads * cfg["head_dim"] * 2
    kv = t * n_full * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    forward = per_chip_batch * (2 * q + 2 * kv + t * heads * 4)
    backward = per_chip_batch * (4 * q + 4 * kv)
    return (per_chip_batch * attention_flops_forward(cfg, t), forward,
            backward)


def moe_experts_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes the routed layers' grouped products need per
    step on one chip, forward and backward, whatever implements them:
    ``flops/mellum.moe_experts_cost``'s count a routed layer, every layer
    routed (the shared expert has a scope and a metric of its own)."""
    return _mellum.moe_experts_cost(cfg, traffic, per_chip_batch)


def gdn_cost(cfg, traffic, per_chip_batch):
    """FLOPs and HBM bytes of the work under ``hvd.gdn`` per step on one
    chip, forward and backward, over every linear layer, from the
    recurrence and the shapes of what goes in and out, whatever chunk or
    kernel computes it: the recurrence's ``3 x 6 d_k d_v`` a token and
    value head; forward, the projections ``qkvz`` and ``ba`` read in
    bfloat16 and the output ``[T, value heads x d_v]`` written in float32;
    backward, both projections and the output's cotangent read and the
    projections' cotangents written. The convolution, the norms and the
    gates are elementwise work on the same tensors and add no bytes."""
    t = traffic["seq_len"] * per_chip_batch
    n_linear, _ = _kinds(cfg)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    ins = t * (2 * hk * dk + 2 * hv * dv + 2 * hv) * 2
    out = t * hv * dv * 4
    flops = 3 * per_chip_batch * recurrence_flops_forward(
        cfg, traffic["seq_len"])
    return flops, n_linear * ((ins + out) + (2 * ins + out))
