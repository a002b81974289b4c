"""``qwen3_next``-family configurations through the program's public API:
``models.TransformerLM`` built from a per-layer description (Gated DeltaNet
mixers, ``models.GatedDelta``, three layers in every
``full_attention_interval``, and a full-attention layer with q/k norms,
rotary over a part of the head and the element-wise output gate; routed
SwiGLU experts beside a gated shared expert in every layer; the
zero-centred RMSNorm), bfloat16 compute over float32 parameters,
``flash_attention``, ``training.token_xent``, ``optax.adamw``. Only names
and shapes are translated here: the weights are the benchmark's
(``reference/qwen3next.make_weights``), handed over as they are, and the
share of the deployment (which heads, experts and vocabulary rows are held)
is the configuration's. Where the configuration asks for
``router_selection`` ``forced_uniform`` the routed layers are handed the
benchmark's scores to choose by (``reference/mellum.forced_scores``), as
they are handed its weights.
"""

import functools

from benchmarks import common

_mellum = common.load_module("adapters", "mellum")
_BLOCK = {
    "g1": ("ln1", "scale"), "g2": ("ln2", "scale"),
    # a linear layer
    "wqkvz": ("in_proj_qkvz", "kernel"), "wba": ("in_proj_ba", "kernel"),
    "conv": ("conv1d",), "A_log": ("A_log",), "dt_bias": ("dt_bias",),
    "gn": ("norm_scale",), "wout": ("out_proj", "kernel"),
    # a full layer
    "wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
    "wv": ("v_proj", "kernel"), "qn": ("q_norm", "scale"),
    "kn": ("k_norm", "scale"), "wo": ("proj", "kernel"),
    # the experts
    "wr": ("router",), "wg": ("experts_gate",), "wu": ("experts_up",),
    "wd": ("experts_down",), "sg": ("shared_gate", "kernel"),
    "su": ("shared_up", "kernel"), "sd": ("shared_down", "kernel"),
    "wsg": ("shared_expert_gate", "kernel")}


def _path(name):
    if "." in name:
        layer, leaf = name.split(".")
        return ("block" + layer[1:],) + _BLOCK[leaf]
    return _mellum._TOP[name]


def to_tree(weights):
    """The benchmark's flat ``name -> array`` as the model's param tree."""
    tree = {}
    for name, value in weights.items():
        node, path = tree, _path(name)
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = value
    return tree


def ref_names(tree, names):
    """A tree shaped like the params, back under the benchmark's names."""
    out = {}
    for name in names:
        node = tree
        for key in _path(name):
            node = node[key]
        out[name] = node
    return out


def layers(cfg):
    """The configuration's layer pattern (``full_attention_interval``) as
    the model's per-layer description."""
    from horovod_tpu import models

    selection = cfg.get("router_selection", "top_k")
    if selection not in ("top_k", "forced_uniform"):
        raise ValueError(f"router_selection {selection!r}")
    experts = functools.partial(
        models.Experts, routed=cfg["num_experts_routed"],
        top_k=cfg["num_experts_per_tok"], width=cfg["moe_intermediate_size"],
        first=cfg["first_expert"], count=cfg["num_experts"],
        shared=cfg["shared_expert_intermediate_size"], shared_gate=True)
    mixer = models.GatedDelta(
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        conv=cfg["linear_conv_kernel_dim"])
    rotary = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    out = []
    for i in range(cfg["num_layers"]):
        ffn = experts(select=_mellum._forced(i)
                      if selection == "forced_uniform" else None)
        if (i + 1) % cfg["full_attention_interval"]:
            out.append(models.Layer(mixer=mixer, ffn=ffn))
        else:
            out.append(models.Layer(
                heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
                kv_heads=cfg["num_key_value_heads"],
                rope_base=cfg["rope_theta"],
                rotary_dim=rotary if rotary != cfg["head_dim"] else None,
                gate="element", qk_norm=True, ffn=ffn))
    return tuple(out)


def build(cfg, workload):
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.models.transformer import ZERO_CENTRED
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.training import token_xent

    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["rope_scaling"] is not None:
        raise ValueError("a qwen3_next configuration here has a sparse MLP "
                         "in every layer and plain rotary positions")
    model = models.TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_layers"], heads=cfg["num_attention_heads"],
        layers=layers(cfg), norm=ZERO_CENTRED, norm_eps=cfg["rms_norm_eps"],
        pos_embedding="rope", max_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg.get("compute_dtype", "bfloat16")),
        attention_fn=flash_attention)
    opt = workload["optimizer"]
    tx = optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                     weight_decay=opt["weight_decay"])
    # each routed block's counter of the last step (the assignments held
    # here) rides in the state the builders hand on
    stats = {f"block{i}": {"moe_rows": jnp.zeros((), jnp.float32)}
             for i in range(cfg["num_layers"])}
    return {"model": model, "tx": tx, "loss_fn": token_xent,
            "to_tree": to_tree, "ref_names": ref_names, "batch_stats": stats}
