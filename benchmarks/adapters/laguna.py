"""``laguna``-family configurations through the program's public API:
``models.TransformerLM`` built from a per-layer description (full and
sliding attention layers with a head count each, rotary over a part of the
head with YaRN on the full ones, a head-wise output gate, a dense SwiGLU
MLP in the leading layer and routed SwiGLU experts beside a shared one in
the others, RMSNorm), bfloat16 compute over float32 parameters,
``flash_attention`` (with its ``window``), ``training.token_xent``,
``optax.adamw``. Only names and shapes are translated here: the weights are
the benchmark's (``reference/laguna.make_weights``), handed over as they
are, and the share of the deployment (which heads, experts and vocabulary
rows are held) is the configuration's. Where the configuration asks for
``router_selection`` ``forced_uniform`` the routed layers are handed the
benchmark's scores to choose by (``reference/mellum.forced_scores``), as
they are handed its weights.
"""

import functools

from benchmarks import common

# the names the two families share, and the scores a timed cell chooses by
_mellum = common.load_module("adapters", "mellum")
_forced = _mellum._forced
_TOP = _mellum._TOP
_BLOCK = dict(
    _mellum._BLOCK, wz=("gate_proj", "kernel"), w1=("mlp_gate", "kernel"),
    w3=("mlp_up", "kernel"), w2=("mlp_down", "kernel"),
    sg=("shared_gate", "kernel"), su=("shared_up", "kernel"),
    sd=("shared_down", "kernel"))


def _path(name):
    if "." in name:
        layer, leaf = name.split(".")
        return ("block" + layer[1:],) + _BLOCK[leaf]
    return _TOP[name]


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
    """The configuration's ``layer_types``, ``mlp_layer_types`` and
    ``num_attention_heads_per_layer`` as the model's per-layer
    description."""
    from horovod_tpu import models

    experts = functools.partial(
        models.Experts, routed=cfg["num_experts_routed"],
        top_k=cfg["num_experts_per_tok"], width=cfg["moe_intermediate_size"],
        first=cfg["first_expert"], count=cfg["num_experts"],
        scale=cfg["moe_routed_scaling_factor"],
        shared=cfg["shared_expert_intermediate_size"])
    selection = cfg.get("router_selection", "top_k")
    if selection not in ("top_k", "forced_uniform"):
        raise ValueError(f"router_selection {selection!r}")
    out = []
    for i, (kind, mlp, heads) in enumerate(zip(
            cfg["layer_types"], cfg["mlp_layer_types"],
            cfg["num_attention_heads_per_layer"])):
        rope = cfg["rope_parameters"][kind]
        rotary = int(cfg["head_dim"] * rope.get("partial_rotary_factor", 1))
        yarn = None
        if rope["rope_type"] == "yarn":
            yarn = models.Yarn(
                factor=rope["factor"],
                original_max_len=rope["original_max_position_embeddings"],
                beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
                attention_factor=rope["attention_factor"])
        if mlp == "dense":
            ffn = models.SwiGLU(cfg["intermediate_size"])
        else:
            ffn = experts(select=_forced(i) if selection == "forced_uniform"
                          else None)
        out.append(models.Layer(
            heads=heads, head_dim=cfg["head_dim"],
            kv_heads=cfg["num_key_value_heads"],
            rope_base=rope["rope_theta"], yarn=yarn,
            rotary_dim=rotary if rotary != cfg["head_dim"] else None,
            gate=bool(cfg["gating"]),
            window=(cfg["sliding_window"] if kind == "sliding_attention"
                    else None), ffn=ffn))
    return tuple(out)


def build(cfg, workload):
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.training import token_xent

    per_layer = (cfg["layer_types"], cfg["mlp_layer_types"],
                 cfg["num_attention_heads_per_layer"])
    if any(len(x) != cfg["num_layers"] for x in per_layer) or not set(
            cfg["mlp_layer_types"]) <= {"dense", "sparse"}:
        raise ValueError(
            "a laguna configuration gives one entry a layer of layer_types, "
            "mlp_layer_types (dense or sparse) and "
            "num_attention_heads_per_layer")
    model = models.TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_layers"], heads=cfg["num_attention_heads"],
        layers=layers(cfg), norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        pos_embedding="rope", max_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg.get("compute_dtype", "bfloat16")),
        attention_fn=flash_attention)
    opt = workload["optimizer"]
    tx = optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                     weight_decay=opt["weight_decay"])
    # each routed block's counter of the last step (the assignments held
    # here) rides in the state the builders hand on
    stats = {f"block{i}": {"moe_rows": jnp.zeros((), jnp.float32)}
             for i, mlp in enumerate(cfg["mlp_layer_types"])
             if mlp == "sparse"}
    return {"model": model, "tx": tx, "loss_fn": token_xent,
            "to_tree": to_tree, "ref_names": ref_names, "batch_stats": stats}
