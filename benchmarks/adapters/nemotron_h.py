"""``nemotron_h``-family configurations through the program's public API:
``models.TransformerLM`` built from a per-layer description, one part a
block as ``hybrid_override_pattern`` says (``M``: a ``models.Mamba2`` mixer
alone; ``E``: routed relu² experts alone, in a latent width, behind a
sigmoid router, beside a relu² shared expert; ``*``: attention alone, no
positions), RMSNorm, bfloat16 compute over float32 parameters,
``flash_attention``, ``training.token_xent``, ``optax.adamw``. Only names
and shapes are translated here: the weights are the benchmark's
(``reference/nemotron_h.make_weights``), handed over as they are, and the
share of the deployment (which heads, experts and vocabulary rows are held)
is the configuration's. Where the configuration asks for
``router_selection`` ``forced_uniform`` the routed layers are handed the
benchmark's scores to choose by (``reference/mellum.forced_scores``), as
they are handed its weights.
"""

from benchmarks import common

_mellum = common.load_module("adapters", "mellum")
_BLOCK = {
    "g": ("ln1", "scale"),
    # a Mamba-2 layer
    "win": ("in_proj", "kernel"), "conv": ("conv1d",),
    "conv_b": ("conv1d_bias",), "dt_bias": ("dt_bias",), "A_log": ("A_log",),
    "D": ("D",), "gn": ("norm_scale",), "wout": ("out_proj", "kernel"),
    # a LatentMoE layer
    "wr": ("router",), "wl1": ("fc1_latent_proj", "kernel"),
    "wl2": ("fc2_latent_proj", "kernel"), "wu": ("experts_up",),
    "wd": ("experts_down",), "su": ("shared_up", "kernel"),
    "sd": ("shared_down", "kernel"),
    # the attention layer
    "wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
    "wv": ("v_proj", "kernel"), "wo": ("proj", "kernel")}


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
    """The configuration's ``hybrid_override_pattern`` as the model's
    per-layer description."""
    from horovod_tpu import models

    selection = cfg.get("router_selection", "top_k")
    if selection not in ("top_k", "forced_uniform"):
        raise ValueError(f"router_selection {selection!r}")
    mixer = models.Mamba2(
        heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
        groups=cfg["n_groups"], state=cfg["ssm_state_size"],
        conv=cfg["conv_kernel"], chunk=cfg["chunk_size"])
    out = []
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        if kind == "M":
            out.append(models.Layer(mixer=mixer, ffn=None))
        elif kind == "E":
            out.append(models.Layer(ffn=models.Experts(
                routed=cfg["num_experts_routed"],
                top_k=cfg["num_experts_per_tok"],
                width=cfg["moe_intermediate_size"],
                first=cfg["first_expert"], count=cfg["n_routed_experts"],
                select=(_mellum._forced(i) if selection == "forced_uniform"
                        else None),
                scale=cfg["routed_scaling_factor"],
                shared=cfg["moe_shared_expert_intermediate_size"],
                activation="relu2", router="sigmoid",
                latent=cfg["moe_latent_size"])))
        elif kind == "*":
            out.append(models.Layer(
                heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
                kv_heads=cfg["num_key_value_heads"], ffn=None))
        else:
            raise ValueError(f"hybrid_override_pattern: no layer kind "
                             f"{kind!r} (M, E or *)")
    return tuple(out)


def build(cfg, workload):
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.training import token_xent

    if (cfg["mlp_hidden_act"] != "relu2" or cfg["mamba_hidden_act"] != "silu"
            or not cfg["use_conv_bias"] or cfg["mamba_proj_bias"]
            or cfg["attention_bias"] or cfg["mlp_bias"]
            or cfg["n_group"] != 1 or not cfg["norm_topk_prob"]
            or len(cfg["hybrid_override_pattern"]) != cfg["num_layers"]):
        raise ValueError(
            "a nemotron_h configuration here has relu2 experts, silu Mamba-2 "
            "layers with a convolution bias, no other biases, one router "
            "group, normalised expert weights and one pattern entry a layer")
    model = models.TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_layers"], heads=cfg["num_attention_heads"],
        layers=layers(cfg), norm="rmsnorm", norm_eps=cfg["layer_norm_epsilon"],
        pos_embedding="none", max_len=cfg["max_position_embeddings"],
        dtype=getattr(jnp, cfg.get("compute_dtype", "bfloat16")),
        attention_fn=flash_attention)
    opt = workload["optimizer"]
    tx = optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                     weight_decay=opt["weight_decay"])
    # each routed block's counter of the last step (the assignments held
    # here) and its selection bias (zeros: no balancing rule runs) ride in
    # the state the builders hand on
    stats = {f"block{i}": {
        "moe_rows": jnp.zeros((), jnp.float32),
        "router_bias": jnp.zeros((cfg["num_experts_routed"],), jnp.float32)}
        for i, kind in enumerate(cfg["hybrid_override_pattern"])
        if kind == "E"}
    return {"model": model, "tx": tx, "loss_fn": token_xent,
            "to_tree": to_tree, "ref_names": ref_names, "batch_stats": stats}
