"""``gpt2``-family configurations through the program's public API:
``models.TransformerLM`` with learned positions, bfloat16 compute over
float32 parameters and ``flash_attention``; ``training.token_xent``;
``optax.adamw``. Only names and shapes are translated here: the weights
are the benchmark's (``reference/gpt2.make_weights``), handed over as they
are.
"""

_BLOCK = {"ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
          "w_qkv": ("qkv", "kernel"), "w_o": ("proj", "kernel"),
          "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
          "w_fc": ("mlp_up", "kernel"), "b_fc": ("mlp_up", "bias"),
          "w_out": ("mlp_down", "kernel"), "b_out": ("mlp_down", "bias")}
_TOP = {"wte": ("tok_embed", "embedding"), "wpe": ("pos_embed",),
        "lnf_g": ("ln_f", "scale"), "lnf_b": ("ln_f", "bias"),
        "w_head": ("lm_head", "kernel")}


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


def build(cfg, workload):
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.training import token_xent

    if cfg["n_inner"] not in (None, 4 * cfg["n_embd"]):
        raise ValueError("TransformerLM's MLP is mlp_ratio x dim: n_inner "
                         f"{cfg['n_inner']} is not 4 x {cfg['n_embd']}")
    model = models.TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["n_embd"], depth=cfg["n_layer"],
        heads=cfg["n_head"], mlp_ratio=4, max_len=cfg["n_positions"],
        dtype=getattr(jnp, cfg.get("compute_dtype", "bfloat16")), pos_embedding="learned",
        attention_fn=flash_attention)
    opt = workload["optimizer"]
    tx = optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                     weight_decay=opt["weight_decay"])
    return {"model": model, "tx": tx, "loss_fn": token_xent,
            "to_tree": to_tree, "ref_names": ref_names, "batch_stats": {}}
