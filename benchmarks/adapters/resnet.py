"""``resnet``-family configurations through the program's public API:
``models.resnet.ResNet`` with bottleneck blocks (bfloat16 compute over
float32 parameters), ``training.softmax_xent``, ``optax.sgd`` with momentum,
as ``bench.py`` builds them. Only names are translated here: the weights
are the benchmark's (``reference/resnet.make_weights``).
"""

_CONV = {"conv1": "Conv_0", "conv2": "Conv_1", "conv3": "Conv_2",
         "convp": "conv_proj"}
_NORM = {"bn1": "BatchNorm_0", "bn2": "BatchNorm_1", "bn3": "BatchNorm_2",
         "bnp": "norm_proj"}
_AFFINE = {"g": "scale", "b": "bias"}


def _paths(names):
    """Benchmark name -> path in the flax tree; blocks are numbered in the
    order ``_blocks`` lists them, as flax numbers ``BottleneckBlock_k``."""
    order, paths = {}, {}
    for name in names:
        if "." in name:
            order.setdefault(name.split(".")[0], len(order))
    for name in names:
        if name == "conv_init":
            paths[name] = ("conv_init", "kernel")
        elif name.startswith("bn_init_"):
            paths[name] = ("bn_init", _AFFINE[name[-1]])
        elif name in ("fc_w", "fc_b"):
            paths[name] = ("head", "kernel" if name == "fc_w" else "bias")
        else:
            block, leaf = name.split(".")
            scope = f"BottleneckBlock_{order[block]}"
            if leaf in _CONV:
                paths[name] = (scope, _CONV[leaf], "kernel")
            else:
                norm, which = leaf.rsplit("_", 1)
                paths[name] = (scope, _NORM[norm], _AFFINE[which])
    return paths


def to_tree(weights):
    tree = {}
    for name, path in _paths(list(weights)).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = weights[name]
    return tree


def ref_names(tree, names):
    out = {}
    for name, path in _paths(names).items():
        node = tree
        for key in path:
            node = node[key]
        out[name] = node
    return out


def build(cfg, workload):
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import resnet
    from horovod_tpu.training import softmax_xent

    model = resnet.ResNet(
        stage_sizes=cfg["stage_sizes"], block_cls=resnet.BottleneckBlock,
        num_classes=cfg["num_classes"], num_filters=cfg["num_filters"],
        dtype=getattr(jnp, cfg.get("compute_dtype", "bfloat16")))
    opt = workload["optimizer"]
    tx = optax.sgd(opt["lr"], momentum=opt["momentum"])
    sample = jnp.zeros((1, cfg["image_size"], cfg["image_size"], 3),
                       jnp.float32)
    stats = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample, train=True)
    )["batch_stats"]
    # running statistics as flax starts them: mean 0, variance 1
    stats = jax.tree_util.tree_map_with_path(
        lambda path, s: (jnp.ones if path[-1].key == "var" else jnp.zeros)(
            s.shape, s.dtype), stats)
    return {"model": model, "tx": tx, "loss_fn": softmax_xent,
            "to_tree": to_tree, "ref_names": ref_names, "batch_stats": stats}
