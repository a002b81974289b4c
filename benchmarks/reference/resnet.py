"""Plain reference of ResNet-50 v1.5 (He et al. 2015, "Deep Residual
Learning for Image Recognition"; v1.5 puts the stride on the 3x3
convolution of a bottleneck), its loss and its gradients, in straightforward
``jax.numpy`` / ``lax`` and float32 at ``highest`` precision. Imports
nothing of ``horovod_tpu``.

Departures from the paper, all of them the program's (``models.resnet``)
and listed in the configuration's ``assumed``: NHWC layout; TensorFlow-style
``SAME`` padding on the strided 3x3 convolutions and the max-pool (as the
Keras ResNet50 of the reference benchmark has it); no bias on convolutions.

The benchmark's weights come from here (``make_weights``): He-normal
convolutions, unit BatchNorm gains (every gain, the blocks' last ones too,
as in the paper), zero shifts, a normal(0.01) classifier.
"""

import functools
import math

import jax
import jax.numpy as jnp

from jax import lax

from benchmarks.reference.steps import cfg_key, cfg_of

HIGHEST = lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")


def _blocks(cfg):
    """(name, in channels, bottleneck width, stride) of every block."""
    out, cin = [], cfg["num_filters"]
    for s, n in enumerate(cfg["stage_sizes"]):
        width = cfg["num_filters"] * 2 ** s
        for j in range(n):
            out.append((f"s{s}b{j}", cin, width, 2 if s > 0 and j == 0 else 1))
            cin = 4 * width
    return out


def weight_shapes(cfg):
    f = cfg["num_filters"]
    shapes = {"conv_init": (7, 7, 3, f), "bn_init_g": (f,), "bn_init_b": (f,)}
    for name, cin, w, _ in _blocks(cfg):
        convs = {"conv1": (1, 1, cin, w), "conv2": (3, 3, w, w),
                 "conv3": (1, 1, w, 4 * w)}
        if cin != 4 * w or name.endswith("b0"):
            convs["convp"] = (1, 1, cin, 4 * w)
        for k, shape in convs.items():
            shapes[f"{name}.{k}"] = shape
            shapes[f"{name}.bn{k[4:]}_g"] = (shape[-1],)
            shapes[f"{name}.bn{k[4:]}_b"] = (shape[-1],)
    feat = 4 * f * 2 ** (len(cfg["stage_sizes"]) - 1)
    shapes["fc_w"] = (feat, cfg["num_classes"])
    shapes["fc_b"] = (cfg["num_classes"],)
    return shapes


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_items):
    shapes = weight_shapes(cfg_of(cfg_items))

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = {}
        for n, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, n)
            if name.endswith("_g"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("_b"):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif name == "fc_w":
                out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = shape[0] * shape[1] * shape[2]
                out[name] = math.sqrt(2.0 / fan_in) * jax.random.normal(
                    k, shape, jnp.float32)
        return out

    return jax.jit(make)


def make_weights(cfg, seed_halves):
    """name -> float32 array, on the default device, from the seed."""
    return _weights_fn(cfg_key(cfg))(*seed_halves)


# ---------------------------------------------------------------- forward


def _int8(x):
    """Per-tensor symmetric int8 image of ``x``."""
    scale = 127.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jnp.clip(jnp.round(x * scale), -127.0, 127.0) / scale


def _conv_f32(x, w, stride, padding):
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding, dimension_numbers=_DN,
        precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_int8(x, w, stride, padding):
    """The control: both operands of every product in int8, forward and
    backward (the incoming gradient too), accumulated in float32."""
    return _conv_f32(_int8(x), _int8(w), stride, padding)


def _conv_int8_fwd(x, w, stride, padding):
    return _conv_int8(x, w, stride, padding), (x, w)


def _conv_int8_bwd(stride, padding, res, g):
    x, w = (_int8(a) for a in res)
    _, vjp = jax.vjp(lambda x, w: _conv_f32(x, w, stride, padding), x, w)
    return vjp(_int8(g))


_conv_int8.defvjp(_conv_int8_fwd, _conv_int8_bwd)

#: convolution by the precision the run states; ``int8`` is the control, the
#: nearest precision below the configuration's bfloat16
CONTROL = "int8"
CONVS = {"float32": _conv_f32, "int8": _conv_int8}


def _bn(x, g, b, eps):
    """Training-mode BatchNorm: the batch's own mean and biased variance."""
    mu = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2))
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _bottleneck(x, w, *, stride, eps, conv):
    y = jax.nn.relu(_bn(conv(x, w["conv1"], 1, "SAME"),
                        w["bn1_g"], w["bn1_b"], eps))
    y = jax.nn.relu(_bn(conv(y, w["conv2"], stride, "SAME"),
                        w["bn2_g"], w["bn2_b"], eps))
    y = _bn(conv(y, w["conv3"], 1, "SAME"), w["bn3_g"], w["bn3_b"], eps)
    if "convp" in w:
        x = _bn(conv(x, w["convp"], stride, "SAME"),
                w["bnp_g"], w["bnp_b"], eps)
    return jax.nn.relu(x + y)


def _loss(weights, images, labels, *, cfg, conv):
    eps = cfg["batch_norm_epsilon"]
    x = conv(images, weights["conv_init"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_bn(x, weights["bn_init_g"], weights["bn_init_b"], eps))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for name, _, _, stride in _blocks(cfg):
        w = {k[len(name) + 1:]: v for k, v in weights.items()
             if k.startswith(name + ".")}
        # checkpointed per block: the backward recomputes one block's float32
        # activations instead of keeping sixteen blocks' worth
        x = jax.checkpoint(functools.partial(
            _bottleneck, stride=stride, eps=eps, conv=conv))(x, w)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.matmul(x, weights["fc_w"], precision=HIGHEST) + weights["fc_b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, precision):
    cfg = cfg_of(cfg_items)
    return jax.jit(jax.value_and_grad(
        functools.partial(_loss, cfg=cfg, conv=CONVS[precision])))


def loss_and_grads(cfg, weights, images, labels, *, precision="float32"):
    """Mean loss over the batch and its gradient. BatchNorm's statistics
    are the whole batch's, so the batch is not cut into blocks of rows; the
    blocks' checkpoints are what makes it fit."""
    return _grad_fn(cfg_key(cfg), precision)(
        weights, jnp.asarray(images), jnp.asarray(labels))
