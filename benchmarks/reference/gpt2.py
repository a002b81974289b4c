"""Plain reference of the GPT-2 block (Radford et al. 2019; OpenAI
``gpt-2/src/model.py``), its loss, its gradients and its optimizer step, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
Imports nothing of ``horovod_tpu``.

Departures from the published block, all of them the program's
(``models.TransformerLM``) and listed in the configuration's ``assumed``:
no bias on the attention projections, an output head of its own (not tied
to ``wte``), LayerNorm epsilon as the configuration's file gives it.

The benchmark's weights come from here too (``make_weights``): one jitted
call from the seed, float32, GPT-2's own initialisation (normal 0.02,
residual projections scaled by 1/sqrt(2 n_layer), zero biases, unit gains).
The program is handed these arrays; the reference makes them again from the
seed when its turn comes, so it takes nothing the program has touched.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.steps import cfg_key, cfg_of

HIGHEST = jax.lax.Precision.HIGHEST
_LAYER_KEYS = ("ln1_g", "ln1_b", "w_qkv", "w_o", "ln2_g", "ln2_b",
               "w_fc", "b_fc", "w_out", "b_out")


def weight_shapes(cfg):
    d, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    shapes = {"wte": (v, d), "wpe": (t, d)}
    for i in range(cfg["n_layer"]):
        p = f"h{i}."
        shapes.update({
            p + "ln1_g": (d,), p + "ln1_b": (d,), p + "w_qkv": (d, 3 * d),
            p + "w_o": (d, d), p + "ln2_g": (d,), p + "ln2_b": (d,),
            p + "w_fc": (d, 4 * d), p + "b_fc": (4 * d,),
            p + "w_out": (4 * d, d), p + "b_out": (d,)})
    shapes.update({"lnf_g": (d,), "lnf_b": (d,), "w_head": (d, v)})
    return shapes


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_items):
    cfg = cfg_of(cfg_items)
    shapes = weight_shapes(cfg)
    std = cfg["initializer_range"]
    resid = std / math.sqrt(2 * cfg["n_layer"])

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = {}
        for n, (name, shape) in enumerate(shapes.items()):
            leaf = name.split(".")[-1]
            if leaf.endswith("_g"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf.endswith("_b") or leaf.startswith("b_"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                s = resid if leaf in ("w_o", "w_out") else std
                out[name] = s * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
        return out

    return jax.jit(make)


def make_weights(cfg, seed_halves):
    """name -> float32 array, on the default device, from the seed."""
    return _weights_fn(cfg_key(cfg))(*seed_halves)


# ---------------------------------------------------------------- forward


def _f32_mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x, dtype, top):
    """Per-tensor scaled float8 image of ``x``."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_mm(a, b):
    """The usual float8 recipe: operands in e4m3 forward, the incoming
    gradient in e5m2 backward, products accumulated in float32."""
    return jnp.matmul(_fp8(a, jnp.float8_e4m3fn, 448.0),
                      _fp8(b, jnp.float8_e4m3fn, 448.0), precision=HIGHEST)


def _fp8_mm_fwd(a, b):
    return _fp8_mm(a, b), (a, b)


def _fp8_mm_bwd(res, g):
    a, b = (_fp8(x, jnp.float8_e4m3fn, 448.0) for x in res)
    g = _fp8(g, jnp.float8_e5m2, 57344.0)
    da = jnp.matmul(g, b.T, precision=HIGHEST)
    db = jnp.matmul(a.reshape(-1, a.shape[-1]).T,
                    g.reshape(-1, g.shape[-1]), precision=HIGHEST)
    return da, db


_fp8_mm.defvjp(_fp8_mm_fwd, _fp8_mm_bwd)


#: matmul of the linear layers, by the precision the run states. ``fp8`` is
#: the control: the nearest precision below the configuration's bfloat16.
CONTROL = "fp8"
MATMULS = {"float32": _f32_mm, "fp8": _fp8_mm}


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, *, heads, eps, mm):
    b, t, d = x.shape
    hd = d // heads
    h = _layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    q, k, v = jnp.split(mm(h, w["w_qkv"]), 3, axis=-1)
    q, k, v = (a.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
               for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HIGHEST)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + mm(a, w["w_o"])
    h = _layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    h = _gelu_new(mm(h, w["w_fc"]) + w["b_fc"])
    return x + mm(h, w["w_out"]) + w["b_out"]


def _stack(weights, n_layer):
    return {k: jnp.stack([weights[f"h{i}.{k}"] for i in range(n_layer)])
            for k in _LAYER_KEYS}


def _sum_loss(weights, tokens, targets, *, cfg, mm):
    """Summed token cross-entropy of a block of rows (the caller divides by
    the batch's token count, so blocks add up to the batch's mean)."""
    n_layer = cfg["n_layer"]
    t = tokens.shape[1]
    x = weights["wte"][tokens] + weights["wpe"][:t]
    body = functools.partial(_block, heads=cfg["n_head"],
                             eps=cfg["layer_norm_epsilon"], mm=mm)
    # checkpointed per layer: the backward recomputes a layer's float32
    # activations (T x T scores among them) instead of keeping 24 layers' worth
    x, _ = jax.lax.scan(jax.checkpoint(lambda c, w: (body(c, w), None)), x,
                        _stack(weights, n_layer))
    x = _layer_norm(x, weights["lnf_g"], weights["lnf_b"],
                    cfg["layer_norm_epsilon"])
    logp = jax.nn.log_softmax(mm(x, weights["w_head"]))
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, precision):
    """``(weights, acc, tokens, targets) -> (loss sum, acc + gradient)``; the
    running sum is donated, so one gradient's worth of memory serves."""
    cfg = cfg_of(cfg_items)
    f = jax.value_and_grad(
        functools.partial(_sum_loss, cfg=cfg, mm=MATMULS[precision]))

    def add(weights, acc, tokens, targets):
        loss, g = f(weights, tokens, targets)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    return jax.jit(add, donate_argnums=(1,))


def loss_and_grads(cfg, weights, tokens, targets, *, precision="float32",
                   rows_per_block=2):
    """Mean loss over the batch and its gradient, in blocks of rows so the
    float32 activations of T x T attention fit beside the state."""
    fn = _grad_fn(cfg_key(cfg), precision)
    n_tok = tokens.shape[0] * tokens.shape[1]
    loss, grads = 0.0, jax.tree_util.tree_map(jnp.zeros_like, weights)
    for r in range(0, tokens.shape[0], rows_per_block):
        l, grads = fn(weights, grads,
                      jnp.asarray(tokens[r:r + rows_per_block]),
                      jnp.asarray(targets[r:r + rows_per_block]))
        loss = loss + l
    return loss / n_tok, _scale(grads, 1.0 / n_tok)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(tree, factor):
    return jax.tree_util.tree_map(lambda g: g * factor, tree)
