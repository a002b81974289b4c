"""Plain reference of one chip's share of a ``laguna`` model (poolside
Laguna: ``model_type`` ``laguna`` in its ``config.json``), its loss, its
gradients and, through ``reference/steps.py``, its optimizer step, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
Imports nothing of ``horovod_tpu``; the mathematics it shares with the
``mellum`` family (RMSNorm, the rotary tables, the masked attention, the
router, the forced scores) is imported from ``reference/mellum.py``.

The block, per layer ``l`` of kind ``layer_types[l]`` with
``H = num_attention_heads_per_layer[l]`` query heads::

    h = rmsnorm(x, g1)                      # x rsqrt(mean(x^2) + eps) g
    q, k, v = h Wq, h Wk, h Wv              # heads of head_dim, no bias
    q, k = rope(q), rope(k)                 # rotate-half over the first
                                            # r = head_dim x partial_rotary_factor
                                            # features, the rest passed through;
                                            # full layers: YaRN reckoned over r
    a = softmax(q k^T / sqrt(head_dim) + mask) v
                                            # full: j <= i; sliding: 0 <= i - j < window
    z = sigmoid(h Wz)                       # [T, H]: one gate a head
    x = x + (a * z[..., None]) Wo
    h = rmsnorm(x, g2)
    dense layer:  x = x + (silu(h W1) * (h W3)) W2
    sparse layer: p = softmax(h Wr) over all routed experts; top-k;
                  w = moe_routed_scaling_factor * w / sum(w)
                  x = x + sum over chosen experts held here of w_j E_j(h) + E_shared(h)
                  E(h) = (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x, gf) Whead;  loss = mean token cross-entropy

This chip's share of a deployment (the configuration's ``deployment``): the
weights made here are each layer's ``num_attention_heads_per_layer`` query
heads on the ``num_key_value_heads`` KV heads, the ``num_experts`` routed
experts from ``first_expert`` of ``num_experts_routed`` and the
``vocab_size`` rows of embedding and head that one chip holds; the shared
expert and the dense MLP whole, as every chip of the group computes them.
What the absent heads and experts would add to the residual stream is left
out; the router keeps its whole width and a token's weights are normalised
over all its ``top-k`` experts, held here or not.

Departures from the published description, each ``assumed`` in the
configuration because the published config does not settle it:

* ``gating: true`` is read as the head-wise sigmoid output gate of
  arXiv:2505.06708 (``z`` above), from the block's normalised input.
* The router is a softmax over all routed experts in float32, top-k, the
  weights normalised over the k and scaled; no bias, no auxiliary loss.
* No normalisation of q and k, no dropout; weights normal(0.02), gains one.
* ``router_selection`` ``forced_uniform`` (a timed cell's configuration; not
  the model's): a token's experts are the ``top-k`` of
  ``reference/mellum.forced_scores``, its weights still the router's.
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt2 import CONTROL, MATMULS  # noqa: F401
from benchmarks.reference.mellum import (
    _attention, _rms_norm, _rope, _scale, _selection, forced_scores,
    rope_tables, route)

_GAINS = ("g1", "g2", "gf")
#: the configuration's keys the mathematics reads: a jitted function is
#: cached under their values
_KEYS = ("hidden_size", "head_dim", "vocab_size", "num_layers",
         "num_attention_heads_per_layer", "num_key_value_heads",
         "intermediate_size", "num_experts", "num_experts_routed",
         "first_expert", "num_experts_per_tok", "moe_intermediate_size",
         "shared_expert_intermediate_size", "moe_routed_scaling_factor",
         "layer_types", "mlp_layer_types", "rope_parameters",
         "sliding_window", "rms_norm_eps", "initializer_range",
         "router_selection", "gating")


def _key(cfg):
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def weight_shapes(cfg):
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    nkv = cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i, (nq, mlp) in enumerate(zip(cfg["num_attention_heads_per_layer"],
                                      cfg["mlp_layer_types"])):
        p = f"l{i}."
        shapes.update({
            p + "g1": (d,), p + "wq": (d, nq * hd), p + "wk": (d, nkv * hd),
            p + "wv": (d, nkv * hd), p + "wo": (nq * hd, d), p + "g2": (d,)})
        if cfg["gating"]:
            shapes[p + "wz"] = (d, nq)
        if mlp == "dense":
            m = cfg["intermediate_size"]
            shapes.update({p + "w1": (d, m), p + "w3": (d, m),
                           p + "w2": (m, d)})
        else:
            s = cfg["shared_expert_intermediate_size"]
            shapes.update({
                p + "wr": (d, cfg["num_experts_routed"]),
                p + "wg": (e, d, f), p + "wu": (e, d, f), p + "wd": (e, f, d),
                p + "sg": (d, s), p + "su": (d, s), p + "sd": (s, d)})
    shapes.update({"gf": (d,), "w_head": (d, v)})
    return shapes


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_key):
    cfg = json.loads(cfg_key)
    shapes = weight_shapes(cfg)
    std = cfg["initializer_range"]

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = {}
        for n, (name, shape) in enumerate(shapes.items()):
            if name.split(".")[-1] in _GAINS:
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
        return out

    return jax.jit(make)


def make_weights(cfg, seed_halves):
    """name -> float32 array, on the default device, from the seed."""
    return _weights_fn(_key(cfg))(*seed_halves)


# ---------------------------------------------------------------- forward


def rotary_dim(cfg, kind):
    """The leading features of a head that a layer of ``kind`` rotates."""
    return int(cfg["head_dim"]
               * cfg["rope_parameters"][kind].get("partial_rotary_factor", 1))


def _rope_part(x, cos, sin):
    """Rotate-half over the first ``2 x cos.shape[-1]`` features of ``[T,
    heads, head_dim]``; the rest pass through."""
    r = 2 * cos.shape[-1]
    return jnp.concatenate([_rope(x[..., :r], cos, sin), x[..., r:]], -1)


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def _experts(h, w, layer, first, *, cfg, mm):
    """The held routed experts' part of the layer, expert by expert over
    every token, each weighted by what the router gave it (zero where the
    token did not choose it), the weights scaled. ``h`` is tokens ``first
    ...`` of the step, in layer ``layer``."""
    scores = None
    if _selection(cfg) == "forced_uniform":
        scores = forced_scores(layer, first, h.shape[0],
                               cfg["num_experts_routed"])
    weights, chosen = route(h, w["wr"], cfg["num_experts_per_tok"], scores)
    weights = weights * cfg["moe_routed_scaling_factor"]

    @jax.checkpoint          # the backward makes an expert's activations again
    def add_expert(y, expert):
        e, wg, wu, wd = expert
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return y + mine[:, None] * _swiglu(h, wg, wu, wd, mm), None

    held = cfg["first_expert"] + jnp.arange(cfg["num_experts"])
    return jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (held, w["wg"], w["wu"], w["wd"]))[0]


def _attention_part(x, w, kind, *, cfg, mm):
    """What a layer's attention adds to the residual stream."""
    t, hd = x.shape[0], cfg["head_dim"]
    h = _rms_norm(x, w["g1"], cfg["rms_norm_eps"])
    q = mm(h, w["wq"]).reshape(t, -1, hd)
    k = mm(h, w["wk"]).reshape(t, -1, hd)
    v = mm(h, w["wv"]).reshape(t, -1, hd)
    cos, sin = rope_tables(cfg["rope_parameters"][kind],
                           rotary_dim(cfg, kind), t)
    q, k = _rope_part(q, cos, sin), _rope_part(k, cos, sin)
    a = _attention(q, k, v, cfg["sliding_window"]
                   if kind == "sliding_attention" else t)
    if cfg["gating"]:
        a = a * jax.nn.sigmoid(mm(h, w["wz"]))[..., None]
    return mm(a.reshape(t, -1), w["wo"])


def _ffn_part(x, w, mlp, layer, first, *, cfg, mm):
    """What a layer's dense MLP, or its routed and shared experts, add."""
    h = _rms_norm(x, w["g2"], cfg["rms_norm_eps"])
    if mlp == "dense":
        return _swiglu(h, w["w1"], w["w3"], w["w2"], mm)
    return (_experts(h, w, layer, first, cfg=cfg, mm=mm)
            + _swiglu(h, w["sg"], w["su"], w["sd"], mm))


def _block(x, w, kind, mlp, layer, first, *, cfg, mm):
    x = x + _attention_part(x, w, kind, cfg=cfg, mm=mm)
    return x + _ffn_part(x, w, mlp, layer, first, cfg=cfg, mm=mm)


def layer_weights(weights, i):
    """Layer ``i``'s weights under their short names."""
    p = f"l{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def logits(weights, tokens, first=0, *, cfg, mm=MATMULS["float32"]):
    """One row of tokens (tokens ``first ...`` of its step) through every
    layer and the head: ``[T, vocab_size]``. The layers differ in shape, so
    each is traced on its own; each is checkpointed: the backward
    recomputes a layer's float32 scores and expert activations instead of
    keeping them."""
    x = weights["embed"][tokens]
    for i, (kind, mlp) in enumerate(zip(cfg["layer_types"],
                                        cfg["mlp_layer_types"])):
        x = jax.checkpoint(functools.partial(
            _block, kind=kind, mlp=mlp, layer=i, first=first, cfg=cfg,
            mm=mm))(x, layer_weights(weights, i))
    return mm(_rms_norm(x, weights["gf"], cfg["rms_norm_eps"]),
              weights["w_head"])


def _sum_loss(weights, tokens, targets, first, *, cfg, mm):
    """Summed token cross-entropy of one row of tokens."""
    logp = jax.nn.log_softmax(logits(weights, tokens, first, cfg=cfg, mm=mm))
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_key, precision):
    """``(weights, acc, tokens, targets, first) -> (loss sum, acc +
    gradient)`` for one row, tokens ``first ...`` of the step; the running
    sum is donated."""
    cfg = json.loads(cfg_key)
    f = jax.value_and_grad(
        functools.partial(_sum_loss, cfg=cfg, mm=MATMULS[precision]))

    def add(weights, acc, tokens, targets, first):
        loss, g = f(weights, tokens, targets, first)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    return jax.jit(add, donate_argnums=(1,))


def loss_and_grads(cfg, weights, tokens, targets, *, precision="float32",
                   rows_per_block=1):
    """Mean loss over the batch and its gradient, row by row
    (``rows_per_block`` is 1: a row is a whole sequence)."""
    if rows_per_block != 1:
        raise ValueError("the laguna reference takes one row at a time")
    fn = _grad_fn(_key(cfg), precision)
    n_tok = tokens.shape[0] * tokens.shape[1]
    loss, grads = 0.0, jax.tree_util.tree_map(jnp.zeros_like, weights)
    for r in range(tokens.shape[0]):
        l, grads = fn(weights, grads, jnp.asarray(tokens[r]),
                      jnp.asarray(targets[r]), r * tokens.shape[1])
        loss = loss + l
    return loss / n_tok, _scale(grads, 1.0 / n_tok)
