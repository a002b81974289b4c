"""Plain reference of one chip's share of a ``qwen3_next`` model (Qwen
Qwen3-Next: ``model_type`` ``qwen3_next`` in its ``config.json``), its loss,
its gradients and, through ``reference/steps.py``, its optimizer step, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
Imports nothing of ``horovod_tpu``; the masked attention, the rotary
tables, the router and the forced scores are ``reference/mellum.py``'s,
the partial rotation ``reference/laguna.py``'s.

``R(x, w) = x rsqrt(mean(x^2) + eps) (1 + w)`` is every norm but the
linear layers' output norm. Layer ``l`` is a full-attention layer where
``(l + 1) % full_attention_interval == 0``, else a Gated DeltaNet layer
(arXiv:2412.06464); every layer's FFN is sparse::

    h = R(x, g1)
    linear:  [q, k, v, z] = h Wqkvz;  [b, a] = h Wba  # grouped by key head
             [q | k | v] = silu(conv(q | k | v))      # causal, depthwise
             q = q / sqrt(sum q^2 + 1e-6) / sqrt(d_k)
             k = k / sqrt(sum k^2 + 1e-6)
             beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
             S = 0; for each token t and value head (its key head's q, k):
                 S = exp(g_t) S
                 S = S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t
             x = x + (o rsqrt(mean(o^2) + eps) gn * silu(z)) Wout
    full:    [q | gate] = h Wq per head;  k, v = h Wk, h Wv
             q, k = R(q, qn), R(k, kn)                # over the head
             q, k = rope(q), rope(k)                  # rotate-half over the
                                                      # first head_dim x
                                                      # partial_rotary_factor
             a = softmax(q k^T / sqrt(head_dim) + causal) v
             x = x + (a * sigmoid(gate)) Wo
    h = R(x, g2)
    p = softmax(h Wr) over all routed experts; top-k; w = w / sum(w)
    x = x + sum over chosen experts held here of w_j E_j(h)
          + sigmoid(h Wsg) E_shared(h)
    E(h) = (silu(h Wg) * (h Wu)) Wd
    logits = R(x, gf) Whead;  loss = mean token cross-entropy

The delta rule here is the recurrence itself, token by token (a
``lax.scan`` over the tokens, checkpointed in blocks of them), not the
program's chunked algebra: it is the check of that algebra. The state runs
on across the packed documents of a row, as attention does in every cell.

This chip's share of a deployment (the configuration's ``deployment``): the
weights made here are the ``linear_num_key_heads`` key heads with their
``linear_num_value_heads`` value heads of every linear layer, the
``num_attention_heads`` query heads (with their gates) on the
``num_key_value_heads`` K/V heads of the full layer, the ``num_experts``
routed experts from ``first_expert`` of ``num_experts_routed`` and the
``vocab_size`` rows of embedding and head that one chip holds; the shared
expert and its gate whole, as every chip of the group computes them.

Initial values (``assumed`` in the configuration), as the published code
sets them: normal(0.02) for every matrix, the convolution's taps included
(``transformers``' ``PreTrainedModel._init_weights`` draws every
``nn.Conv1d`` so); ``A_log = log U(0, 16)``; ``dt_bias`` and ``gn`` ones;
every ``R``'s ``w`` zeros.

``router_selection`` ``forced_uniform`` (a timed cell's configuration; not
the model's): a token's experts are the ``top-k`` of
``reference/mellum.forced_scores``, its weights still the router's.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt2 import CONTROL, MATMULS  # noqa: F401
from benchmarks.reference.laguna import _rope_part, _swiglu, layer_weights
from benchmarks.reference.mellum import (
    HIGHEST, _attention, _experts, _scale, rope_tables)

#: norms of the ``1 + w`` form: zeros at start
_ZERO = ("g1", "g2", "gf", "qn", "kn")
_ONES = ("gn", "dt_bias")
#: tokens whose recurrence the backward keeps state by state at once
_TOKEN_BLOCK = 128
#: the configuration's keys the mathematics reads: a jitted function is
#: cached under their values
_KEYS = ("hidden_size", "head_dim", "vocab_size", "num_layers",
         "num_attention_heads", "num_key_value_heads",
         "full_attention_interval", "linear_num_key_heads",
         "linear_num_value_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts",
         "num_experts_routed", "first_expert", "num_experts_per_tok",
         "moe_intermediate_size", "shared_expert_intermediate_size",
         "partial_rotary_factor", "rope_theta", "rms_norm_eps",
         "initializer_range", "router_selection")


def _key(cfg):
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def layer_kind(cfg, i):
    """``linear_attention`` or ``full_attention``, as the published code
    lays layers out from ``full_attention_interval``."""
    return ("full_attention" if (i + 1) % cfg["full_attention_interval"] == 0
            else "linear_attention")


def weight_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    s = cfg["shared_expert_intermediate_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_layers"]):
        p = f"l{i}."
        shapes[p + "g1"] = (d,)
        if layer_kind(cfg, i) == "linear_attention":
            shapes.update({
                p + "wqkvz": (d, 2 * hk * dk + 2 * hv * dv),
                p + "wba": (d, 2 * hv),
                p + "conv": (2 * hk * dk + hv * dv,
                             cfg["linear_conv_kernel_dim"]),
                p + "A_log": (hv,), p + "dt_bias": (hv,), p + "gn": (dv,),
                p + "wout": (hv * dv, d)})
        else:
            shapes.update({
                p + "wq": (d, 2 * nq * hd), p + "wk": (d, nkv * hd),
                p + "wv": (d, nkv * hd), p + "qn": (hd,), p + "kn": (hd,),
                p + "wo": (nq * hd, d)})
        shapes.update({
            p + "g2": (d,), p + "wr": (d, cfg["num_experts_routed"]),
            p + "wg": (e, d, f), p + "wu": (e, d, f), p + "wd": (e, f, d),
            p + "sg": (d, s), p + "su": (d, s), p + "sd": (s, d),
            p + "wsg": (d, 1)})
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
            leaf, k = name.split(".")[-1], jax.random.fold_in(key, n)
            if leaf in _ZERO:
                out[name] = jnp.zeros(shape, jnp.float32)
            elif leaf in _ONES:
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 0.0, 16.0))
            else:
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)


def make_weights(cfg, seed_halves):
    """name -> float32 array, on the default device, from the seed."""
    return _weights_fn(_key(cfg))(*seed_halves)


# ---------------------------------------------------------------- forward


def _norm(x, w, eps):
    """``R``: the ``1 + w`` form."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token: ``q``, ``k`` ``[T, H, d_k]``,
    ``v`` ``[T, H, d_v]``, ``g``, ``beta`` ``[T, H]`` -> ``o`` ``[T, H,
    d_v]``, from a zero state. The scan over each block of tokens is
    checkpointed: the backward keeps a block's states, not the row's."""
    t, h, dk = q.shape

    def token(state, x):
        q, k, v, g, beta = x
        state = jnp.exp(g)[:, None, None] * state
        u = beta[:, None] * (v - jnp.einsum("hde,hd->he", state, k,
                                            precision=HIGHEST))
        state = state + k[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q, precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    size = math.gcd(t, _TOKEN_BLOCK)
    xs = [x.reshape(t // size, size, *x.shape[1:]) for x in (q, k, v, g, beta)]
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
                        xs)
    return o.reshape(t, h, v.shape[-1])


def _conv(x, w):
    """Depthwise causal convolution of ``x`` ``[T, C]`` with ``w`` ``[C,
    K]``, torch's ``Conv1d(groups=C, padding=K-1)`` cut to ``T``."""
    out = jax.lax.conv_general_dilated(
        x.T[None], w[:, None, :], window_strides=(1,),
        padding=[(w.shape[1] - 1, 0)], feature_group_count=w.shape[0],
        precision=HIGHEST)
    return out[0].T


def _linear_part(x, w, *, cfg, mm):
    """What a Gated DeltaNet layer adds to the residual stream."""
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = hv // hk
    h = _norm(x, w["g1"], eps)
    qkvz = mm(h, w["wqkvz"]).reshape(t, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    ba = mm(h, w["wba"]).reshape(t, hk, 2 * r)
    b, a = ba[..., :r].reshape(t, hv), ba[..., r:].reshape(t, hv)
    mixed = jax.nn.silu(_conv(jnp.concatenate(
        [q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, -1)], -1),
        w["conv"]))
    q = mixed[:, :hk * dk].reshape(t, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    # value head j uses key head j // r
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])
    o = delta_rule(q, k, v, g, jax.nn.sigmoid(b))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * w["gn"]
    return mm((o * jax.nn.silu(z)).reshape(t, -1), w["wout"])


def rotary_dim(cfg):
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def _full_part(x, w, *, cfg, mm):
    """What a full-attention layer adds to the residual stream."""
    t, hd, eps = x.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    h = _norm(x, w["g1"], eps)
    qg = mm(h, w["wq"]).reshape(t, -1, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = mm(h, w["wk"]).reshape(t, -1, hd)
    v = mm(h, w["wv"]).reshape(t, -1, hd)
    q, k = _norm(q, w["qn"], eps), _norm(k, w["kn"], eps)
    rope = {"rope_type": "default", "rope_theta": cfg["rope_theta"]}
    cos, sin = rope_tables(rope, rotary_dim(cfg), t)
    q, k = _rope_part(q, cos, sin), _rope_part(k, cos, sin)
    a = _attention(q, k, v, t) * jax.nn.sigmoid(gate)
    return mm(a.reshape(t, -1), w["wo"])


def _ffn_part(x, w, layer, first, *, cfg, mm):
    """What a layer's routed experts and gated shared expert add."""
    h = _norm(x, w["g2"], cfg["rms_norm_eps"])
    return (_experts(h, w, layer, first, cfg=cfg, mm=mm)
            + jax.nn.sigmoid(mm(h, w["wsg"]))
            * _swiglu(h, w["sg"], w["su"], w["sd"], mm))


def _block(x, w, layer, first, *, cfg, mm):
    mixer = (_linear_part if layer_kind(cfg, layer) == "linear_attention"
             else _full_part)
    x = x + mixer(x, w, cfg=cfg, mm=mm)
    return x + _ffn_part(x, w, layer, first, cfg=cfg, mm=mm)


def logits(weights, tokens, first=0, *, cfg, mm=MATMULS["float32"]):
    """One row of tokens (tokens ``first ...`` of its step) through every
    layer and the head: ``[T, vocab_size]``. Each layer is checkpointed."""
    x = weights["embed"][tokens]
    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(functools.partial(
            _block, layer=i, first=first, cfg=cfg, mm=mm))(
                x, layer_weights(weights, i))
    return mm(_norm(x, weights["gf"], cfg["rms_norm_eps"]),
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
        raise ValueError("the qwen3next reference takes one row at a time")
    fn = _grad_fn(_key(cfg), precision)
    n_tok = tokens.shape[0] * tokens.shape[1]
    loss, grads = 0.0, jax.tree_util.tree_map(jnp.zeros_like, weights)
    for r in range(tokens.shape[0]):
        l, grads = fn(weights, grads, jnp.asarray(tokens[r]),
                      jnp.asarray(targets[r]), r * tokens.shape[1])
        loss = loss + l
    return loss / n_tok, _scale(grads, 1.0 / n_tok)
