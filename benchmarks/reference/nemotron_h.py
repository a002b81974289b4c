"""Plain reference of one chip's share of a ``nemotron_h`` model (NVIDIA
Nemotron-H / Nemotron 3: ``model_type`` ``nemotron_h`` in its
``config.json``), its loss, its gradients and, through
``reference/steps.py``, its optimizer step, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. Imports nothing
of ``horovod_tpu``; the masked attention and the forced scores are
``reference/mellum.py``'s.

Every block is one part, as ``hybrid_override_pattern`` says: ``M`` a
Mamba-2 layer (arXiv:2405.21060), ``E`` a LatentMoE layer, ``*`` an
attention layer. ``R(x, w) = x rsqrt(mean(x^2) + eps) w``::

    x = x + part(R(x, g))
    M:  [z | xBC | dt] = h Win                      # I | I + 2 G N | H
        xBC = silu(conv(xBC) + b_conv)              # causal, depthwise
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S = 0; for each token t and head h (of group h // (H / G)):
            S = exp(dt_t A) S + dt_t x_t B_t^T;   y_t = S C_t + D x_t
        y = R_group(y * silu(z), gn)                # groups of I / G
        part = y Wout
    E:  s = sigmoid(h Wr) over all routed experts (float32)
        chosen = top-k(s + b), b the selection bias (zeros)
        w = routed_scaling_factor * s_chosen / (sum s_chosen + 1e-20)
        l = h Wl1                                   # hidden -> latent
        part = (sum over chosen experts held here of w_j relu(l U_j)^2 V_j)
               Wl2 + relu(h Su)^2 Sd
    *:  q, k, v = h Wq, h Wk, h Wv                  # no rotary (NoPE)
        part = softmax(q k^T / sqrt(head_dim) + causal) v Wo
    logits = R(x, gf) Whead;  loss = mean token cross-entropy

The recurrence here is the recurrence itself, token by token (a ``lax.scan``
over the tokens, checkpointed in blocks of them), not the program's
chunked algebra: it is the check of that algebra. The routed part is a
loop over the held experts, each over every token. The state runs on
across the packed documents of a row, as attention does in every cell.

This chip's share of a deployment (the configuration's ``deployment``): the
weights made here are the ``mamba_num_heads`` heads with their ``n_groups``
groups of ``B`` and ``C`` of every Mamba-2 layer, the
``num_attention_heads`` query heads on the ``num_key_value_heads`` K/V
heads of the attention layer, the ``n_routed_experts`` routed experts from
``first_expert`` of ``num_experts_routed`` and the ``vocab_size`` rows of
embedding and head that one chip holds; the router, the latent projections
and the shared expert whole, as every chip of the group computes them.

Initial values (``assumed`` in the configuration), as the published code
sets them: normal(``initializer_range``) for every matrix but the Mamba-2
layers' ``out_proj``, which ``rescale_prenorm_residual`` draws as torch's
``kaiming_uniform_(a=sqrt(5))`` over the layer's published fan-in
(``expand x hidden_size``) and divides by ``sqrt(num_hidden_layers)``; the
convolution's taps and bias as torch draws a ``Conv1d``'s, uniform within
``1 / sqrt(conv_kernel)``; ``A_log = log U(1, 16)``; ``D`` ones;
``dt_bias`` the inverse softplus of ``dt``, log-uniform between
``time_step_min`` and ``time_step_max``, at least ``time_step_floor``; every
norm's weight ones.

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
from benchmarks.reference.laguna import layer_weights
from benchmarks.reference.mellum import (
    HIGHEST, _attention, _scale, _selection, forced_scores)

#: tokens whose recurrence the backward keeps state by state at once
_TOKEN_BLOCK = 128
#: the configuration's keys the mathematics reads: a jitted function is
#: cached under their values
_KEYS = ("hidden_size", "vocab_size", "num_layers", "hybrid_override_pattern",
         "num_hidden_layers", "expand", "mamba_num_heads", "mamba_head_dim",
         "n_groups", "ssm_state_size", "conv_kernel", "num_attention_heads",
         "num_key_value_heads", "head_dim", "n_routed_experts",
         "num_experts_routed", "first_expert", "num_experts_per_tok",
         "moe_intermediate_size", "moe_latent_size",
         "moe_shared_expert_intermediate_size", "routed_scaling_factor",
         "norm_topk_prob", "layer_norm_epsilon", "initializer_range",
         "time_step_min", "time_step_max", "time_step_floor",
         "router_selection")


def _key(cfg):
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def layer_kind(cfg, i):
    """``M``, ``E`` or ``*``: layer ``i``'s part."""
    return cfg["hybrid_override_pattern"][i]


def _check(cfg):
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_layers"] or set(pattern) - set("ME*"):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: one of M, E, * a layer, "
            f"num_layers ({cfg['num_layers']}) of them")
    if not cfg["norm_topk_prob"]:
        raise ValueError("a nemotron_h configuration here normalises its "
                         "chosen experts' weights (norm_topk_prob)")


def weight_shapes(cfg):
    _check(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner, bc = h * p, 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    lat, s = cfg["moe_latent_size"], cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_layers"]):
        pre = f"l{i}."
        shapes[pre + "g"] = (d,)
        kind = layer_kind(cfg, i)
        if kind == "M":
            shapes.update({
                pre + "win": (d, 2 * inner + bc + h),
                pre + "conv": (inner + bc, cfg["conv_kernel"]),
                pre + "conv_b": (inner + bc,), pre + "dt_bias": (h,),
                pre + "A_log": (h,), pre + "D": (h,), pre + "gn": (inner,),
                pre + "wout": (inner, d)})
        elif kind == "E":
            shapes.update({
                pre + "wr": (d, cfg["num_experts_routed"]),
                pre + "wl1": (d, lat), pre + "wl2": (lat, d),
                pre + "wu": (e, lat, f), pre + "wd": (e, f, lat),
                pre + "su": (d, s), pre + "sd": (s, d)})
        else:
            shapes.update({
                pre + "wq": (d, nq * hd), pre + "wk": (d, nkv * hd),
                pre + "wv": (d, nkv * hd), pre + "wo": (nq * hd, d)})
    shapes.update({"gf": (d,), "w_head": (d, v)})
    return shapes


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_key):
    cfg = json.loads(cfg_key)
    shapes = weight_shapes(cfg)
    std = cfg["initializer_range"]
    # torch's kaiming_uniform_(a=sqrt(5)) is U(+-1/sqrt(fan_in)); the
    # published fan-in of out_proj is the layer's whole inner width
    out_bound = (1.0 / math.sqrt(cfg["expand"] * cfg["hidden_size"])
                 / math.sqrt(cfg["num_hidden_layers"]))
    conv_bound = 1.0 / math.sqrt(cfg["conv_kernel"])
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])

    def make(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
        out = {}
        for n, (name, shape) in enumerate(shapes.items()):
            leaf, k = name.split(".")[-1], jax.random.fold_in(key, n)

            def uniform(a, b):
                return jax.random.uniform(k, shape, jnp.float32, a, b)

            if leaf in ("g", "gf", "gn", "D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf == "A_log":
                out[name] = jnp.log(uniform(1.0, 16.0))
            elif leaf == "dt_bias":
                dt = jnp.maximum(jnp.exp(uniform(lo, hi)),
                                 cfg["time_step_floor"])
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif leaf in ("conv", "conv_b"):
                out[name] = uniform(-conv_bound, conv_bound)
            elif leaf == "wout":
                out[name] = uniform(-out_bound, out_bound)
            else:
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)


def make_weights(cfg, seed_halves):
    """name -> float32 array, on the default device, from the seed."""
    return _weights_fn(_key(cfg))(*seed_halves)


# ---------------------------------------------------------------- forward


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _conv(x, w):
    """Depthwise causal convolution of ``x`` ``[T, C]`` with ``w`` ``[C,
    K]``, torch's ``Conv1d(groups=C, padding=K-1)`` cut to ``T``, as a sum
    over the taps: ``out[t] = sum_j w[:, j] x[t - K + 1 + j]``. (The TPU's
    compiler refuses the gradient of ``lax.conv_general_dilated`` over
    these 1,280 channels with a bias beside it: an HLO verifier
    ``RET_CHECK`` on a broadcast.)"""
    k, t = w.shape[1], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(padded[j:j + t] * w[:, j] for j in range(k))


def recurrence(x, dt, a, b, c):
    """The state-space recurrence token by token: ``x`` ``[T, H, P]``,
    ``dt`` ``[T, H]``, ``a`` ``[H]``, ``b`` and ``c`` ``[T, G, N]`` -> ``y``
    ``[T, H, P]`` (without ``D``), from a zero state. The scan over each
    block of tokens is checkpointed: the backward keeps a block's states,
    not the row's."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    b, c = jnp.repeat(b, h // g, axis=1), jnp.repeat(c, h // g, axis=1)

    def token(state, xs):
        x, dt, b, c = xs
        state = (jnp.exp(dt * a)[:, None, None] * state
                 + (dt[:, None] * x)[:, :, None] * b[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c, precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    size = math.gcd(t, _TOKEN_BLOCK)
    xs = [v.reshape(t // size, size, *v.shape[1:]) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(block, jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape(t, h, p)


def _mamba_part(h, w, *, cfg, mm):
    """What a Mamba-2 layer adds, from the block's normalised input."""
    t = h.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * p
    zxbcdt = mm(h, w["win"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * g * n],
                  zxbcdt[:, 2 * inner + 2 * g * n:])
    xbc = jax.nn.silu(_conv(xbc, w["conv"]) + w["conv_b"])
    x = xbc[:, :inner].reshape(t, heads, p)
    b = xbc[:, inner:inner + g * n].reshape(t, g, n)
    c = xbc[:, inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["A_log"]), b, c) + w["D"][:, None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = _norm(y, 1.0, cfg["layer_norm_epsilon"]).reshape(t, inner) * w["gn"]
    return mm(y, w["wout"])


def route(h, wr, top_k, scale, scores=None):
    """The sigmoid router: float32 ``s = sigmoid(h Wr)``, the ``top_k``
    largest of ``s`` plus the selection bias (zeros), or of ``scores``
    where given, weighed by ``s`` over the chosen ``s``' sum, times
    ``scale``."""
    s = jax.nn.sigmoid(jnp.matmul(h, wr, precision=HIGHEST))
    _, e = jax.lax.top_k(s if scores is None else scores, top_k)
    w = jnp.take_along_axis(s, e, axis=-1)
    return scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), e


def _relu2(h, up, down, mm):
    return mm(jnp.square(jax.nn.relu(mm(h, up))), down)


def _moe_part(h, w, layer, first, *, cfg, mm):
    """What a LatentMoE layer adds: the held routed experts' part, expert by
    expert over every token in the latent space, back to the hidden width,
    and the shared expert. ``h`` is tokens ``first ...`` of the step, in
    layer ``layer``."""
    scores = None
    if _selection(cfg) == "forced_uniform":
        scores = forced_scores(layer, first, h.shape[0],
                               cfg["num_experts_routed"])
    weights, chosen = route(h, w["wr"], cfg["num_experts_per_tok"],
                            cfg["routed_scaling_factor"], scores)
    latent = mm(h, w["wl1"])

    @jax.checkpoint          # the backward makes an expert's activations again
    def add_expert(y, expert):
        e, wu, wd = expert
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return y + mine[:, None] * _relu2(latent, wu, wd, mm), None

    held = cfg["first_expert"] + jnp.arange(cfg["n_routed_experts"])
    routed = jax.lax.scan(add_expert, jnp.zeros_like(latent),
                          (held, w["wu"], w["wd"]))[0]
    return mm(routed, w["wl2"]) + _relu2(h, w["su"], w["sd"], mm)


def _attention_part(h, w, *, cfg, mm):
    """What the attention layer adds: causal GQA, no positions."""
    t, hd = h.shape[0], cfg["head_dim"]
    q = mm(h, w["wq"]).reshape(t, -1, hd)
    k = mm(h, w["wk"]).reshape(t, -1, hd)
    v = mm(h, w["wv"]).reshape(t, -1, hd)
    return mm(_attention(q, k, v, t).reshape(t, -1), w["wo"])


def _block(x, w, layer, first, *, cfg, mm):
    h = _norm(x, w["g"], cfg["layer_norm_epsilon"])
    kind = layer_kind(cfg, layer)
    if kind == "M":
        return x + _mamba_part(h, w, cfg=cfg, mm=mm)
    if kind == "E":
        return x + _moe_part(h, w, layer, first, cfg=cfg, mm=mm)
    return x + _attention_part(h, w, cfg=cfg, mm=mm)


def logits(weights, tokens, first=0, *, cfg, mm=MATMULS["float32"]):
    """One row of tokens (tokens ``first ...`` of its step) through every
    layer and the head: ``[T, vocab_size]``. Each layer is checkpointed."""
    x = weights["embed"][tokens]
    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(functools.partial(
            _block, layer=i, first=first, cfg=cfg, mm=mm))(
                x, layer_weights(weights, i))
    return mm(_norm(x, weights["gf"], cfg["layer_norm_epsilon"]),
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
        raise ValueError("the nemotron_h reference takes one row at a time")
    fn = _grad_fn(_key(cfg), precision)
    n_tok = tokens.shape[0] * tokens.shape[1]
    loss, grads = 0.0, jax.tree_util.tree_map(jnp.zeros_like, weights)
    for r in range(tokens.shape[0]):
        l, grads = fn(weights, grads, jnp.asarray(tokens[r]),
                      jnp.asarray(targets[r]), r * tokens.shape[1])
        loss = loss + l
    return loss / n_tok, _scale(grads, 1.0 / n_tok)
