"""Plain reference of one chip's share of a ``mellum`` model (JetBrains
Mellum 2: ``model_type`` ``mellum`` in its ``config.json``), its loss, its
gradients and, through ``reference/steps.py``, its optimizer step, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
Imports nothing of ``horovod_tpu``.

The block, per layer ``l`` of kind ``layer_types[l]``::

    h = rmsnorm(x, g1)                      # x rsqrt(mean(x^2) + eps) g
    q, k, v = h Wq, h Wk, h Wv              # heads of head_dim, no bias
    q, k = rope(q), rope(k)                 # rotate-half, theta; full layers: YaRN
    a = softmax(q k^T / sqrt(head_dim) + mask) v
                                            # full: j <= i; sliding: 0 <= i - j < window
    x = x + a Wo
    h = rmsnorm(x, g2)
    p = softmax(h Wr) over all routed experts; top-k; w = w / sum(w)
    x = x + sum over chosen experts held here of w_j (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x, gf) Whead;  loss = mean token cross-entropy

This chip's share of a deployment (the configuration's ``deployment``): the
weights made here are the ``num_attention_heads`` query heads on the
``num_key_value_heads`` KV heads, the ``num_experts`` experts from
``first_expert`` of ``num_experts_routed`` and the ``vocab_size`` rows of
embedding and head that one chip holds. What the absent heads and experts
would add to the residual stream is left out; the router keeps its whole
width and a token's weights are normalised over all its ``top-k`` experts,
held here or not.

``assumed`` in the configuration (absent from the published config, so
absent here): no normalisation of q and k, no router bias, no auxiliary
loss, no dropout, no multi-token-prediction head; weights normal(0.02),
gains one.

``router_selection`` ``forced_uniform`` (a timed cell's configuration; not
the model's): a token's experts are the ``top-k`` of :func:`forced_scores`,
numbers that are uniform and the same in every run, in the place of its
probabilities; the weights stay the router's. Seeded weights make an
untrained router, which AdamW collapses within ten steps onto a few experts,
held here or not by the seed, so a step's time would measure the seed
(``PERF.md`` section 6, PR 35); a trained router is held near balance by its
training. Megatron-LM times its routed layers the same way
(``--moe-router-force-load-balancing``).
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt2 import CONTROL, MATMULS  # noqa: F401
from benchmarks.reference.steps import cfg_key, cfg_of

HIGHEST = jax.lax.Precision.HIGHEST
_LAYER_KEYS = ("g1", "wq", "wk", "wv", "wo", "g2", "wr", "wg", "wu", "wd")
#: query rows whose float32 scores against the whole sequence are alive at
#: once: [heads, 1024, T]
_Q_BLOCK = 1024


def weight_shapes(cfg):
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_layers"]):
        p = f"l{i}."
        shapes.update({
            p + "g1": (d,), p + "wq": (d, nq * hd), p + "wk": (d, nkv * hd),
            p + "wv": (d, nkv * hd), p + "wo": (nq * hd, d), p + "g2": (d,),
            p + "wr": (d, cfg["num_experts_routed"]),
            p + "wg": (e, d, f), p + "wu": (e, d, f), p + "wd": (e, f, d)})
    shapes.update({"gf": (d,), "w_head": (d, v)})
    return shapes


@functools.lru_cache(maxsize=None)
def _weights_fn(cfg_items):
    cfg = cfg_of(cfg_items)
    shapes = weight_shapes(cfg)
    std = cfg["initializer_range"]

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = {}
        for n, (name, shape) in enumerate(shapes.items()):
            if name.split(".")[-1].startswith("g"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
        return out

    return jax.jit(make)


def make_weights(cfg, seed_halves):
    """name -> float32 array, on the default device, from the seed."""
    return _weights_fn(cfg_key(cfg))(*seed_halves)


# ---------------------------------------------------------------- forward


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope_tables(rope, head_dim, t):
    """cos and sin ``[t, head_dim / 2]`` of one layer kind's rotary
    settings (``rope_parameters[kind]``). ``yarn`` as the published code
    computes it: each frequency blended between ``theta^(-2j/d)`` and that
    over ``factor`` by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow``, cos and sin times
    ``attention_factor``."""
    half = head_dim // 2
    inv = rope["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        def correction_dim(turns):
            return head_dim * math.log(
                rope["original_max_position_embeddings"]
                / (turns * 2 * math.pi)) / (2 * math.log(rope["rope_theta"]))

        low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rope["beta_slow"])), head_dim - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / rope["factor"] * ramp + inv * (1.0 - ramp)
        scale = rope["attention_factor"]
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rope(x, cos, sin):
    """Rotate-half on ``[T, heads, head_dim]``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window):
    """``q`` [T, Hq, D] on ``k``, ``v`` [T, Hkv, D]; every query head of a
    group uses its KV head; row i sees column j where ``0 <= i - j <
    window`` (a full layer's window is the sequence). In blocks of query
    rows against the whole sequence."""
    t, hq, d = q.shape
    group = hq // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(_Q_BLOCK, t)
    cols = jnp.arange(t)[None, :]

    @jax.checkpoint          # the backward makes a block's scores again
    def rows_of(args):
        q_rows, first = args
        rows = first + jnp.arange(block)[:, None]
        mask = (cols <= rows) & (rows - cols < window)
        s = jnp.einsum("qhd,khd->hqk", q_rows, k,
                       precision=HIGHEST) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(rows_of, (q.reshape(t // block, block, hq, d),
                                jnp.arange(0, t, block)))
    return out.reshape(t, hq, d)


def forced_scores(layer, first, tokens, experts):
    """Scores ``[tokens, experts]`` float32 whose ``top-k`` spread tokens
    ``first ... first + tokens - 1`` of a step evenly over the experts of
    layer ``layer``: 24 bits of a hash (lowbias32) of (layer, token,
    expert), so a token's scores do not depend on how many tokens there
    are, nor on the seed."""
    x = ((jnp.uint32(layer) * jnp.uint32(0x9E3779B9))
         + (jnp.uint32(first) + jnp.arange(tokens, dtype=jnp.uint32)[:, None])
         * jnp.uint32(experts) + jnp.arange(experts, dtype=jnp.uint32)[None, :])
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return ((x ^ (x >> 16)) >> 8).astype(jnp.float32)


def route(h, wr, top_k, scores=None):
    """The router: float32 probabilities over every routed expert, the
    ``top_k`` largest (of ``scores`` where given) and their weights,
    normalised to sum to one."""
    p = jax.nn.softmax(jnp.matmul(h, wr, precision=HIGHEST), axis=-1)
    _, e = jax.lax.top_k(p if scores is None else scores, top_k)
    w = jnp.take_along_axis(p, e, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True), e


def _selection(cfg):
    kind = cfg.get("router_selection", "top_k")
    if kind not in ("top_k", "forced_uniform"):
        raise ValueError(f"router_selection {kind!r}")
    return kind


def _experts(h, w, layer, first, *, cfg, mm):
    """The held experts' part of the routed layer, expert by expert over
    every token, each weighted by what the router gave it (zero where the
    token did not choose it). ``h`` is tokens ``first ...`` of the step, in
    layer ``layer``."""
    scores = None
    if _selection(cfg) == "forced_uniform":
        scores = forced_scores(layer, first, h.shape[0],
                               cfg["num_experts_routed"])
    weights, chosen = route(h, w["wr"], cfg["num_experts_per_tok"], scores)

    @jax.checkpoint          # and an expert's activations
    def add_expert(y, expert):
        e, wg, wu, wd = expert
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        a = jax.nn.silu(mm(h, wg)) * mm(h, wu)
        return y + mine[:, None] * mm(a, wd), None

    held = cfg["first_expert"] + jnp.arange(cfg["num_experts"])
    return jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (held, w["wg"], w["wu"], w["wd"]))[0]


def _block(x, w, cos, sin, window, layer, first, *, cfg, mm):
    t, _ = x.shape
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, w["g1"], eps)
    q = mm(h, w["wq"]).reshape(t, -1, hd)
    k = mm(h, w["wk"]).reshape(t, -1, hd)
    v = mm(h, w["wv"]).reshape(t, -1, hd)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    a = _attention(q, k, v, window)
    x = x + mm(a.reshape(t, -1), w["wo"])
    h = _rms_norm(x, w["g2"], eps)
    return x + _experts(h, w, layer, first, cfg=cfg, mm=mm)


def layer_inputs(cfg, t):
    """What tells one layer from another besides its weights, stacked by
    layer: the rotary tables of its kind and its window (the sequence's
    length for a full layer)."""
    tables = {kind: rope_tables(cfg["rope_parameters"][kind],
                                cfg["head_dim"], t)
              for kind in set(cfg["layer_types"])}
    cos, sin = (jnp.stack([tables[kind][i] for kind in cfg["layer_types"]])
                for i in (0, 1))
    windows = jnp.asarray([cfg["sliding_window"]
                           if kind == "sliding_attention" else t
                           for kind in cfg["layer_types"]], jnp.int32)
    return cos, sin, windows


def _layers(weights, tokens, first, *, cfg, mm):
    """One row of tokens (tokens ``first ...`` of its step) through every
    layer: the last residual stream."""
    stacked = {k: jnp.stack([weights[f"l{i}.{k}"]
                             for i in range(cfg["num_layers"])])
               for k in _LAYER_KEYS}
    body = functools.partial(_block, cfg=cfg, mm=mm)
    # one body for every layer, checkpointed: the backward recomputes a
    # layer's float32 scores and expert activations instead of keeping them
    return jax.lax.scan(
        jax.checkpoint(lambda x, layer: (body(x, *layer, first), None)),
        weights["embed"][tokens],
        (stacked, *layer_inputs(cfg, tokens.shape[0]),
         jnp.arange(cfg["num_layers"])))[0]


def _sum_loss(weights, tokens, targets, first, *, cfg, mm):
    """Summed token cross-entropy of one row of tokens."""
    x = _layers(weights, tokens, first, cfg=cfg, mm=mm)
    x = _rms_norm(x, weights["gf"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(x, weights["w_head"]))
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def _split_cfg(cfg):
    """``cfg_key`` keeps numbers, strings and lists of numbers: the layer
    kinds and the nested rotary settings ride beside it, hashable."""
    return (cfg_key(cfg), tuple(cfg["layer_types"]), tuple(sorted(
        (kind, tuple(sorted(r.items())))
        for kind, r in cfg["rope_parameters"].items())))


def _joined_cfg(cfg_items, layer_types, rope_parameters):
    return dict(cfg_of(cfg_items), layer_types=list(layer_types),
                rope_parameters={kind: dict(items)
                                 for kind, items in rope_parameters})


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_parts, precision):
    """``(weights, acc, tokens, targets, first) -> (loss sum, acc +
    gradient)`` for one row, tokens ``first ...`` of the step; the running
    sum is donated."""
    cfg = _joined_cfg(*cfg_parts)
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
        raise ValueError("the mellum reference takes one row at a time")
    fn = _grad_fn(_split_cfg(cfg), precision)
    n_tok = tokens.shape[0] * tokens.shape[1]
    loss, grads = 0.0, jax.tree_util.tree_map(jnp.zeros_like, weights)
    for r in range(tokens.shape[0]):
        l, grads = fn(weights, grads, jnp.asarray(tokens[r]),
                      jnp.asarray(targets[r]), r * tokens.shape[1])
        loss = loss + l
    return loss / n_tok, _scale(grads, 1.0 / n_tok)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(tree, factor):
    return jax.tree_util.tree_map(lambda g: g * factor, tree)
