"""A ``qwen3_next`` model (``TransformerLM(layers=...)`` with Gated DeltaNet
mixers, ``models.GatedDelta``, three layers in four, and a full-attention
layer with q/k norms, quarter rotary and the element-wise gate; routed
experts beside a gated shared expert; the zero-centred norm) against the
benchmark's plain reference of the family on seeded weights, at a small
size: the chunked delta rule against the reference's token-by-token
recurrence, outputs and gradients; logits, loss and every gradient leaf of
the tiny model; the shares of a deployment adding up to the uncut layer;
each new field against its equation; the refusals of every path that does
not compute them; and the programs of the configurations that were there,
unchanged.
"""

import functools
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from horovod_tpu import models
from horovod_tpu.models.transformer import (
    ZERO_CENTRED, TransformerBlock, ZeroCentredRMSNorm, apply_rope,
    default_attention)
from horovod_tpu.ops import gated_delta

OPT = {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 1e-4}
T = 32


def _tiny(family="qwen3next"):
    with open(os.path.join(common.BENCH_DIR, "tests",
                           f"tiny_{family}.json")) as f:
        return dict(json.load(f), compute_dtype="float32")


@pytest.fixture(scope="module")
def cfg():
    return _tiny()


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "qwen3next")


@pytest.fixture(scope="module")
def adapter():
    return common.load_module("adapters", "qwen3next")


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------- the chunked rule against the recurrence


def _rule_inputs(seed, t, heads, dk=16, dv=8, a_log=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gated_delta.l2_normalize(jax.random.normal(ks[0], (1, t, heads, dk)))
    k = gated_delta.l2_normalize(jax.random.normal(ks[1], (1, t, heads, dk)))
    v = jax.random.normal(ks[2], (1, t, heads, dv))
    g = -math.exp(a_log) * jax.nn.softplus(
        jax.random.normal(ks[3], (1, t, heads)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, heads)))
    return q * dk ** -0.5, k, v, g, beta


def _recurrence(ref):
    return lambda q, k, v, g, beta: ref.delta_rule(
        q[0], k[0], v[0], g[0], beta[0])[None]


def _max_gap(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


#: both sides float32 over the same inputs: the chunked algebra adds a
#: triangular solve and differences of cumulative decays, a few roundings
#: more a token than the recurrence's; 2e-5 of the largest value is ten
#: times what any reading here gives (<= 2e-6) and 1/50 of a bfloat16
#: operand's rounding, which the last case shows it catches
_RULE_TOL = 2e-5
#: the decays' gradient: each ``g_s`` enters every later ``G_r`` and the
#: chunked form reaches it through differences ``G_r - G_s`` whose
#: gradients cancel (the first token's is 0 in the recurrence and ~1e-9 of
#: cancelled terms here); with ``exp(A_log)`` at 16 the whole gradient is
#: ~1e-5 and that rounding reads up to 7.4e-5 of it. Still 1/40 of a
#: bfloat16 operand's rounding
_DECAY_TOL = 2e-4


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("a_log", [0.0, math.log(16.0)], ids=["A1", "A16"])
def test_chunked_rule_is_the_recurrence(ref, chunk, a_log):
    """Outputs and the gradients of q, k, v, g and beta, with T 37 (no
    multiple of any chunk) and ``exp(A_log)`` up to 16: no overflow, no
    NaN, where a chunk's decays reach ``exp(-1000)``."""
    args = _rule_inputs(1, 37, 3, a_log=a_log)
    got = gated_delta.chunked_delta_rule(*args, chunk)
    want = _recurrence(ref)(*args)
    assert bool(jnp.isfinite(got).all())
    assert _max_gap(got, want) < _RULE_TOL
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                        argnums=(0, 1, 2, 3, 4))(*args)

    for name, g_got, g_want in zip(
            "q k v g beta".split(),
            grads(lambda *a: gated_delta.chunked_delta_rule(*a, chunk)),
            grads(_recurrence(ref))):
        assert bool(jnp.isfinite(g_got).all()), name
        assert _max_gap(g_got, g_want) < (
            _DECAY_TOL if name == "g" else _RULE_TOL), name


def test_a_precision_below_fails_the_rule_tolerance(ref):
    """The same rule over bfloat16-rounded q, k and v, the operands one
    precision down, lands outside the tolerance."""
    args = _rule_inputs(2, 37, 3)
    want = _recurrence(ref)(*args)
    low = [x.astype(jnp.bfloat16).astype(jnp.float32) for x in args[:3]]
    got = gated_delta.chunked_delta_rule(*low, *args[3:], 16)
    assert _max_gap(got, want) > 10 * _RULE_TOL


def test_the_chunk_is_chosen_from_the_shapes():
    """64 tokens a chunk for a long row (the cell's 8,192: 128 chunks), the
    row itself where it is shorter; both booked as trace-time gauges."""
    from horovod_tpu.observability import metrics

    assert gated_delta.chunk_length(8192) == 64
    assert gated_delta.chunk_length(37) == 37
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        jax.eval_shape(gated_delta.gated_delta_rule, *_rule_inputs(3, 200, 2))
    finally:
        metrics.set_enabled(was)
    assert metrics.value("gdn_chunk") == 64
    assert metrics.value("gdn_chunks") == 4


# ------------------------------------------------------------ the whole model


def _batch(rows=2, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (rows, T)).astype(np.int32),
            rng.integers(0, 256, (rows, T)).astype(np.int32))


def _forward(built, tokens):
    return lambda params: built["model"].apply(
        {"params": params, "batch_stats": built["batch_stats"]}, tokens,
        mutable=["batch_stats"])[0]


#: float32 on both sides at ``highest``: what is left is the order of
#: sums (the chunked rule, the sorted buffer, flash's blocks); the
#: readings are <= 5e-7 for the logits and <= 4e-5 of a leaf's largest
#: gradient, or of a tenth of the median leaf's where that is larger: the
#: decays' ``A_log`` and ``dt_bias`` of a head that forgets within a few
#: tokens read 1e-3 of the median leaf, so their round-off is held on that
_LOGITS_TOL, _GRAD_TOL = 2e-5, 2e-4


@pytest.mark.parametrize("selection", ["top_k", "forced_uniform"])
def test_logits_loss_and_every_gradient_leaf(cfg, ref, adapter, highest,
                                             selection):
    cfg = dict(cfg, router_selection=selection)
    built = adapter.build(cfg, {"optimizer": OPT})
    weights = ref.make_weights(cfg, common.split_seed(5))
    tokens, targets = _batch()
    want_loss, want = ref.loss_and_grads(cfg, weights, tokens, targets)
    forward = _forward(built, tokens)
    params = built["to_tree"](weights)
    got_logits = forward(params)
    for r in range(tokens.shape[0]):
        np.testing.assert_allclose(
            got_logits[r], ref.logits(weights, tokens[r], r * T, cfg=cfg),
            atol=_LOGITS_TOL)
    got_loss, got = jax.value_and_grad(
        lambda p: built["loss_fn"](forward(p), targets))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got = built["ref_names"](got, list(weights))
    assert set(got) == set(want)
    scales = {name: float(jnp.abs(g).max()) for name, g in want.items()}
    floor = 0.1 * float(np.median(list(scales.values())))
    for name in want:
        assert scales[name] > 0, name
        np.testing.assert_allclose(
            got[name], want[name], rtol=0,
            atol=_GRAD_TOL * max(scales[name], floor), err_msg=name)


def test_a_bfloat16_model_fails_the_logits_tolerance(cfg, ref, adapter,
                                                     highest):
    """The configuration's own precision, one step down from the test's
    float32, is caught by the logits' tolerance."""
    low = dict(cfg, compute_dtype="bfloat16")
    built = adapter.build(low, {"optimizer": OPT})
    weights = ref.make_weights(low, common.split_seed(5))
    tokens, _ = _batch()
    got = _forward(built, tokens)(built["to_tree"](weights))
    want = ref.logits(weights, tokens[0], 0, cfg=low)
    assert float(jnp.abs(got[0].astype(jnp.float32) - want).max()) \
        > 10 * _LOGITS_TOL


def test_the_model_holds_what_the_configuration_says(cfg, ref, adapter):
    """Three linear layers and a full one, each with its experts, router
    of the whole width and gated shared expert; the initial values of the
    published code where the model initialises itself."""
    built = adapter.build(cfg, {"optimizer": OPT})
    tree = built["to_tree"](ref.make_weights(cfg, common.split_seed(1)))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, tree)
    linear, full = shapes["block0"], shapes["block3"]
    assert linear["in_proj_qkvz"]["kernel"] == (64, 16 + 16 + 2 * 16 + 2 * 16)
    assert linear["in_proj_ba"]["kernel"] == (64, 4)
    assert linear["conv1d"] == (16 + 16 + 2 * 16, 4)
    assert linear["A_log"] == linear["dt_bias"] == (2,)
    assert linear["out_proj"]["kernel"] == (32, 64)
    assert "q_proj" not in linear and "in_proj_qkvz" not in full
    assert full["q_proj"]["kernel"] == (64, 2 * 16)
    assert full["q_norm"]["scale"] == full["k_norm"]["scale"] == (16,)
    for block in ("block0", "block3"):
        assert shapes[block]["router"] == (64, 16)
        assert shapes[block]["experts_gate"] == (4, 64, 32)
        assert shapes[block]["shared_expert_gate"]["kernel"] == (64, 1)
    init = built["model"].init(jax.random.PRNGKey(0),
                               jnp.zeros((1, T), jnp.int32))["params"]
    assert (jax.tree_util.tree_map(lambda a: a.shape, init) == shapes)
    block = init["block0"]
    assert float(jnp.abs(block["ln1"]["scale"]).max()) == 0.0
    assert float(jnp.abs(block["norm_scale"] - 1).max()) == 0.0
    assert float(jnp.abs(block["dt_bias"] - 1).max()) == 0.0
    assert 0.01 < float(jnp.std(block["conv1d"])) < 0.03
    assert float(jnp.exp(block["A_log"]).max()) <= 16.0
    assert list(built["batch_stats"]) == [f"block{i}" for i in range(4)]


# -------------------------------------------- each new field, by its equation


def _block(layer, **kw):
    kw.setdefault("norm", ZERO_CENTRED)
    return TransformerBlock(**models.TransformerLM(
        vocab=8, dim=64, depth=1, heads=1, layers=(layer,),
        pos_embedding="rope", dtype=jnp.float32, **kw).block_config(0))


def _run(block, params, x):
    return block.apply({"params": params}, x[None],
                       positions=jnp.arange(x.shape[0])[None])[0]


def _init(block, x, seed=0):
    return block.init(jax.random.PRNGKey(seed), x[None],
                      positions=jnp.arange(x.shape[0])[None])["params"]


def _no_mlp(params):
    """The block's GELU MLP (``ffn=1``) off: the block is x + its mixer."""
    return dict(params, mlp_down=jax.tree_util.tree_map(
        jnp.zeros_like, params["mlp_down"]))


def _r(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1 + w)


def test_zero_centred_norm_is_its_formula():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 24)) * 3.0
    w = jax.random.normal(jax.random.PRNGKey(1), (24,))
    norm = ZeroCentredRMSNorm(epsilon=1e-6, dtype=jnp.float32)
    np.testing.assert_allclose(norm.apply({"params": {"scale": w}}, x),
                               _r(x, w), rtol=1e-6, atol=1e-6)
    # zeros at start: the norm is x rsqrt(mean x^2 + eps)
    start = norm.init(jax.random.PRNGKey(2), x)["params"]["scale"]
    assert start.shape == (24,)
    np.testing.assert_array_equal(start, 0)


def _full_formula(params, x, *, gate, qk_norm, heads=2, kv_heads=1, hd=16,
                  rotary=4):
    """A full-attention block's attention part, written out."""
    t = x.shape[0]
    h = _r(x, params["ln1"]["scale"])
    q = h @ params["q_proj"]["kernel"]
    if gate:
        q = q.reshape(t, heads, 2 * hd)
        q, z = q[..., :hd], q[..., hd:]
    q = q.reshape(t, heads, hd)
    k = (h @ params["k_proj"]["kernel"]).reshape(t, kv_heads, hd)
    v = (h @ params["v_proj"]["kernel"]).reshape(t, kv_heads, hd)
    if qk_norm:
        q = _r(q, params["q_norm"]["scale"])
        k = _r(k, params["k_norm"]["scale"])
    pos = jnp.arange(t)[None]
    q = apply_rope(q[None], pos, base=1e7, rotary_dim=rotary)[0]
    k = apply_rope(k[None], pos, base=1e7, rotary_dim=rotary)[0]
    a = default_attention(q[None], k[None], v[None])[0]
    if gate:
        a = a * jax.nn.sigmoid(z)
    return a.reshape(t, -1) @ params["proj"]["kernel"]


@pytest.mark.parametrize("gate,qk_norm", [("element", False), (False, True),
                                          ("element", True)])
def test_element_gate_and_qk_norm_by_their_equation(highest, gate, qk_norm):
    """``[q | gate]`` a head from one projection, ``o * sigmoid(gate)``
    feature by feature before ``W_o``; q and k normalised over the head by
    the ``1 + w`` norm before the rotation."""
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 64))
    block = _block(models.Layer(
        heads=2, head_dim=16, kv_heads=1, rope_base=1e7, rotary_dim=4,
        gate=gate, qk_norm=qk_norm, ffn=1))
    params = _no_mlp(_init(block, x))
    width = 2 * 16 * (2 if gate else 1)
    assert params["q_proj"]["kernel"].shape == (64, width)
    assert ("q_norm" in params) == qk_norm and "gate_proj" not in params
    if qk_norm:
        for name in ("q_norm", "k_norm"):
            params[name] = {"scale": jax.random.normal(
                jax.random.PRNGKey(len(name)), (16,))}
    want = x + _full_formula(params, x, gate=gate, qk_norm=qk_norm)
    np.testing.assert_allclose(_run(block, params, x), want, atol=2e-5)


def test_shared_gate_by_its_equation(highest):
    """``routed(h) + sigmoid(h w_sg) shared(h)``: with ``w_sg`` zero the
    shared expert is halved, under ``hvd.moe_shared``."""
    x = jax.random.normal(jax.random.PRNGKey(4), (T, 64))
    experts = dict(routed=8, top_k=2, width=32, count=4, shared=48)
    layer = dict(heads=2, head_dim=16, kv_heads=1)
    plain = _block(models.Layer(**layer, ffn=models.Experts(**experts)),
                   norm="rmsnorm")
    gated = _block(models.Layer(**layer, ffn=models.Experts(
        **experts, shared_gate=True)), norm="rmsnorm")
    params = _init(gated, x)
    assert params["shared_expert_gate"]["kernel"].shape == (64, 1)
    rest = {k: v for k, v in params.items() if k != "shared_expert_gate"}
    no_shared = dict(rest, shared_down={"kernel": jnp.zeros((48, 64))})
    routed = _run(plain, no_shared, x)
    both = _run(plain, rest, x)
    assert float(jnp.abs(both - routed).max()) > 1e-3
    zero = {"kernel": jnp.zeros((64, 1))}
    np.testing.assert_allclose(
        _run(gated, dict(rest, shared_expert_gate=zero), x),
        routed + 0.5 * (both - routed), atol=1e-5)
    # and far up it lets the whole shared expert through
    np.testing.assert_allclose(
        _run(gated, dict(rest, shared_expert_gate={
            "kernel": jnp.full((64, 1), 1e4) * jnp.sign(x[:1].T)}), x)[0],
        both[0], atol=1e-5)


def _gdn_layer(hk, hv, dk=16, dv=16):
    return models.Layer(mixer=models.GatedDelta(hk, hv, dk, dv), ffn=1)


@pytest.mark.parametrize("hk,hv", [(2, 2), (2, 4)],
                         ids=["one-value-head-a-key", "two"])
def test_gated_delta_block_is_the_reference_mixer(ref, highest, hk, hv):
    """The block's mixer (the published layout of ``in_proj_qkvz`` and
    ``in_proj_ba``, the convolution, the rule, the gated norm) is the
    reference's token-by-token linear layer, with ``A_log`` at log 16."""
    x = jax.random.normal(jax.random.PRNGKey(5), (T, 64))
    block = _block(_gdn_layer(hk, hv))
    params = _no_mlp(_init(block, x))
    params["A_log"] = jnp.full((hv,), math.log(16.0))
    params["ln1"] = {"scale": 0.1 * jax.random.normal(
        jax.random.PRNGKey(6), (64,))}
    w = {"g1": params["ln1"]["scale"],
         "wqkvz": params["in_proj_qkvz"]["kernel"],
         "wba": params["in_proj_ba"]["kernel"], "conv": params["conv1d"],
         "A_log": params["A_log"], "dt_bias": params["dt_bias"],
         "gn": params["norm_scale"], "wout": params["out_proj"]["kernel"]}
    small = dict(hidden_size=64, linear_num_key_heads=hk,
                 linear_num_value_heads=hv, linear_key_head_dim=16,
                 linear_value_head_dim=16, rms_norm_eps=1e-6)
    want = x + ref._linear_part(x, w, cfg=small, mm=ref.MATMULS["float32"])
    got = _run(block, params, x)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-5)


# ----------------------------------------------------- the shares add up

SHARES = 4


def _uncut(cfg):
    """The tiny configuration with every head and expert of the layers the
    shares split 4 ways: 4 key heads on 8 value heads, 4 query heads on 2
    K/V heads, all 16 experts."""
    return dict(
        cfg, linear_num_key_heads=SHARES, linear_num_value_heads=2 * SHARES,
        num_attention_heads=SHARES, num_key_value_heads=2,
        num_experts=cfg["num_experts_routed"], first_expert=0)


def _share_of(full, cfg, kind, share):
    """Share ``share`` of one uncut layer's weights: a linear layer's key
    head with its two value heads (their columns of ``in_proj_qkvz`` and
    ``in_proj_ba``, their channels of the convolution, their ``A_log`` and
    ``dt_bias``, their rows of ``out_proj``), or the full layer's query
    head with its gate on its K/V head; 4 of the 16 experts; the router,
    the norms and the gated shared expert whole."""
    mine = dict(full)
    held = cfg["num_experts_routed"] // SHARES
    for k in ("wg", "wu", "wd"):
        mine[k] = full[k][share * held:(share + 1) * held]
    if kind == "linear_attention":
        dk, dv, r = (cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                     2)
        group = 2 * dk + 2 * r * dv
        keys = SHARES * dk
        conv = full["conv"]
        mine.update(
            wqkvz=full["wqkvz"][:, share * group:(share + 1) * group],
            wba=full["wba"][:, share * 2 * r:(share + 1) * 2 * r],
            conv=jnp.concatenate([
                conv[share * dk:(share + 1) * dk],
                conv[keys + share * dk:keys + (share + 1) * dk],
                conv[2 * keys + share * r * dv:
                     2 * keys + (share + 1) * r * dv]]),
            A_log=full["A_log"][share * r:(share + 1) * r],
            dt_bias=full["dt_bias"][share * r:(share + 1) * r],
            wout=full["wout"][share * r * dv:(share + 1) * r * dv])
    else:
        hd = cfg["head_dim"]
        kv = slice((share // 2) * hd, (share // 2 + 1) * hd)
        mine.update(wq=full["wq"][:, share * 2 * hd:(share + 1) * 2 * hd],
                    wk=full["wk"][:, kv], wv=full["wv"][:, kv],
                    wo=full["wo"][share * hd:(share + 1) * hd])
    return mine


@pytest.mark.parametrize("layer", [0, 3], ids=["linear", "full"])
def test_the_shares_add_up_to_the_uncut_layer(cfg, ref, adapter, layer,
                                              highest):
    """Heads split 4 ways each add their part of the mixer's out-projection
    (both mixer kinds), experts split 4 ways their part of the routed sum;
    the gated shared expert and the residual, which every chip computes
    alike, count once: the program's parts over shares 0-3 sum to what the
    reference's uncut layer computes."""
    uncut = _uncut(cfg)
    kind = ref.layer_kind(cfg, layer)
    full = ref.layer_weights(ref.make_weights(uncut, common.split_seed(3)),
                             layer)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, cfg["hidden_size"]))
    mm = ref.MATMULS["float32"]
    mixer = ref._linear_part if kind == "linear_attention" else ref._full_part
    want_mixer = mixer(x, full, cfg=uncut, mm=mm)
    want = ref._block(x, full, layer, 0, cfg=uncut, mm=mm)
    out_proj = "wout" if kind == "linear_attention" else "wo"

    def block_of(share, weights):
        share_cfg = dict(cfg, first_expert=share * 4)
        described = adapter.layers(share_cfg)[layer]
        block = TransformerBlock(**models.TransformerLM(
            vocab=8, dim=cfg["hidden_size"], depth=1, heads=1,
            layers=(described,), norm=ZERO_CENTRED, pos_embedding="rope",
            dtype=jnp.float32).block_config(0))
        tree = adapter.to_tree(
            {f"l0.{k}": v for k, v in weights.items()})["block0"]
        return lambda x: block.apply({"params": tree}, x[None],
                                     positions=jnp.arange(T)[None])[0]

    def zeroed(weights, names):
        return {k: jnp.zeros_like(v) if k in names else v
                for k, v in weights.items()}

    got_mixer = 0.0
    for share in range(SHARES):
        mine = _share_of(full, cfg, kind, share)
        got_mixer = got_mixer + block_of(
            share, zeroed(mine, ("wd", "sd")))(x) - x
    np.testing.assert_allclose(got_mixer, want_mixer, atol=2e-6)

    x1 = x + got_mixer
    total = x1
    for share in range(SHARES):
        mine = zeroed(_share_of(full, cfg, kind, share),
                      (out_proj,) + (("sd",) if share else ()))
        total = total + block_of(share, mine)(x1) - x1
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert float(jnp.abs(want_mixer).max()) > 1e-3
    assert float(jnp.abs(want - x1).max()) > 1e-3


# --------------------------------- the paths that do not compute the new forms


def _qwen_model(cfg, adapter):
    return adapter.build(cfg, {"optimizer": OPT})["model"]


def test_decode_refuses_the_new_forms_by_name(cfg, adapter):
    model = _qwen_model(cfg, adapter)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="gated-delta layer") as err:
        models.generate(model, {}, prompt, max_new_tokens=2)
    for field in ("gate='element'", "qk_norm=True", "shared_gate=True",
                  ZERO_CENTRED):
        assert field in str(err.value), field
    x = jnp.zeros((1, 4, 64))
    for layer in (_gdn_layer(1, 2), models.Layer(
            heads=2, head_dim=16, gate="element", qk_norm=True)):
        block = _block(layer, decode=True)
        with pytest.raises(ValueError, match="kv-cache decoding"):
            block.init(jax.random.PRNGKey(0), x,
                       positions=jnp.zeros((1, 4), jnp.int32))


def test_inference_engine_refuses_the_gated_delta_layer(cfg, adapter):
    from horovod_tpu.serving.engine import InferenceEngine

    with pytest.raises(ValueError, match="InferenceEngine.*gated-delta"):
        InferenceEngine(_qwen_model(cfg, adapter), max_seq_len=32,
                        num_pages=8)


def test_pipeline_split_refuses_the_gated_delta_layer(cfg, adapter):
    from horovod_tpu.training import split_transformer_for_pp

    with pytest.raises(ValueError,
                       match="split_transformer_for_pp.*gated-delta"):
        split_transformer_for_pp(_qwen_model(cfg, adapter), {}, 2)


@pytest.mark.parametrize("layer, leaf", [
    (_gdn_layer(1, 2), "gated-delta layer"),
    (models.Layer(heads=2, head_dim=16, qk_norm=True), "q/k norm")])
def test_param_specs_refuse_the_new_layers_by_name(layer, leaf):
    x = jnp.zeros((T, 64))
    params = _init(_block(layer), x)
    with pytest.raises(ValueError, match=leaf):
        models.transformer_param_specs(params)


@pytest.mark.parametrize("layer", [
    _gdn_layer(1, 2), models.Layer(heads=2, head_dim=16, qk_norm=True)])
def test_tp_block_apply_refuses_the_new_layers(layer):
    from horovod_tpu.models.transformer import tp_block_apply

    params = _init(_block(layer), jnp.zeros((T, 64)))
    with pytest.raises(ValueError, match="softmax-attention blocks only"):
        tp_block_apply(params, jnp.zeros((1, 4, 64)), heads=2)


def test_a_layer_gives_attention_or_a_mixer():
    # heads without a head size, or attention beside a mixer (no heads and
    # no mixer is a block of its FFN alone)
    for layer in (models.Layer(heads=2, ffn=1), models.Layer(
            heads=2, head_dim=16, mixer=models.GatedDelta(1, 2, 16, 16))):
        model = models.TransformerLM(vocab=8, dim=64, depth=1, heads=1,
                                     layers=(layer,), pos_embedding="rope")
        with pytest.raises(ValueError, match="one of the two"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("given, held", [
    (True, "head"), (False, None), ("head", "head"), ("element", "element"),
    (None, None)])
def test_a_layer_gate_is_head_or_element(given, held):
    """One field of two forms; ``True`` is the head-wise gate's spelling."""
    assert models.Layer(heads=2, head_dim=16, gate=given).gate == held


def test_a_layer_refuses_a_gate_of_no_form():
    with pytest.raises(ValueError, match="gate must be one of"):
        models.Layer(heads=2, head_dim=16, gate="channel")


# -------------------------------- what was there traces what it traced before

#: sha256 of the lowered text of loss-and-gradients of the tiny Laguna
#: configuration (float32, two rows of 32 tokens), as it was traced before
#: the gated-delta layer, the q/k norm, the element-wise gate and the shared
#: expert's gate came in; ``tests/test_laguna_model.py`` pins GPT-2's and
#: Mellum2's the same way
_LAGUNA = "ee75e8251fb1156518addca8388301e13d8ca54f45aa342d95189e7ad8d72888"


def test_laguna_program_is_unchanged(tmp_path):
    cfg = _tiny("laguna")
    built = common.load_module("adapters", "laguna").build(
        cfg, {"optimizer": OPT})
    ref = common.load_module("reference", "laguna")
    shapes = jax.eval_shape(functools.partial(
        ref.make_weights, cfg, (np.int32(0), np.int32(0))))
    params = built["to_tree"](shapes)
    tokens = jax.ShapeDtypeStruct((2, T), jnp.int32)

    def loss(p, tokens, targets):
        logits, _ = built["model"].apply(
            {"params": p, "batch_stats": built["batch_stats"]}, tokens,
            mutable=["batch_stats"])
        return built["loss_fn"](logits, targets)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, tokens, tokens).as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    if digest != _LAGUNA:
        (tmp_path / "laguna.lowered.txt").write_text(text)
    assert digest == _LAGUNA, f"the lowered text is in {tmp_path}"
