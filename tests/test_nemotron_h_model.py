"""A ``nemotron_h`` model (``TransformerLM(layers=...)`` of blocks of one
part each: ``models.Mamba2`` mixers, LatentMoE layers of relu² experts
behind a sigmoid router beside a relu² shared expert, and attention with no
positions) against the benchmark's plain reference of the family on seeded
weights, at a small size: the chunked state-space recurrence against the
reference's token-by-token one, outputs and gradients; logits, loss and
every gradient leaf of the tiny model; the relu² expert kernels
(interpreted) and the sigmoid router against their equations; the shares of
a deployment adding up to the uncut layer; the sorted buffer's bound; the
refusals of every path that does not compute the new forms.
"""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from horovod_tpu import models
from horovod_tpu.models.transformer import TransformerBlock
from horovod_tpu.observability import metrics
from horovod_tpu.ops import mamba2
from horovod_tpu.parallel import moe

OPT = {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 1e-4}
T = 32


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(common.BENCH_DIR, "tests",
                           "tiny_nemotron_h.json")) as f:
        return dict(json.load(f), compute_dtype="float32")


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "nemotron_h")


@pytest.fixture(scope="module")
def adapter():
    return common.load_module("adapters", "nemotron_h")


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _max_gap(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


# -------------------------------- the chunked recurrence against the scan


def _ssd_inputs(seed, t, heads=4, groups=2, p=8, n=16, a_max=16.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (1, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, t, heads)) - 1.0)
    a = -jnp.linspace(1.0, a_max, heads)
    b = jax.random.normal(ks[2], (1, t, groups, n))
    c = jax.random.normal(ks[3], (1, t, groups, n))
    return x, dt, a, b, c


def _scan(ref):
    return lambda x, dt, a, b, c: ref.recurrence(
        x[0], dt[0], a, b[0], c[0])[None]


#: both sides float32 over the same inputs: the chunked form sums a token's
#: terms in another order (within the chunk, then through the chunks'
#: totals) and takes its decays as differences of cumulative sums; its
#: readings are <= 2e-6 of the largest value, outputs and gradients alike.
#: 2e-5 is ten times that and under a fifth of what bfloat16 operands give
#: (the next test)
_SSD_TOL = 2e-5


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("a_max", [1.0, 16.0], ids=["A1", "A16"])
def test_chunked_recurrence_is_the_scan(ref, chunk, a_max):
    """Outputs and the gradients of x, dt, A, B and C with T 37 (no
    multiple of any chunk, across 2-5 chunk boundaries, and under one
    chunk of 64) and decays ``exp(dt A)`` down to ``exp(-16 dt)``."""
    args = _ssd_inputs(1, 37, a_max=a_max)
    got = mamba2.ssd_chunked(*args, chunk)
    want = _scan(ref)(*args)
    assert bool(jnp.isfinite(got).all())
    assert _max_gap(got, want) < _SSD_TOL
    cot = jax.random.normal(jax.random.PRNGKey(9), want.shape)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                        argnums=range(5))(*args)

    for name, g_got, g_want in zip(
            "x dt A B C".split(),
            grads(lambda *a: mamba2.ssd_chunked(*a, chunk)),
            grads(_scan(ref))):
        assert bool(jnp.isfinite(g_got).all()), name
        assert _max_gap(g_got, g_want) < _SSD_TOL, name


def test_a_precision_below_fails_the_recurrence_tolerance(ref):
    """The same recurrence over bfloat16-rounded x, B and C, the operands
    one precision down, lands outside the tolerance."""
    args = _ssd_inputs(2, 37)
    want = _scan(ref)(*args)
    low = [v.astype(jnp.bfloat16).astype(jnp.float32) for v in args]
    got = mamba2.ssd_chunked(low[0], args[1], args[2], low[3], low[4], 16)
    assert _max_gap(got, want) > 5 * _SSD_TOL


def test_the_chunk_is_chosen_from_the_shapes():
    """The model's 128 tokens a chunk for a long row (the cell's 4,096: 32
    chunks), the row itself where it is shorter; both booked as
    trace-time gauges."""
    assert mamba2.chunk_length(4096, 128) == 128
    assert mamba2.chunk_length(37, 128) == 37
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        jax.eval_shape(functools.partial(mamba2.ssd, chunk=128),
                       *_ssd_inputs(3, 300))
    finally:
        metrics.set_enabled(was)
    assert metrics.value("ssm_chunk") == 128
    assert metrics.value("ssm_chunks") == 3


# ------------------------------------------------------------ the whole model


def _batch(rows=2, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (rows, T)).astype(np.int32),
            rng.integers(0, 256, (rows, T)).astype(np.int32))


def _forward(built, tokens):
    return lambda params: built["model"].apply(
        {"params": params, "batch_stats": built["batch_stats"]}, tokens,
        mutable=["batch_stats"])[0]


#: float32 on both sides at ``highest``: what is left is the order of sums
#: (the chunked recurrence, the sorted buffer, flash's blocks); the
#: readings are <= 3e-7 for the logits and <= 4e-6 of a leaf's largest
#: gradient
_LOGITS_TOL, _GRAD_TOL = 2e-5, 1e-4


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
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=_GRAD_TOL * scale, err_msg=name)


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


def test_a_bfloat16_recurrence_fails_the_gradient_tolerance(
        cfg, ref, adapter, highest, monkeypatch):
    """The recurrence's operands and result in bfloat16 where float32 is
    stated: the gradient leaves' tolerance catches it."""
    built = adapter.build(cfg, {"optimizer": OPT})
    weights = ref.make_weights(cfg, common.split_seed(5))
    tokens, targets = _batch()
    _, want = ref.loss_and_grads(cfg, weights, tokens, targets)
    chunked = mamba2.ssd_chunked

    def low(x, dt, a, b, c, chunk):
        down = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
        return down(chunked(down(x), dt, a, down(b), down(c), chunk))

    monkeypatch.setattr(mamba2, "ssd_chunked", low)
    forward = _forward(built, tokens)
    got = built["ref_names"](jax.grad(lambda p: built["loss_fn"](
        forward(p), targets))(built["to_tree"](weights)), list(weights))
    worst = max(float(jnp.abs(got[k] - want[k]).max()
                      / jnp.abs(want[k]).max()) for k in want)
    assert worst > 10 * _GRAD_TOL


def test_the_model_holds_what_the_configuration_says(cfg, ref, adapter):
    """One part a block, as the pattern says; the published layout of the
    in-projection; experts in the latent width with no gate matrix; no
    position table; the selection bias in ``batch_stats``, not among the
    parameters; the published code's initial values where the model
    initialises itself."""
    built = adapter.build(cfg, {"optimizer": OPT})
    tree = built["to_tree"](ref.make_weights(cfg, common.split_seed(1)))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, tree)
    m, e, a = shapes["block0"], shapes["block1"], shapes["block3"]
    inner, bc = 4 * 8, 2 * 2 * 16
    assert m["in_proj"]["kernel"] == (64, 2 * inner + bc + 4)
    assert m["conv1d"] == (inner + bc, 4) and m["conv1d_bias"] == (inner + bc,)
    assert m["A_log"] == m["D"] == m["dt_bias"] == (4,)
    assert m["norm_scale"] == (inner,) and m["out_proj"]["kernel"] == (inner,
                                                                       64)
    assert set(m) == {"ln1", "in_proj", "conv1d", "conv1d_bias", "A_log",
                      "D", "dt_bias", "norm_scale", "out_proj"}
    assert set(e) == {"ln1", "router", "fc1_latent_proj", "fc2_latent_proj",
                      "experts_up", "experts_down", "shared_up",
                      "shared_down"}
    assert e["router"] == (64, 16) and e["experts_up"] == (4, 32, 48)
    assert e["experts_down"] == (4, 48, 32)
    assert e["fc1_latent_proj"]["kernel"] == (64, 32)
    assert set(a) == {"ln1", "q_proj", "k_proj", "v_proj", "proj"}
    init = built["model"].init(jax.random.PRNGKey(0),
                               jnp.zeros((1, T), jnp.int32))
    assert jax.tree_util.tree_map(lambda v: v.shape, init["params"]) == shapes
    assert "pos_embed" not in init["params"]
    assert init["batch_stats"]["block1"]["router_bias"].shape == (16,)
    assert set(built["batch_stats"]) == {"block1", "block4"}
    block = init["params"]["block0"]
    np.testing.assert_array_equal(block["D"], 1.0)
    np.testing.assert_array_equal(block["norm_scale"], 1.0)
    assert 1.0 <= float(jnp.exp(block["A_log"]).min()) \
        <= float(jnp.exp(block["A_log"]).max()) <= 16.0
    dt = jax.nn.softplus(block["dt_bias"])
    assert 1e-3 - 1e-7 <= float(dt.min()) <= float(dt.max()) <= 0.1 + 1e-6
    assert float(jnp.abs(block["conv1d"]).max()) <= 0.5
    weights = ref.make_weights(cfg, common.split_seed(1))
    bound = 1 / math.sqrt(2 * 64) / math.sqrt(88)
    assert float(jnp.abs(weights["l0.wout"]).max()) <= bound
    assert float(jnp.abs(weights["l0.wout"]).max()) > 0.9 * bound


# -------------------------------------------- the routed layer's new forms


def _relu2_inputs(tokens=96, dim=64, width=32, count=4, routed=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (tokens, dim)),
            jax.random.normal(ks[1], (dim, routed)) * 0.5,
            jax.random.normal(ks[2], (count, dim, width)) * 0.1,
            jax.random.normal(ks[3], (count, width, dim)) * 0.1)


def _dense_relu2(x, router, up, down, top_k, first, bias=None):
    """The routed relu² layer by its equation: every held expert over every
    token, weighted by the sigmoid router's normalised score (0 where the
    token did not choose it)."""
    s = jax.nn.sigmoid(jnp.matmul(x, router, precision="highest"))
    choice = s if bias is None else s + bias
    _, chosen = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    y = 0.0
    for j in range(up.shape[0]):
        mine = jnp.sum(jnp.where(chosen == first + j, w, 0.0), axis=-1)
        y = y + mine[:, None] * (jnp.square(jax.nn.relu(x @ up[j])) @ down[j])
    return y


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("first,count,top_k", [(0, 8, 2), (2, 4, 2),
                                               (0, 4, 6), (4, 3, 5)])
def test_relu2_experts_match_a_dense_loop(first, count, top_k, dtype,
                                          highest):
    """The relu² experts' kernels (interpreted), through
    ``routed_experts`` with the sigmoid router: the layer and the gradient
    of x, of the router and of both expert matrices against the dense
    per-expert loop, in float32 and with bfloat16 products; ``top_k`` above
    the experts held among the cases."""
    args = _relu2_inputs(count=count)
    dtype = getattr(jnp, dtype)
    c = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    metrics.REGISTRY.reset()

    def routed(x, router, up, down):
        y, _ = moe.routed_experts(x.astype(dtype), router, None, up, down,
                                  top_k=top_k, first=first, dtype=dtype,
                                  router_kind="sigmoid")
        assert y.dtype == dtype
        return jnp.sum(c * y)

    dense = lambda *a: jnp.sum(c * _dense_relu2(*a, top_k, first))
    got = jax.value_and_grad(routed, argnums=range(4))(*args)
    assert metrics.value("moe_experts_fused") == 1
    want = jax.value_and_grad(dense, argnums=range(4))(*args)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[0], want[0], rtol=tol,
                               atol=tol * float(jnp.abs(c).sum()) * 1e-3)
    for a, b in zip(got[1], want[1]):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=tol * float(jnp.abs(b).max()))


def test_relu2_kernels_are_the_layers_calls():
    """Four calls a layer, named for the experts' metric: one forward, the
    backward's and the two matrices' gradients."""
    args = _relu2_inputs(tokens=300, dim=128, width=64, count=4)
    loss = lambda x, r, u, d: moe.routed_experts(
        x, r, None, u, d, top_k=2, router_kind="sigmoid")[0].sum()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=range(4)))(*args))
    for name in ("hvd_moe_relu2_fwd", "hvd_moe_relu2_bwd", "hvd_moe_tgmm"):
        assert name in text, name
    assert "hvd_moe_mlp_fwd" not in text and "hvd_moe_gmm" not in text


def test_relu2_experts_too_wide_keep_two_products(highest, monkeypatch):
    """Where an expert's two matrices do not fit the fused calls' VMEM the
    layer is ``relu(xs up)^2`` and ``down`` as two grouped products, with
    the same numbers. The cell's ``[1024, 2688]`` fits."""
    assert moe._relu2_fit(1024, 2688, 2)
    assert not moe._relu2_fit(4096, 2688, 2)
    args = _relu2_inputs(count=4)
    c = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    monkeypatch.setattr(moe, "_relu2_fit", lambda *a: False)
    got = jnp.sum(c * moe.routed_experts(
        args[0], args[1], None, *args[2:], top_k=2, router_kind="sigmoid")[0])
    want = jnp.sum(c * _dense_relu2(*args, 2, 0))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_sigmoid_router_choice_and_weights():
    """Chosen by ``top_k(s + b)``, weighed by ``s`` over the chosen ``s``'
    sum; the bias moves the choice and no gradient reaches it; ``select``
    takes the choice in the router's place."""
    x, router = _relu2_inputs(tokens=40)[:2]
    s = np.asarray(jax.nn.sigmoid(jnp.matmul(x, router,
                                             precision="highest")))
    w, e = moe.route_top_k(x, router, 3, kind="sigmoid")
    want_e = np.argsort(-s, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(e, -1), np.sort(want_e, -1))
    picked = np.take_along_axis(s, np.asarray(e), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    bias = jnp.zeros((8,)).at[7].set(10.0)
    w_b, e_b = moe.route_top_k(x, router, 3, kind="sigmoid", bias=bias)
    assert bool(jnp.all(jnp.any(e_b == 7, axis=-1)))
    picked = np.take_along_axis(s, np.asarray(e_b), -1)
    np.testing.assert_allclose(w_b, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    g = jax.grad(lambda b: moe.route_top_k(x, router, 3, kind="sigmoid",
                                           bias=b)[0].sum())(bias)
    np.testing.assert_array_equal(g, 0.0)
    forced = lambda scores: -jnp.broadcast_to(jnp.arange(8.0), scores.shape)
    _, e_f = moe.route_top_k(x, router, 3, forced, kind="sigmoid")
    np.testing.assert_array_equal(np.sort(e_f, -1),
                                  np.broadcast_to([0, 1, 2], (40, 3)))
    with pytest.raises(ValueError, match="softmax' or 'sigmoid"):
        moe.route_top_k(x, router, 3, kind="tanh")


# ---------------------------------------------------- the sorted buffer


@pytest.mark.parametrize("tokens,top_k,count,first", [
    (300, 6, 4, 0), (300, 22, 8, 8), (37, 5, 3, 2)])
def test_a_buffer_of_min_top_k_count_rows_a_token_drops_nothing(
        tokens, top_k, count, first):
    """``top_k`` above the experts held: a token's experts are distinct, so
    at most ``count`` of its slots are held here. Every held slot gets a
    row of its own inside the buffer, in use, and the buffer has
    ``min(top_k, count)`` rows a token and a tile an expert. Choices that
    put every token on every held expert fill it to the bound."""
    routed = max(top_k, first + count) * 2
    rows = moe.buffer_rows(tokens, top_k, count)
    assert rows == (-(-tokens * min(top_k, count) // moe.TILE_ROWS)
                    + count) * moe.TILE_ROWS
    # the worst case: each token's first `count` choices the held experts
    worst = (jnp.arange(top_k)[None, :] + first) % routed
    worst = jnp.broadcast_to(worst, (tokens, top_k)).astype(jnp.int32)
    for chosen in (worst, jax.lax.top_k(jax.random.uniform(
            jax.random.PRNGKey(0), (tokens, routed)), top_k)[1]):
        plan = moe._plan(chosen.astype(jnp.int32), first=first, count=count)
        held = np.asarray((chosen >= first) & (chosen < first + count))
        row = np.asarray(plan["row_of_slot"]).reshape(tokens, top_k)
        assert plan["slot_of_row"].shape == (rows,)
        assert (row[held] < rows).all() and (row[~held] == rows).all()
        assert len(set(row[held].tolist())) == held.sum()
        assert int(plan["local"]) == held.sum()
        slots = np.asarray(plan["slot_of_row"])
        assert sorted(slots[slots < tokens * top_k].tolist()) == sorted(
            np.flatnonzero(held.reshape(-1)).tolist())
        assert int(plan["tiles"][0]) * moe.TILE_ROWS <= rows
    assert held.sum() <= tokens * min(top_k, count)


@pytest.mark.parametrize("tokens,top_k,count,rows", [
    (8192, 8, 16, 69632),    # mellum2_train_1chip
    (8192, 8, 32, 73728),    # laguna_train_1chip
    (8192, 10, 32, 90112),   # qwen3next_train_1chip
    (4096, 22, 8, 34816),    # nemotron3super_train_1chip: 8 a token, not 22
])
def test_the_routed_cells_buffer_rows(tokens, top_k, count, rows):
    """The three routed cells before it hold ``top_k <= count`` and keep
    the rows they had (``tokens x top_k`` and a tile an expert); the
    Nemotron cell's 22 of 512 over 8 held is bounded by the 8."""
    assert moe.buffer_rows(tokens, top_k, count) == rows
    if top_k <= count:
        assert rows == (-(-tokens * top_k // moe.TILE_ROWS)
                        + count) * moe.TILE_ROWS


# ----------------------------------------------------- the shares add up

MIXER_SHARES, EXPERT_SHARES = 2, 4


def _uncut(cfg):
    """The tiny configuration with every head and expert of the layers the
    shares split: 8 Mamba-2 heads in 4 groups (2 mixer shares of 4 heads on
    2 groups), 4 query heads on 2 K/V heads, all 16 experts."""
    return dict(cfg, mamba_num_heads=8, n_groups=4, num_attention_heads=4,
                num_key_value_heads=2, n_routed_experts=16, first_expert=0)


def _mamba_share(full, cfg, share):
    """Mixer share ``share`` of an uncut Mamba-2 layer: its heads' columns
    of ``z``, ``x`` and ``dt`` and its groups' of ``B`` and ``C`` in the
    in-projection, their channels of the convolution, their ``A_log``,
    ``D``, ``dt_bias`` and norm weight, their rows of the out-projection."""
    p, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    heads, groups = 8 // MIXER_SHARES, 4 // MIXER_SHARES
    inner, gn = 8 * p, 4 * n
    hs = slice(share * heads * p, (share + 1) * heads * p)
    gs = slice(share * groups * n, (share + 1) * groups * n)
    hd = slice(share * heads, (share + 1) * heads)

    def cols(w, offset, part):
        return w[..., offset + part.start:offset + part.stop]

    win = full["win"]
    mine = dict(full)
    mine["win"] = jnp.concatenate([
        cols(win, 0, hs), cols(win, inner, hs), cols(win, 2 * inner, gs),
        cols(win, 2 * inner + gn, gs), cols(win, 2 * inner + 2 * gn, hd)],
        -1)
    for k in ("conv", "conv_b"):
        v = full[k]
        mine[k] = jnp.concatenate([v[hs], v[inner + gs.start:inner + gs.stop],
                                   v[inner + gn + gs.start:
                                     inner + gn + gs.stop]])
    for k in ("A_log", "D", "dt_bias"):
        mine[k] = full[k][hd]
    mine["gn"], mine["wout"] = full["gn"][hs], full["wout"][hs]
    return mine


def _share_cfg(cfg, share):
    return dict(cfg, mamba_num_heads=8 // MIXER_SHARES,
                n_groups=4 // MIXER_SHARES,
                num_attention_heads=4 // MIXER_SHARES, num_key_value_heads=1,
                n_routed_experts=16 // EXPERT_SHARES,
                first_expert=share * (16 // EXPERT_SHARES))


@pytest.mark.parametrize("layer", [0, 1, 3], ids=["mamba", "moe",
                                                  "attention"])
def test_the_shares_add_up_to_the_uncut_layer(cfg, ref, adapter, layer,
                                              highest):
    """Mamba-2 heads split 2 ways (each share with its groups) and query
    heads split 2 ways (each on its K/V head) each add their part of the
    out-projection; experts split 4 ways their part of the routed sum, each
    through ``fc2_latent_proj``; the shared expert and the residual, which
    every chip computes alike, count once: the program's parts over the
    shares sum to what the reference's uncut layer computes."""
    uncut = _uncut(cfg)
    kind = ref.layer_kind(cfg, layer)
    full = ref.layer_weights(ref.make_weights(uncut, common.split_seed(3)),
                             layer)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, cfg["hidden_size"]))
    want = ref._block(x, full, layer, 0, cfg=uncut,
                      mm=ref.MATMULS["float32"])

    def block_of(share, weights):
        share_cfg = _share_cfg(cfg, share)
        block = TransformerBlock(**models.TransformerLM(
            vocab=8, dim=cfg["hidden_size"], depth=1, heads=1,
            layers=(adapter.layers(share_cfg)[layer],), norm="rmsnorm",
            norm_eps=cfg["layer_norm_epsilon"], pos_embedding="none",
            dtype=jnp.float32).block_config(0))
        tree = adapter.to_tree(
            {f"l0.{k}": v for k, v in weights.items()})["block0"]
        return block.apply({"params": tree}, x[None])[0] - x

    total = x
    if kind == "E":
        held = 16 // EXPERT_SHARES
        for share in range(EXPERT_SHARES):
            mine = dict(full, wu=full["wu"][share * held:(share + 1) * held],
                        wd=full["wd"][share * held:(share + 1) * held])
            if share:
                mine["sd"] = jnp.zeros_like(full["sd"])
            total = total + block_of(share, mine)
    elif kind == "M":
        for share in range(MIXER_SHARES):
            total = total + block_of(share, _mamba_share(full, cfg, share))
    else:
        hd = cfg["head_dim"]
        for share in range(MIXER_SHARES):
            q = slice(share * 2 * hd, (share + 1) * 2 * hd)
            kv = slice(share * hd, (share + 1) * hd)
            total = total + block_of(share, dict(
                full, wq=full["wq"][:, q], wk=full["wk"][:, kv],
                wv=full["wv"][:, kv], wo=full["wo"][q]))
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert float(jnp.abs(want - x).max()) > 1e-3


# --------------------------------- the paths that do not compute the new forms


def _model(cfg, adapter):
    return adapter.build(cfg, {"optimizer": OPT})["model"]


def test_generate_refuses_the_new_forms_by_name(cfg, adapter):
    with pytest.raises(ValueError, match="Mamba-2 layer") as err:
        models.generate(_model(cfg, adapter), {}, jnp.zeros((1, 4), jnp.int32),
                        max_new_tokens=2)
    for form in ("pos_embedding='none'", "activation='relu2'",
                 "router='sigmoid'", "latent=32", "no heads and no mixer",
                 "ffn=None"):
        assert form in str(err.value), form


def _block(layer):
    return TransformerBlock(**models.TransformerLM(
        vocab=8, dim=64, depth=1, heads=1, layers=(layer,),
        pos_embedding="none", norm="rmsnorm",
        dtype=jnp.float32).block_config(0))


_MAMBA = models.Layer(mixer=models.Mamba2(4, 8, 2, 16, chunk=8), ffn=None)
_MOE = models.Layer(ffn=models.Experts(
    routed=8, top_k=2, width=32, count=4, shared=48, activation="relu2",
    router="sigmoid", latent=32))
_ATTENTION = models.Layer(heads=2, head_dim=16, kv_heads=1, ffn=None)


def _init(layer):
    x = jnp.zeros((1, T, 64))
    return _block(layer).init(jax.random.PRNGKey(0), x)["params"]


@pytest.mark.parametrize("shift,held", [(10.0, 2 * T), (-10.0, 0)])
def test_the_selection_bias_in_batch_stats_moves_the_choice(shift, held):
    """The layer selects by ``top_k(s + router_bias)``, the bias read from
    ``batch_stats``: lifting the four held experts (of eight routed) sends
    both of every token's slots here, sinking them sends none; the same
    tokens under a zero bias land on both sides."""
    block = _block(_MOE)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, 64))
    params = block.init(jax.random.PRNGKey(0), x)["params"]

    def rows(bias):
        _, stats = block.apply(
            {"params": params, "batch_stats": {"router_bias": bias}}, x,
            mutable=["batch_stats"])
        np.testing.assert_array_equal(stats["batch_stats"]["router_bias"],
                                      bias)
        return int(stats["batch_stats"]["moe_rows"])

    assert 0 < rows(jnp.zeros((8,))) < 2 * T
    assert rows(jnp.zeros((8,)).at[:4].set(shift)) == held


@pytest.mark.parametrize("layer,form", [
    (_MAMBA, "Mamba-2 layer"), (_MOE, "no heads and no mixer"),
    (_ATTENTION, "ffn=None")])
def test_decode_refuses_the_new_blocks_by_name(layer, form):
    block = TransformerBlock(**models.TransformerLM(
        vocab=8, dim=64, depth=1, heads=1, layers=(layer,),
        pos_embedding="none", norm="rmsnorm", decode=True,
        dtype=jnp.float32).block_config(0))
    with pytest.raises(ValueError, match="kv-cache decoding") as err:
        block.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 64)),
                   positions=jnp.zeros((1, 4), jnp.int32))
    assert form in str(err.value)


def test_inference_engine_refuses_the_mamba_layer(cfg, adapter):
    from horovod_tpu.serving.engine import InferenceEngine

    with pytest.raises(ValueError, match="InferenceEngine.*Mamba-2 layer"):
        InferenceEngine(_model(cfg, adapter), max_seq_len=32, num_pages=8)


def test_pipeline_split_refuses_the_mamba_layer(cfg, adapter):
    from horovod_tpu.training import split_transformer_for_pp

    with pytest.raises(ValueError,
                       match="split_transformer_for_pp.*Mamba-2 layer"):
        split_transformer_for_pp(_model(cfg, adapter), {}, 2)


@pytest.mark.parametrize("layer,leaf", [
    (_MAMBA, "Mamba-2 layer"), (_MOE, "routed-expert block")])
def test_param_specs_refuse_the_new_layers_by_name(layer, leaf):
    with pytest.raises(ValueError, match=leaf):
        models.transformer_param_specs(_init(layer))


@pytest.mark.parametrize("layer,message", [
    (_MAMBA, "Mamba-2 layer"), (_MOE, "one part alone"),
    (_ATTENTION, "one part alone")])
def test_tp_block_apply_refuses_the_new_layers(layer, message):
    from horovod_tpu.models.transformer import tp_block_apply

    with pytest.raises(ValueError, match=message):
        tp_block_apply(_init(layer), jnp.zeros((1, 4, 64)), heads=2)


@pytest.mark.parametrize("layer,message", [
    (models.Layer(heads=2, head_dim=16, mixer=models.Mamba2(4, 8, 2, 16),
                  ffn=1), "heads and head_dim or a mixer, one of the two"),
    (models.Layer(head_dim=16, ffn=1),
     "heads and head_dim or a mixer, one of the two"),
    (models.Layer(ffn=None), "no heads, no mixer and no FFN")])
def test_a_block_of_its_ffn_alone_takes_no_mixer(layer, message):
    """A block with no heads and no mixer is its FFN alone; one with part
    of attention's sizes, or attention and a mixer both, or no part at
    all, is refused."""
    model = models.TransformerLM(vocab=8, dim=64, depth=1, heads=1,
                                 layers=(layer,), pos_embedding="none")
    with pytest.raises(ValueError, match=message):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("field,value", [("activation", "gelu"),
                                         ("router", "tanh")])
def test_experts_refuse_a_form_they_do_not_have(field, value):
    with pytest.raises(ValueError, match=field):
        models.Experts(routed=8, top_k=2, width=32, **{field: value})
