"""A block chosen per layer (``TransformerLM(layers=...)``: window and full
attention, YaRN, routed experts, RMSNorm) against the benchmark's plain
reference of the ``mellum`` family on seeded weights, at a small size
(hidden 64, four layers sliding x3 + full with a window of 8 at T 32,
8 experts top-2): the loss, every gradient leaf, three AdamW steps through
``make_jit_train_step`` + ``DistributedOptimizer``; the four shares of the
deployment add up to the uncut layer; the routed layer against a dense
per-expert loop under the most uneven routing there is; and what the older
entry points do with a kind of block they do not handle.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, compare
from benchmarks.reference import steps as ref_steps
from horovod_tpu import models
from horovod_tpu.models.transformer import TransformerBlock
from horovod_tpu.observability import metrics
from horovod_tpu.parallel import moe

OPT = {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 1e-4}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(common.BENCH_DIR, "tests",
                           "tiny_mellum.json")) as f:
        return dict(json.load(f), compute_dtype="float32")


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "mellum")


@pytest.fixture(scope="module")
def adapter():
    return common.load_module("adapters", "mellum")


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _batches(n, rows=2, t=32, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, (rows, t)).astype(np.int32),
             rng.integers(0, 256, (rows, t)).astype(np.int32))
            for _ in range(n)]


SELECTIONS = ["top_k", "forced_uniform"]


@pytest.mark.parametrize("selection", SELECTIONS)
def test_loss_and_every_gradient_leaf(cfg, ref, adapter, highest, selection):
    cfg = dict(cfg, router_selection=selection)
    built = adapter.build(cfg, {"optimizer": OPT})
    weights = ref.make_weights(cfg, common.split_seed(5))
    (tokens, targets), = _batches(1)
    want_loss, want = ref.loss_and_grads(cfg, weights, tokens, targets)

    def loss(params):
        logits, _ = built["model"].apply(
            {"params": params, "batch_stats": built["batch_stats"]}, tokens,
            mutable=["batch_stats"])
        return built["loss_fn"](logits, targets)

    got_loss, got = jax.value_and_grad(loss)(built["to_tree"](weights))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got = built["ref_names"](got, list(weights))
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("selection", SELECTIONS)
def test_three_adamw_steps_through_the_jit_builder(hvd, cfg, ref, adapter,
                                                   highest, selection):
    from horovod_tpu import training

    cfg = dict(cfg, router_selection=selection)
    built = adapter.build(cfg, {"optimizer": OPT})
    halves = common.split_seed(11)
    pool = _batches(3, rows=8)
    tx = hvd.DistributedOptimizer(built["tx"])
    step = training.make_jit_train_step(built["model"], tx,
                                        loss_fn=built["loss_fn"])
    weights = ref.make_weights(cfg, halves)
    params = training.replicate(built["to_tree"](weights))
    stats = training.replicate(built["batch_stats"])
    opt_state = training.replicate(tx.init(params))
    losses = []
    for tokens, targets in pool:
        params, stats, opt_state, loss = step(
            params, stats, opt_state, training.shard_batch(tokens),
            training.shard_batch(targets))
        losses.append(float(loss))
    reference = ref_steps.first_steps(
        ref, cfg, {"optimizer": OPT, "reference": {"rows_per_block": 1}},
        halves, pool)
    np.testing.assert_allclose(losses, reference["losses"], rtol=1e-5)
    got = ref_steps.to_floats(ref_steps.diff_norms(
        built["ref_names"](params, list(weights)),
        ref.make_weights(cfg, halves)))
    for gap, leaf, *_ in compare.leaf_gaps(got, reference["update_norms"]):
        assert gap < 2e-3, (leaf, gap)
    # the routed blocks' counters came through the builder's state: 8 rows
    # of 32 tokens, top-2 over 4 of 8 experts
    rows = moe.record_rows(stats)
    assert 0 < rows < 4 * 8 * 32 * 2
    if selection == "forced_uniform":
        # what the scores choose, whatever the router has become
        want = sum(int((jax.lax.top_k(ref.forced_scores(i, 0, 8 * 32, 8),
                                      2)[1] < 4).sum()) for i in range(4))
        assert rows == want
    assert metrics.value("moe_local_rows") == rows
    assert metrics.value("moe_rows_budget") == moe.buffer_rows(8 * 32, 2, 4)


# ----------------------------------------------------- the four shares add up


def _share_cfg(cfg, share):
    return dict(cfg, first_expert=share * 2, num_experts=2)


def _uncut(cfg):
    """The tiny configuration with every head and expert: four times the
    share's heads, all 8 experts."""
    return dict(cfg, num_attention_heads=4 * cfg["num_attention_heads"],
                num_key_value_heads=4 * cfg["num_key_value_heads"],
                num_experts=8, first_expert=0)


def _share_of(full, cfg, share):
    """Share ``share`` of 4 of one uncut layer's weights: its query heads
    and their KV head, its experts; the router whole."""
    hd = cfg["head_dim"]
    q = slice(share * cfg["num_attention_heads"] * hd,
              (share + 1) * cfg["num_attention_heads"] * hd)
    kv = slice(share * cfg["num_key_value_heads"] * hd,
               (share + 1) * cfg["num_key_value_heads"] * hd)
    e = slice(share * 2, share * 2 + 2)
    return dict(full, wq=full["wq"][:, q], wk=full["wk"][:, kv],
                wv=full["wv"][:, kv], wo=full["wo"][q],
                wg=full["wg"][e], wu=full["wu"][e], wd=full["wd"][e])


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_four_shares_add_up_to_the_uncut_layer(cfg, ref, adapter, kind,
                                                   highest):
    """Heads split 4 ways each add their part of the attention projection,
    experts split 4 ways their part of the routed sum: the program's parts
    over shares 0-3 sum to what the reference's uncut layer computes."""
    uncut = _uncut(cfg)
    weights = ref.make_weights(uncut, common.split_seed(3))
    full = {k: weights[f"l0.{k}"] for k in ref._LAYER_KEYS}
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg["hidden_size"]))
    ropes = ref.rope_tables(cfg["rope_parameters"][kind], cfg["head_dim"],
                            32)
    window = cfg["sliding_window"] if kind == "sliding_attention" else 32
    mm = ref.MATMULS["float32"]

    def ref_block(w, c):
        return ref._block(x, w, *ropes, window, 0, 0, cfg=c, mm=mm)

    silent = dict(full, wd=jnp.zeros_like(full["wd"]))
    want_attention = ref_block(silent, uncut) - x
    h = ref._rms_norm(x, full["g2"], cfg["rms_norm_eps"])
    want_experts = ref._experts(h, full, 0, 0, cfg=uncut, mm=mm)
    np.testing.assert_allclose(
        ref_block(full, uncut) - x - want_attention,
        ref._experts(ref._rms_norm(x + want_attention, full["g2"],
                                   cfg["rms_norm_eps"]),
                     full, 0, 0, cfg=uncut, mm=mm), atol=1e-6)

    got_attention, got_experts, landed = 0.0, 0.0, 0.0
    for share in range(4):
        mine = _share_of(full, cfg, share)
        share_cfg = dict(_share_cfg(cfg, share), layer_types=[kind],
                         mlp_layer_types=["sparse"], num_layers=1)
        layer, = adapter.layers(share_cfg)
        block = TransformerBlock(**models.TransformerLM(
            vocab=8, dim=cfg["hidden_size"], depth=1, heads=1,
            layers=(layer,), norm="rmsnorm", pos_embedding="rope",
            dtype=jnp.float32).block_config(0))
        tree = adapter.to_tree(
            {f"l0.{k}": v for k, v in mine.items()})["block0"]
        silent = dict(tree, experts_down=jnp.zeros_like(tree["experts_down"]))
        out = block.apply({"params": silent}, x[None],
                          positions=jnp.arange(32)[None])
        got_attention = got_attention + (out[0] - x)
        part, local = moe.routed_experts(
            h, mine["wr"], mine["wg"], mine["wu"], mine["wd"], top_k=2,
            first=share * 2)
        landed += float(local)
        got_experts = got_experts + part
    # every one of the 32 tokens' two assignments landed on one share
    assert landed == 32 * 2
    np.testing.assert_allclose(got_attention, want_attention, atol=2e-6)
    np.testing.assert_allclose(got_experts, want_experts, atol=2e-6)


# ------------------------------------------------------------ the routed layer


def _dense_experts(x, router, gate, up, down, top_k, first, select=None):
    weights, chosen = moe.route_top_k(x, router, top_k, select)
    y = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + mine[:, None] * (
            (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return y


def _routed_inputs(tokens=96, dim=64, width=32, count=4, routed=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (tokens, dim)),
            jax.random.normal(ks[1], (dim, routed)) * 0.5,
            jax.random.normal(ks[2], (count, dim, width)) * 0.1,
            jax.random.normal(ks[3], (count, dim, width)) * 0.1,
            jax.random.normal(ks[4], (count, width, dim)) * 0.1)


@pytest.mark.parametrize("first,count", [(0, 8), (0, 4), (4, 4), (2, 3)])
def test_routed_layer_matches_a_dense_per_expert_loop(first, count, highest):
    args = _routed_inputs(count=count)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(fn, *a):
        return jnp.sum(fn(*a) * w)

    routed = lambda *a: moe.routed_experts(*a, top_k=2, first=first)[0]
    dense = functools.partial(_dense_experts, top_k=2, first=first)
    got = jax.value_and_grad(functools.partial(loss, routed),
                             argnums=range(5))(*args)
    want = jax.value_and_grad(functools.partial(loss, dense),
                              argnums=range(5))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


def test_select_chooses_and_the_router_weighs(highest):
    """Scores handed in choose a token's experts; its weights are the
    router's probabilities of those experts over their sum, and the router
    has its gradient."""
    args = _routed_inputs(count=4)
    x, router = args[:2]
    scores = jax.random.uniform(jax.random.PRNGKey(3), (x.shape[0], 8))
    select = lambda probs: scores
    weights, chosen = moe.route_top_k(x, router, 2, select)
    np.testing.assert_array_equal(chosen, jax.lax.top_k(scores, 2)[1])
    probs = jnp.take_along_axis(jax.nn.softmax(x @ router, axis=-1), chosen,
                                axis=-1)
    np.testing.assert_allclose(
        weights, probs / probs.sum(-1, keepdims=True), rtol=1e-5)

    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    routed = lambda *a: jnp.sum(w * moe.routed_experts(
        *a, top_k=2, select=select)[0])
    dense = lambda *a: jnp.sum(w * _dense_experts(*a, 2, 0, select))
    got = jax.value_and_grad(routed, argnums=range(5))(*args)
    want = jax.value_and_grad(dense, argnums=range(5))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))
    assert float(jnp.abs(got[1][1]).max()) > 0
    local = moe.routed_experts(*args, top_k=2, select=select)[1]
    assert float(local) == float((chosen < 4).sum())


def test_weights_stay_finite_where_the_chosen_probabilities_vanish(highest):
    """A router that has collapsed onto experts a token was not sent to:
    the chosen experts' probabilities round to nothing in float32, their
    weights and the gradient do not."""
    x, router, gate, up, down = _routed_inputs(count=8)
    x = jnp.abs(x)
    router = router.at[:, 0].set(40.0)            # logits of 1,000 and more
    select = lambda probs: jnp.arange(8.0)[None, :] + 0 * probs  # 7 and 6
    weights, chosen = moe.route_top_k(x, router, 2, select)
    assert bool(jnp.all(chosen == jnp.array([7, 6])))
    probs = jax.nn.softmax(x @ router, axis=-1)
    assert float(probs[:, 6:].max()) == 0.0
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    grads = jax.grad(lambda *a: jnp.sum(moe.routed_experts(
        *a, top_k=2, select=select)[0]), argnums=range(5))(
            x, router, gate, up, down)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


@pytest.mark.parametrize("layer", [0, 3])
def test_forced_scores_spread_the_tokens(ref, layer):
    """The scores a timed cell chooses by: every expert gets its share of a
    step's assignments within a fifth, and a token's scores are its own
    whatever the step holds beside it."""
    scores = ref.forced_scores(layer, 0, 2048, 64)
    chosen = np.asarray(jax.lax.top_k(scores, 8)[1])
    assert all(len(set(row)) == 8 for row in chosen[:64])
    counts = np.bincount(chosen.ravel(), minlength=64)
    assert counts.min() > 0.8 * 256 and counts.max() < 1.2 * 256
    np.testing.assert_array_equal(scores[512:640],
                                  ref.forced_scores(layer, 512, 128, 64))
    assert not np.array_equal(scores, ref.forced_scores(layer + 1, 0, 2048,
                                                        64))


def _routed_grads(x, router, gate, up, down, **kw):
    def loss(*a):
        return jnp.sum(moe.routed_experts(*a, top_k=2, **kw)[0])

    return jax.grad(loss, argnums=range(5))(x, router, gate, up, down)


def test_every_token_to_one_expert_drops_nothing(highest):
    """The most uneven routing: the router sends every token's first
    choice to expert 0. The buffer holds the worst case: every assignment
    has its row."""
    x, router, gate, up, down = _routed_inputs(count=8)
    x = jnp.abs(x)
    router = router.at[:, 0].set(10.0)
    weights, chosen = moe.route_top_k(x, router, 2)
    assert bool(jnp.all(chosen[:, 0] == 0)) and float(weights[:, 0].min()) > 0.99
    y, local = moe.routed_experts(x, router, gate, up, down, top_k=2)
    assert float(local) == 2.0 * x.shape[0]
    np.testing.assert_allclose(
        y, _dense_experts(x, router, gate, up, down, 2, 0), atol=1e-5)


def test_no_assignment_here_adds_nothing(highest):
    """The other end: no token chooses an expert held here. Each held
    expert keeps its one tile, all padding; the rows the products never
    wrote (interpreted, they read NaN) reach neither the result nor any
    gradient, and the held matrices' gradients are written, as zeros."""
    x, router, gate, up, down = _routed_inputs(count=4)
    x = jnp.abs(x)
    router = router.at[:, :2].set(10.0)
    y, local = moe.routed_experts(x, router, gate, up, down, top_k=2,
                                  first=4)
    assert float(local) == 0.0 and not np.any(np.asarray(y))
    grads = _routed_grads(x, router, gate, up, down, first=4)
    for g in grads:
        assert not np.any(np.asarray(g))


@pytest.mark.parametrize("tokens", [64, 600])
@pytest.mark.parametrize("call", ["product", "expert_mlp"])
def test_the_products_pass_over_tiles_no_row_fills(call, tokens, highest):
    """The grouped products' work follows the rows the router sent here:
    ``tiles`` counts each held expert's whole tiles (one at least), the
    runs fill the buffer's first ``tiles`` tiles, and a product leaves the
    rows past them as they were allocated. The fused calls of the experts'
    MLP too, forward and backward: with every operand's rows past the
    tiles in use poisoned, whatever they write for a tile in use and every
    matrix's gradient is finite, and they write nothing past them."""
    x, router, gate, up, down = _routed_inputs(tokens=tokens, count=4)
    _, chosen = moe.route_top_k(x, router, 2)
    plan = moe._plan(chosen, first=2, count=4)
    sizes = np.bincount(np.asarray(chosen).ravel(), minlength=8)[2:6]
    tiles = int(np.maximum(-(-sizes // moe.TILE_ROWS), 1).sum())
    assert plan["tiles"].tolist() == [tiles]
    assert int(plan["local"]) == sizes.sum()
    rows = moe.buffer_rows(tokens, 2, 4)
    assert plan["slot_of_row"].shape == (rows,) and tiles < rows // moe.TILE_ROWS
    in_use = np.asarray(plan["slot_of_row"]) < tokens * 2
    assert in_use.sum() == sizes.sum()
    used = tiles * moe.TILE_ROWS
    assert not in_use[used:].any()
    groups = (plan["tile_expert"], plan["tiles"], True)
    if call == "product":
        xs = jnp.ones((rows, x.shape[1]), jnp.float32)
        out = np.asarray(moe.grouped_matmul(xs, gate, *groups))
        assert np.isfinite(out[:used]).all()
        assert np.isnan(out[used:]).all()
        return
    poisoned = lambda a: a.at[used:].set(jnp.nan)
    keys = jax.random.split(jax.random.PRNGKey(tokens), 3)
    xs = poisoned(jax.random.normal(keys[0], (rows, x.shape[1])))
    w_rows = poisoned(jnp.where(in_use, jax.random.uniform(keys[1], (rows,)),
                                0))
    # as ``_to_tokens_bwd`` hands it in: zeros on the padding rows
    dys = poisoned(jnp.where(in_use[:, None],
                             jax.random.normal(keys[2], xs.shape), 0))
    ys, back = jax.vjp(lambda *a: moe.expert_mlp(*a, *groups), xs, w_rows,
                       gate, up, down)
    dxs, dw, *matrices = back(dys)
    for by_row in (ys, dxs, dw):
        by_row = np.asarray(by_row)
        assert by_row.shape[0] == rows and np.isfinite(by_row[:used]).all()
        assert np.isnan(by_row[used:]).all()
    assert not np.asarray(ys)[:used][~in_use[:used]].any()
    for got, like in zip(matrices, (gate, up, down)):
        assert got.shape == like.shape and np.isfinite(np.asarray(got)).all()
    # the same numbers as the three products and the passes between them
    act = (jax.nn.silu(moe.grouped_matmul(xs, gate, *groups))
           * moe.grouped_matmul(xs, up, *groups))
    want = moe.grouped_matmul(act, down, *groups) * w_rows[:, None]
    np.testing.assert_allclose(np.asarray(ys)[:used], np.asarray(want)[:used],
                               rtol=1e-5, atol=1e-6)


def _routing_case(routing, count):
    """Inputs whose router sends the tokens ``routing``'s way."""
    x, router, gate, up, down = _routed_inputs(tokens=300, dim=128,
                                               count=count)
    select = None
    if routing == "one expert":
        x, router = jnp.abs(x), router.at[:, 0].set(10.0)
    elif routing == "an expert with no row":
        # scores no token's top 2 reach on expert 5: held by every case
        select = lambda probs: probs.at[:, 5].set(-1.0)
    return (x, router, gate, up, down), select


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("first,count,routing", [
    (0, 8, "router"), (4, 4, "router"), (2, 3, "router"),
    (0, 8, "an expert with no row"), (4, 4, "an expert with no row"),
    (2, 4, "an expert with no row"),
    (0, 8, "one expert"), (0, 3, "one expert"),
])
def test_fused_expert_mlp_matches_a_dense_loop(first, count, routing, dtype,
                                               highest):
    """The activation, the combine's weighting and their backwards inside
    the grouped products' kernels (interpreted), through
    ``routed_experts``: the layer and the gradient of x, of the router and
    of every expert matrix against the dense per-expert loop, in float32
    and with bfloat16 products."""
    args, select = _routing_case(routing, count)
    dtype = getattr(jnp, dtype)
    c = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    metrics.REGISTRY.reset()

    def routed(*a):
        y, _ = moe.routed_experts(a[0].astype(dtype), *a[1:], top_k=2,
                                  first=first, select=select, dtype=dtype)
        assert y.dtype == dtype
        return jnp.sum(c * y)

    dense = lambda *a: jnp.sum(c * _dense_experts(*a, 2, first, select))
    got = jax.value_and_grad(routed, argnums=range(5))(*args)
    assert metrics.value("moe_experts_fused") == 1
    want = jax.value_and_grad(dense, argnums=range(5))(*args)
    if routing == "an expert with no row":
        _, chosen = moe.route_top_k(*args[:2], 2, select)
        assert not bool(jnp.any(chosen == 5))
        assert not np.any(np.asarray(got[1][2][5 - first]))
    if routing == "one expert":
        assert bool(jnp.all(moe.route_top_k(*args[:2], 2)[1][:, 0] == 0))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[0], want[0], rtol=tol,
                               atol=tol * float(jnp.abs(c).sum()) * 1e-3)
    for a, b in zip(got[1], want[1]):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=tol * float(jnp.abs(b).max()))


def test_experts_too_wide_for_the_fused_calls_keep_the_three_products():
    """Which form runs is read from the shapes: two matrices of an expert,
    double-buffered, must fit the kernels' VMEM beside the tiles. Both
    routed cells' experts do; at ``[4096, 1408]`` in bfloat16 (46 MB for
    the four) the layer is the three separate products with the passes
    between them, and the gauge ``moe_experts_fused`` stays unset."""
    assert moe._experts_fit(2304, 896, 2) and moe._experts_fit(2048, 512, 2)
    assert not moe._experts_fit(4096, 1408, 2)
    S = jax.ShapeDtypeStruct
    for dim, width, fused in ((4096, 1408, False), (2048, 512, True)):
        metrics.REGISTRY.reset()
        text = str(jax.make_jaxpr(lambda *a: moe.routed_experts(
            *a, top_k=2, interpret=True)[0])(
                S((64, dim), jnp.bfloat16), S((dim, 8), jnp.float32),
                S((2, dim, width), jnp.float32),
                S((2, dim, width), jnp.float32),
                S((2, width, dim), jnp.float32)))
        assert ("hvd_moe_mlp_fwd" in text) == fused
        assert text.count("name=_pallas_gmm") == (0 if fused else 3)
        assert metrics.value("moe_experts_fused") == (1 if fused else None)


def _sees(jaxpr, shapes, found):
    """The primitives of ``jaxpr`` (and of every jaxpr inside it but a
    ``pallas_call``'s kernel) that read or write an array of one of
    ``shapes``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    _sees(inner, shapes, found)
        if eqn.primitive.name in ("pjit", "jit", "custom_vjp_call",
                                  "custom_jvp_call", "pallas_call"):
            continue
        if any(getattr(v.aval, "shape", None) in shapes
               for v in list(eqn.invars) + list(eqn.outvars)):
            found.append(eqn.primitive.name)
    return found


def test_what_still_runs_over_the_whole_buffer():
    """ROADMAP S12's remaining list, from the jaxpr of the layer and its
    gradient: outside the kernels, the only operations over a ``[rows, D]``
    or ``[rows, F]`` array are the dispatch's two gathers (``_to_rows`` of
    x, and of the combine's cotangent, whose padding rows read a row of
    zeros). No ``add_any`` (the gate's and the up's dX are summed in a
    kernel), no ``mul``, ``logistic`` or ``select_n`` (silu * up, the
    combine's weighting and their backwards are the kernels' too)."""
    args = _routed_inputs(tokens=300, dim=128, width=64, count=4)
    args = (args[0].astype(jnp.bfloat16),) + args[1:]
    rows = moe.buffer_rows(300, 2, 4)
    loss = lambda *a: moe.routed_experts(*a, top_k=2, first=2)[0].astype(
        jnp.float32).sum()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(5)))(*args)
    found = _sees(jaxpr.jaxpr, {(rows, 128), (rows, 64)}, [])
    assert sorted(found) == ["gather", "gather"], found
    text = str(jaxpr)
    for name in ("hvd_moe_mlp_fwd", "hvd_moe_mlp_bwd", "hvd_moe_tgmm"):
        assert name in text, name


# ------------------------------------------- the way back to the tokens


def _way_back(tokens, first, count, routing="uniform", routed=8, top_k=2,
              dim=128, dtype=jnp.float32, offset=0):
    """A plan and a sorted buffer whose rows past the tiles in use hold
    NaN, as the grouped products leave them interpreted. ``"five rows"``:
    token tile 1 has 5 rows on expert ``first``, ``offset`` rows into its
    run (tile 0's), and every 64th token one on the next expert."""
    keys = jax.random.split(jax.random.PRNGKey(tokens + 7 * first), 2)
    away = [e for e in range(routed) if not first <= e < first + count]
    if routing == "one expert":
        chosen = jnp.tile(jnp.array([[first, first + 1]], jnp.int32),
                          (tokens, 1))
    elif routing == "none here":
        chosen = jnp.tile(jnp.array([away[:top_k]], jnp.int32), (tokens, 1))
    elif routing == "five rows":
        t = jnp.arange(tokens)[:, None]
        chosen = jnp.tile(jnp.array([away[:top_k]], jnp.int32), (tokens, 1))
        mine = (t < offset) | ((t >= moe.TOKEN_TILE)
                               & (t < moe.TOKEN_TILE + 5))
        chosen = jnp.where(mine & (jnp.arange(top_k) == 0), first, chosen)
        chosen = jnp.where((t % 64 == 1) & (jnp.arange(top_k) == 1),
                           first + 1, chosen)
    else:
        chosen = jax.lax.top_k(jax.random.uniform(keys[0], (tokens, routed)),
                               top_k)[1].astype(jnp.int32)
    plan = moe._plan(chosen, first=first, count=count)
    y = jax.random.normal(keys[1], (moe.buffer_rows(tokens, top_k, count),
                                    dim))
    y = y.at[int(plan["tiles"][0]) * moe.TILE_ROWS:].set(jnp.nan)
    return y.astype(dtype), plan, top_k, routed


def _matches_the_gather(y, plan, top_k, routed):
    """The kernel (interpreted) against the gather of every slot's row:
    the same float32 sums, and nothing of the rows no product wrote."""
    jaxpr = str(jax.make_jaxpr(
        lambda a, w: moe._to_tokens(a, w, top_k, routed, True))(y, plan))
    assert "pallas_call" in jaxpr and "gather" not in jaxpr
    got = np.asarray(moe._to_tokens(y, plan, top_k, routed, True), np.float32)
    want = np.asarray(moe._gather_to_tokens(y, plan["row_of_slot"], top_k),
                      np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    return got


@pytest.mark.parametrize("tokens,first,count,routing,dtype", [
    (64, 0, 8, "uniform", "float32"),       # all held
    (64, 4, 4, "uniform", "float32"),
    (64, 2, 3, "uniform", "bfloat16"),
    (600, 0, 8, "uniform", "bfloat16"),     # all held, three token tiles
    (600, 4, 4, "uniform", "float32"),
    (600, 2, 3, "uniform", "float32"),
    (333, 0, 8, "uniform", "float32"),      # a count no tile divides
    (333, 4, 4, "uniform", "bfloat16"),
    (333, 2, 3, "uniform", "float32"),
    (600, 0, 8, "one expert", "float32"),   # segments of several windows
    (333, 2, 3, "one expert", "bfloat16"),
    (600, 4, 4, "none here", "float32"),
])
def test_the_way_back_reads_the_rows_held_here(tokens, first, count, routing,
                                               dtype):
    """The kernel (interpreted) against the gather of every slot's row: the
    same float32 sums, and nothing of the rows no product wrote."""
    y, plan, top_k, routed = _way_back(tokens, first, count, routing,
                                       dtype=getattr(jnp, dtype))
    assert bool(jnp.isnan(y[-1, 0]))
    got = _matches_the_gather(y, plan, top_k, routed)
    assert got.shape == (tokens, y.shape[1])
    if routing == "none here":
        assert int(plan["local"]) == 0 and not got.any()
    if routing == "one expert":
        assert int(plan["seg_rows"].max()) == min(tokens, moe.TOKEN_TILE)


@pytest.mark.parametrize("top_k,routed,count,dtype,window,groups", [
    (4, 32, 24, "float32", 64, (16, 8)),
    (8, 256, 40, "bfloat16", 32, (32, 8)),   # Laguna's windows
    (2, 1024, 130, "float32", 8, (128, 2)),
])
def test_the_way_back_takes_the_held_experts_in_groups(top_k, routed, count,
                                                       dtype, window, groups):
    """More held experts than one product takes windows of (the rule's
    window, ``_PRODUCT_ROWS`` rows of them a product): the groups' sums add
    up."""
    align = 32 // jnp.dtype(dtype).itemsize
    assert moe._combine_tile(top_k, routed, count, align) == (window,
                                                             groups[0])
    assert sum(groups) == count
    y, plan, top_k, routed = _way_back(300, 4, count, routed=routed,
                                       top_k=top_k, dtype=getattr(jnp, dtype))
    _matches_the_gather(y, plan, top_k, routed)
    assert int(plan["local"]) > 300 * top_k * count // routed // 2


@pytest.mark.parametrize("first,count,routed,dtype,window", [
    (0, 32, 64, "bfloat16", 32),     # 32 held, windows of 32: eight rounds
    (0, 4, 512, "float32", 8),       # windows of 8: 32 rounds
    (2, 3, 8, "bfloat16", 64),
])
def test_a_segment_longer_than_the_window_takes_rounds(first, count, routed,
                                                       dtype, window):
    """Every token on two experts: a token tile's 256 rows on each are read
    in as many rounds of the window the rule chose from even routing."""
    assert moe._combine_tile(2, routed, count,
                             32 // jnp.dtype(dtype).itemsize)[0] == window
    y, plan, top_k, routed = _way_back(600, first, count, "one expert",
                                       routed=routed,
                                       dtype=getattr(jnp, dtype))
    assert int(plan["seg_rows"].max()) == moe.TOKEN_TILE
    _matches_the_gather(y, plan, top_k, routed)


@pytest.mark.parametrize("dtype,offset,crosses", [
    ("bfloat16", 11, False), ("bfloat16", 12, True), ("bfloat16", 15, True),
    ("float32", 3, False), ("float32", 4, True), ("float32", 7, True),
])
def test_a_segment_across_a_window_end(dtype, offset, crosses):
    """Top-2 of 512 puts one row of a token tile on an expert as a rule:
    windows of the dtype's alignment (16 rows, 8 in float32). Five rows
    that start ``offset`` rows into an aligned row fit one window up to
    ``window - 5`` and cross its end past that, into a second round."""
    align = 32 // jnp.dtype(dtype).itemsize
    window, _ = moe._combine_tile(2, 512, 4, align)
    assert window == align
    y, plan, top_k, routed = _way_back(512, 0, 4, "five rows", routed=512,
                                       dtype=getattr(jnp, dtype),
                                       offset=offset)
    start, size = int(plan["seg_start"][1, 0]), int(plan["seg_rows"][1, 0])
    assert size == 5 and start % align == offset
    assert (offset + size > window) == crosses
    got = _matches_the_gather(y, plan, top_k, routed)
    assert got[moe.TOKEN_TILE:moe.TOKEN_TILE + 5].any()


def test_segments_are_the_rows_of_a_token_tile_on_an_expert():
    """``seg_start`` / ``seg_rows``: the sort is stable, so the rows that a
    tile of tokens has on one held expert follow each other."""
    _, plan, top_k, _ = _way_back(600, 2, 3)
    rows = np.asarray(plan["row_of_slot"]).reshape(-1, top_k)
    expert_of_row = np.repeat(np.asarray(plan["tile_expert"]), moe.TILE_ROWS)
    for tile in range(-(-600 // moe.TOKEN_TILE)):
        mine = rows[tile * moe.TOKEN_TILE:(tile + 1) * moe.TOKEN_TILE]
        mine = mine[mine < expert_of_row.size]
        for e in range(3):
            here = np.sort(mine[expert_of_row[mine] == e])
            start, n = (int(plan[k][tile, e]) for k in ("seg_start",
                                                        "seg_rows"))
            assert n == here.size
            np.testing.assert_array_equal(here, start + np.arange(n))


def test_an_operand_one_lane_wide_keeps_the_gather():
    """The router weights' ``f32[rows, 1]`` (and any operand that is not
    ``[rows, D]`` in whole lanes) gathers, chosen from its shape; the gauge
    ``moe_combine_tile`` says which form a trace took."""
    y, plan, top_k, routed = _way_back(600, 4, 4)
    slots = jax.random.normal(jax.random.PRNGKey(2), (y.shape[0], 1))
    # ... and rows too wide for the kernel's windows to fit its VMEM
    wide = jax.ShapeDtypeStruct((y.shape[0], 1 << 14), jnp.bfloat16)
    assert moe._combine_fits(2304, 2, 16 * 64) and moe._combine_fits(
        2048, 2, 32 * 32) and not moe._combine_fits(1 << 14, 2, 4 * 64)
    for operand, k in ((slots, 1), (y[:, :64], top_k), (y[:, :128], 1),
                       (wide, top_k)):
        metrics.REGISTRY.reset()
        jaxpr = str(jax.make_jaxpr(
            lambda a, w: moe._to_tokens(a, w, k, routed, True))(operand,
                                                               plan))
        assert "pallas_call" not in jaxpr and "gather" in jaxpr
        assert metrics.value("moe_combine_tile", dim="tokens") is None
    jax.make_jaxpr(lambda a, w: moe._to_tokens(a, w, top_k, routed, True))(
        y, plan)
    # top-2 of 8 in float32: 64 rows a tile on an expert, windows of 64,
    # the four held experts' windows in one product
    window, windows = moe._combine_tile(top_k, routed, 4, 8)
    assert (window, windows) == (64, 4)
    assert metrics.value("moe_combine_tile", dim="tokens") == moe.TOKEN_TILE
    assert metrics.value("moe_combine_tile", dim="rows") == window
    assert metrics.value("moe_combine_tile", dim="windows") == windows


@pytest.mark.parametrize("first,count", [(0, 8), (4, 4), (2, 3)])
def test_routed_layer_through_the_kernel_matches_a_dense_loop(first, count,
                                                              highest):
    """Hidden 128, whole lanes: the combine and the dispatch's transpose
    both run the kernel, and the layer and every gradient still match the
    dense per-expert loop."""
    args = _routed_inputs(tokens=300, dim=128, count=count)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    routed = lambda *a: jnp.sum(w * moe.routed_experts(
        *a, top_k=2, first=first)[0])
    dense = lambda *a: jnp.sum(w * _dense_experts(*a, 2, first))
    jaxpr = str(jax.make_jaxpr(jax.grad(routed, argnums=range(5)))(*args))
    # the experts' MLP in 1 + 3 calls, the combine and the dispatch's
    # transpose (each a jitted function its sites share); the scalar
    # weights' way back is the one gather of ``top_k`` x tokens rows
    assert jaxpr.count("name=_pallas_") == 6
    got = jax.value_and_grad(routed, argnums=range(5))(*args)
    want = jax.value_and_grad(dense, argnums=range(5))(*args)
    # a sum of 38,400 terms that cancel to a thousandth of their size, in
    # another order than the loop's (the weight is on the activation)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


def test_buffer_rows():
    # the benchmark's layer: 8192 tokens at top-8, 16 experts held: every
    # assignment and a tile of padding an expert
    assert moe.buffer_rows(8192, 8, 16) == 65536 + 16 * 256
    assert moe.buffer_rows(64, 2, 8) == (1 + 8) * 256


# ------------------------------------- scopes and names, in the TPU lowering


def test_routed_layer_lowers_for_tpu_under_its_scopes():
    """The experts' MLP is four Mosaic calls under ``hvd.moe_experts``,
    each named ``hvd_moe_*``: ``hvd_moe_mlp_fwd`` forward,
    ``hvd_moe_mlp_bwd`` and two ``hvd_moe_tgmm`` (the gate's and the up's
    gradients in one, the down's in the other) backward. The gathers and
    the kernel of the way back (a call with no name of its own, so its
    time is its scope's) are under ``hvd.moe_route``, in the forward and in
    the backward: what the benchmark's ``moe_*`` readers key on through
    ``profiler.scope_of``. Each call is a jitted function that takes its
    site's scope. No gather reads a row of width ``D`` for every slot."""
    import re

    from horovod_tpu import profiler

    S = jax.ShapeDtypeStruct
    args = (S((512, 256), jnp.bfloat16), S((256, 8), jnp.float32),
            S((4, 256, 128), jnp.float32), S((4, 256, 128), jnp.float32),
            S((4, 128, 256), jnp.float32))

    @jax.named_scope("hvd.forward")
    def loss(*a):
        y, _ = moe.routed_experts(*a, top_k=2, interpret=False)
        return y.astype(jnp.float32).sum()

    # with the value: the gradient of a sum needs no combine of its own
    text = jax.jit(jax.value_and_grad(loss, argnums=range(5))).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = set(re.findall(r'kernel_name = "([^"]*)"', text))
    assert names == {"_combine_kernel", "hvd_moe_mlp_fwd", "hvd_moe_mlp_bwd",
                     "hvd_moe_tgmm"}
    # inside its function a call is named ``<kernel>/pallas_call``; the
    # function's sites give the scope
    inner = set(re.findall(r'loc\("([^"]*)/pallas_call"', text))
    assert inner == names - {"_combine_kernel"}, inner
    sites = re.findall(r'loc\("([^"]*/jit\(_pallas_\w+\))"', text)
    kernel_of = {"_pallas_mlp_fwd": "hvd_moe_mlp_fwd",
                 "_pallas_mlp_bwd": "hvd_moe_mlp_bwd",
                 "_pallas_tgmm": "hvd_moe_tgmm"}
    calls, way_back = [], []
    for site in set(sites):
        wrapper = re.search(r"jit\((\w+)\)$", site).group(1)
        if wrapper == "_pallas_combine":
            way_back.append(site + "/pallas_call")
        else:
            assert "hvd.moe_experts" in site, site
            calls.append(f"{site}/{kernel_of[wrapper]}/pallas_call")
    assert len(calls) == 3 and len(way_back) == 2, (calls, way_back)
    assert not any("hvd_moe_" in c for c in way_back)
    kinds = {profiler.scope_of(c, "custom-call") for c in calls + way_back}
    assert kinds == {("forward", "hvd_moe_mlp_fwd"),
                     ("backward", "hvd_moe_mlp_bwd"),
                     ("backward", "hvd_moe_tgmm"),
                     ("forward", "hvd.moe_route"),
                     ("backward", "hvd.moe_route")}
    # three matrices' gradients: the gate's and the up's read ``xs`` once
    tgmm = re.findall(r"call @(_pallas_tgmm\w*)", text)
    assert len(tgmm) == 2 and len(set(tgmm)) == 2, tgmm
    gathers = [n for n in re.findall(r'loc\("([^"]*)"', text)
               if n.endswith("/gather")]
    assert any(profiler.scope_of(n) == ("forward", "hvd.moe_route")
               for n in gathers)
    assert any(profiler.scope_of(n) == ("backward", "hvd.moe_route")
               for n in gathers)
    assert "stablehlo.scatter" not in text
    # 512 tokens x top-2: the one gather of as many rows left is the scalar
    # weights' (``f32[1024, 1]``)
    wide = [l for l in text.splitlines() if "stablehlo.gather" in l
            and re.search(r"-> tensor<1024x256x\w+>", l)]
    assert "stablehlo.gather" in text and not wide, wide


def test_scope_of_names_the_routed_scopes():
    from horovod_tpu.profiler import scope_of

    assert scope_of("jit(s)/jvp(hvd.forward)/block0/hvd.moe_route/sort") == (
        "forward", "hvd.moe_route")
    assert scope_of("jit(s)/transpose(jvp(hvd.forward))/block0/"
                    "hvd.moe_experts/mul") == ("backward", "hvd.moe_experts")
    assert scope_of("jit(s)/jvp(hvd.forward)/block0/hvd.moe_experts/"
                    "hvd_moe_gmm/pallas_call") == ("forward", "hvd_moe_gmm")
    # the way back's call has no name: in the forward it is the combine's,
    # in the backward the transpose of the dispatch, both ``hvd.moe_route``
    assert scope_of("jit(s)/jvp(hvd.forward)/block0/hvd.moe_route/"
                    "jit(_pallas_combine)/pallas_call",
                    "custom-call") == ("forward", "hvd.moe_route")
    assert scope_of("jit(s)/transpose(jvp(hvd.forward))/block0/"
                    "hvd.moe_route/hvd.moe_route/jit(_pallas_combine)/"
                    "pallas_call", "custom-call") == ("backward",
                                                      "hvd.moe_route")


# ------------------------------ what the older entry points do with such blocks


def _tiny_lm(**kw):
    layer = models.Layer(heads=4, head_dim=16, kv_heads=1, **kw)
    return models.TransformerLM(
        vocab=64, dim=64, depth=2, heads=4, layers=(layer, layer),
        norm="rmsnorm", pos_embedding="rope", max_len=64, dtype=jnp.float32)


def test_param_tree_of_a_described_block():
    model = _tiny_lm(window=8, ffn=models.Experts(routed=8, top_k=2,
                                                  width=32, count=4))
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), "int32"))
    block = variables["params"]["block0"]
    assert set(block) == {"ln1", "q_proj", "k_proj", "v_proj", "proj", "ln2",
                          "router", "experts_gate", "experts_up",
                          "experts_down"}
    assert set(block["ln1"]) == {"scale"}
    assert block["q_proj"]["kernel"].shape == (64, 64)
    assert block["k_proj"]["kernel"].shape == (64, 16)
    assert block["router"].shape == (64, 8)
    assert block["experts_gate"].shape == (4, 64, 32)
    assert "pos_embed" not in variables["params"]
    assert variables["batch_stats"]["block1"]["moe_rows"].shape == ()


def test_the_default_blocks_are_what_they_were():
    """No ``layers``: the parameter tree of ``depth`` blocks alike, names
    and shapes as ever (the GPT-2 adapter's model)."""
    model = models.TransformerLM(vocab=64, dim=32, depth=1, heads=4,
                                 max_len=16, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), "int32"))["params"]
    assert set(params) == {"tok_embed", "pos_embed", "block0", "ln_f",
                           "lm_head"}
    assert set(params["block0"]) == {"ln1", "qkv", "proj", "ln2", "mlp_up",
                                     "mlp_down"}
    assert set(params["block0"]["ln1"]) == {"scale", "bias"}


def test_layers_must_match_depth_and_rotary():
    layer = models.Layer(heads=4, head_dim=16)
    tokens = jnp.zeros((1, 8), "int32")
    for kw in (dict(depth=3, pos_embedding="rope"),
               dict(depth=2, pos_embedding="learned")):
        model = models.TransformerLM(vocab=64, dim=64, heads=4,
                                     layers=(layer, layer), **kw)
        with pytest.raises(ValueError, match="layers describes 2 blocks"):
            model.init(jax.random.PRNGKey(0), tokens)


def test_a_described_mlp_block_generates():
    """A described block with full attention and an MLP decodes through the
    kv cache as a plain block does: generate() against a full-forward
    rollout."""
    model = _tiny_lm(ffn=2)
    prompt = jnp.arange(6)[None] % 64
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    out = models.generate(model, params, prompt, max_new_tokens=4)
    tokens = prompt
    for _ in range(4):
        nxt = jnp.argmax(model.apply({"params": params}, tokens)[:, -1], -1)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, tokens)


@pytest.mark.parametrize("kw", [
    dict(window=8), dict(ffn=models.Experts(routed=8, top_k=2, width=32))])
def test_kinds_the_kv_cache_does_not_handle_raise(kw):
    model = _tiny_lm(**kw)
    prompt = jnp.zeros((1, 4), "int32")
    variables = model.init(jax.random.PRNGKey(0), prompt)
    with pytest.raises(NotImplementedError, match="kv-cache decoding"):
        models.generate(model, variables["params"], prompt, max_new_tokens=2)


def test_param_specs_and_tp_block_refuse_routed_blocks():
    model = _tiny_lm(ffn=models.Experts(routed=8, top_k=2, width=32))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), "int32"))["params"]
    with pytest.raises(ValueError, match="routed-expert block"):
        models.transformer_param_specs(params)
    from horovod_tpu.models.transformer import tp_block_apply

    with pytest.raises(ValueError, match="fused qkv|LayerNorm \\+ MLP"):
        tp_block_apply(params["block0"], jnp.zeros((1, 4, 64)), heads=4)
    # separate k and v projections split by column, as q does
    mlp = _tiny_lm(ffn=2)
    specs = models.transformer_param_specs(mlp.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), "int32"))["params"])
    assert specs["block0"]["k_proj"]["kernel"] == specs["block0"]["q_proj"][
        "kernel"] != specs["block0"]["proj"]["kernel"]


def test_yarn_matches_the_reference_tables(cfg, ref):
    from horovod_tpu.models.transformer import apply_rope

    rope = cfg["rope_parameters"]["full_attention"]
    yarn = models.Yarn(
        factor=rope["factor"],
        original_max_len=rope["original_max_position_embeddings"],
        beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
        attention_factor=rope["attention_factor"])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
    got = apply_rope(x, jnp.arange(32)[None], base=rope["rope_theta"],
                     yarn=yarn)
    want = ref._rope(x[0], *ref.rope_tables(rope, 16, 32))
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    plain = apply_rope(x, jnp.arange(32)[None], base=rope["rope_theta"])
    assert not np.allclose(plain, got, atol=1e-3)
