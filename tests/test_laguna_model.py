"""A ``laguna`` block (``TransformerLM(layers=...)`` with a head count per
layer, rotary over part of the head, the head-wise output gate, a dense
SwiGLU MLP, routed experts with a scaling factor beside a shared expert)
against the benchmark's plain reference of the family on seeded weights, at
a small size (hidden 64, five layers full + sliding x3 + full with a window
of 8 at T 32, 2 / 4 query heads, 16 experts top-2): logits, loss, every
gradient leaf; each new field alone against its equation; the eight shares
of the deployment add up to the uncut layer; the new scope in forward and
backward; and the programs of the configurations that were there, unchanged.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from horovod_tpu import models, profiler
from horovod_tpu.models.transformer import TransformerBlock, apply_rope

OPT = {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 1e-4}
T = 32


def _tiny(family):
    with open(os.path.join(common.BENCH_DIR, "tests",
                           f"tiny_{family}.json")) as f:
        return dict(json.load(f), compute_dtype="float32")


@pytest.fixture(scope="module")
def cfg():
    return _tiny("laguna")


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "laguna")


@pytest.fixture(scope="module")
def adapter():
    return common.load_module("adapters", "laguna")


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _batch(rows=2, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (rows, T)).astype(np.int32),
            rng.integers(0, 256, (rows, T)).astype(np.int32))


@pytest.mark.parametrize("selection", ["top_k", "forced_uniform"])
def test_logits_loss_and_every_gradient_leaf(cfg, ref, adapter, highest,
                                             selection):
    cfg = dict(cfg, router_selection=selection)
    built = adapter.build(cfg, {"optimizer": OPT})
    weights = ref.make_weights(cfg, common.split_seed(5))
    tokens, targets = _batch()
    want_loss, want = ref.loss_and_grads(cfg, weights, tokens, targets)

    def forward(params):
        return built["model"].apply(
            {"params": params, "batch_stats": built["batch_stats"]}, tokens,
            mutable=["batch_stats"])[0]

    params = built["to_tree"](weights)
    got_logits = forward(params)
    for r in range(tokens.shape[0]):
        np.testing.assert_allclose(
            got_logits[r], ref.logits(weights, tokens[r], r * T, cfg=cfg),
            atol=2e-5)
    got_loss, got = jax.value_and_grad(
        lambda p: built["loss_fn"](forward(p), targets))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got = built["ref_names"](got, list(weights))
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=2e-5 * scale, err_msg=name)


def test_the_model_holds_what_the_configuration_says(cfg, ref, adapter):
    """Two head counts in one model, the dense layer first, a router of
    the whole width over the experts held, one gate column a head."""
    built = adapter.build(cfg, {"optimizer": OPT})
    weights = ref.make_weights(cfg, common.split_seed(1))
    tree = built["to_tree"](weights)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, tree)
    assert shapes["block0"]["q_proj"]["kernel"] == (64, 2 * 16)
    assert shapes["block1"]["q_proj"]["kernel"] == (64, 4 * 16)
    assert shapes["block0"]["gate_proj"]["kernel"] == (64, 2)
    assert shapes["block1"]["gate_proj"]["kernel"] == (64, 4)
    assert shapes["block0"]["mlp_gate"]["kernel"] == (64, 128)
    assert "router" not in shapes["block0"]
    assert shapes["block4"]["router"] == (64, 16)
    assert shapes["block4"]["experts_gate"] == (4, 64, 32)
    assert shapes["block4"]["shared_down"]["kernel"] == (32, 64)
    init = jax.eval_shape(
        lambda: built["model"].init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, T), jnp.int32)))["params"]
    assert (jax.tree_util.tree_map(lambda a: a.shape, init)
            == jax.tree_util.tree_map(lambda a: a.shape, tree))
    assert list(built["batch_stats"]) == ["block1", "block2", "block3",
                                          "block4"]


# ------------------------------------------- each new field, by its equation


def test_rotary_dim_rotates_the_first_features_only():
    """Feature i < r/2 pairs with feature i + r/2 and turns by position x
    base^(-2i/r); the features from r on pass through."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    pos = jnp.arange(3, 9)[None]
    got = np.asarray(apply_rope(x, pos, base=100.0, rotary_dim=8))
    xs, want = np.asarray(x), np.array(x)
    for t, p in enumerate(range(3, 9)):
        for i in range(4):
            angle = p * 100.0 ** (-2 * i / 8)
            a, b = xs[0, t, :, i], xs[0, t, :, i + 4]
            want[0, t, :, i] = a * np.cos(angle) - b * np.sin(angle)
            want[0, t, :, i + 4] = a * np.sin(angle) + b * np.cos(angle)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], xs[..., 8:])
    np.testing.assert_array_equal(apply_rope(x, pos, rotary_dim=16),
                                  apply_rope(x, pos))
    for bad in (7, 18, 0):
        with pytest.raises(ValueError, match="rotary_dim"):
            apply_rope(x, pos, rotary_dim=bad)


def test_yarn_is_reckoned_over_the_rotated_part(cfg, ref):
    """YaRN's correction dims count the rotated features, not the head: the
    program's rotation of the first 8 of 16 features is the reference's
    tables for a head of 8."""
    rope = cfg["rope_parameters"]["full_attention"]
    yarn = models.Yarn(
        factor=rope["factor"], beta_fast=rope["beta_fast"],
        beta_slow=rope["beta_slow"], attention_factor=rope["attention_factor"],
        original_max_len=rope["original_max_position_embeddings"])
    x = jax.random.normal(jax.random.PRNGKey(1), (T, 3, 16))
    got = apply_rope(x[None], jnp.arange(T)[None], base=rope["rope_theta"],
                     yarn=yarn, rotary_dim=8)[0]
    cos, sin = ref.rope_tables(rope, 8, T)
    assert cos.shape == (T, 4)
    np.testing.assert_allclose(got, ref._rope_part(x, cos, sin), atol=1e-5)
    # and they differ from the tables a whole head of 16 would take
    other = ref.rope_tables(rope, 16, T)[0][:, :4]
    assert float(jnp.abs(other - cos).max()) > 1e-3


def _block(layer, **kw):
    return TransformerBlock(**models.TransformerLM(
        vocab=8, dim=64, depth=1, heads=1, layers=(layer,), norm="rmsnorm",
        pos_embedding="rope", dtype=jnp.float32, **kw).block_config(0))


def _run(block, params, x):
    return block.apply({"params": params}, x[None],
                       positions=jnp.arange(x.shape[0])[None])[0]


def _init(block, x, seed=0):
    return block.init(jax.random.PRNGKey(seed), x[None],
                      positions=jnp.arange(x.shape[0])[None])["params"]


def _silent_attention(params):
    return dict(params, proj={"kernel": jnp.zeros_like(
        params["proj"]["kernel"])})


def _rms(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def test_gate_scales_each_head_before_the_output_projection(highest):
    """``x + (a * sigmoid(h Wg)[..., None]) Wo``: with ``Wg`` zero every
    head is halved; with head 0's column far up and the others' far down,
    what is left is the ungated block with the other heads' rows of ``Wo``
    struck."""
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 64))
    plain = _block(models.Layer(heads=4, head_dim=16, kv_heads=1, ffn=1))
    gated = _block(models.Layer(heads=4, head_dim=16, kv_heads=1, ffn=1,
                                gate=True))
    params = _init(gated, x)
    assert params["gate_proj"]["kernel"].shape == (64, 4)
    rest = {k: v for k, v in params.items() if k != "gate_proj"}
    # the MLP off, so that the block is x + attention
    rest["mlp_down"] = jax.tree_util.tree_map(jnp.zeros_like,
                                              rest["mlp_down"])
    attention = _run(plain, rest, x) - x
    zero = dict(rest, gate_proj={"kernel": jnp.zeros((64, 4))})
    np.testing.assert_allclose(_run(gated, zero, x) - x, 0.5 * attention,
                               atol=1e-6)
    # sigmoid(+-1e6 x the sum of h's features), and no token's sum is zero
    h = _rms(x)
    sign = jnp.sign(jnp.sum(h, -1, keepdims=True))
    column = jnp.array([1e6, -1e6, -1e6, -1e6])
    one_head = dict(rest, gate_proj={"kernel": jnp.ones((64, 1)) * column})
    struck = dict(rest, proj={"kernel": rest["proj"]["kernel"].at[16:].set(0)})
    flipped = dict(rest, proj={"kernel": rest["proj"]["kernel"].at[:16].set(0)})
    want = jnp.where(sign > 0, _run(plain, struck, x),
                     _run(plain, flipped, x))
    np.testing.assert_allclose(_run(gated, one_head, x), want, atol=1e-5)


def _swiglu(h, p, prefix):
    k = {part: p[f"{prefix}_{part}"]["kernel"]
         for part in ("gate", "up", "down")}
    return (jax.nn.silu(h @ k["gate"]) * (h @ k["up"])) @ k["down"]


def test_swiglu_ffn_is_three_bias_free_products(highest):
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 64))
    block = _block(models.Layer(heads=2, head_dim=16, kv_heads=1,
                                ffn=models.SwiGLU(96)))
    params = _silent_attention(_init(block, x))
    assert set(params) == {"ln1", "q_proj", "k_proj", "v_proj", "proj",
                           "ln2", "mlp_gate", "mlp_up", "mlp_down"}
    assert params["mlp_gate"]["kernel"].shape == (64, 96)
    assert all(set(params[f"mlp_{p}"]) == {"kernel"}
               for p in ("gate", "up", "down"))
    np.testing.assert_allclose(_run(block, params, x),
                               x + _swiglu(_rms(x), params, "mlp"),
                               atol=1e-5)


def test_shared_expert_and_scale_by_their_equation(highest):
    """``x + scale * routed(h) + shared(h)``: the same parameters through a
    block without the two fields give ``x + routed(h)``."""
    x = jax.random.normal(jax.random.PRNGKey(4), (T, 64))
    experts = dict(routed=8, top_k=2, width=32, first=2, count=4)
    layer = dict(heads=2, head_dim=16, kv_heads=1)
    plain = _block(models.Layer(**layer, ffn=models.Experts(**experts)))
    scaled = _block(models.Layer(**layer, ffn=models.Experts(
        **experts, scale=2.5)))
    both = _block(models.Layer(**layer, ffn=models.Experts(
        **experts, scale=2.5, shared=48)))
    params = _silent_attention(_init(both, x))
    assert params["shared_up"]["kernel"].shape == (64, 48)
    routed_only = {k: v for k, v in params.items()
                   if not k.startswith("shared_")}
    routed = _run(plain, routed_only, x) - x
    assert float(jnp.abs(routed).max()) > 0
    np.testing.assert_allclose(_run(scaled, routed_only, x) - x,
                               2.5 * routed, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        _run(both, params, x) - x,
        2.5 * routed + _swiglu(_rms(x), params, "shared"), rtol=1e-5,
        atol=1e-5)


def test_scale_reaches_the_routed_parameters_gradients(highest):
    """The backward of ``scale``: under a loss linear in the block's output
    the gradients of router and experts are ``scale`` times what a block
    without it gives, and a scale of one leaves the routed sum as it was."""
    x = jax.random.normal(jax.random.PRNGKey(5), (T, 64))
    c = jax.random.normal(jax.random.PRNGKey(6), (T, 64))
    experts = dict(routed=8, top_k=2, width=32, first=2, count=4)
    layer = dict(heads=2, head_dim=16, kv_heads=1)
    plain = _block(models.Layer(**layer, ffn=models.Experts(**experts)))
    scaled = _block(models.Layer(**layer, ffn=models.Experts(
        **experts, scale=2.5)))
    one = _block(models.Layer(**layer, ffn=models.Experts(
        **experts, scale=1.0)))
    params = _silent_attention(_init(plain, x))
    np.testing.assert_array_equal(_run(one, params, x),
                                  _run(plain, params, x))
    g1, g2 = (jax.grad(lambda p, b=b: jnp.sum(_run(b, p, x) * c))(params)
              for b in (plain, scaled))
    for name in ("router", "experts_gate", "experts_up", "experts_down"):
        assert float(jnp.abs(g1[name]).max()) > 0, name
        np.testing.assert_allclose(g2[name], 2.5 * g1[name], rtol=1e-4,
                                   atol=1e-6, err_msg=name)


def test_decode_refuses_the_new_blocks_by_name():
    x = jnp.zeros((1, 4, 64))
    for field, layer in (
            ("rotary_dim=8", models.Layer(heads=2, head_dim=16,
                                          rotary_dim=8)),
            ("gate=head", models.Layer(heads=2, head_dim=16, gate=True)),
            ("ffn=SwiGLU(width=96)", models.Layer(
                heads=2, head_dim=16, ffn=models.SwiGLU(96)))):
        block = _block(layer, decode=True)
        with pytest.raises(NotImplementedError) as err:
            block.init(jax.random.PRNGKey(0), x,
                       positions=jnp.zeros((1, 4), jnp.int32))
        assert field in str(err.value), (field, str(err.value))


@pytest.mark.parametrize("field, leaf", [
    (dict(gate=True), "gate_proj"), (dict(ffn=models.SwiGLU(96)), "mlp_gate")])
def test_param_specs_refuse_a_gate_and_a_swiglu_by_name(field, leaf):
    """No model the function lays out has either: it says so, where the
    name tests would have put ``gate_proj`` with ``proj``."""
    x = jnp.zeros((T, 64))
    block = _block(models.Layer(heads=4, head_dim=16, kv_heads=1, **field))
    with pytest.raises(ValueError, match=leaf):
        models.transformer_param_specs(_init(block, x))


# ---------------------------------------------------- the eight shares add up

SHARES = 8


def _uncut(cfg):
    """The tiny configuration with every head and expert of the layers the
    shares split 8 ways: 8 times a share's heads, all 16 experts."""
    return dict(
        cfg, num_key_value_heads=SHARES * cfg["num_key_value_heads"],
        num_attention_heads_per_layer=[
            SHARES * n for n in cfg["num_attention_heads_per_layer"]],
        num_experts=cfg["num_experts_routed"], first_expert=0)


def _share_of(full, cfg, heads, share):
    """Share ``share`` of 8 of one uncut layer's weights: its query heads
    with their gate columns and their KV head, its 2 of 16 experts; the
    router, the shared expert and the dense MLP whole."""
    hd = cfg["head_dim"]
    q = slice(share * heads * hd, (share + 1) * heads * hd)
    kv = slice(share * hd, (share + 1) * hd)
    held = cfg["num_experts_routed"] // SHARES
    e = slice(share * held, (share + 1) * held)
    mine = dict(full, wq=full["wq"][:, q], wk=full["wk"][:, kv],
                wv=full["wv"][:, kv], wo=full["wo"][q],
                wz=full["wz"][:, share * heads:(share + 1) * heads])
    for k in ("wg", "wu", "wd"):
        if k in full:
            mine[k] = full[k][e]
    return mine


@pytest.mark.parametrize("layer", [0, 1, 4], ids=[
    "full+dense", "sliding+routed", "full+routed"])
def test_the_eight_shares_add_up_to_the_uncut_layer(cfg, ref, adapter, layer,
                                                    highest):
    """Heads split 8 ways each add their part of the attention projection,
    experts split 8 ways their part of the routed sum; the shared expert,
    the dense MLP and the residual, which every chip computes alike, count
    once: the program's parts over shares 0-7 sum to what the reference's
    uncut layer computes."""
    uncut = _uncut(cfg)
    kind, mlp = cfg["layer_types"][layer], cfg["mlp_layer_types"][layer]
    heads = cfg["num_attention_heads_per_layer"][layer]
    full = ref.layer_weights(ref.make_weights(uncut, common.split_seed(3)),
                             layer)
    assert full["wq"].shape == (64, SHARES * heads * 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, cfg["hidden_size"]))
    mm = ref.MATMULS["float32"]
    want_attention = ref._attention_part(x, full, kind, cfg=uncut, mm=mm)
    want = ref._block(x, full, kind, mlp, layer, 0, cfg=uncut, mm=mm)
    once = ("w2", "sd")        # the down projections of what counts once
    ffn_down = ("w2", "sd", "wd")

    def block_of(share, weights):
        share_cfg = dict(
            cfg, first_expert=share * 2, num_experts=2, num_layers=1,
            layer_types=[kind], mlp_layer_types=[mlp],
            num_attention_heads_per_layer=[heads])
        described, = adapter.layers(share_cfg)
        block = TransformerBlock(**models.TransformerLM(
            vocab=8, dim=cfg["hidden_size"], depth=1, heads=1,
            layers=(described,), norm="rmsnorm", pos_embedding="rope",
            dtype=jnp.float32).block_config(0))
        tree = adapter.to_tree(
            {f"l0.{k}": v for k, v in weights.items()})["block0"]
        return lambda x: block.apply({"params": tree}, x[None],
                                     positions=jnp.arange(T)[None])[0]

    def zeroed(weights, names):
        return {k: jnp.zeros_like(v) if k in names else v
                for k, v in weights.items()}

    attention = 0.0
    for share in range(SHARES):
        mine = _share_of(full, cfg, heads, share)
        attention = attention + block_of(
            share, zeroed(mine, ffn_down))(x) - x
    np.testing.assert_allclose(attention, want_attention, atol=2e-6)

    x1 = x + attention
    total = x1
    for share in range(SHARES):
        mine = zeroed(_share_of(full, cfg, heads, share),
                      ("wo",) + (once if share else ()))
        total = total + block_of(share, mine)(x1) - x1
    np.testing.assert_allclose(total, want, atol=5e-6)
    # and every part was there to be counted
    assert float(jnp.abs(want - x1).max()) > 1e-3


# --------------------------------------------------- the scope, both passes


def test_shared_expert_runs_under_its_scope_in_both_passes():
    """``hvd.moe_shared`` is the innermost ``hvd.moe_*`` component of the
    shared expert's products in the forward and in the transposed pass (the
    benchmark's ``moe_shared_ms.train`` keys on it through
    ``profiler.scope_of``), and of none of the routed layer's."""
    import re

    x = jnp.zeros((T, 64))
    block = _block(models.Layer(
        heads=2, head_dim=16, kv_heads=1,
        ffn=models.Experts(routed=8, top_k=2, width=32, count=4, shared=48)))
    params = _init(block, x)

    @jax.named_scope("hvd.forward")
    def loss(p):
        return _run(block, p, x).sum()

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*hvd\.moe_shared[^"]*)"', text))
    scopes = {profiler.scope_of(n) for n in names}
    assert ("forward", "hvd.moe_shared") in scopes
    assert ("backward", "hvd.moe_shared") in scopes
    assert {k for _, k in scopes} == {"hvd.moe_shared"}
    dots = [n for n in names if n.endswith("dot_general")]
    assert any("transpose(" in n for n in dots)
    assert any("transpose(" not in n for n in dots)
    routed = set(re.findall(r'loc\("([^"]*hvd\.moe_(?:route|experts)[^"]*)"',
                            text))
    assert routed and not any("moe_shared" in n for n in routed)


# -------------------------------- what was there traces what it traced before

#: sha256 of the lowered text of loss-and-gradients of the tiny GPT-2 and
#: Mellum2 configurations (float32, two rows of 32 tokens). A change that
#: moves one has changed the program of a cell that was there: mean it,
#: measure the cell, and put the new digest here. ``gpt2``: as the parent
#: of the PR that added the fields above traced it (commit 6ed5b91); it
#: never reaches ``parallel/moe.py``, so a change to the routed layer must
#: leave it as it is. ``mellum``: as PR 38's commit traces it (the
#: experts' MLP as one ``custom_vjp`` of four fused Pallas calls, and the
#: way back's transpose without its ``where``); 2f4c3fc1… before it.
_PROGRAMS = {
    "gpt2": "e2cfb221fac66d092f4cd071e7911798ba978915b9c12246f11b50bdba6bd913",
    "mellum": "737451b6c960e038c4ce4dcedb3e6b753c6e4a76f766439909f0219713c2da1a",
}


@pytest.mark.parametrize("family", sorted(_PROGRAMS))
def test_programs_without_the_new_fields_are_unchanged(family, tmp_path):
    cfg = _tiny(family)
    built = common.load_module("adapters", family).build(
        cfg, {"optimizer": OPT})
    ref = common.load_module("reference", family)
    shapes = jax.eval_shape(lambda: ref.make_weights(
        cfg, (np.int32(0), np.int32(0))))
    params = built["to_tree"](shapes)
    tokens = jax.ShapeDtypeStruct((2, T), jnp.int32)
    stats = built.get("batch_stats", {})

    def loss(p, tokens, targets):
        if stats:
            logits, _ = built["model"].apply(
                {"params": p, "batch_stats": stats}, tokens,
                mutable=["batch_stats"])
        else:
            logits = built["model"].apply({"params": p}, tokens)
        return built["loss_fn"](logits, targets)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, tokens, tokens).as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    if digest != _PROGRAMS[family]:
        # a digest shows nothing: leave the text to diff against the same
        # lowering at the commit that set the pin
        (tmp_path / f"{family}.lowered.txt").write_text(text)
    assert digest == _PROGRAMS[family], (
        f"the lowered text is in {tmp_path}/{family}.lowered.txt")
