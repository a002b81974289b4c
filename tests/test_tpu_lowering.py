"""Cross-lower every Pallas kernel for the TPU platform, on the CPU.

Tier-1 runs the kernels with ``interpret=True``, which never meets the
Pallas TPU lowering's tiling rules: six of eight kernels once passed
every CPU test while ``HOROVOD_PALLAS=auto`` armed them on a chip where
they could not even be traced. ``jit(f).trace(...).lower(
lowering_platforms=("tpu",))`` applies those rules without a chip, so
the next refusal shows here. Mosaic itself only runs on the chip —
``chip_smoke.py``'s kernel phase covers that half.
"""

import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.compression import INT8_BLOCK
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.ops.flash_attention import flash_attention

S = jax.ShapeDtypeStruct
L = 1 << 20
N = 4
F32 = functools.partial(S, dtype=jnp.float32)


def _lower_for_tpu(fn, *shapes):
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """Kernels armed and NOT interpreted, as on a TPU backend."""
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    monkeypatch.setattr(pk, "interpret", lambda: False)


_WIRE = (S((N, L // N), jnp.int8), S((N, L // N // INT8_BLOCK), jnp.bfloat16))

_KERNELS = {
    "quantize_blockwise": (
        lambda x: pk.quantize_blockwise(x, INT8_BLOCK), F32((L,))),
    "quantize_roundtrip": (
        lambda x: pk.quantize_roundtrip(x, INT8_BLOCK), F32((L,))),
    "quantize_one_block": (
        lambda x: pk.quantize_blockwise(x, INT8_BLOCK), F32((INT8_BLOCK,))),
    "dequant_accumulate": (
        lambda q, s: pk.dequant_accumulate(q, s, jnp.float32, INT8_BLOCK),
        *_WIRE),
    "dequant_accumulate_requantize": (
        lambda q, s: pk.dequant_accumulate_requantize(
            q, s, jnp.float32, INT8_BLOCK, divisor=N), *_WIRE),
    "dequantize_rows": (
        lambda q, s: pk.dequantize_rows(q, s, jnp.float32, INT8_BLOCK),
        *_WIRE),
    "adasum_pair_combine": (pk.adasum_pair_combine, F32((L,)), F32((L,))),
    "adasum_pair_combine_bf16_odd": (
        pk.adasum_pair_combine, S((40, 30), jnp.bfloat16),
        S((40, 30), jnp.bfloat16)),
    "adasum_segment_combine": (
        lambda a, b, s: pk.adasum_segment_combine(a, b, s, 5),
        F32((L,)), F32((L,)), S((L,), jnp.int32)),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_lowers_for_tpu(compiled_kernels, name):
    fn, *shapes = _KERNELS[name]
    _lower_for_tpu(fn, *shapes)


#: the ``hvd_<kernel>`` names each entry's Mosaic calls must carry, in
#: issue order (``pallas_call(name=)``: the device trace's kernel scopes)
_KERNEL_NAMES = {
    "quantize_blockwise": ["hvd_quantize"],
    "quantize_roundtrip": ["hvd_quantize_roundtrip"],
    "quantize_one_block": ["hvd_quantize"],
    "dequant_accumulate": ["hvd_dequant_accumulate"],
    "dequant_accumulate_requantize": ["hvd_dequant_accumulate_requantize"],
    "dequantize_rows": ["hvd_dequantize_rows"],
    "adasum_pair_combine": [
        "hvd_adasum_pair_reduce", "hvd_adasum_pair_blend"],
    "adasum_pair_combine_bf16_odd": [
        "hvd_adasum_pair_reduce", "hvd_adasum_pair_blend"],
    "adasum_segment_combine": [
        "hvd_adasum_segment_reduce", "hvd_adasum_segment_blend"],
    "fused_adam": ["hvd_fused_adam"],
    "flash_attention": ["hvd_flash_fwd"],
}


@pytest.mark.parametrize("name", sorted(_KERNEL_NAMES))
def test_tpu_custom_call_carries_its_kernel_name(compiled_kernels, name):
    """Every cross-lowered ``tpu_custom_call`` names its kernel
    ``hvd_<kernel>``, as the Mosaic ``kernel_name`` and as the innermost
    scope of its location: what ``profiler.scope_of`` reads as the
    kernel."""
    import re

    if name == "fused_adam":
        from horovod_tpu.optim import fused_adam

        fn, shapes = fused_adam(1e-3).update, (F32((L,)), jax.eval_shape(
            optax.adam(1e-3).init, F32((L,))))
    elif name == "flash_attention":
        fn = functools.partial(flash_attention, causal=True, use_pallas=True)
        shapes = (S((2, 512, 4, 64), jnp.bfloat16),) * 3
    else:
        fn, *shapes = _KERNELS[name]
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    want = _KERNEL_NAMES[name]
    assert text.count("stablehlo.custom_call @tpu_custom_call") == len(want)
    assert re.findall(r'kernel_name = "([^"]*)"', text) == want
    for kernel in want:
        assert re.search(r'loc\("[^"]*%s/pallas_call"' % kernel, text), kernel


@pytest.mark.parametrize("shape", [(L,), (30,), (N, L // N)])
def test_fused_adam_lowers_for_tpu(compiled_kernels, shape):
    """Plain, tiny-leaf, and the vmapped ``[N, shard]`` form
    ``optim._zero_update`` applies — whose step count, and therefore the
    bias-correction operand, is batched too."""
    from horovod_tpu.optim import fused_adam

    fa, ref = fused_adam(1e-3), optax.adam(1e-3)
    g = F32(shape)
    if len(shape) == 2:
        _lower_for_tpu(jax.vmap(fa.update), g,
                       jax.eval_shape(jax.vmap(ref.init), g))
    else:
        _lower_for_tpu(fa.update, g, jax.eval_shape(ref.init, g))


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim", [
    (2, 512, 12, 12, 64),    # TransformerSmall
    (2, 512, 4, 4, 128),
    (2, 512, 8, 2, 128),     # GQA
    (2, 512, 4, 1, 64),      # MQA
    (8, 1024, 16, 16, 64),   # the benchmark's GPT-2 medium cell
    (1, 4096, 4, 4, 128),    # several blocks a row: all three block kinds
])
def test_flash_attention_lowers_for_tpu(batch, seq, heads, kv_heads,
                                        head_dim):
    """The shape-chosen tile passes the Pallas TPU lowering, and the one
    custom call a forward makes is still ``hvd_flash_fwd`` with results
    ``(bf16[B*H, T, D], f32[B*H, T, 1])``: what the benchmark's
    ``flash_fwd_roofline.train`` finds it by."""
    import re

    flash = functools.partial(flash_attention, causal=True, use_pallas=True)
    q = S((batch, seq, heads, head_dim), jnp.bfloat16)
    kv = S((batch, seq, kv_heads, head_dim), jnp.bfloat16)
    text = jax.jit(flash).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [l for l in text.splitlines() if "@tpu_custom_call" in l]
    assert len(calls) == 1
    assert re.findall(r'kernel_name = "([^"]*)"', text) == ["hvd_flash_fwd"]
    rows = batch * heads
    assert (f"-> (tensor<{rows}x{seq}x{head_dim}xbf16>, "
            f"tensor<{rows}x{seq}x1xf32>)") in calls[0]


def _flash_grad(flash):
    @jax.named_scope("hvd.forward")
    def loss(q, k, v):
        return flash(q, k, v).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim,kernels", [
    # the benchmark's GPT-2 medium cell: one block a sequence, one call
    (8, 1024, 16, 16, 64, ["hvd_flash_bwd"]),
    (2, 1024, 8, 2, 128, ["hvd_flash_bwd"]),
    (1, 4096, 4, 4, 128, ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]),
    (1, 2048, 4, 1, 64, ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]),
])
def test_flash_backward_lowers_for_tpu_under_its_scope(
        batch, seq, heads, kv_heads, head_dim, kernels):
    """``jax.grad`` of ``flash_attention`` passes the Pallas TPU lowering
    with the backward as Mosaic calls named after the kernel functions.
    The calls carry no ``name=``, so no scope of their own sits inside
    ``hvd.flash_bwd``: ``profiler.scope_of`` keys their device time
    ``hvd.flash_bwd``, the scope the benchmark's ``flash_bwd_roofline.train``
    divides by (a ``pallas_call(name="hvd_flash_bwd")`` would be keyed
    ``hvd_flash_bwd`` and leave that reader the glue alone)."""
    import re

    from horovod_tpu import profiler

    flash = functools.partial(flash_attention, causal=True, use_pallas=True)
    q = S((batch, seq, heads, head_dim), jnp.bfloat16)
    kv = S((batch, seq, kv_heads, head_dim), jnp.bfloat16)
    text = jax.jit(_flash_grad(flash)).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [
        "hvd_flash_fwd"] + kernels
    assert "stablehlo.while" not in text
    op_names = re.findall(r'loc\("([^"]*/pallas_call)"', text)
    backward = {n for n in op_names if "hvd_flash_fwd" not in n}
    assert len(backward) == 1 and len(op_names) > 1, op_names
    for op_name in backward:
        assert op_name.endswith("/hvd.flash_bwd/pallas_call"), op_name
        assert profiler.scope_of(op_name, "custom-call") == (
            "backward", "hvd.flash_bwd")


def test_jit_train_step_runs_flash_attention_per_batch_shard(hvd):
    """A pallas_call is opaque to the SPMD partitioner. The global-jit
    builder owns the batch-sharded layout, so it runs the model's
    attention per batch shard: no all-gather around the kernel, and the
    kernel's operands hold one chip's rows."""
    import numpy as np

    from horovod_tpu.models import TransformerTiny
    from horovod_tpu.training import (
        make_jit_train_step, replicate, shard_batch, token_xent)

    n, seq = hvd.size(), 128
    tiny = functools.partial(TransformerTiny, vocab=64, depth=1, heads=2,
                             max_len=seq)
    tokens = np.random.RandomState(0).randint(0, 64, (n, seq), np.int32)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = jax.jit(tiny().init)(jax.random.PRNGKey(0), tokens[:1])["params"]
    losses = {}
    for name, kw in (("flash", dict(attention_fn=functools.partial(
            flash_attention, use_pallas=True, interpret=True))),
                     ("dense", {})):
        step = make_jit_train_step(tiny(dtype=jnp.float32, **kw), tx,
                                   loss_fn=token_xent, instrument=False,
                                   donate=False)
        args = (replicate(params), {}, replicate(tx.init(params)),
                shard_batch(tokens), shard_batch(np.roll(tokens, -1, 1)))
        compiled = step.lower(*args).compile()
        hlo = compiled.as_text()
        assert "all-reduce" in hlo and "all-gather" not in hlo, name
        losses[name] = float(compiled(*args)[3])
    assert abs(losses["flash"] - losses["dense"]) < 1e-4 * losses["dense"]

    # the same step lowered for TPU: the Mosaic call takes [1 x heads, T, D]
    model = tiny(attention_fn=functools.partial(
        flash_attention, use_pallas=True, interpret=False))
    step = make_jit_train_step(model, tx, loss_fn=token_xent,
                               instrument=False)
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    heads, hd = model.heads, model.dim // model.heads
    assert calls and all(
        f"tensor<{heads}x{seq}x{hd}xbf16>" in l for l in calls)


def test_flash_attention_leaves_placement_to_its_caller(hvd):
    """The kernel does not consult hvd state: q/k/v committed to ONE device
    of the initialised 8-device mesh (single-chip eval on a multi-chip
    host) run there, eagerly and under jit, whatever the batch divides."""
    import numpy as np

    flash = functools.partial(flash_attention, causal=True, use_pallas=True,
                              interpret=True)
    dev = jax.devices()[3]
    x = jax.device_put(jnp.asarray(
        np.random.RandomState(0).randn(hvd.size(), 128, 2, 64),
        jnp.bfloat16), dev)
    assert "shard_map" not in str(jax.make_jaxpr(flash)(x, x, x))
    for f in (flash, jax.jit(flash)):
        out = f(x, x, x)
        assert out.shape == x.shape and out.devices() == {dev}


def test_flash_attention_rejects_blocks_below_the_tile():
    """No multiple of 8 divides T = 1028 = 4 * 257, and the sequence is
    too long for one block — an error here, not a kernel Mosaic refuses.
    (T = 204 runs as one 204-row block.)"""
    x = S((1, 204, 2, 64), jnp.bfloat16)
    jax.eval_shape(functools.partial(flash_attention, use_pallas=True),
                   x, x, x)
    x = S((1, 1028, 2, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="below the 8-row TPU tile"):
        jax.eval_shape(
            functools.partial(flash_attention, use_pallas=True), x, x, x)


# ----------------------------------------- Mosaic itself, for a described chip


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four-chip v5e host, described and not attached: the TPU's own
    compiler runs for it here."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_2x2):
    """One chip of it: Mosaic runs for that chip, so a tile that overflows
    the scoped VMEM or a slice off the tiling fails in tier-1, not on the
    chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim,dtype", [
    (8, 1024, 16, 16, 64, "bfloat16"),   # the benchmark's GPT-2 medium cell
    (2, 2048, 4, 2, 128, "bfloat16"),    # chip_smoke's GQA head, 2 x 2 blocks
    (1, 2048, 2, 2, 256, "float32"),     # the widest operands: a shrunk block
    (1, 8192, 1, 1, 256, "bfloat16"),    # qwen3next_train_1chip's full layer
])
def test_flash_forward_compiles_for_v5e(one_v5e_chip, batch, seq, heads,
                                        kv_heads, head_dim, dtype):
    flash = functools.partial(flash_attention, causal=True, use_pallas=True)
    q = S((batch, seq, heads, head_dim), dtype, sharding=one_v5e_chip)
    kv = S((batch, seq, kv_heads, head_dim), dtype, sharding=one_v5e_chip)
    compiled = jax.jit(flash).lower(q, kv, kv).compile()
    assert "hvd_flash_fwd" in compiled.as_text()


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim,dtype,kernels", [
    # the benchmark's GPT-2 medium cell: the fused call
    (8, 1024, 16, 16, 64, "bfloat16", ["hvd_flash_bwd"]),
    # chip_smoke's GQA head: two blocks, the dk/dv and dq calls
    (2, 2048, 4, 2, 128, "bfloat16",
     ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]),
    (2, 1024, 4, 1, 128, "float32", ["hvd_flash_bwd"]),
    # the widest operands: a shrunk block
    (1, 2048, 2, 2, 256, "float32",
     ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]),
    # qwen3next_train_1chip's full layer: D 256, eight blocks a row
    (1, 8192, 1, 1, 256, "bfloat16",
     ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]),
])
def test_flash_backward_compiles_for_v5e(one_v5e_chip, batch, seq, heads,
                                         kv_heads, head_dim, dtype, kernels):
    """Mosaic takes the backward kernels at their shape-chosen tile: a tile
    over the VMEM limit the calls ask for fails here, not on the chip."""
    flash = functools.partial(flash_attention, causal=True, use_pallas=True)
    q = S((batch, seq, heads, head_dim), dtype, sharding=one_v5e_chip)
    kv = S((batch, seq, kv_heads, head_dim), dtype, sharding=one_v5e_chip)
    text = jax.jit(_flash_grad(flash)).lower(q, kv, kv).compile().as_text()
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1 + len(kernels)
    assert sum("/hvd.flash_bwd/pallas_call" in l for l in calls) == len(
        kernels)
    assert " while(" not in text


# ------------------------------------- what the token loss keeps, on the chip


@pytest.mark.parametrize("dtype,wide_f32,temp_gb", [
    ("bfloat16", 0, 1.0),   # the GPT-2 cells' head: 0.824 GB (2.472 before)
    ("float32", 1, 1.7),    # an f32 model: the logits themselves, 1.648 GB
])
def test_token_xent_keeps_no_float32_copy_of_the_logits(one_v5e_chip, dtype,
                                                        wide_f32, temp_gb):
    """Final LayerNorm + the ``[1024, 50257]`` head + ``token_xent`` under
    ``value_and_grad`` at the GPT-2 cells' ``[8, 1024]`` batch, compiled for
    the chip: beside the logits in the head's own dtype and dW, nothing as
    wide as the vocabulary is written to HBM. Through ``log_softmax`` the
    compiled step wrote an ``f32[8,1024,50257]`` of log-probabilities
    (1.65 GB, 3.8 ms a step; PERF.md section 6, PR 34): an upcast in the
    model's tail or a loss that autodiff differentiates brings it back."""
    import flax.linen as nn

    from horovod_tpu.training import token_xent

    norm = nn.LayerNorm(dtype=dtype)
    head = nn.Dense(50257, use_bias=False, dtype=dtype)

    def loss(x, norm_params, kernel, targets):
        h = norm.apply({"params": norm_params}, x)
        return token_xent(
            head.apply({"params": {"kernel": kernel}}, h), targets)

    def shape(dims, dt):
        return S(dims, dt, sharding=one_v5e_chip)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape((8, 1024, 1024), dtype),
        {"scale": shape((1024,), "float32"),
         "bias": shape((1024,), "float32")},
        shape((1024, 50257), "float32"), shape((8, 1024), "int32")).compile()
    text = compiled.as_text()
    # each ENTRY instruction's result type(s), tuple reads left out
    results = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) (?!get-tuple-element)[\w\-]+\(",
        text[text.index("\nENTRY "):], re.M)]
    assert sum("f32[8,1024,50257]" in r for r in results) == wide_f32, [
        r for r in results if "50257]" in r]
    assert sum("[8,1024,50257]" in r for r in results) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9


# -------------------------- the routed layer's way back to the tokens, on the chip


@pytest.mark.parametrize("tokens,top_k,count,dim,dtype", [
    (8192, 8, 16, 2304, "bfloat16"),   # mellum2_train_1chip
    (8192, 8, 32, 2048, "bfloat16"),   # laguna_train_1chip: 8 rows a window
    (2048, 8, 16, 2304, "bfloat16"),   # the ladder's other shape
    (600, 2, 3, 256, "float32"),       # a count no tile divides, exact f32
    (8192, 10, 32, 2048, "bfloat16"),  # qwen3next_train_1chip: 5 rows
    (4096, 22, 8, 1024, "bfloat16"),   # nemotron3super_train_1chip: 11
])
def test_moe_way_back_compiles_for_v5e(one_v5e_chip, tokens, top_k, count,
                                       dim, dtype):
    """Mosaic takes the kernel that sums a token's rows of the sorted
    buffer: its window copies start on the dtype's tiling, its scratch
    fits the VMEM it asks for, and no gather is left beside it. The
    windows are the rule's: 64 rows, 16 a product, at Mellum2's 32 rows a
    token tile on an expert; 32 rows, all 32 held experts' in one product,
    at Laguna's 8 and Qwen3-Next's 5."""
    from horovod_tpu.observability import metrics
    from horovod_tpu.parallel import moe

    # the router's width, and the window and windows a product the rule
    # gives there
    routed, window, windows = {(8, 16): (64, 64, 16), (8, 32): (256, 32, 32),
                               (10, 32): (512, 32, 32), (2, 3): (8, 64, 3),
                               (22, 8): (512, 32, 8)}[top_k, count]
    assert moe._combine_tile(top_k, routed, count,
                             32 // jnp.dtype(dtype).itemsize) == (window,
                                                                  windows)
    metrics.REGISTRY.reset()
    rows = moe.buffer_rows(tokens, top_k, count)
    tiles = -(-tokens // moe.TOKEN_TILE)
    ints = functools.partial(S, dtype=jnp.int32, sharding=one_v5e_chip)
    plan = {"slot_of_row": ints((rows,)),
             "row_of_slot": ints((tokens * top_k,)),
             "seg_start": ints((tiles, count)),
             "seg_rows": ints((tiles, count))}
    y = S((rows, dim), dtype, sharding=one_v5e_chip)
    text = jax.jit(
        lambda y, plan: moe._to_tokens(y, plan, top_k, routed, False)
    ).lower(y, plan).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " gather(" not in text and " while(" not in text
    assert metrics.value("moe_combine_tile", dim="rows") == window
    assert metrics.value("moe_combine_tile", dim="windows") == windows


@pytest.mark.parametrize("tokens,top_k,count,dim,width", [
    (8192, 8, 16, 2304, 896),          # mellum2_train_1chip
    (8192, 8, 32, 2048, 512),          # laguna_train_1chip
    (8192, 10, 32, 2048, 512),         # qwen3next_train_1chip: 5.7 % in use
])
def test_moe_expert_mlp_compiles_for_v5e(one_v5e_chip, tokens, top_k, count,
                                         dim, width):
    """Mosaic takes the fused calls of the experts' MLP at both routed
    cells' shapes, forward and backward: an expert's three matrices
    beside the tiles, and both of the gate's and the up's float32
    gradients whole, fit the VMEM asked for, a row's weight comes in and
    its gradient goes out as rows of 256 lanes, and no XLA operation is
    left over a ``[rows, D]`` or ``[rows, F]`` array but the casts of the
    cotangent this test hands in."""
    from horovod_tpu.parallel import moe

    assert moe._experts_fit(dim, width, 2)
    rows = moe.buffer_rows(tokens, top_k, count)
    on_chip = functools.partial(S, sharding=one_v5e_chip)
    args = (on_chip((rows, dim), jnp.bfloat16), on_chip((rows,), jnp.float32),
            on_chip((count, dim, width), jnp.float32),
            on_chip((count, dim, width), jnp.float32),
            on_chip((count, width, dim), jnp.float32),
            on_chip((rows // moe.TILE_ROWS,), jnp.int32),
            on_chip((1,), jnp.int32))

    def both(*a):
        ys, back = jax.vjp(lambda *d: moe.expert_mlp(*d, *a[5:], False),
                           *a[:5])
        return ys, back(ys)

    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    wide = [l for l in entry.splitlines()
            if re.search(r"= \(?bf16\[%d,(%d|%d)\]" % (rows, dim, width), l)
            and "custom-call" not in l and "parameter(" not in l
            and "get-tuple-element" not in l and " tuple(" not in l]
    assert not wide, wide


def test_moe_relu2_mlp_compiles_for_v5e(one_v5e_chip):
    """Mosaic takes the relu² experts' calls at
    ``nemotron3super_train_1chip``'s shapes (experts of ``[1024, 2688]`` in
    the latent, 8 held, 22 of 512 chosen over 4,096 tokens: the buffer
    bounded at 8 rows a token), forward and backward: both matrices beside
    the tiles fit the VMEM asked for, and no XLA operation is left over a
    ``[rows, D]`` or ``[rows, F]`` array but the casts of the cotangent
    this test hands in."""
    from horovod_tpu.parallel import moe

    tokens, top_k, count, dim, width = 4096, 22, 8, 1024, 2688
    assert moe._relu2_fit(dim, width, 2)
    rows = moe.buffer_rows(tokens, top_k, count)
    assert rows == 34816
    on_chip = functools.partial(S, sharding=one_v5e_chip)
    args = (on_chip((rows, dim), jnp.bfloat16), on_chip((rows,), jnp.float32),
            on_chip((count, dim, width), jnp.float32),
            on_chip((count, width, dim), jnp.float32),
            on_chip((rows // moe.TILE_ROWS,), jnp.int32),
            on_chip((1,), jnp.int32))

    def both(*a):
        ys, back = jax.vjp(
            lambda *d: moe.expert_relu2_mlp(*d, *a[4:], False), *a[:4])
        return ys, back(ys)

    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    # the copies the compiler starts between memory spaces move the
    # forward's result that this test hands back as its cotangent
    wide = [l for l in entry.splitlines()
            if re.search(r"= \(?bf16\[%d,(%d|%d)\]" % (rows, dim, width), l)
            and "custom-call" not in l and "parameter(" not in l
            and "get-tuple-element" not in l and " tuple(" not in l
            and " copy-start(" not in l and " copy-done(" not in l]
    assert not wide, wide


def test_mamba2_recurrence_compiles_for_v5e(one_v5e_chip):
    """The chunked recurrence at ``nemotron3super_train_1chip``'s shapes
    (one row of 4,096 tokens, 16 heads of 64 on one group of state 128,
    chunks of 128), forward and every gradient: batched products over the
    32 chunks, no ``while``, under 0.5 GB of temporaries."""
    from horovod_tpu.ops import mamba2

    on_chip = functools.partial(S, dtype=jnp.float32, sharding=one_v5e_chip)
    args = (on_chip((1, 4096, 16, 64)), on_chip((1, 4096, 16)),
            on_chip((16,)), on_chip((1, 4096, 1, 128)),
            on_chip((1, 4096, 1, 128)))

    def both(*a):
        y, back = jax.vjp(functools.partial(mamba2.ssd, chunk=128), *a)
        return y, back(y)

    compiled = jax.jit(both).lower(*args).compile()
    assert " while(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 5e8


# ------------------------------- the gated delta rule, on the chip


def test_gated_delta_rule_compiles_for_v5e(one_v5e_chip):
    """The chunked rule at ``qwen3next_train_1chip``'s shapes (one row of
    8,192 tokens, two value heads of 128 on key heads of 128), forward and
    every gradient: the triangular solve and the scan over 128 chunks are
    taken by the TPU's compiler, and the whole holds under 0.1 GB of
    temporaries."""
    from horovod_tpu.ops import gated_delta

    on_chip = functools.partial(S, dtype=jnp.float32, sharding=one_v5e_chip)
    qk, g = on_chip((1, 8192, 2, 128)), on_chip((1, 8192, 2))

    def both(*a):
        o, back = jax.vjp(gated_delta.gated_delta_rule, *a)
        return o, back(o)

    compiled = jax.jit(both).lower(qk, qk, qk, g, g).compile()
    assert compiled.as_text().count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


# ------------------------------- the staged backward, after the TPU's compiler


def _entry_schedule(text):
    """The ENTRY computation's all-reduces (``all-reduce[-start|-done]``)
    and matmuls (``matmul``: a dot or convolution, alone or inside the
    fusion that holds it) in scheduled order."""
    bodies = dict(re.findall(r"^%(\S+) \(.*?\{\n(.*?)^\}", text,
                             re.M | re.S))
    holds_matmul = {name for name, body in bodies.items()
                    if re.search(r" (dot|convolution)\(", body)}
    kinds = []
    for line in text[text.index("\nENTRY "):].splitlines()[1:]:
        if (m := re.search(r" (all-reduce(?:-start|-done)?)\(", line)):
            kinds.append(m.group(1))
        elif re.search(r" (dot|convolution)\(", line) or any(
                c in holds_matmul
                for c in re.findall(r"calls=%([\w.\-]+)", line)):
            kinds.append("matmul")
    return kinds


def test_sync_hook_order_does_not_survive_the_tpu_compiler(hvd, v5e_2x2):
    """``ops.overlap.sync_hook`` threads an ``optimization_barrier`` token so
    each block's gradient all-reduce is issued inside the backward pass;
    the jaxpr order and the equal gradients are pinned in
    ``test_overlap.py``. This pins what the described 2x2's compiler makes
    of it: the three all-reduces combined into ONE synchronous tuple
    ``all-reduce`` (no ``-start`` / ``-done`` pair) scheduled after the
    last matmul of the backward, as in the monolithic step, and no
    ``opt-barrier`` left. The same at
    widths 1024 and 2048 (48 MB of gradients; PERF.md section 6). When a
    change of flags or of the hook makes this fail, the compiler has
    started to keep the interleaving: that is ROADMAP S8's news, pin it."""
    import types

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from test_overlap import _hooked_and_mono_steps

    axis = hvd.data_axis()
    mesh = Mesh(np.array(v5e_2x2.devices), (axis,))
    described = types.SimpleNamespace(mesh=lambda: mesh,
                                      data_axis=lambda: axis)
    hooked, mono, ws, x = _hooked_and_mono_steps(described)
    ws = [S(w.shape, w.dtype, sharding=NamedSharding(mesh, P()))
          for w in ws]
    x = S(x.shape, x.dtype, sharding=NamedSharding(mesh, P(axis)))
    for step in (hooked, mono):
        text = jax.jit(step).lower(ws, x).compile().as_text()
        kinds = _entry_schedule(text)
        assert kinds.count("matmul") >= 2 * len(ws), kinds
        assert [k for k in kinds if k.startswith("all-reduce")] == [
            "all-reduce"], kinds
        assert "matmul" not in kinds[kinds.index("all-reduce"):], kinds
        assert "opt-barrier" not in text
        reduced = re.search(r"= \((.*?)\) all-reduce\(", text).group(1)
        assert reduced.count("f32[") == len(ws), reduced
