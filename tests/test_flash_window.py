"""``flash_attention(causal=True, window=W)``: row i sees column j where
``0 <= i - j < W``. Forward and gradients against dense masked attention,
on the scan path and on the Pallas path in interpret mode, for sequences of
one block and of several, with square and unequal blocks; then the TPU
lowering and Mosaic itself at the shape the benchmark's windowed layers run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (
    _band_blocks,
    flash_attention,
)


def _dense(q, k, v, window):
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _inputs(t, heads=2, kv_heads=1, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, t, heads, d), jnp.float32)
    k = jax.random.normal(ks[1], (2, t, kv_heads, d), jnp.float32)
    v = jax.random.normal(ks[2], (2, t, kv_heads, d), jnp.float32)
    w = jax.random.normal(ks[3], (2, t, heads, d), jnp.float32)
    return q, k, v, w


def _check(flash, t, window, **kw):
    q, k, v, w = _inputs(t, **kw)

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v) * w)

    want = jax.value_and_grad(
        functools.partial(loss, functools.partial(_dense, window=window)),
        argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(
        functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t,window,block_k", [
    (32, 8, None),     # one K/V block
    (64, 8, 16),       # several, most of them before the band for late rows
    (64, 23, 16),      # an edge that crosses blocks off their boundaries
    (64, 1, 16),       # each row sees itself alone
    (64, 64, 16),      # the band is the whole triangle
    (64, 500, 16),
])
def test_window_on_the_scan_path(t, window, block_k):
    flash = functools.partial(flash_attention, causal=True, window=window,
                              use_pallas=False, block_k=block_k)
    _check(flash, t, window)


@pytest.mark.parametrize("t,window,block_q,block_k", [
    (32, 8, None, None),    # one block: the fused backward, masked inside
    (64, 16, 16, 16),       # square blocks: diagonal + one edge block
    (64, 8, 16, 16),        # the band narrower than a block
    (64, 23, 16, 16),       # two blocks the far edge crosses or touches
    (64, 40, 16, 16),       # one block wholly inside the band
    (128, 33, 32, 32),
    (64, 24, 32, 16),       # unequal blocks: per-sub-tile schedule, scan bwd
    (64, 24, 16, 32),
    (64, 64, 16, 16),       # the whole triangle: the unwindowed kernels
])
def test_window_on_the_pallas_path(t, window, block_q, block_k):
    flash = functools.partial(flash_attention, causal=True, window=window,
                              use_pallas=True, interpret=True,
                              block_q=block_q, block_k=block_k)
    _check(flash, t, window)


def test_window_with_sub_tiles_inside_a_block():
    """Blocks of 1024 rows are computed as 512 x 512 sub-tiles: the band's
    edges then cross sub-tiles inside a block (the benchmark's shape: window
    = block = 1024), on the fused backward and on the two-call one."""
    for t in (1024, 2048):
        flash = functools.partial(flash_attention, causal=True, window=600,
                                  use_pallas=True, interpret=True)
        _check(flash, t, 600, heads=1, kv_heads=1, d=8)


def test_window_needs_a_causal_square_problem():
    q, k, v, _ = _inputs(32)
    with pytest.raises(ValueError, match="window needs causal=True"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window needs causal=True"):
        flash_attention(q, k[:, :16], v[:, :16], causal=True, window=8)


def test_band_blocks():
    # window 1024 over blocks of 1024: the diagonal block and the one before
    assert _band_blocks(1024, 1024) == 2
    assert _band_blocks(1025, 1024) == 2
    assert _band_blocks(1026, 1024) == 3
    assert _band_blocks(1, 16) == 1
    assert _band_blocks(2, 16) == 2


# --------------------------------------------------------- lowering, Mosaic

S = jax.ShapeDtypeStruct


def _grad(flash):
    @jax.named_scope("hvd.forward")
    def loss(q, k, v):
        return flash(q, k, v).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))


def test_window_none_traces_what_it_traced():
    """``window=None`` and a window at least the sequence's length trace the
    program of plain causal attention, kernels' bodies, grids and index maps
    included: the GPT-2 cells' kernels are as they were. (The serialized
    Mosaic module in a lowering's text differs from one lowering of one
    program to the next, so the jaxprs are compared.)"""
    q = S((1, 2048, 4, 64), jnp.bfloat16)

    def text(**kw):
        flash = functools.partial(flash_attention, causal=True,
                                  use_pallas=True, **kw)
        return str(jax.make_jaxpr(_grad(flash))(q, q, q))

    plain = text()
    assert "pallas_call" in plain
    assert text(window=None) == plain == text(window=2048)
    assert text(window=1024) != plain


def test_windowed_flash_compiles_for_v5e():
    """The benchmark's windowed layer, [1, 8192, 8 q heads on 1 kv head,
    128], window 1024: Mosaic takes the forward and the two-call backward."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    flash = functools.partial(flash_attention, causal=True, window=1024,
                              use_pallas=True)
    q = S((1, 8192, 8, 128), jnp.bfloat16, sharding=chip)
    kv = S((1, 8192, 1, 128), jnp.bfloat16, sharding=chip)
    text = jax.jit(_grad(flash)).lower(q, kv, kv).compile().as_text()
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 3
    assert sum("/hvd.flash_bwd/pallas_call" in l for l in calls) == 2
    assert " while(" not in text
