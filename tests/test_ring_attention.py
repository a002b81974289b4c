"""Long-context stack tests: flash attention (scan + pallas-interpret paths)
and sequence-parallel ring/Ulysses attention on the 8-device CPU mesh,
validated against dense reference attention."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

try:
    from jax import shard_map as shard_map_fn
except ImportError:  # pragma: no cover - older jax
    from jax.experimental.shard_map import shard_map as shard_map_fn

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import (
    SEQUENCE_AXIS, build_mesh, ring_attention, ulysses_attention,
)


def dense_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = np.tril(np.ones((t_q, t_k), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(b=2, t=64, h=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(b, t, h, d).astype(np.float32)
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_scan_matches_dense(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal, use_pallas=False,
                          block_k=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_pallas_interpret_matches_dense(causal):
    q, k, v = qkv(b=1, t=32, h=2, d=8)
    out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                          interpret=True, block_q=16, block_k=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_dense():
    q, k, v = qkv(b=1, t=32, h=2, d=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                use_pallas=False, block_k=8) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _seq_sharded(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P(None, SEQUENCE_AXIS)))


def _run_sp(fn, mesh, q, k, v):
    spec = P(None, SEQUENCE_AXIS, None, None)
    wrapped = shard_map_fn(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sh = NamedSharding(mesh, spec)
    return jax.jit(wrapped)(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = build_mesh({SEQUENCE_AXIS: 8})
    q, k, v = qkv(b=2, t=64, h=2, d=16)
    out = _run_sp(
        functools.partial(ring_attention, causal=causal, block_k=8),
        mesh, q, k, v,
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grad_matches_dense():
    mesh = build_mesh({SEQUENCE_AXIS: 4}, devices=jax.devices()[:4])
    q, k, v = qkv(b=1, t=32, h=2, d=8, seed=3)
    spec = P(None, SEQUENCE_AXIS, None, None)
    sh = NamedSharding(mesh, spec)

    ring = shard_map_fn(
        functools.partial(ring_attention, causal=True, block_k=8),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )

    def loss_ring(q, k, v):
        return (ring(q, k, v) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh))
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    mesh = build_mesh({SEQUENCE_AXIS: 4}, devices=jax.devices()[:4])
    q, k, v = qkv(b=2, t=32, h=4, d=8, seed=1)  # heads divisible by 4
    out = _run_sp(
        functools.partial(
            ulysses_attention, causal=causal,
            attention_fn=functools.partial(flash_attention,
                                           use_pallas=False)),
        mesh, q, k, v,
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_heads_not_divisible_raises():
    mesh = build_mesh({SEQUENCE_AXIS: 8})
    q, k, v = qkv(b=1, t=32, h=3, d=8)
    with pytest.raises(Exception, match="divisible"):
        _run_sp(ulysses_attention, mesh, q, k, v)


def test_ring_attention_long_context_many_blocks():
    # more k-blocks per shard than one: exercises the inner scan x ring loop
    mesh = build_mesh({SEQUENCE_AXIS: 8})
    q, k, v = qkv(b=1, t=128, h=2, d=8, seed=2)
    out = _run_sp(
        functools.partial(ring_attention, causal=True, block_k=4),
        mesh, q, k, v,
    )
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_pallas_grad_matches_dense():
    # pallas forward (interpret) supplies lse for the blockwise backward
    q, k, v = qkv(b=1, t=32, h=2, d=8, seed=4)

    def loss_pallas(q, k, v):
        return (flash_attention(q, k, v, causal=True, use_pallas=True,
                                interpret=True, block_q=16,
                                block_k=16) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- zigzag ring


from horovod_tpu.parallel import zigzag_permutation, zigzag_ring_attention


def test_zigzag_permutation_layout():
    perm = zigzag_permutation(16, 4)
    # device 0 holds chunks 0 and 7, device 1 chunks 1 and 6, ...
    assert perm.tolist() == [
        0, 1, 14, 15, 2, 3, 12, 13, 4, 5, 10, 11, 6, 7, 8, 9
    ]
    assert sorted(perm.tolist()) == list(range(16))
    with pytest.raises(ValueError, match="divisible"):
        zigzag_permutation(12, 8)


@pytest.mark.parametrize("n,t", [(4, 64), (8, 64), (2, 32)])
def test_zigzag_ring_attention_matches_dense(n, t):
    mesh = build_mesh({SEQUENCE_AXIS: n}, devices=jax.devices()[:n])
    q, k, v = qkv(b=2, t=t, h=2, d=16, seed=5)
    perm = zigzag_permutation(t, n)
    inv = np.argsort(perm)
    out_zz = _run_sp(
        functools.partial(zigzag_ring_attention, block_k=8),
        mesh, q[:, perm], k[:, perm], v[:, perm],
    )
    out = np.asarray(out_zz)[:, inv]
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_zigzag_ring_attention_grad_matches_dense():
    n, t = 4, 32
    mesh = build_mesh({SEQUENCE_AXIS: n}, devices=jax.devices()[:n])
    q, k, v = qkv(b=1, t=t, h=2, d=8, seed=7)
    perm = zigzag_permutation(t, n)
    inv = np.argsort(perm)
    spec = P(None, SEQUENCE_AXIS, None, None)
    sh = NamedSharding(mesh, spec)

    zz = shard_map_fn(
        functools.partial(zigzag_ring_attention, block_k=8),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )

    def loss_zz(qp, kp, vp):
        return (zz(qp, kp, vp) ** 2).sum()  # sum is permutation-invariant

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(
        jax.device_put(q[:, perm], sh), jax.device_put(k[:, perm], sh),
        jax.device_put(v[:, perm], sh))
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a)[:, inv], np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_zigzag_rejects_odd_local_length():
    mesh = build_mesh({SEQUENCE_AXIS: 8})
    q, k, v = qkv(b=1, t=8, h=2, d=8)  # local length 1 per device
    with pytest.raises(Exception, match="2\\*Tc|odd local"):
        _run_sp(
            functools.partial(zigzag_ring_attention, block_k=8),
            mesh, q, k, v,
        )


# -------------------------------------------------------------------- GQA


def test_flash_attention_gqa_matches_repeated_dense():
    """Grouped-query attention: H_kv < H kv heads broadcast over query
    groups; result must equal dense attention with explicitly repeated
    heads, and gradients must flow."""
    rng = np.random.RandomState(9)
    b, t, h, h_kv, d = 2, 32, 8, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, use_pallas=False, block_k=8)
    ref = dense_attention(
        q, jnp.repeat(k, h // h_kv, axis=2), jnp.repeat(v, h // h_kv, axis=2),
        causal=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # mqa (single kv head) + gradient flow
    k1 = jnp.asarray(rng.randn(b, t, 1, d).astype(np.float32))
    v1 = jnp.asarray(rng.randn(b, t, 1, d).astype(np.float32))
    g = jax.grad(
        lambda kk: (flash_attention(q, kk, v1, causal=False,
                                    use_pallas=False, block_k=8) ** 2).sum()
    )(k1)
    assert g.shape == k1.shape
    assert np.isfinite(np.asarray(g)).all()
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, jnp.asarray(rng.randn(b, t, 3, d),), 
                        jnp.asarray(rng.randn(b, t, 3, d)), use_pallas=False)


def test_ring_and_ulysses_gqa_match_dense():
    n = 4
    mesh = build_mesh({SEQUENCE_AXIS: n}, devices=jax.devices()[:n])
    rng = np.random.RandomState(10)
    b, t, h, h_kv, d = 1, 32, 4, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    ref = dense_attention(
        q, jnp.repeat(k, h // h_kv, axis=2),
        jnp.repeat(v, h // h_kv, axis=2), causal=True,
    )
    out_ring = _run_sp(
        functools.partial(ring_attention, causal=True, block_k=8),
        mesh, q, k, v,
    )
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    out_uly = _run_sp(
        functools.partial(ulysses_attention, causal=True),
        mesh, q, k, v,
    )
    np.testing.assert_allclose(np.asarray(out_uly), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,h_kv,n", [
    (8, 4, 4),   # h_kv % n == 0: the SMALL-bundle a2a branch
    (8, 2, 4),   # h_kv % n != 0: lcm fallback (repeat to 4 heads, not 8)
    (4, 1, 4),   # MQA: lcm fallback repeats to n heads
])
def test_ulysses_gqa_branches_match_dense(h, h_kv, n):
    """Both Ulysses GQA exchange strategies — small-bundle a2a and the
    lcm-bounded repeat fallback — against dense attention with repeated
    heads (pins the post-a2a head-group alignment)."""
    mesh = build_mesh({SEQUENCE_AXIS: n}, devices=jax.devices()[:n])
    rng = np.random.RandomState(13)
    b, t, d = 1, 32, 8
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    out = _run_sp(
        functools.partial(ulysses_attention, causal=True),
        mesh, q, k, v,
    )
    ref = dense_attention(
        q, jnp.repeat(k, h // h_kv, axis=2),
        jnp.repeat(v, h // h_kv, axis=2), causal=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_ring_and_zigzag_grads_match_dense():
    """GQA gradients through the ring passes: the rotating dk/dv bundles
    stay H_kv-wide (group contributions reduced per fold) and must match
    dense attention on explicitly repeated heads, reduced over groups."""
    n, t = 4, 32
    mesh = build_mesh({SEQUENCE_AXIS: n}, devices=jax.devices()[:n])
    rng = np.random.RandomState(11)
    b, h, h_kv, d = 1, 4, 2, 8
    grp = h // h_kv
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
    spec = P(None, SEQUENCE_AXIS, None, None)
    sh = NamedSharding(mesh, spec)

    def loss_dense(q_, k_, v_):
        return (dense_attention(
            q_, jnp.repeat(k_, grp, axis=2), jnp.repeat(v_, grp, axis=2),
            causal=True) ** 2).sum()

    ref_g = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)

    ring = shard_map_fn(
        functools.partial(ring_attention, causal=True, block_k=8),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    g_ring = jax.jit(jax.grad(
        lambda a, b_, c: (ring(a, b_, c) ** 2).sum(), argnums=(0, 1, 2)
    ))(jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh))
    for a, b_ in zip(g_ring, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)

    perm = zigzag_permutation(t, n)
    inv = np.argsort(perm)
    zz = shard_map_fn(
        functools.partial(zigzag_ring_attention, block_k=8),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    g_zz = jax.jit(jax.grad(
        lambda a, b_, c: (zz(a, b_, c) ** 2).sum(), argnums=(0, 1, 2)
    ))(jax.device_put(q[:, perm], sh), jax.device_put(k[:, perm], sh),
       jax.device_put(v[:, perm], sh))
    for a, b_ in zip(g_zz, ref_g):
        np.testing.assert_allclose(np.asarray(a)[:, inv], np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_pallas_gqa_interpret_matches_dense():
    """The Pallas kernel's GQA kv index map (grid row -> kv head) against
    dense attention with repeated heads — interpret mode, both causal
    flavors, including MQA."""
    rng = np.random.RandomState(12)
    b, t, d = 1, 32, 8
    for h, h_kv in [(4, 2), (4, 1)]:
        q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, t, h_kv, d).astype(np.float32))
        for causal in (False, True):
            out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                                  interpret=True, block_q=16, block_k=16)
            ref = dense_attention(
                q, jnp.repeat(k, h // h_kv, axis=2),
                jnp.repeat(v, h // h_kv, axis=2), causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)


# ------------------------------------------- the forward's shape-chosen tile


from horovod_tpu.ops import flash_attention as fa


def _legal(block, t):
    return t % block == 0 and (block % 8 == 0 or block == t)


#: (t_q, t_k, heads, kv_heads, dtype, causal) — one B, few heads: interpret
#: mode walks every grid step in Python
_TILE_CASES = [
    (8, 8, 2, 2, "bfloat16", True),
    (24, 24, 2, 2, "bfloat16", True),
    (136, 136, 2, 2, "bfloat16", True),
    (384, 384, 2, 2, "bfloat16", True),
    (1024, 1024, 2, 2, "bfloat16", True),
    (2048, 2048, 1, 1, "bfloat16", True),
    (1024, 1024, 1, 1, "float32", True),
    (1024, 1024, 1, 1, "bfloat16", False),
    (2048, 2048, 1, 1, "float32", False),
    (1024, 1024, 4, 2, "bfloat16", True),     # GQA
    (1024, 1024, 4, 1, "float32", True),      # MQA
    (384, 1024, 2, 2, "bfloat16", True),      # t_q != t_k: unequal blocks
    (1024, 384, 2, 1, "float32", True),
    (2048, 1024, 1, 1, "bfloat16", True),
    (1000, 1000, 1, 1, "float32", True),      # 5 x 5 blocks of 200
]


@pytest.mark.parametrize("t_q,t_k,h,h_kv,dtype,causal", _TILE_CASES)
def test_flash_forward_chosen_tile_matches_dense(t_q, t_k, h, h_kv, dtype,
                                                 causal):
    """No block named: the kernel tiles itself from the shapes, and still
    agrees with dense float32 attention."""
    d = 64
    (bq, bk), (cq, ck) = fa._fwd_tile(t_q, t_k, d, dtype)
    assert _legal(bq, t_q) and _legal(bk, t_k), (bq, bk)
    assert _legal(cq, bq) and _legal(ck, bk), (cq, ck)
    rng = np.random.RandomState(t_q + t_k + h)
    q, k, v = (jnp.asarray(rng.randn(1, t, n, d), dtype)
               for t, n in ((t_q, h), (t_k, h_kv), (t_k, h_kv)))
    out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                          interpret=True)
    assert out.dtype == q.dtype and out.shape == q.shape
    f32 = lambda x, n=1: jnp.repeat(x.astype(jnp.float32), n, axis=2)
    ref = dense_attention(f32(q), f32(k, h // h_kv), f32(v, h // h_kv),
                          causal=causal)
    tol = 2e-5 if dtype == "float32" else 2e-2   # bf16: the output's own ulp
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_fwd_tile_at_the_benchmark_shape_is_pinned():
    """What PERF.md §6 (PR 27) records for GPT-2 medium's attention: one
    1024 x 1024 block a (batch, head), computed as 512 x 512 sub-tiles."""
    assert fa._fwd_tile(1024, 1024, 64, jnp.bfloat16) == (
        (1024, 1024), (512, 512))
    # longer sequences keep the block; wide float32 heads shrink it, square
    assert fa._fwd_tile(32768, 32768, 128, jnp.bfloat16)[0] == (1024, 1024)
    bq, bk = fa._fwd_tile(2048, 2048, 512, jnp.float32)[0]
    assert bq == bk < 1024
    # no multiple of 8 divides 1028 = 4 * 257
    assert fa._fwd_tile(1028, 1028, 64, jnp.bfloat16) == ((None, None), None)


def _eqns(jaxpr, name):
    """Every equation of primitive ``name``, nested jaxprs included."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            found.append(e)
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _eqns(sub, name)
    return found


def _pallas_grid(fn, *args):
    calls = _eqns(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")
    assert len(calls) == 1
    return tuple(calls[0].params["grid_mapping"].grid)


def test_flash_forward_tile_gauges_and_explicit_blocks(hvd):
    """``flash_fwd_tile`` / ``flash_fwd_grid_steps`` say which tile a trace
    compiled with; an explicit block is still honoured."""
    x = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16)
    flash = functools.partial(flash_attention, causal=True, use_pallas=True)
    assert _pallas_grid(flash, x, x, x) == (8, 1, 1)
    assert hvd.metrics.value("flash_fwd_tile", dim="q") == 1024
    assert hvd.metrics.value("flash_fwd_tile", dim="k") == 1024
    assert hvd.metrics.value("flash_fwd_grid_steps") == 8
    explicit = functools.partial(flash, block_q=16, block_k=32)
    assert _pallas_grid(explicit, x, x, x) == (8, 64, 32)
    assert hvd.metrics.value("flash_fwd_tile", dim="q") == 16
    assert hvd.metrics.value("flash_fwd_tile", dim="k") == 32
    assert hvd.metrics.value("flash_fwd_grid_steps") == 8 * 64 * 32


def test_fully_masked_rows_give_zeros_and_lse_masked():
    """Rows no key reached (ring attention skips K/V shards wholly in the
    causal future, so their state stays at its start: l == 0) come out as
    zeros with ``LSE_MASKED``, not NaN, and a recomputed probability
    against that lse vanishes. The Pallas kernel never meets such a row
    (row i always sees key 0) but keeps the same guard at its write."""
    b, h, t, d = 1, 2, 16, 8
    q, k, v = qkv(b=b, t=t, h=h, d=d, seed=7)
    m, l, acc = fa._attention_scan(
        q[:, 8:], k, v, causal=True, sm_scale=d ** -0.5, q_offset=8,
        kv_offset=0, block_k=8)
    # rows 0..7 untouched, rows 8..15 attended
    pad = lambda x, fill: jnp.concatenate(
        [jnp.full(x.shape[:2] + (8,) + x.shape[3:], fill, x.dtype), x], axis=2)
    m, l, acc = pad(m, fa.NEG_INF), pad(l, 0.0), pad(acc, 0.0)
    out = np.asarray(fa._finalize(m, l, acc, q.dtype))
    lse = np.asarray(fa.lse_from_state(m, l))
    assert np.isfinite(out).all() and np.isfinite(lse).all()
    assert (out[:, :8] == 0).all() and (lse[:, :, :8] == fa.LSE_MASKED).all()
    np.testing.assert_allclose(
        out[:, 8:], np.asarray(dense_attention(q, k, v, causal=True))[:, 8:],
        rtol=2e-5, atol=2e-5)
    assert np.exp(1e4 - lse[:, :, :8]).max() == 0.0


def _flash_grads(q, k, v, causal=True, weights=None, **kw):
    """Gradients of a weighted sum of the Pallas path's output (interpret
    mode), in float32."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                              interpret=True, **kw).astype(jnp.float32)
        return (out * weights).sum() if weights is not None else (
            out ** 2).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def test_pallas_backward_engages_and_matches_the_scan():
    """With no block named the backward on the Pallas path is the fused
    kernel, not the scan, and its gradients are those of the scan in
    explicit blocks of 128 (the parent's backward)."""
    t, d = 1024, 64
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, t, 1, d), jnp.float32)
               for _ in range(3))
    jaxpr = jax.make_jaxpr(_flash_grads)(q, k, v)
    assert not _eqns(jaxpr.jaxpr, "scan")
    grids = [tuple(e.params["grid_mapping"].grid)
             for e in _eqns(jaxpr.jaxpr, "pallas_call")]
    assert grids == [(1, 1, 1), (1, 1)], grids      # forward, fused backward
    g_pallas = _flash_grads(q, k, v)

    def scan_loss(q, k, v):
        return (flash_attention(q, k, v, causal=True, use_pallas=False,
                                block_k=128) ** 2).sum()

    scan = jax.grad(scan_loss, argnums=(0, 1, 2))
    assert [e.params["length"] for e in _eqns(
        jax.make_jaxpr(scan)(q, k, v).jaxpr, "scan")] == [8, 8]
    for a, b in zip(g_pallas, scan(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


#: (t, heads, kv_heads, head_dim, dtype, causal) -> the backward's blocks a
#: side. One B, few heads: interpret mode walks every grid step in Python
_BWD_CASES = [
    (8, 2, 2, 64, "float32", True),
    (24, 2, 2, 64, "bfloat16", True),
    (136, 2, 2, 64, "bfloat16", False),
    (384, 2, 2, 128, "float32", True),
    (1024, 2, 2, 64, "bfloat16", True),        # the cells' tile, fused
    (1024, 1, 1, 64, "float32", True),
    (1024, 1, 1, 128, "bfloat16", False),
    (1024, 4, 2, 64, "bfloat16", True),        # GQA: the group's sum
    (1024, 4, 1, 64, "float32", True),         # MQA
    (2048, 1, 1, 64, "float32", True),         # two blocks: dk/dv + dq calls
    (2048, 2, 1, 128, "bfloat16", True),       # two blocks, MQA
    (2048, 1, 1, 64, "bfloat16", False),
    (1536, 1, 1, 64, "float32", True),         # two blocks of 768
    (200, 1, 1, 64, "float32", True),          # one block, not whole lanes
]


@pytest.mark.parametrize("t,h,h_kv,d,dtype,causal", _BWD_CASES)
def test_flash_backward_pallas_matches_dense(t, h, h_kv, d, dtype, causal):
    """The Pallas backward at its shape-chosen tile against ``jax.grad`` of
    dense float32 attention."""
    blk, sub = fa._bwd_tile(t, t, d, dtype)
    assert _legal(blk, t) and _legal(sub, blk), (blk, sub)
    rng = np.random.RandomState(t + h + d)
    q, k, v = (jnp.asarray(rng.randn(1, t, n, d), dtype)
               for n in (h, h_kv, h_kv))
    w = jnp.asarray(rng.randn(1, t, h, d), jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(
        _flash_grads, causal=causal, weights=w))(q, k, v)
    assert not _eqns(jaxpr.jaxpr, "scan")
    assert len(_eqns(jaxpr.jaxpr, "pallas_call")) == (2 if blk == t else 3)
    got = _flash_grads(q, k, v, causal=causal, weights=w)
    f32 = lambda x, n=1: jnp.repeat(x.astype(jnp.float32), n, axis=2)

    def dense_loss(q, k, v):
        return (dense_attention(q, f32(k, h // h_kv), f32(v, h // h_kv),
                                causal=causal) * w).sum()

    want = jax.grad(dense_loss, argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == jnp.dtype(dtype) and a.shape == b.shape, name
        # bf16: operands, p and ds are rounded once each, the result once
        tol = 2e-4 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), rtol=tol,
            atol=tol * float(jnp.abs(b).max()), err_msg="d" + name)


def test_bwd_tile_at_the_benchmark_shape_is_pinned():
    """What PERF.md §6 (PR 31) records for GPT-2 medium's attention: one
    1024-row block a (batch, head) in one fused call, as 512 x 512
    sub-tiles; the scan where the kernels have no block."""
    assert fa._bwd_tile(1024, 1024, 64, jnp.bfloat16) == (1024, 512)
    assert fa._bwd_tile(32768, 32768, 128, jnp.bfloat16) == (1024, 512)
    assert fa._bwd_tile(2048, 2048, 128, jnp.float32) == (1024, 512)
    blk, sub = fa._bwd_tile(2048, 2048, 512, jnp.float32)  # wide f32 heads
    assert blk < 1024 and 2048 % blk == 0
    assert fa._bwd_vmem_bytes(1024, 512, 64, 2) < fa._BWD_VMEM_BUDGET \
        < fa._BWD_VMEM_LIMIT
    # explicit blocks: square ones are honoured, unequal ones scan
    assert fa._bwd_tile(1024, 1024, 64, jnp.bfloat16, 128, 128) == (128, 128)
    assert fa._bwd_tile(1024, 1024, 64, jnp.bfloat16, 128, 256) is None
    assert fa._bwd_tile(1024, 1024, 64, jnp.bfloat16, None, 128) is None
    assert fa._bwd_tile(384, 1024, 64, jnp.bfloat16) is None
    # several blocks take lse in rows of whole lanes: 2000 = 10 x 200 has
    # no block that is a multiple of 128, 1028 = 4 * 257 none of 8
    assert fa._bwd_tile(2000, 2000, 64, jnp.bfloat16) is None
    assert fa._bwd_tile(1028, 1028, 64, jnp.bfloat16) is None
    assert fa._bwd_tile(1024, 1024, 64, jnp.bfloat16, 16, 16) is None
    assert fa._bwd_tile(200, 200, 64, jnp.bfloat16) == (200, 200)


def test_flash_backward_gauges_say_which_backward_was_traced(hvd):
    """``flash_bwd_tile`` / ``flash_bwd_grid_steps`` are set when the Pallas
    backward is traced and absent when the scan is."""
    hvd.metrics.REGISTRY.reset()
    x = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 1024, 2, 64), jnp.bfloat16)
    jax.make_jaxpr(functools.partial(_flash_grads, block_q=128,
                                     block_k=256))(x, kv, kv)
    assert hvd.metrics.value("flash_fwd_tile", dim="k") == 256
    assert hvd.metrics.value("flash_bwd_tile", dim="q") is None
    assert hvd.metrics.value("flash_bwd_grid_steps") is None
    jax.make_jaxpr(_flash_grads)(x, kv, kv)
    assert hvd.metrics.value("flash_bwd_tile", dim="q") == 1024
    assert hvd.metrics.value("flash_bwd_tile", dim="k") == 1024
    assert hvd.metrics.value("flash_bwd_grid_steps") == 8
    jax.make_jaxpr(functools.partial(_flash_grads, block_q=256,
                                     block_k=256))(x, kv, kv)
    assert hvd.metrics.value("flash_bwd_tile", dim="q") == 256
    # dk/dv: 2*2 kv rows x 4 k blocks x 2 q heads x 4 q blocks; dq: 8 x 4 x 4
    assert hvd.metrics.value("flash_bwd_grid_steps") == 128 + 128


@pytest.mark.parametrize("case", ["use_pallas=False", "t_q != t_k",
                                  "unequal blocks", "blocks of 16 rows",
                                  "no legal block"])
def test_flash_backward_fallbacks_still_scan(case):
    """Where the kernels have no block the backward is the parent's scan,
    with the parent's gradients."""
    rng = np.random.RandomState(11)
    t_q, t_k, kw = 64, 64, dict(use_pallas=True, interpret=True)
    if case == "use_pallas=False":
        kw = dict(use_pallas=False, block_k=16)
    elif case == "t_q != t_k":
        t_q = 32
    elif case == "unequal blocks":
        kw.update(block_q=16, block_k=32)
    elif case == "blocks of 16 rows":    # several blocks, not whole lanes
        kw.update(block_q=16, block_k=16)
    elif case == "no legal block":
        # the forward takes 5 x 5 blocks of 200 rows; lse reaches the
        # backward's kernels in rows of whole lanes, and 200 is not
        t_q = t_k = 1000
    q, k, v = (jnp.asarray(rng.randn(1, t, 2, 16), jnp.float32)
               for t in (t_q, t_k, t_k))
    causal = t_q == t_k

    def loss(q, k, v, fn=functools.partial(flash_attention, **kw)):
        return (fn(q, k, v, causal=causal) ** 2).sum()

    def dense_loss(q, k, v):
        return (dense_attention(q, k, v, causal=causal) ** 2).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(q, k, v)
    got = grad(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    bwd_calls = [e for e in _eqns(jaxpr.jaxpr, "pallas_call")
                 if len(e.outvars) != 2 or e.outvars[1].aval.shape[-1] != 1]
    assert not bwd_calls and _eqns(jaxpr.jaxpr, "scan")
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("blocks", [None, 128])
def test_pallas_backward_of_fully_masked_rows_is_zero(blocks):
    """Residuals as ring attention's skipped shards leave them (l == 0:
    out 0, lse ``LSE_MASKED``): the kernels recompute p = 0 there, so the
    rows' dq is exactly zero, nothing is non-finite, and dk / dv are the
    scan's on the same residuals."""
    b, t, h, d = 1, 256, 2, 16
    q, k, v = qkv(b=b, t=t, h=h, d=d, seed=5)
    g = jnp.asarray(np.random.RandomState(6).randn(b, t, h, d), jnp.float32)
    sm_scale = d ** -0.5
    out, res = fa._flash_fwd(q, k, v, True, sm_scale,
                             (None, None, False, False), None)
    masked = np.zeros((t,), bool)
    masked[:8] = masked[150:160] = True
    out = jnp.where(masked[None, :, None, None], 0.0, out)
    lse = jnp.where(masked[None, None, :], fa.LSE_MASKED, res[4])
    res = (q, k, v, out, lse)
    bwd = functools.partial(fa._flash_bwd, True, sm_scale)
    got = bwd((blocks, blocks, True, True), None, res, g)
    want = bwd((blocks, blocks, False, False), None, res, g)
    assert all(np.isfinite(np.asarray(x)).all() for x in got)
    assert (np.asarray(got[0])[:, masked] == 0).all()
    assert np.abs(np.asarray(got[0])[:, ~masked]).min() > 0
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)
