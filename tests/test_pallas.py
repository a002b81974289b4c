"""Pallas kernels for the int8 wire hot path, Adasum, and the fused
ZeRO-1 Adam shard update (``horovod_tpu.ops.pallas_kernels``,
``HOROVOD_PALLAS``).

Acceptance pins (ISSUE 12) on the 8-device CPU mesh, all via Pallas
INTERPRET mode (the equivalence harness — no TPU hardware needed):

1. the fused quantize kernel is BIT-identical to the discrete HLO
   ``compression.quantize_blockwise`` (odd lengths, exact block
   boundaries, all-zero blocks, bf16-scale rounding, per-bucket
   ``BucketPlan`` shapes);
2. the fused dequant-accumulate(-requantize) epilogues are bit-identical
   to the discrete sum → divide → requantize sequence;
3. int8+EF ZeRO-1 trajectories are BIT-identical across
   ``HOROVOD_PALLAS=0/1`` and Adasum trajectories match within the
   chunked-reduction tolerance;
4. the fused Adam kernel matches optax within a few ULP at the update
   scale and its state checkpoints are bit-stable across the knob;
5. every pinned schedule-fingerprint cell (16 monolithic + 4 overlap +
   the hierarchical 8) is byte-identical with ``HOROVOD_PALLAS=1`` —
   Pallas replaces elementwise HLO, never collectives.
"""

import json
import os
import pathlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu.compression import (
    Compression,
    INT8_BLOCK,
    _pad_to_block,
    dequantize_blockwise,
    quantize_blockwise,
    quantize_chunked,
    quantize_roundtrip_chunked,
)
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.ops.collective import _smap, allreduce, Average

pytestmark = pytest.mark.pallas

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FINGERPRINT_FILE = (
    pathlib.Path(__file__).parent / "data" / "schedule_fingerprints.json"
)


@pytest.fixture()
def pallas_on(monkeypatch):
    monkeypatch.setenv("HOROVOD_PALLAS", "1")


def _rng(seed=0):
    return np.random.RandomState(seed)


# --------------------------------------------------------------------------
# knob semantics


def test_knob_semantics(monkeypatch):
    monkeypatch.delenv("HOROVOD_PALLAS", raising=False)
    # auto on the CPU harness: kernels off (TPU only)
    assert pk.enabled() is False and pk.interpret() is False
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    assert pk.enabled() is True
    assert pk.interpret() is True  # CPU backend -> interpret harness
    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    assert pk.enabled() is False
    assert pk.cache_key() == (False, False)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    assert pk.cache_key() == (True, True)
    monkeypatch.setenv("HOROVOD_PALLAS", "bogus")
    with pytest.raises(ValueError, match="HOROVOD_PALLAS"):
        pk.enabled()


# --------------------------------------------------------------------------
# quantize kernel: bit-equivalence vs the discrete HLO reference


@pytest.mark.parametrize("length", [
    256,      # exactly one block
    2048,     # exact block boundary, multi-tile
    1111,     # odd length -> shared tail pad
    255,      # below one block
    4096 + 3, # tail beside full tiles
])
def test_quantize_bit_equal(pallas_on, length):
    flat = jnp.asarray(_rng(length).randn(length).astype(np.float32))
    q_hlo, s_hlo = quantize_blockwise(flat, use_pallas=False)
    q_pl, s_pl = quantize_blockwise(flat)  # knob dispatches to Pallas
    assert (np.asarray(q_hlo) == np.asarray(q_pl)).all()
    assert (np.asarray(s_hlo) == np.asarray(s_pl)).all()
    # and both consume the SAME shared pad layout
    assert q_pl.shape[0] == _pad_to_block(flat, INT8_BLOCK).shape[0]


def test_quantize_all_zero_blocks(pallas_on):
    """A zero block must emit scale 0 and q 0 (not NaN from 0/0) on both
    paths."""
    flat = jnp.concatenate([
        jnp.zeros((256,), jnp.float32),
        jnp.asarray(_rng(1).randn(256).astype(np.float32)),
        jnp.zeros((256,), jnp.float32),
    ])
    q_hlo, s_hlo = quantize_blockwise(flat, use_pallas=False)
    q_pl, s_pl = quantize_blockwise(flat)
    assert (np.asarray(q_pl) == np.asarray(q_hlo)).all()
    assert (np.asarray(s_pl) == np.asarray(s_hlo)).all()
    assert np.asarray(s_pl)[0] == 0 and np.asarray(q_pl)[:256].sum() == 0


def test_quantize_bf16_scale_rounding(pallas_on):
    """Scales are rounded to bf16 BEFORE the divide; amax values chosen
    to straddle bf16 rounding boundaries must still agree bitwise."""
    base = np.linspace(0.9, 1.1, 256).astype(np.float32)
    rows = []
    for amax in (1.0, 1.0 + 2 ** -9, 127.0 * (1 + 2 ** -8), 3e-5, 1e37):
        r = base.copy()
        r[17] = amax
        rows.append(r / r.max() * amax)
    flat = jnp.asarray(np.concatenate(rows))
    q_hlo, s_hlo = quantize_blockwise(flat, use_pallas=False)
    q_pl, s_pl = quantize_blockwise(flat)
    assert (np.asarray(q_pl) == np.asarray(q_hlo)).all()
    assert (np.asarray(s_pl) == np.asarray(s_hlo)).all()


def test_quantize_bucketplan_shapes(pallas_on):
    """Every per-bucket flat length a BucketPlan partition produces (leaf
    splits, mixed sizes, padded Lp) quantizes bit-identically — the
    shapes the bucketed ZeRO-1 exchange actually feeds the kernel."""
    from horovod_tpu.ops.overlap import BucketPlan

    leaves = [
        jax.ShapeDtypeStruct((40, 30), jnp.float32),
        jax.ShapeDtypeStruct((33,), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((7,), jnp.float32),
    ]
    plan = BucketPlan.build(leaves, n=8, bucket_bytes=4096)
    assert len(plan.buckets) >= 2
    for i, b in enumerate(plan.buckets):
        flat = jnp.asarray(_rng(100 + i).randn(b.Lp).astype(np.float32))
        q_hlo, s_hlo = quantize_blockwise(flat, use_pallas=False)
        q_pl, s_pl = quantize_blockwise(flat)
        assert (np.asarray(q_pl) == np.asarray(q_hlo)).all()
        assert (np.asarray(s_pl) == np.asarray(s_hlo)).all()


def test_quantize_roundtrip_fused_one_pass(pallas_on):
    """The fused (q, scales, deq) triple equals the discrete quantize +
    dequantize pair bit-for-bit, for the chunked wire layout error
    feedback consumes."""
    flat = jnp.asarray(_rng(7).randn(2048).astype(np.float32))
    q0, s0, rt0 = quantize_chunked(flat, 8, use_pallas=False)
    q1, s1, rt1 = quantize_chunked(flat, 8)
    assert (np.asarray(q0) == np.asarray(q1)).all()
    assert (np.asarray(s0) == np.asarray(s1)).all()
    assert (np.asarray(rt0) == np.asarray(rt1)).all()
    # the public roundtrip helper rides the same path
    assert (np.asarray(quantize_roundtrip_chunked(flat, 8))
            == np.asarray(rt0)).all()


# --------------------------------------------------------------------------
# dequant-accumulate(-requantize) epilogues


def _wire_image(n, sp, seed=3):
    r = _rng(seed)
    qr = jnp.asarray(r.randint(-127, 128, (n, sp)).astype(np.int8))
    scr = jnp.asarray(
        (np.abs(r.randn(n, sp // INT8_BLOCK)) * 0.01).astype(np.float32)
    ).astype(jnp.bfloat16)
    return qr, scr


def test_dequant_accumulate_bit_equal(pallas_on):
    n, sp = 8, 1536
    qr, scr = _wire_image(n, sp)
    ref = dequantize_blockwise(
        qr.reshape(-1), scr.reshape(-1), jnp.float32).reshape(n, sp) \
        .sum(axis=0)
    out = pk.dequant_accumulate(qr, scr, jnp.float32, INT8_BLOCK)
    assert (np.asarray(out) == np.asarray(ref)).all()


@pytest.mark.parametrize("divisor", [None, 8])
def test_dequant_accumulate_requantize_bit_equal(pallas_on, divisor):
    n, sp = 8, 2048
    qr, scr = _wire_image(n, sp, seed=4)
    shard = dequantize_blockwise(
        qr.reshape(-1), scr.reshape(-1), jnp.float32).reshape(n, sp) \
        .sum(axis=0)
    if divisor is not None:
        shard = shard / divisor
    q_ref, s_ref = quantize_blockwise(shard, use_pallas=False)
    q2, s2 = pk.dequant_accumulate_requantize(
        qr, scr, jnp.float32, INT8_BLOCK, divisor=divisor)
    assert (np.asarray(q2) == np.asarray(q_ref)).all()
    assert (np.asarray(s2) == np.asarray(s_ref)).all()


# --------------------------------------------------------------------------
# Adasum combine kernels


def _ref_pair_combine(a, b):
    dot = jnp.vdot(a, b).real.astype(jnp.float32)
    na = jnp.vdot(a, a).real.astype(jnp.float32)
    nb = jnp.vdot(b, b).real.astype(jnp.float32)
    ca = jnp.where(na == 0, 0.0, 1.0 - dot / (2.0 * jnp.maximum(na, 1e-30)))
    cb = jnp.where(nb == 0, 0.0, 1.0 - dot / (2.0 * jnp.maximum(nb, 1e-30)))
    return (ca * a.astype(jnp.float32)
            + cb * b.astype(jnp.float32)).astype(a.dtype)


@pytest.mark.parametrize("shape", [(1200,), (40, 30), (3000,), (8,)])
def test_adasum_pair_combine_matches(pallas_on, shape):
    r = _rng(11)
    a = jnp.asarray(r.randn(*shape).astype(np.float32))
    b = jnp.asarray(r.randn(*shape).astype(np.float32))
    out = pk.adasum_pair_combine(a, b)
    ref = _ref_pair_combine(a, b)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_adasum_pair_combine_zero_operands(pallas_on):
    """``|a|² == 0`` zeroes the coefficient (the reference's guard), so
    combine(0, b) == cb·b and combine(0, 0) == 0 — no NaNs from 0/0."""
    z = jnp.zeros((600,), jnp.float32)
    b = jnp.asarray(_rng(12).randn(600).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(pk.adasum_pair_combine(z, b)),
        np.asarray(_ref_pair_combine(z, b)), rtol=2e-5, atol=2e-6)
    assert np.all(np.asarray(pk.adasum_pair_combine(z, z)) == 0)


def test_adasum_segment_combine_matches(pallas_on):
    """Per-segment combine over an unaligned concat layout (incl. a
    length-1 segment and a segment spanning a chunk boundary) tracks the
    discrete segment_sum reference."""
    sizes = [1000, 1, 500, 1571]
    L = sum(sizes)
    r = _rng(13)
    a = jnp.asarray(r.randn(L).astype(np.float32))
    b = jnp.asarray(r.randn(L).astype(np.float32))
    seg = jnp.asarray(np.repeat(np.arange(len(sizes)), sizes))
    out = pk.adasum_segment_combine(a, b, seg, len(sizes))
    dot = jax.ops.segment_sum(a * b, seg, num_segments=len(sizes))
    na = jax.ops.segment_sum(a * a, seg, num_segments=len(sizes))
    nb = jax.ops.segment_sum(b * b, seg, num_segments=len(sizes))
    ca = jnp.where(na == 0, 0.0, 1.0 - dot / (2.0 * jnp.maximum(na, 1e-30)))
    cb = jnp.where(nb == 0, 0.0, 1.0 - dot / (2.0 * jnp.maximum(nb, 1e-30)))
    ref = ca[seg] * a + cb[seg] * b
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_adasum_allreduce_knob_equivalence(hvd, monkeypatch):
    """The eager VHDD butterfly (stacked per-rank values) produces the
    same reduction with kernels on and off, and the compiled-program
    cache cannot leak across the knob flip."""
    ax = hvd.data_axis()
    from horovod_tpu.ops.adasum import adasum_allreduce

    vals = jnp.asarray(_rng(14).randn(8, 500).astype(np.float32))
    vs = jax.device_put(vals, NamedSharding(hvd.mesh(), P(ax)))
    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    off = adasum_allreduce(vs, axis=ax)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    on = adasum_allreduce(vs, axis=ax)
    np.testing.assert_allclose(
        np.asarray(on), np.asarray(off), rtol=2e-5, atol=2e-6)


def test_grouped_adasum_knob_equivalence(hvd, monkeypatch):
    ax = hvd.data_axis()
    from horovod_tpu.ops.adasum import grouped_adasum_allreduce

    r = _rng(15)
    ts = [
        jax.device_put(
            jnp.asarray(r.randn(8, 40, 30).astype(np.float32)),
            NamedSharding(hvd.mesh(), P(ax))),
        jax.device_put(
            jnp.asarray(r.randn(8, 7).astype(np.float32)),
            NamedSharding(hvd.mesh(), P(ax))),
    ]
    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    off = grouped_adasum_allreduce(ts, axis=ax)
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    on = grouped_adasum_allreduce(ts, axis=ax)
    for x, y in zip(on, off):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=2e-5, atol=2e-6)


# --------------------------------------------------------------------------
# fused Adam kernel


def test_fused_adam_kernel_vs_reference_ops(pallas_on):
    """The kernel against the identical jnp expression sequence: within
    ~1 ULP elementwise (interpret-mode jit may contract the moment
    multiply-add into an FMA — tolerance is ULP-at-operand-scale, the
    tightest bound FMA contraction admits)."""
    r = _rng(21)
    g = jnp.asarray(r.randn(1200).astype(np.float32))
    mu = jnp.asarray((r.randn(1200) * 0.01).astype(np.float32))
    nu = jnp.asarray((np.abs(r.randn(1200)) * 1e-4).astype(np.float32))
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-3
    cnt = jnp.asarray(3, jnp.int32)
    b1c = 1 - b1 ** cnt
    b2c = 1 - b2 ** cnt
    mu_ref = (1 - b1) * g + b1 * mu
    nu_ref = (1 - b2) * (g ** 2) + b2 * nu
    u_ref = -lr * ((mu_ref / b1c) / (jnp.sqrt(nu_ref / b2c) + eps))
    u, m, v = pk.fused_adam_update(
        g, mu, nu, b1c, b2c, lr=lr, b1=b1, b2=b2, eps=eps)

    def ulp_close(a, b, scale, ulps=2):
        a, b = np.asarray(a), np.asarray(b)
        tol = ulps * np.spacing(
            np.maximum(np.maximum(np.abs(a), np.abs(b)), scale)
            .astype(np.float32))
        assert (np.abs(a - b) <= tol).all(), np.abs(a - b).max()

    ulp_close(m, mu_ref, scale=np.abs(np.asarray(g)).max())
    ulp_close(v, nu_ref, scale=float(np.asarray(nu_ref).max()))
    ulp_close(u, u_ref, scale=lr)


def test_fused_adam_matches_optax(pallas_on):
    """Drop-in parity with ``optax.adam``: identical state treedef, and
    updates/moments within a few ULP at the update scale over several
    steps (optax's own jitted bias-correction rewrites set the floor)."""
    from horovod_tpu.optim import fused_adam

    r = _rng(22)
    p = {"w": jnp.asarray(r.randn(40, 30).astype(np.float32)),
         "b": jnp.asarray(r.randn(30).astype(np.float32))}
    ref = optax.adam(1e-3)
    fa = fused_adam(1e-3)
    s0, s1 = ref.init(p), fa.init(p)
    assert jax.tree_util.tree_structure(s0) == \
        jax.tree_util.tree_structure(s1)
    for i in range(5):
        g = {"w": jnp.asarray(r.randn(40, 30).astype(np.float32)),
             "b": jnp.asarray(r.randn(30).astype(np.float32))}
        u0, s0 = ref.update(g, s0, p)
        u1, s1 = fa.update(g, s1, p)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(u1[k]), np.asarray(u0[k]),
                rtol=5e-5, atol=5e-8)


def test_fused_adam_knob_off_is_optax_bitwise(monkeypatch):
    """With the kernels off the transformation IS optax.adam, bit for
    bit — the contract the 0/1 checkpoint interchange rests on."""
    from horovod_tpu.optim import fused_adam

    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    r = _rng(23)
    p = {"w": jnp.asarray(r.randn(40, 30).astype(np.float32))}
    g = {"w": jnp.asarray(r.randn(40, 30).astype(np.float32))}
    ref, fa = optax.adam(1e-3), fused_adam(1e-3)
    s0, s1 = ref.init(p), fa.init(p)
    for _ in range(3):
        u0, s0 = ref.update(g, s0, p)
        u1, s1 = fa.update(g, s1, p)
    assert (np.asarray(u0["w"]) == np.asarray(u1["w"])).all()
    assert (np.asarray(s0[0].mu["w"]) == np.asarray(s1[0].mu["w"])).all()


def test_fused_adam_rejects_schedule():
    from horovod_tpu.optim import fused_adam

    with pytest.raises(ValueError, match="static float"):
        fused_adam(optax.linear_schedule(1e-3, 1e-4, 10))


# --------------------------------------------------------------------------
# mesh trajectories: the knob must not move the math


_SHAPE = (40, 30)


def _params():
    r = _rng(31)
    return {"w": jnp.asarray(r.randn(*_SHAPE).astype(np.float32) * 0.1),
            "b": jnp.zeros((_SHAPE[1],), jnp.float32)}


def _batch(n):
    r = _rng(32)
    x = jnp.asarray(r.randn(2 * n, _SHAPE[0]), jnp.float32)
    y = jnp.asarray(r.randn(2 * n, _SHAPE[1]), jnp.float32)
    return x, y


def _loss(p, x, y):
    return jnp.mean((x @ p["w"] + p["b"][None] - y) ** 2)


def _run_zero1(hvd, inner, steps=6, compression=None, error_feedback=True):
    from horovod_tpu.training import shard_batch

    ax = hvd.data_axis()
    mesh = hvd.mesh()
    dtx = hvd.DistributedOptimizer(
        inner, compression=compression or Compression.int8,
        error_feedback=error_feedback, shard_optimizer=True)
    p = jax.tree_util.tree_map(jnp.array, _params())
    s = dtx.init(p)

    def step(pp, ss, xx, yy):
        l, g = jax.value_and_grad(_loss)(pp, xx, yy)
        u, ss = dtx.update(g, ss, pp)
        pp = optax.apply_updates(pp, u)
        return pp, ss, allreduce(l, Average, axis=ax)

    sm = jax.jit(_smap(
        step, mesh, (P(), P(ax), P(ax), P(ax)), (P(), P(ax), P())))
    x, y = _batch(hvd.size())
    xs, ys = shard_batch(x), shard_batch(y)
    for _ in range(steps):
        p, s, l = sm(p, s, xs, ys)
    return p, s, float(l)


def test_zero1_int8_ef_trajectory_bit_identical(hvd, monkeypatch):
    """The acceptance trajectory: ZeRO-1 + int8 + error feedback on the
    8-mesh, 6 steps — BIT-identical across HOROVOD_PALLAS=0/1 (the
    quantize kernels are bit-equal and the accumulate order matches, so
    nothing may move; this also covers the fused one-pass EF
    residual/wire reuse)."""
    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    p0, s0, l0 = _run_zero1(hvd, optax.adam(1e-2))
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    p1, s1, l1 = _run_zero1(hvd, optax.adam(1e-2))
    assert l0 == l1
    for k in ("w", "b"):
        assert (np.asarray(p0[k]) == np.asarray(p1[k])).all()
    r0 = np.asarray(s0.residual["float32"])
    r1 = np.asarray(s1.residual["float32"])
    assert (r0 == r1).all()


def test_zero1_fused_adam_trajectory_close(hvd, monkeypatch):
    """fused_adam as the ZeRO-1 inner optimizer: the knob=1 trajectory
    tracks knob=0 (== optax.adam bitwise) at ULP-accumulation level."""
    from horovod_tpu.optim import fused_adam

    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    p0, _, _ = _run_zero1(hvd, fused_adam(1e-2))
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    p1, _, _ = _run_zero1(hvd, fused_adam(1e-2))
    for k in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(p1[k]), np.asarray(p0[k]), rtol=1e-5, atol=1e-7)


def test_fused_adam_checkpoint_bit_stable_across_knob(hvd, monkeypatch,
                                                      tmp_path):
    """The acceptance pin: a fused-Adam ZeRO-1 state saved under
    HOROVOD_PALLAS=1 restores BIT-identically (same treedef, same bytes)
    and continues training under HOROVOD_PALLAS=0 — and vice versa. The
    state pytree is optax.adam's, so the checkpoint carries no trace of
    which kernel wrote it."""
    from horovod_tpu.optim import fused_adam

    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    p1, s1, _ = _run_zero1(hvd, fused_adam(1e-2), steps=3)
    leaves, treedef = jax.tree_util.tree_flatten((p1, s1))
    path = tmp_path / "state.npz"
    np.savez(path, **{str(i): np.asarray(l) for i, l in enumerate(leaves)})
    loaded = np.load(path)
    restored = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(loaded[str(i)]) for i in range(len(leaves))])
    rp, rs = restored
    for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                    jax.tree_util.tree_leaves((rp, rs))):
        assert (np.asarray(a) == np.asarray(b)).all()

    # continue under the OTHER knob from the restored state: the step
    # must accept the state unchanged (structure + shapes) and train
    from horovod_tpu.training import shard_batch

    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    ax = hvd.data_axis()
    dtx = hvd.DistributedOptimizer(
        fused_adam(1e-2), compression=Compression.int8,
        error_feedback=True, shard_optimizer=True)

    def step(pp, ss, xx, yy):
        l, g = jax.value_and_grad(_loss)(pp, xx, yy)
        u, ss = dtx.update(g, ss, pp)
        return optax.apply_updates(pp, u), ss, allreduce(
            l, Average, axis=ax)

    sm = jax.jit(_smap(
        step, hvd.mesh(), (P(), P(ax), P(ax), P(ax)), (P(), P(ax), P())))
    x, y = _batch(hvd.size())
    xs, ys = shard_batch(x), shard_batch(y)
    p2, s2, l2 = sm(rp, rs, xs, ys)
    assert np.isfinite(float(l2))
    # the continued trajectory matches continuing under knob=1 within ULP
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    p3, s3, l3 = _run_zero1(hvd, fused_adam(1e-2), steps=4)
    np.testing.assert_allclose(
        np.asarray(p2["w"]), np.asarray(p3["w"]), rtol=1e-5, atol=1e-7)


def test_eager_quant_kernels_rekey_on_knob_flip(hvd, monkeypatch):
    """Flipping HOROVOD_PALLAS between eager int8 collectives of the
    SAME signature must rebuild the compiled program (the knob is part
    of the cache key), never replay a stale one — and the results stay
    bit-identical either way."""
    from horovod_tpu.ops.collective import _eager_quant_allreduce_fn

    x = jnp.asarray(_rng(41).randn(2000).astype(np.float32))
    monkeypatch.setenv("HOROVOD_PALLAS", "0")
    a0 = allreduce(x, Average, compression=Compression.int8)
    before = _eager_quant_allreduce_fn.cache_info()
    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    a1 = allreduce(x, Average, compression=Compression.int8)
    after = _eager_quant_allreduce_fn.cache_info()
    assert after.misses == before.misses + 1, (before, after)
    assert (np.asarray(a0) == np.asarray(a1)).all()


# --------------------------------------------------------------------------
# schedule-fingerprint regression gate: HOROVOD_PALLAS=1 must not move
# a single pinned cell (Pallas replaces elementwise HLO, not collectives)


def _build_cell(sync: str, comp_name: str, overlap: bool = False):
    """Compact mirror of tests/test_schedule.py::_build_cell — the same
    cells, rebuilt here under HOROVOD_PALLAS=1."""
    comps = {
        "none": lambda: Compression.none,
        "fp16": lambda: Compression.fp16,
        "int8": lambda: Compression.int8,
        "powersgd": lambda: Compression.powersgd(2),
    }
    comp = comps[comp_name]()
    ef = comp_name != "none"
    kw = dict(overlap=True, bucket_bytes=4096) if overlap else \
        dict(overlap=False)
    dtx = hvd_mod.DistributedOptimizer(
        optax.adam(1e-2), compression=comp, error_feedback=ef,
        shard_optimizer=(sync == "zero1"), **kw)
    rng = np.random.RandomState(0)
    p = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32) * 0.1),
         "b": jnp.zeros((32,), jnp.float32)}
    s = dtx.init(p)
    ax = hvd_mod.data_axis()
    mesh = hvd_mod.mesh()
    opt_spec = P(ax) if sync == "zero1" else P()

    def loss(pp, x, y):
        return jnp.mean((x @ pp["w"] + pp["b"][None] - y) ** 2)

    def step(pp, ss, x, y):
        l, g = jax.value_and_grad(loss)(pp, x, y)
        u, ss = dtx.update(g, ss, pp)
        pp = optax.apply_updates(pp, u)
        return pp, ss, allreduce(l, Average, axis=ax)

    sm = _smap(
        step, mesh, (P(), opt_spec, P(ax), P(ax)), (P(), opt_spec, P()))
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(16, 64), jnp.float32)
    y = jnp.asarray(rng.randn(16, 32), jnp.float32)
    return sm, (p, s, x, y)


def _pins():
    with open(FINGERPRINT_FILE, encoding="utf-8") as f:
        return json.load(f)


def test_fingerprints_flat_and_overlap_invariant_under_pallas(
        hvd, monkeypatch):
    """All 8 flat monolithic cells + the 4 overlap cells re-derived with
    HOROVOD_PALLAS=1 fingerprint byte-identically to the pinned matrix:
    kernel substitution may not add, drop, reorder, reshape or re-dtype
    ONE collective."""
    from horovod_tpu.analysis import collective_schedule

    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    pins = _pins()
    for sync in ("allreduce", "zero1"):
        for comp in ("none", "fp16", "int8", "powersgd"):
            fn, args = _build_cell(sync, comp)
            sched = collective_schedule(fn, *args)
            key = f"{sync}|{comp}|flat"
            assert sched.fingerprint() == pins[key]["fingerprint"], (
                f"cell {key} moved under HOROVOD_PALLAS=1"
            )
    for sync in ("allreduce", "zero1"):
        for comp in ("none", "int8"):
            fn, args = _build_cell(sync, comp, overlap=True)
            sched = collective_schedule(fn, *args)
            key = f"{sync}|{comp}|flat|overlap"
            assert sched.fingerprint() == pins[key]["fingerprint"], (
                f"overlap cell {key} moved under HOROVOD_PALLAS=1"
            )


def test_fingerprints_hierarchical_invariant_under_pallas(monkeypatch):
    """The 8 hierarchical cells (2×4 host mesh, cross-hop compression)
    under HOROVOD_PALLAS=1 — byte-identical to the pins."""
    from horovod_tpu.analysis import collective_schedule
    from horovod_tpu.ops.hierarchical import set_hierarchical
    from horovod_tpu.parallel.mesh import build_host_mesh

    monkeypatch.setenv("HOROVOD_PALLAS", "1")
    hvd_mod.init(mesh=build_host_mesh(local=4))
    set_hierarchical(True)
    try:
        pins = _pins()
        for sync in ("allreduce", "zero1"):
            for comp in ("none", "fp16", "int8", "powersgd"):
                fn, args = _build_cell(sync, comp)
                sched = collective_schedule(fn, *args)
                key = f"{sync}|{comp}|hier"
                assert sched.fingerprint() == pins[key]["fingerprint"], (
                    f"hier cell {key} moved under HOROVOD_PALLAS=1"
                )
    finally:
        set_hierarchical(None)
        hvd_mod.shutdown()


# --------------------------------------------------------------------------
# analytic HBM model + bench rung


def test_pallas_hot_path_byte_model():
    import sys

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    from scaling_projection import pallas_hot_path_bytes

    m = pallas_hot_path_bytes([(784, 64), (64,)], 8)
    # fusing can only remove HBM round-trips, never add them
    assert m["fused_bytes"] < m["discrete_bytes"]
    assert 0.0 < m["savings_ratio"] < 1.0
    # the wire bytes match the int8 compressor's pricing of the buffer
    from horovod_tpu.compression import Int8Compressor

    assert m["wire_bytes"] == Int8Compressor.wire_bytes(
        (m["elems"],), jnp.float32)
    # EF off drops the discrete roundtrip pass AND the fused rt write
    m_no_ef = pallas_hot_path_bytes(
        [(784, 64), (64,)], 8, error_feedback=False)
    assert m_no_ef["discrete_bytes"] < m["discrete_bytes"]
    assert m_no_ef["fused_bytes"] < m["fused_bytes"]
    # allreduce epilogue adds the requantize stage to both sides
    m_ar = pallas_hot_path_bytes([(784, 64), (64,)], 8,
                                 epilogue="allreduce")
    assert m_ar["discrete_bytes"] > m["discrete_bytes"]
    with pytest.raises(ValueError, match="epilogue"):
        pallas_hot_path_bytes([(8,)], 8, epilogue="bogus")
