"""Low-bit gradient compression: int8 quantized collectives + PowerSGD
low-rank sync (``Compression.int8`` / ``Compression.powersgd(r)``).

Acceptance pins on the 8-device CPU mesh:

1. int8+EF and PowerSGD(rank=4)+EF Adam trajectories track the
   uncompressed trajectory within tolerance over >= 12 steps;
2. reported ``grad_sync_bytes_per_step`` for int8 is <= ~27% of fp32
   (incl. blockwise-scale overhead) and PowerSGD rank-4 <= 10% on the
   transformer-block tree;
3. both compose with ``shard_optimizer=True`` and survive an 8→4→8
   ``consolidate_opt_state`` reshard with EF-residual mass preserved.
"""

import functools
import os

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
    int8_roundtrip,
    quantize_blockwise,
)
from horovod_tpu.ops.collective import _smap, allreduce, Average

pytestmark = pytest.mark.compression


def _block_params():
    """A transformer-block-shaped tree: fat 2-D projections plus 1-D
    biases/layernorms — the shape mix the PowerSGD rank-4 ratio claim is
    made on."""
    rng = np.random.RandomState(0)
    d = 64

    def w(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.05)

    return {
        "attn": {"qkv": w(d, 3 * d), "proj": w(d, d),
                 "qkv_b": jnp.zeros((3 * d,), jnp.float32)},
        "mlp": {"up": w(d, 4 * d), "down": w(4 * d, d),
                "up_b": jnp.zeros((4 * d,), jnp.float32)},
        "ln": {"scale": jnp.ones((d,), jnp.float32),
               "bias": jnp.zeros((d,), jnp.float32)},
    }


#: the int8 trajectory/reshard tree: 40x30 = 1200 elements, above the
#: min-quantize floor so the wire genuinely quantizes
_INT8_SHAPE = (40, 30)
#: the PowerSGD trajectory tree: narrow enough (rank 4 of min-dim 12) that
#: a rank-4 factorization is a meaningful approximation — the regime
#: PowerSGD targets — while still truncating (rank < 12)
_PSGD_SHAPE = (16, 12)


def _small_params(shape=_INT8_SHAPE):
    rng = np.random.RandomState(1)
    din, dout = shape
    return {
        "w": jnp.asarray(rng.randn(din, dout).astype(np.float32) * 0.1),
        "b": jnp.zeros((dout,), jnp.float32),
    }


def _data(n, shape=_INT8_SHAPE):
    rng = np.random.RandomState(2)
    din, dout = shape
    x = jnp.asarray(rng.randn(2 * n, din), jnp.float32)
    y = jnp.asarray(rng.randn(2 * n, dout), jnp.float32)
    return x, y


def _loss(p, x, y):
    return jnp.mean((x @ p["w"] + p["b"][None] - y) ** 2)


def _make_step(hvd, dtx, opt_spec, ax):
    mesh = hvd.mesh()

    def step(params, opt_state, x, y):
        l, grads = jax.value_and_grad(_loss)(params, x, y)
        upd, opt_state = dtx.update(grads, opt_state, params)
        params = optax.apply_updates(params, upd)
        return params, opt_state, allreduce(l, Average, axis=ax)

    return jax.jit(_smap(
        step, mesh, (P(), opt_spec, P(ax), P(ax)), (P(), opt_spec, P())
    ))


def _run_trajectory(hvd, tx, opt_spec, steps=12, shape=_INT8_SHAPE):
    ax = hvd.data_axis()
    from horovod_tpu.training import shard_batch

    x, y = _data(hvd.size(), shape)
    xs, ys = shard_batch(x), shard_batch(y)
    p = jax.tree_util.tree_map(jnp.array, _small_params(shape))
    s = tx.init(p)
    step = _make_step(hvd, tx, opt_spec, ax)
    losses = []
    for _ in range(steps):
        p, s, l = step(p, s, xs, ys)
        losses.append(float(l))
    return p, losses


_FP32_BASELINE = {}


def _fp32_trajectory(hvd, steps=12, shape=_INT8_SHAPE):
    """The uncompressed Adam baseline several tests compare against —
    computed once per (steps, shape) (one less shard_map compile each)."""
    key = (steps, shape)
    if key not in _FP32_BASELINE:
        _FP32_BASELINE[key] = _run_trajectory(
            hvd, hvd.DistributedOptimizer(optax.adam(1e-2)), P(),
            steps=steps, shape=shape)
    return _FP32_BASELINE[key]


# ------------------------------------------------------------- quantization


def test_int8_roundtrip_error_bound(hvd):
    """Blockwise quantization error is bounded by half a quantization step
    per element: |x - rt(x)| <= block_maxabs / 127 (bf16 scale slack)."""
    rng = np.random.RandomState(0)
    x = rng.randn(3000).astype(np.float32)
    rt = np.asarray(int8_roundtrip(jnp.asarray(x)))
    assert (rt != x).any()  # above the min-quantize floor: genuinely lossy
    pad = np.zeros(((-len(x)) % INT8_BLOCK,), np.float32)
    blocks = np.concatenate([x, pad]).reshape(-1, INT8_BLOCK)
    bound = np.repeat(np.abs(blocks).max(axis=1) / 127, INT8_BLOCK)[:len(x)]
    assert (np.abs(rt - x) <= bound * 1.01).all()
    # all-zero input quantizes to exactly zero (no 0/0 in the scale)
    z = np.asarray(int8_roundtrip(jnp.zeros(2048, jnp.float32)))
    np.testing.assert_array_equal(z, 0.0)


def test_hlo_quantizer_divides_by_the_wire_scale():
    """The receiver only ever sees the bf16 scale, so the sender must
    divide by exactly that value. An ``astype`` pair is not enough: XLA
    may elide f32->bf16->f32 (it does on TPU, where 4 % of codes then
    came from the unrounded scale) — the rounding is a reduce_precision,
    which no pass removes — and the codes match a NumPy oracle of the
    documented semantics."""
    x = np.random.RandomState(0).randn(64 * INT8_BLOCK).astype(np.float32)
    hlo = functools.partial(quantize_blockwise, use_pallas=False)
    assert "reduce_precision" in str(jax.make_jaxpr(hlo)(x))
    q, scales = jax.jit(hlo)(x)
    m = x.reshape(-1, INT8_BLOCK)
    wire = np.asarray(jnp.asarray(np.abs(m).max(1) / np.float32(127.0))
                      .astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(scales.astype(jnp.float32)), wire)
    np.testing.assert_array_equal(
        np.asarray(q).reshape(m.shape),
        np.clip(np.round(m / wire[:, None]), -127, 127).astype(np.int8))


def test_int8_compress_decompress_shapes(hvd):
    x = jnp.asarray(np.random.RandomState(1).randn(40, 40).astype(np.float32))
    c, ctx = Compression.int8.compress(x)
    assert c.dtype == jnp.int8
    scales = ctx[0]
    assert scales.dtype == jnp.bfloat16
    out = Compression.int8.decompress(c, ctx)
    assert out.shape == x.shape and out.dtype == x.dtype


def test_int8_passthrough_dtypes(hvd):
    """Integer and already-16-bit leaves pass through untouched, exactly
    as fp16 compression passes integers through — and so do float leaves
    below the min-quantize floor, where the ring's per-chunk block padding
    would cost more wire than fp32."""
    for v in (jnp.arange(5, dtype=jnp.int32),
              jnp.full((4,), 1.5, jnp.bfloat16),
              jnp.ones((10,), jnp.float32)):  # tiny bias: below the floor
        c, ctx = Compression.int8.compress(v)
        assert ctx is None and c is v
        assert Compression.int8.decompress(c, ctx) is v
    assert np.asarray(
        int8_roundtrip(jnp.full((10,), 1.0 + 2e-4)))[0] == np.float32(
            1.0 + 2e-4)


def test_wire_bytes_hooks(hvd):
    shape = (784, 512)
    n = 784 * 512
    assert Compression.none.wire_bytes(shape, jnp.float32) == 4 * n
    assert Compression.fp16.wire_bytes(shape, jnp.float32) == 2 * n
    assert Compression.fp16.wire_bytes((6,), jnp.int32) == 24
    assert Compression.int8.wire_bytes(shape, jnp.float32) == \
        n + -(-n // INT8_BLOCK) * 2
    assert Compression.int8.wire_bytes((6,), jnp.int32) == 24
    # below the min-quantize floor: billed dense (and sent dense)
    assert Compression.int8.wire_bytes((512,), jnp.float32) == 512 * 4
    ps = Compression.powersgd(4)
    assert ps.wire_bytes(shape, jnp.float32) == (784 + 512) * 4 * 4
    # 1-D leaves fall back to the int8 pricing (incl. its dense floor)
    assert ps.wire_bytes((2048,), jnp.float32) == 2048 + 8 * 2
    assert ps.wire_bytes((512,), jnp.float32) == 512 * 4
    # a tiny 2-D leaf fails the (d0+m)*r < d0*m crossover and bills dense
    assert not ps.factorizes((2, 3), jnp.float32)
    assert ps.wire_bytes((2, 3), jnp.float32) == 6 * 4


def test_legacy_compressor_falls_back_to_itemsize_probe(hvd):
    """A user compressor predating the wire_bytes hook is billed by the
    scalar-probe itemsize — the old behavior, kept as the fallback."""
    from horovod_tpu.optim import _tree_sync_wire_bytes

    class LegacyHalf:  # no wire_bytes attribute
        @staticmethod
        def compress(t):
            return t.astype(np.float16), t.dtype

        @staticmethod
        def decompress(t, ctx):
            return t.astype(ctx)

    grads = {"w": jnp.ones((64, 32), jnp.float32)}
    assert _tree_sync_wire_bytes(grads, LegacyHalf) == 2048 * 2
    # and a blockwise compressor is billed per leaf, not per element
    assert _tree_sync_wire_bytes(grads, Compression.int8) == 2048 + 8 * 2


# ------------------------------------------------------------- collectives


def test_int8_allreduce_matches_mean(hvd):
    """Eager replicated, eager stacked, and in-jit bound int8 allreduce all
    land within quantization tolerance of the exact mean."""
    n = hvd.size()
    ax = hvd.data_axis()
    rng = np.random.RandomState(3)
    x = rng.randn(n, 1500).astype(np.float32)
    tol = np.abs(x).max() / 127 * 1.5

    out = hvd_mod.allreduce(
        jnp.asarray(x[0]), op=hvd_mod.Average, compression=Compression.int8)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), x[0], atol=tol)

    xs = jax.device_put(
        jnp.asarray(x), NamedSharding(hvd_mod.mesh(), P(ax)))
    out = hvd_mod.allreduce(
        xs, op=hvd_mod.Average, compression=Compression.int8)
    np.testing.assert_allclose(np.asarray(out), x.mean(0), atol=tol)

    def step(v):
        v = jnp.squeeze(v, 0)
        return allreduce(v, Average, axis=ax, compression=Compression.int8)

    f = jax.jit(_smap(step, hvd_mod.mesh(), (P(ax),), P()))
    np.testing.assert_allclose(np.asarray(f(xs)), x.mean(0), atol=tol)
    # and the compiled program must carry s8 collectives — the wire saving
    # is real int8 on the interconnect, not a simulated cast
    hlo = f.lower(xs).compile().as_text()
    assert "s8[" in hlo and "all-to-all" in hlo


def test_int8_sum_op(hvd):
    n = hvd.size()
    x = jnp.full((2000,), 0.5, jnp.float32)
    out = hvd_mod.allreduce(
        x, op=hvd_mod.Sum, compression=Compression.int8)
    np.testing.assert_allclose(np.asarray(out), 0.5 * n, rtol=2e-2)


def test_allreduce_rejects_factorized(hvd):
    with pytest.raises(ValueError, match="PowerSGD"):
        hvd_mod.allreduce(
            jnp.ones(4), compression=Compression.powersgd(2))


# --------------------------------------------------- trajectory acceptance


def test_int8_ef_adam_trajectory_tracks_fp32(hvd):
    """Acceptance 1a: int8+EF Adam over 12 steps tracks the uncompressed
    trajectory within tolerance."""
    p0, l0 = _fp32_trajectory(hvd)
    p1, l1 = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), compression=Compression.int8,
            error_feedback=True), P())
    assert abs(l1[-1] - l0[-1]) / l0[-1] < 0.02
    for k in p0:
        np.testing.assert_allclose(
            np.asarray(p1[k]), np.asarray(p0[k]), atol=0.05)


def test_powersgd_ef_adam_trajectory_tracks_fp32(hvd):
    """Acceptance 1b: PowerSGD(rank=4)+EF over 12 steps — rank-4
    truncation of a 16x12 gradient is genuinely lossy, so the tolerance is
    looser than int8's, but the loss must still track the fp32 descent."""
    p0, l0 = _fp32_trajectory(hvd, shape=_PSGD_SHAPE)
    p1, l1 = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), compression=Compression.powersgd(4),
            error_feedback=True), P(), shape=_PSGD_SHAPE)
    assert l1[-1] < l1[0]                       # it descends
    assert abs(l1[-1] - l0[-1]) / l0[-1] < 0.25  # and tracks fp32


def test_powersgd_full_rank_is_exact(hvd):
    """rank >= min(d0, m) makes one power iteration a projection onto the
    full column space — the factor sync reproduces the matrix exactly
    (the warm-start invariant the trajectory tests build on)."""
    from horovod_tpu.optim import _psgd_factor_sync

    rng = np.random.RandomState(5)
    m2d = jnp.asarray(rng.randn(24, 8).astype(np.float32))
    q0 = jnp.asarray(rng.randn(8, 8).astype(np.float32))
    approx, qn = _psgd_factor_sync(m2d, q0, lambda x: x)
    np.testing.assert_allclose(
        np.asarray(approx), np.asarray(m2d), rtol=1e-4, atol=1e-5)
    assert qn.shape == (8, 8)


def test_powersgd_tiny_leaf_falls_back(hvd):
    """A tiny 2-D leaf fails the (d0+m)*r < d0*m wire crossover and must
    NOT be factorized: its Q slot is None and the update is exact
    (below the int8 floor it rides dense)."""
    from horovod_tpu.optim import _q_leaves

    params = {"w": jnp.ones((2, 3), jnp.float32)}
    tx = hvd.DistributedOptimizer(
        optax.sgd(1.0), compression=Compression.powersgd(4),
        error_feedback=True)
    s = tx.init(params)
    assert _q_leaves(s.q) == [None]
    u, s = tx.update({"w": jnp.full((2, 3), 0.5)}, s, params)
    np.testing.assert_allclose(np.asarray(u["w"]), -0.5, rtol=1e-6)


def test_compressed_sharded_trajectories_compose(hvd):
    """Acceptance 3 (trajectory half): int8 and PowerSGD compose with
    shard_optimizer=True — the sharded trajectory matches its non-sharded
    twin (PowerSGD exactly: same factors, same math; int8 within the
    one-requantize-leg difference) and tracks fp32."""
    ax = hvd.data_axis()
    _, l0 = _fp32_trajectory(hvd)

    _, li = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), shard_optimizer=True,
            compression=Compression.int8, error_feedback=True), P(ax))
    assert abs(li[-1] - l0[-1]) / l0[-1] < 0.02

    _, l0n = _fp32_trajectory(hvd, shape=_PSGD_SHAPE)
    _, lp = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), shard_optimizer=True,
            compression=Compression.powersgd(4), error_feedback=True),
        P(ax), shape=_PSGD_SHAPE)
    _, lp2 = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), compression=Compression.powersgd(4),
            error_feedback=True), P(), shape=_PSGD_SHAPE)
    np.testing.assert_allclose(lp[-1], lp2[-1], rtol=1e-4)
    assert abs(lp[-1] - l0n[-1]) / l0n[-1] < 0.25


@pytest.mark.slow
def test_int8_ef_soak_50_steps(hvd):
    """Soak: EF keeps the int8 trajectory glued to fp32 over 50 steps."""
    _, l0 = _run_trajectory(
        hvd, hvd.DistributedOptimizer(optax.adam(1e-2)), P(), steps=50)
    _, l1 = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), compression=Compression.int8,
            error_feedback=True), P(), steps=50)
    assert abs(l1[-1] - l0[-1]) / max(l0[-1], 1e-6) < 0.05


@pytest.mark.slow
def test_powersgd_ef_soak_50_steps(hvd):
    """Soak: the warm-started rank-4 factorization + EF keeps descending
    over 50 steps — the random quadratic's optimal update is full-rank, so
    rank-4 legitimately trails fp32; the pin is sustained convergence (EF
    keeps feeding the truncated mass back in), not parity."""
    _, l1 = _run_trajectory(
        hvd, hvd.DistributedOptimizer(
            optax.adam(1e-2), compression=Compression.powersgd(4),
            error_feedback=True), P(), steps=50, shape=_PSGD_SHAPE)
    assert l1[-1] < 0.3 * l1[0]       # sustained descent
    assert l1[-1] < l1[11] * 0.75     # still improving past step 12


# ------------------------------------------------------- wire-byte gauges


def test_wire_byte_gauges_int8_and_powersgd_ratios(hvd):
    """Acceptance 2: on the transformer-block tree the reported
    grad_sync_bytes_per_step is <= ~27% of fp32 for int8 (incl. scale
    overhead) and <= 10% for PowerSGD rank-4."""
    hvd.metrics.reset()
    params = _block_params()
    g = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.01), params)

    def gauge_for(compression, ef):
        tx = hvd.DistributedOptimizer(
            optax.sgd(0.1), compression=compression, error_feedback=ef)
        s = tx.init(params)
        tx.update(g, s, params)
        return hvd.metrics.value("grad_sync_bytes_per_step", mode="allreduce")

    fp32 = gauge_for(Compression.none, False)
    i8 = gauge_for(Compression.int8, True)
    ps = gauge_for(Compression.powersgd(4), True)
    assert fp32 and i8 and ps
    assert i8 / fp32 <= 0.27
    assert ps / fp32 <= 0.10
    # and the exact model: 1 byte/elt + bf16 scale per 256-block for
    # leaves above the min-quantize floor, dense fp32 below it
    from horovod_tpu.compression import MIN_QUANT_ELEMS

    wire = sum(
        (p.size + -(-p.size // INT8_BLOCK) * 2)
        if p.size >= MIN_QUANT_ELEMS else 4 * p.size
        for p in jax.tree_util.tree_leaves(params)
    )
    elems = sum(p.size for p in jax.tree_util.tree_leaves(params))
    ring2 = 2 * (hvd.size() - 1) / hvd.size()
    assert i8 == pytest.approx(ring2 * wire)
    assert fp32 == pytest.approx(ring2 * 4 * elems)


def test_sharded_int8_gauge_prices_blockwise(hvd):
    """The sharded (reduce-scatter) gauge prices the padded flat buffer at
    the blockwise int8 rate through the wire_bytes hook."""
    hvd.metrics.reset()
    n = hvd.size()
    params = _small_params()
    tx = hvd.DistributedOptimizer(
        optax.sgd(0.1), shard_optimizer=True,
        compression=Compression.int8, error_feedback=True)
    s = tx.init(params)
    g = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.5), params)
    tx.update(g, s, params)
    got = hvd.metrics.value("grad_sync_bytes_per_step", mode="sharded")
    total = sum(p.size for p in jax.tree_util.tree_leaves(params))
    Lp = total + ((-total) % n)
    ring = (n - 1) / n
    assert got == pytest.approx(ring * (Lp + 2 * -(-Lp // INT8_BLOCK)))


# --------------------------------------------------- reshard / persistence


def test_int8_sharded_reshard_8_4_8_ef_mass(hvd, tmp_path):
    """Acceptance 3 (reshard half, int8): save → consolidate to 4 → back
    to 8; the summed EF residual (total untransmitted gradient mass) is
    invariant and updates continue identically."""
    from horovod_tpu import checkpoint

    params = _small_params()
    tx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.int8, error_feedback=True)
    state = tx.init(params)
    g = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, 1.0 + 2e-3), params)
    for _ in range(3):
        _, state = tx.update(g, state, params)
    mass = {k: np.asarray(v).sum(axis=0) for k, v in state.residual.items()}
    assert any(np.abs(m).max() > 0 for m in mass.values())

    total = sum(p.size for p in jax.tree_util.tree_leaves(params))
    checkpoint.save(str(tmp_path), 3, {"opt": state})
    loaded = checkpoint.restore(str(tmp_path), 3)["opt"]
    st4 = checkpoint.consolidate_opt_state(loaded, params, to_size=4)
    for k, v in st4.residual.items():
        assert v.shape[0] == 4
        np.testing.assert_allclose(
            np.asarray(v).sum(axis=0)[:total], mass[k][:total],
            rtol=1e-5, atol=1e-6)
    st8 = checkpoint.consolidate_opt_state(st4, params, to_size=8)
    u1, _ = tx.update(g, state, params)
    u2, _ = tx.update(g, st8, params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(u1[k]), np.asarray(u2[k]), rtol=1e-5, atol=1e-7)


def test_powersgd_sharded_reshard_8_4_8(hvd, tmp_path):
    """Acceptance 3 (reshard half, PowerSGD): moments, flat EF residuals
    AND the warm-started Q factors survive the 8→4→8 consolidate — Q rows
    re-tile (identical by construction) and updates continue identically."""
    from horovod_tpu import checkpoint
    from horovod_tpu.optim import _q_leaves

    params = _small_params()
    tx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.powersgd(4), error_feedback=True)
    state = tx.init(params)
    g = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.3), params)
    for _ in range(3):
        _, state = tx.update(g, state, params)
    mass = {k: np.asarray(v).sum(axis=0) for k, v in state.residual.items()}
    assert any(np.abs(m).max() > 0 for m in mass.values())

    total = sum(p.size for p in jax.tree_util.tree_leaves(params))
    checkpoint.save(str(tmp_path), 3, {"opt": state})
    loaded = checkpoint.restore(str(tmp_path), 3)["opt"]
    st4 = checkpoint.consolidate_opt_state(loaded, params, to_size=4)
    q4 = [q for q in _q_leaves(st4.q) if q is not None]
    assert all(q.shape[0] == 4 for q in q4)
    for k, v in st4.residual.items():
        np.testing.assert_allclose(
            np.asarray(v).sum(axis=0)[:total], mass[k][:total],
            rtol=1e-5, atol=1e-6)
    st8 = checkpoint.consolidate_opt_state(st4, params, to_size=8)
    for a, b in zip(_q_leaves(state.q), _q_leaves(st8.q)):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    u1, _ = tx.update(g, state, params)
    u2, _ = tx.update(g, st8, params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(u1[k]), np.asarray(u2[k]), rtol=1e-5, atol=1e-7)


def test_broadcast_optimizer_state_skips_powersgd_sharded(hvd):
    """Sharded PowerSGD state leaves (moments, residual, Q — all carrying
    the leading rank axis) are per-rank data: broadcast leaves them be."""
    params = _small_params()
    tx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.powersgd(4), error_feedback=True)
    state = tx.init(params)
    g = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.5), params)
    _, state = tx.update(g, state, params)
    out = hvd.broadcast_optimizer_state(state)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(out)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- composition


def test_powersgd_requires_error_feedback(hvd):
    with pytest.raises(ValueError, match="error_feedback"):
        hvd.DistributedOptimizer(
            optax.sgd(0.1), compression=Compression.powersgd(4))


def test_quantized_rejects_predivide_and_adasum(hvd):
    with pytest.raises(ValueError, match="predivide"):
        hvd.DistributedOptimizer(
            optax.sgd(0.1), compression=Compression.int8,
            gradient_predivide_factor=2.0)
    with pytest.raises(ValueError, match="Adasum"):
        hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Adasum, compression=Compression.int8)


def test_compression_from_env(hvd, monkeypatch):
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), error_feedback=True)
    p = {"w": jnp.full((1200,), 1.0 + 2e-3)}
    s = tx.init(p)
    _, s = tx.update({"w": jnp.full((1200,), 1.0 + 2e-3)}, s, p)
    assert np.abs(np.asarray(s.residual["w"])).max() > 0  # int8 was lossy

    monkeypatch.setenv("HOROVOD_COMPRESSION", "powersgd")
    monkeypatch.setenv("HOROVOD_POWERSGD_RANK", "2")
    from horovod_tpu.optim import _PowerSGDState, _q_leaves

    # env-resolved PowerSGD must work on call sites that never opted into
    # compression kwargs: it implies the error feedback it needs
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    st = tx.init({"w": jnp.ones((8, 6))})
    assert isinstance(st, _PowerSGDState)
    assert [q.shape for q in _q_leaves(st.q) if q is not None] == [(6, 2)]

    monkeypatch.setenv("HOROVOD_COMPRESSION", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        hvd.DistributedOptimizer(optax.sgd(1.0))


def test_gradient_accumulation_composes(hvd):
    """backward_passes_per_step > 1 accumulates locally, then the int8+EF
    exchange fires on the accumulated gradient."""
    tx = hvd.DistributedOptimizer(
        optax.sgd(1.0), compression=Compression.int8,
        error_feedback=True, backward_passes_per_step=2)
    p = {"w": jnp.zeros((1200,), jnp.float32)}
    s = tx.init(p)
    u1, s = tx.update({"w": jnp.ones(1200)}, s, p)
    np.testing.assert_allclose(np.asarray(u1["w"]), 0.0)  # accumulating
    u2, s = tx.update({"w": jnp.ones(1200)}, s, p)
    np.testing.assert_allclose(np.asarray(u2["w"]), -1.0, rtol=2e-2)


def test_eager_stacked_int8_update(hvd):
    """Eager per-rank stacked gradients through the non-sharded int8+EF
    optimizer: the applied update is the mean of the quantized
    contributions."""
    n = hvd.size()
    params = {"w": jnp.ones((40, 30), jnp.float32)}
    tx = hvd.DistributedOptimizer(
        optax.sgd(1.0), compression=Compression.int8, error_feedback=True)
    s = tx.init(params)
    g = np.stack(
        [np.full((40, 30), float(r), np.float32) for r in range(n)])
    grads = {"w": jax.device_put(
        g, NamedSharding(hvd.mesh(), P(hvd.data_axis())))}
    u, s = tx.update(grads, s, params)
    np.testing.assert_allclose(
        np.asarray(u["w"]), -g.mean(axis=0), atol=(n - 1) / 127 * 1.5)


def test_mixed_dtype_sharded_int8_update(hvd):
    """A mixed f32/bf16 tree under sharded int8: the f32 group rides the
    quantized ring (its flat buffer is above the quantize floor), the bf16
    group passes through uncompressed, dtypes and shapes survive."""
    params = {
        "a": jnp.ones((40, 30), jnp.float32),
        "b": jnp.ones((5,), jnp.bfloat16),
        "c": jnp.ones((2, 2), jnp.float32),
    }
    tx = hvd.DistributedOptimizer(
        optax.sgd(1.0), shard_optimizer=True, compression=Compression.int8)
    state = tx.init(params)
    g = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 0.5, p.dtype), params)
    upd, state = tx.update(g, state, params)
    for k, p in params.items():
        assert upd[k].dtype == p.dtype and upd[k].shape == p.shape
        np.testing.assert_allclose(
            np.asarray(upd[k], np.float32), -0.5, rtol=2e-2)


# ------------------------------------------------- hierarchical (2x4 mesh)


@pytest.fixture()
def hvd24():
    from horovod_tpu.ops.hierarchical import set_hierarchical
    from horovod_tpu.parallel.mesh import build_host_mesh

    mesh = build_host_mesh(local=4)
    hvd_mod.init(mesh=mesh)
    set_hierarchical(True)
    yield hvd_mod
    set_hierarchical(None)
    hvd_mod.shutdown()


def test_hier_int8_compresses_cross_hop_only(hvd24):
    """Two-axis int8 allreduce under HOROVOD_HIERARCHICAL_ALLREDUCE:
    the DCN ``cross`` hop rides the int8 ring while the local ICI
    reduce-scatter / all-gather stay full-width — pinned by the compiled
    HLO (the s8 exchange groups over cross, size 2; f32 legs over local,
    size 4) and by numeric equivalence with the flat mean."""
    mesh = hvd24.mesh()
    rng = np.random.RandomState(0)
    x = rng.randn(8, 48, 32).astype(np.float32)
    xs = jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, P(("cross", "local"))))

    def step(v):
        v = jnp.squeeze(v, 0)
        return allreduce(v, Average, axis=("cross", "local"),
                         compression=Compression.int8)

    f = jax.jit(_smap(step, mesh, (P(("cross", "local")),), P()))
    out = np.asarray(f(xs))
    np.testing.assert_allclose(
        out, x.mean(0), atol=np.abs(x).max() / 127 * 2)
    hlo = f.lower(xs).compile().as_text()
    assert "s8[" in hlo
    # the int8 payloads exchange over the cross axis (group size 2): with
    # row-major (cross, local) device order those groups are {i, i+4}
    assert "{{0,4}" in hlo.replace(" ", "")
