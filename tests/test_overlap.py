"""Bucketed backward-pass gradient sync (``horovod_tpu.ops.overlap``).

Acceptance (ISSUE 10):

- ZeRO-1 bucketed and monolithic sync produce **bit-identical** Adam
  trajectories over 12 steps on the 8-device CPU mesh for none/fp16
  (packing is a permutation; the elementwise wire and the cross-rank sum
  commute with it — pinned exactly).
- allreduce-mode bucketed sync produces **bit-identical reduced
  gradients** per step; the full trajectory is pinned to 1e-6 (the two
  programs fuse the Adam elementwise math differently — XLA FMA
  contraction — a 1-ULP/step compiler artifact, not a sync difference;
  the gradient pin isolates the sync itself as exact).
- int8 wire: blockwise scales are layout-dependent, so bucketing
  legitimately re-rounds; trajectories track within quantization
  tolerance with error feedback keyed by bucket.
- interleaving pins: a ``sync_hook``-staged backward issues >= 2
  collectives BETWEEN backward compute fragments (jaxpr profile and
  optimized-HLO text), where the monolithic step issues 0.
- ``hvd.tuning.apply_xla_flags`` never clobbers user-set ``XLA_FLAGS``
  entries and withholds TPU-only flags on non-TPU targets (where they
  are a fatal parse error).
- CI guard: every ``HOROVOD_BUCKET_*`` / ``HOROVOD_OVERLAP*`` /
  ``HOROVOD_XLA_FLAGS*`` env knob in the source appears in the
  docs/performance.md knob table.
"""

import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import tuning
from horovod_tpu.compression import Compression
from horovod_tpu.ops import overlap as ov
from horovod_tpu.ops.collective import _smap, allreduce, Average, Sum

pytestmark = pytest.mark.overlap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# BucketPlan unit tests


class _S:
    def __init__(self, shape, dtype=np.float32):
        self.shape, self.dtype = shape, dtype


def test_plan_reverse_emission_order_and_split():
    # leaves declared [b, w]: backprop emits w's cotangent first, so the
    # plan iterates in reverse leaf order and w fills the first buckets
    leaves = [_S((33,)), _S((64, 33))]
    plan = ov.BucketPlan.build(leaves, n=8, bucket_bytes=4096)  # 1024 elems
    assert plan.buckets[0].segs[0].idx == 1
    assert plan.buckets[0].segs[0].start == 0
    # 64*33 = 2112 elems -> buckets of 1024, 1024, then 64 + the 33-elem b
    sizes = [b.L for b in plan.buckets]
    assert sizes == [1024, 1024, 64 + 33]
    # the boundary splits w: its last segment and b share the final bucket
    last = plan.buckets[-1]
    assert [s.idx for s in last.segs] == [1, 0]
    assert last.segs[0].start == 2048 and last.segs[0].stop == 2112
    # Lp pads to the axis size
    assert all(b.Lp % 8 == 0 for b in plan.buckets)


def test_plan_single_leaf_and_oversized_bucket():
    one = ov.BucketPlan.build([_S((5, 3))], n=8, bucket_bytes=1 << 30)
    assert len(one) == 1 and one.buckets[0].L == 15
    # a bucket capacity below one element still makes progress (1 elem min)
    tiny = ov.BucketPlan.build([_S((3,))], n=1, bucket_bytes=1)
    assert [b.L for b in tiny.buckets] == [1, 1, 1]


def test_plan_mixed_dtypes_stream_per_dtype():
    leaves = [_S((100,), np.float32), _S((100,), np.int32),
              _S((100,), jnp.bfloat16), _S((100,), np.float32)]
    plan = ov.BucketPlan.build(leaves, n=4, bucket_bytes=1 << 20)
    keys = [b.key for b in plan.buckets]
    assert keys == ["float32#0", "bfloat16#0", "int32#0"]
    # the two f32 leaves share one bucket; emission order is reversed
    f32 = plan.groups["float32#0"]
    assert [s.idx for s in f32.segs] == [3, 0]


def test_plan_boundaries_are_world_size_independent():
    leaves = [_S((1000,)), _S((500,))]
    a = ov.BucketPlan.build(leaves, n=2, bucket_bytes=1024)
    b = ov.BucketPlan.build(leaves, n=8, bucket_bytes=1024)
    assert [(x.key, x.segs, x.L) for x in a.buckets] == \
           [(x.key, x.segs, x.L) for x in b.buckets]
    assert [x.Lp for x in a.buckets] != [x.Lp for x in b.buckets] or all(
        x.L % 8 == 0 for x in a.buckets)


def test_pack_assemble_roundtrip_with_split_and_padding():
    rng = np.random.RandomState(0)
    leaves = [jnp.asarray(rng.randn(10).astype(np.float32)),
              jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
              jnp.asarray(rng.randn(4, 5).astype(np.float32))]
    plan = ov.BucketPlan.build(leaves, n=4, bucket_bytes=32)
    flats = {k: ov.pack_group(leaves, b) for k, b in plan.groups.items()}
    for k, b in plan.groups.items():
        assert flats[k].shape == (b.Lp,)
    out = ov.assemble(
        flats, plan.groups, [l.shape for l in leaves],
        [l.dtype for l in leaves])
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resolve_bucket_bytes_env_and_kwargs(monkeypatch):
    monkeypatch.delenv("HOROVOD_OVERLAP", raising=False)
    monkeypatch.delenv("HOROVOD_BUCKET_BYTES", raising=False)
    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
    assert ov.resolve_bucket_bytes(None, None) is None
    assert ov.resolve_bucket_bytes(True, None) == ov.DEFAULT_BUCKET_BYTES
    assert ov.resolve_bucket_bytes(None, 123) == 123  # bytes imply overlap
    monkeypatch.setenv("HOROVOD_OVERLAP", "1")
    assert ov.resolve_bucket_bytes(None, None) == ov.DEFAULT_BUCKET_BYTES
    # the explicit kwarg wins over the env
    assert ov.resolve_bucket_bytes(False, None) is None
    # HOROVOD_BUCKET_BYTES, then the existing fusion-threshold knob
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "2048")
    assert ov.resolve_bucket_bytes(True, None) == 2048
    monkeypatch.setenv("HOROVOD_BUCKET_BYTES", "4096")
    assert ov.resolve_bucket_bytes(True, None) == 4096


# --------------------------------------------------------------------------
# trajectory equivalence: bucketed vs monolithic


def _mk_params(uneven=False):
    rng = np.random.RandomState(0)
    d = 33 if uneven else 32  # 33: nothing divides the 8-way padding
    return {
        "w": jnp.asarray(rng.randn(64, d).astype(np.float32) * 0.1),
        "b": jnp.zeros((d,), jnp.float32),
    }


def _mk_batch(d):
    rng = np.random.RandomState(1)
    return (jnp.asarray(rng.randn(16, 64), jnp.float32),
            jnp.asarray(rng.randn(16, d), jnp.float32))


def _loss(p, x, y):
    return jnp.mean((x @ p["w"] + p["b"][None] - y) ** 2)


def _run_cell(hvd, *, overlap, shard, compression=None, ef=False,
              steps=12, bucket_bytes=4096, uneven=False):
    mesh, ax = hvd.mesh(), hvd.data_axis()
    params = _mk_params(uneven)
    x, y = _mk_batch(params["b"].shape[0])
    kw = dict(shard_optimizer=shard)
    if compression is not None:
        kw.update(compression=compression, error_feedback=ef)
    if overlap:
        kw.update(overlap=True, bucket_bytes=bucket_bytes)
    dtx = hvd.DistributedOptimizer(optax.adam(1e-2), **kw)
    p = jax.tree_util.tree_map(jnp.array, params)
    s = dtx.init(p)
    opt_spec = P(ax) if shard else P()

    def step(pp, ss, xx, yy):
        l, g = jax.value_and_grad(_loss)(pp, xx, yy)
        u, ss = dtx.update(g, ss, pp)
        return optax.apply_updates(pp, u), ss, allreduce(l, Average, axis=ax)

    sm = jax.jit(_smap(
        step, mesh, (P(), opt_spec, P(ax), P(ax)), (P(), opt_spec, P())))
    for _ in range(steps):
        p, s, l = sm(p, s, x, y)
    return p, s, float(l)


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


@pytest.mark.parametrize("comp,ef", [(None, False), (Compression.fp16, True)])
def test_zero1_bucketed_trajectory_bit_identical(hvd, comp, ef):
    pa, _, la = _run_cell(hvd, overlap=False, shard=True,
                          compression=comp, ef=ef)
    pb, sb, lb = _run_cell(hvd, overlap=True, shard=True,
                           compression=comp, ef=ef)
    assert _leaves_equal(pa, pb), "bucketed ZeRO-1 trajectory diverged"
    assert la == lb
    # the bucketed state really is bucketed: per-bucket [N, shard_k]
    # buffers under dtype#k keys
    keys = {
        k for path in map(str, [
            p for p, _ in jax.tree_util.tree_leaves_with_path(sb)
        ]) for k in re.findall(r"float32#\d+", path)
    }
    assert len(keys) >= 2, f"expected multiple buckets, saw {keys}"


@pytest.mark.parametrize("comp,ef", [(None, False), (Compression.fp16, True)])
def test_zero1_bucketed_uneven_padding_bit_identical(hvd, comp, ef):
    """Uneven leading dims (33-wide leaves: every bucket needs its own
    ZeRO padding) — the per-bucket zero padding is inert through Adam."""
    pa, _, _ = _run_cell(hvd, overlap=False, shard=True,
                         compression=comp, ef=ef, uneven=True)
    pb, _, _ = _run_cell(hvd, overlap=True, shard=True,
                         compression=comp, ef=ef, uneven=True)
    assert _leaves_equal(pa, pb)


def test_zero1_single_bucket_matches_monolithic(hvd):
    """One bucket larger than all gradients: the plan degenerates to the
    monolithic packing (modulo the dtype#0 key) — bit-identical."""
    pa, _, _ = _run_cell(hvd, overlap=False, shard=True)
    pb, sb, _ = _run_cell(hvd, overlap=True, shard=True,
                          bucket_bytes=1 << 30)
    assert _leaves_equal(pa, pb)
    paths = "".join(
        str(p) for p, _ in jax.tree_util.tree_leaves_with_path(sb))
    assert "float32#0" in paths and "float32#1" not in paths


def test_allreduce_bucketed_grads_bit_identical_trajectory_close(hvd):
    """Non-sharded mode: the bucketed reduced gradients are bit-identical
    to per-leaf allreduce every step (pinned directly); the 12-step
    trajectory is 1e-6-close — the residual difference is XLA fusing the
    Adam elementwise chain differently between the two programs (FMA
    contraction), not the sync."""
    mesh, ax = hvd.mesh(), hvd.data_axis()
    params = _mk_params()
    x, y = _mk_batch(32)

    def mono(p, xx, yy):
        g = jax.grad(_loss)(p, xx, yy)
        return jax.tree_util.tree_map(
            lambda t: allreduce(t, Average, axis=ax), g)

    def buck(p, xx, yy):
        g = jax.grad(_loss)(p, xx, yy)
        return ov.bucketed_allreduce(
            g, Average, axis=ax, bucket_bytes=4096)[0]

    ga = jax.jit(_smap(mono, mesh, (P(), P(ax), P(ax)), P()))(params, x, y)
    gb = jax.jit(_smap(buck, mesh, (P(), P(ax), P(ax)), P()))(params, x, y)
    assert _leaves_equal(ga, gb), "bucketed sync changed the gradients"

    pa, _, la = _run_cell(hvd, overlap=False, shard=False)
    pb, _, lb = _run_cell(hvd, overlap=True, shard=False)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=0)
    assert abs(la - lb) < 1e-6


def test_allreduce_fp16_bucketed_ef_keyed_by_bucket(hvd):
    """fp16 + EF, non-sharded: residuals ride the bucket-keyed flat
    layout and the trajectory tracks monolithic. Tolerance is an fp16
    ULP, not 1e-6: the non-sharded programs differ by 1 f32 ULP/step
    (XLA FMA fusion — see the `none` test), and once params differ at
    all, values near an fp16 rounding boundary round differently, so the
    divergence floor is the wire's own quantum (EF keeps it bounded)."""
    pa, _, _ = _run_cell(hvd, overlap=False, shard=False,
                         compression=Compression.fp16, ef=True)
    pb, sb, _ = _run_cell(hvd, overlap=True, shard=False,
                          compression=Compression.fp16, ef=True)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=0)
    res = sb.residual
    assert isinstance(res, dict) and all("#" in k for k in res)
    assert len(res) >= 2
    assert all(v.ndim == 1 for v in res.values())


@pytest.mark.parametrize("shard", [False, True])
def test_int8_bucketed_tracks_within_quantization_tolerance(hvd, shard):
    """int8's blockwise scales are layout-dependent: bucketing re-rounds,
    so bit-identicality is impossible by construction — the pin is that
    the EF-corrected trajectories track and converge together."""
    pa, _, la = _run_cell(hvd, overlap=False, shard=shard,
                          compression=Compression.int8, ef=True)
    pb, _, lb = _run_cell(hvd, overlap=True, shard=shard,
                          compression=Compression.int8, ef=True)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=0.05, rtol=0)
    assert abs(la - lb) < 5e-3


def test_mixed_dtype_tree_bucketed_sync_exact(hvd):
    """Mixed f32/bf16/i32 gradient tree through bucketed_allreduce: each
    dtype rides its own bucket stream, bit-equal to per-leaf allreduce."""
    mesh, ax = hvd.mesh(), hvd.data_axis()
    rng = np.random.RandomState(2)
    tree = {
        "f": jnp.asarray(rng.randn(40, 7).astype(np.float32)),
        "h": jnp.asarray(rng.randn(30).astype(np.float32)).astype(
            jnp.bfloat16),
        "i": jnp.arange(24, dtype=jnp.int32).reshape(6, 4),
    }

    def mono(t, seed):
        t = jax.tree_util.tree_map(lambda v: v + seed.astype(v.dtype), t)
        return jax.tree_util.tree_map(
            lambda v: allreduce(v, Sum, axis=ax), t)

    def buck(t, seed):
        t = jax.tree_util.tree_map(lambda v: v + seed.astype(v.dtype), t)
        return ov.bucketed_allreduce(t, Sum, axis=ax, bucket_bytes=64)[0]

    seed = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) * 0
    # per-rank perturbation via the bound axis index
    def mk(fn):
        def inner(t, s):
            idx = jax.lax.axis_index(ax).astype(jnp.float32)
            return fn(t, idx * 0.5)
        return jax.jit(_smap(inner, mesh, (P(), P(ax)), P()))

    ra = mk(mono)(tree, seed)
    rb = mk(buck)(tree, seed)
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(ra[k]), np.asarray(rb[k]))


def test_eager_bucketed_allreduce_replicated_and_stacked(hvd):
    """Eager dispatch: replicated leaves and stacked [N, ...] per-rank
    leaves both reduce bit-equal to the per-leaf eager allreduce."""
    mesh, ax = hvd.mesh(), hvd.data_axis()
    rng = np.random.RandomState(3)
    rep = {"a": jnp.asarray(rng.randn(50).astype(np.float32)),
           "b": jnp.asarray(rng.randn(9, 3).astype(np.float32))}
    out, _ = ov.bucketed_allreduce(rep, Average, axis=ax, bucket_bytes=128)
    ref = jax.tree_util.tree_map(
        lambda v: allreduce(v, Average, axis=ax), rep)
    assert _leaves_equal(out, ref)
    # stacked per-rank values
    st = jax.device_put(
        jnp.asarray(rng.randn(8, 20).astype(np.float32)),
        NamedSharding(mesh, P(ax)))
    out2, _ = ov.bucketed_allreduce(
        {"s": st}, Average, axis=ax, bucket_bytes=32)
    ref2 = allreduce(st, Average, axis=ax)
    np.testing.assert_array_equal(np.asarray(out2["s"]), np.asarray(ref2))


def test_bucketed_sync_rejects_adasum_and_powersgd(hvd):
    from horovod_tpu.ops.collective import Adasum

    with pytest.raises(ValueError, match="Adasum"):
        hvd.DistributedOptimizer(
            optax.adam(1e-3), op=Adasum, overlap=True)
    with pytest.raises(ValueError, match="PowerSGD"):
        hvd.DistributedOptimizer(
            optax.adam(1e-3), compression=Compression.powersgd(2),
            error_feedback=True, overlap=True)
    with pytest.raises(ValueError, match="Adasum"):
        ov.bucketed_allreduce({"a": jnp.ones(4)}, Adasum)


def test_grad_sync_buckets_gauge(hvd):
    hvd.metrics.reset()
    _run_cell(hvd, overlap=True, shard=True, steps=1, bucket_bytes=4096)
    assert hvd.metrics.value("grad_sync_buckets", mode="sharded") >= 2
    _run_cell(hvd, overlap=False, shard=True, steps=1)
    assert hvd.metrics.value("grad_sync_buckets", mode="sharded") == 1


# --------------------------------------------------------------------------
# reshard: bucketed states across world sizes


def test_bucketed_state_reshards_8_4_8(hvd):
    params = _mk_params(uneven=True)
    dtx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.fp16, error_feedback=True,
        overlap=True, bucket_bytes=4096)
    s8 = dtx.init(jax.tree_util.tree_map(jnp.array, params))
    s4 = hvd.reshard_optimizer_state(
        s8, params, to_size=4, bucket_bytes=4096)
    for v in s4.residual.values():
        assert v.shape[0] == 4
    back = hvd.reshard_optimizer_state(
        s4, params, to_size=8, bucket_bytes=4096)
    for (k, a), b in zip(
            sorted(s8.residual.items()),
            (v for _, v in sorted(back.residual.items()))):
        assert a.shape == b.shape
    # mass preservation: the summed residual is unchanged by the trip
    for k in s8.residual:
        np.testing.assert_allclose(
            np.asarray(s8.residual[k]).sum(),
            np.asarray(back.residual[k]).sum(), atol=1e-6)


def test_bucketed_reshard_ambiguous_tail_bucket_uses_key(hvd):
    """A tail bucket whose ZeRO padding makes it the SAME padded size as
    a full sibling (2044 f32 elems @ 4096-byte buckets → L=1024 and
    L=1020, both [8, 128] at n=8) must re-pack by its bucket KEY, not by
    shape guessing — otherwise the 1020-bucket resizes as if it were
    1024 long and the restored state mis-slices."""
    params = {"w": jnp.zeros((2044,), jnp.float32)}
    dtx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.fp16, error_feedback=True,
        overlap=True, bucket_bytes=4096)
    s8 = dtx.init(params)
    assert {v.shape for v in s8.residual.values()} == {(8, 1024)}
    s4 = hvd.reshard_optimizer_state(
        s8, params, to_size=4, bucket_bytes=4096)
    # full bucket: pad(1024, 4)=1024 → [4, 1024]; tail: pad(1020, 4)=1020
    assert s4.residual["float32#0"].shape == (4, 1024)
    assert s4.residual["float32#1"].shape == (4, 1020)
    # and the inner [n, shard] buffers followed their keys too
    mu = jax.tree_util.tree_leaves(s4.inner)
    assert {(4, 256), (4, 255)} <= {tuple(x.shape) for x in mu}
    back = hvd.reshard_optimizer_state(
        s4, params, to_size=8, bucket_bytes=4096)
    assert {v.shape for v in back.residual.values()} == {(8, 1024)}


def test_reshard_plain_state_with_hash_in_param_names_passes_through(hvd):
    """'#' in a USER param name must not trip bucket-state detection:
    plain (non-sharded) states over such trees pass through untouched
    (the documented consolidate_opt_state contract) instead of raising
    the bucket-plan-mismatch error."""
    params = {"block#0": jnp.ones((5,), jnp.float32)}
    tx = optax.adam(1e-2)
    s = tx.init(params)
    out = hvd.reshard_optimizer_state(s, params, to_size=4)
    assert _leaves_equal(s, out)


def test_bucketed_state_reshard_wrong_bucket_bytes_raises(hvd):
    params = _mk_params()
    dtx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.fp16, error_feedback=True,
        overlap=True, bucket_bytes=4096)
    s8 = dtx.init(jax.tree_util.tree_map(jnp.array, params))
    with pytest.raises(ValueError, match="HOROVOD_BUCKET_BYTES"):
        hvd.reshard_optimizer_state(
            s8, params, to_size=4, bucket_bytes=1024)


# --------------------------------------------------------------------------
# interleaving pins: the staged (custom_vjp hook) backward


def _hooked_and_mono_steps(hvd, n_blocks=3, width=32):
    mesh, ax = hvd.mesh(), hvd.data_axis()
    rng = np.random.RandomState(0)
    ws = [jnp.asarray(rng.randn(width, width).astype(np.float32) * 0.1)
          for _ in range(n_blocks)]
    x = jnp.asarray(rng.randn(16, width), jnp.float32)

    def block(w, h):
        return jnp.tanh(h @ w)

    sync = lambda gp: ov.bucketed_allreduce(  # noqa: E731
        gp, Average, axis=ax, bucket_bytes=1 << 20)[0]
    hooked_block = ov.sync_hook(block, sync)

    def loss_hooked(w_list, xx):
        h = xx
        for w in w_list:
            h = hooked_block(w, h)
        return jnp.mean(h ** 2)

    def loss_plain(w_list, xx):
        h = xx
        for w in w_list:
            h = block(w, h)
        return jnp.mean(h ** 2)

    def step_hooked(w_list, xx):
        return jax.grad(loss_hooked)(w_list, xx)

    def step_mono(w_list, xx):
        g = jax.grad(loss_plain)(w_list, xx)
        return jax.tree_util.tree_map(
            lambda t: allreduce(t, Average, axis=ax), g)

    smh = _smap(step_hooked, mesh, (P(), P(ax)), P())
    smm = _smap(step_mono, mesh, (P(), P(ax)), P())
    return smh, smm, ws, x


def test_sync_hook_interleaves_collectives_in_backward(hvd):
    """THE overlap pin: >= 2 collectives strictly between backward
    compute fragments in the staged step's jaxpr; 0 in the monolithic
    step; gradients bit-identical between the two."""
    from horovod_tpu.analysis import (
        collectives_before_last_compute, interleave_profile,
    )

    smh, smm, ws, x = _hooked_and_mono_steps(hvd)
    ph = interleave_profile(smh, ws, x)
    pm = interleave_profile(smm, ws, x)
    assert collectives_before_last_compute(ph) >= 2, ph
    assert collectives_before_last_compute(pm) == 0, pm
    gh = jax.jit(smh)(ws, x)
    gm = jax.jit(smm)(ws, x)
    assert _leaves_equal(gh, gm)


def test_sync_hook_barrier_off_still_correct(hvd):
    mesh, ax = hvd.mesh(), hvd.data_axis()
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(16, 16).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)

    def block(p, h):
        return h @ p

    hooked = ov.sync_hook(
        block, lambda g: allreduce(g, Average, axis=ax), barrier=False)

    def step(p, xx):
        return jax.grad(lambda q: jnp.sum(hooked(q, xx) ** 2))(p)

    def mono(p, xx):
        g = jax.grad(lambda q: jnp.sum(block(q, xx) ** 2))(p)
        return allreduce(g, Average, axis=ax)

    a = jax.jit(_smap(step, mesh, (P(), P(ax)), P()))(w, x)
    b = jax.jit(_smap(mono, mesh, (P(), P(ax)), P()))(w, x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_shardmap_train_step_overlap_schedule_and_equivalence(hvd):
    """Builder integration: overlap=True swaps the per-leaf allreduces
    for K bucket collectives (schedule extractor pin) and the loss
    trajectory matches the default step to fp tolerance."""
    import flax.linen as nn

    from horovod_tpu.analysis import collective_schedule
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(64)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    x_np = np.random.RandomState(0).rand(32, 12, 12).astype(np.float32)
    y_np = np.random.RandomState(1).randint(0, 10, 32)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 12, 12), jnp.float32))
    params0 = variables.get("params", variables)

    def drive(overlap):
        tx = optax.adam(1e-3)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, instrument=False,
            overlap=overlap, bucket_bytes=8192 if overlap else None)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        opt = replicate(tx.init(params))
        xs, ys = shard_batch(x_np), shard_batch(y_np)
        sched = collective_schedule(step, params, {}, opt, xs, ys)
        for _ in range(6):
            params, _stats, opt, loss = step(params, {}, opt, xs, ys)
        return sched, float(loss)

    sched_ov, loss_ov = drive(True)
    sched_mono, loss_mono = drive(False)
    n_ov = sched_ov.counts().get("psum", 0)
    n_mono = sched_mono.counts().get("psum", 0)
    # monolithic: one psum per gradient leaf (4) + stats/loss reductions;
    # bucketed: K buckets replace the per-leaf sync
    assert n_ov != n_mono
    assert n_ov >= 3  # >= 2 gradient buckets + the loss reduction
    assert abs(loss_ov - loss_mono) < 1e-5


def test_make_jit_train_step_accepts_overlap_on_cpu(hvd):
    """pjit-style overlap= arms the XLA flags; on a CPU target the
    TPU-only flags are withheld (they would be a fatal parse error), so
    the call is a clean no-op and the step still trains."""
    import flax.linen as nn

    from horovod_tpu.training import (
        make_jit_train_step, replicate, shard_batch, softmax_xent,
    )

    before = os.environ.get("XLA_FLAGS", "")

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(10)(x.reshape((x.shape[0], -1)))

    model = Tiny()
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8), jnp.float32))
    params = replicate(variables.get("params", variables))
    tx = optax.sgd(1e-2)
    step = make_jit_train_step(
        model, tx, loss_fn=softmax_xent, instrument=False, overlap=True)
    opt = replicate(tx.init(params))
    xs = shard_batch(np.random.RandomState(0).rand(
        32, 8, 8).astype(np.float32))
    ys = shard_batch(np.random.RandomState(1).randint(0, 10, 32))
    params, _stats, opt, loss = step(params, {}, opt, xs, ys)
    assert np.isfinite(float(loss))
    assert os.environ.get("XLA_FLAGS", "") == before, (
        "TPU-only flags leaked into XLA_FLAGS on a CPU target"
    )


# --------------------------------------------------------------------------
# hvd.tuning


def test_tuning_applies_preset_idempotently_on_tpu_target():
    env = {"JAX_PLATFORMS": "tpu"}
    added, skipped = tuning.apply_xla_flags("overlap", env=env)
    assert added and not skipped
    # libtpu parses its flags from LIBTPU_INIT_ARGS; the same flag in
    # XLA_FLAGS aborts backend init ("Unknown flags in XLA_FLAGS")
    assert env["LIBTPU_INIT_ARGS"].split() == added
    assert "XLA_FLAGS" not in env
    again, skipped2 = tuning.apply_xla_flags("overlap", env=env)
    assert not again and len(skipped2) == len(added)


@pytest.mark.parametrize("var", ["LIBTPU_INIT_ARGS", "XLA_FLAGS"])
def test_tuning_never_clobbers_user_set_entries(var):
    user = "--xla_tpu_enable_latency_hiding_scheduler=false"
    env = {"JAX_PLATFORMS": "tpu", var: user}
    added, skipped = tuning.apply_xla_flags("overlap", env=env)
    assert user in env[var]
    everything = env.get("XLA_FLAGS", "") + " " + env["LIBTPU_INIT_ARGS"]
    assert everything.count("xla_tpu_enable_latency_hiding_scheduler") == 1
    assert any("latency_hiding" in f for f in skipped)
    assert all("latency_hiding" not in f for f in added)


def test_tuning_withholds_tpu_flags_on_cpu_target():
    """A --xla_tpu_* flag on a CPU jaxlib is a FATAL parse error, not a
    no-op — the preset must be withheld entirely."""
    env = {"JAX_PLATFORMS": "cpu"}
    added, skipped = tuning.apply_xla_flags("overlap", env=env)
    assert not added and skipped
    assert "XLA_FLAGS" not in env and "LIBTPU_INIT_ARGS" not in env


def test_tuning_env_knob_and_unknown_preset():
    assert tuning.maybe_apply_from_env({}) == ([], [])
    env = {"JAX_PLATFORMS": "tpu",
           tuning.PRESET_ENV: "overlap"}
    added, _ = tuning.maybe_apply_from_env(env)
    assert added
    with pytest.raises(ValueError, match="unknown"):
        tuning.apply_xla_flags("warp-speed", env={})
    assert tuning.apply_xla_flags("none", env={}) == ([], [])


# --------------------------------------------------------------------------
# CI guard: every overlap env knob is in the docs knob table


def test_overlap_env_knobs_documented():
    """Every HOROVOD_BUCKET_* / HOROVOD_OVERLAP* / HOROVOD_XLA_FLAGS* /
    HOROVOD_PALLAS* / HOROVOD_SERVING_* / HOROVOD_ENGINE_* /
    HOROVOD_SLO_* / HOROVOD_REQTRACE* / HOROVOD_FLEET_* /
    HOROVOD_RETRY_ROUTE_* / HOROVOD_PREFIX_* / HOROVOD_SPEC_* /
    HOROVOD_KV_REPLICA* / HOROVOD_KV_FENC* / HOROVOD_FSDP_* /
    HOROVOD_TP_* env knob
    named in the source must appear in docs/performance.md's,
    docs/serving.md's, docs/observability.md's, docs/fault_tolerance.md's,
    or docs/running.md's knob tables
    (metric-catalog-guard pattern, PR 7/9)."""
    knob_re = re.compile(
        r"HOROVOD_(?:BUCKET_[A-Z]+(?:_[A-Z]+)*"
        r"|OVERLAP(?:_[A-Z]+)*"
        r"|PALLAS(?:_[A-Z]+)*"
        r"|SERVING_[A-Z]+(?:_[A-Z]+)*"
        r"|ENGINE_[A-Z]+(?:_[A-Z]+)*"
        r"|SLO(?:_[A-Z]+)*"
        r"|REQTRACE(?:_[A-Z]+)*"
        r"|FLEET_[A-Z]+(?:_[A-Z]+)*"
        r"|RETRY_ROUTE(?:_[A-Z]+)*"
        r"|PREFIX_[A-Z]+(?:_[A-Z]+)*"
        r"|SPEC_[A-Z]+(?:_[A-Z]+)*"
        r"|KV_REPLICA[A-Z]*(?:_[A-Z]+)*"
        r"|KV_FENC[A-Z]*(?:_[A-Z]+)*"
        r"|FSDP_[A-Z]+(?:_[A-Z]+)*"
        r"|TP_[A-Z]+(?:_[A-Z]+)*"
        r"|XLA_FLAGS_[A-Z]+(?:_[A-Z]+)*)")
    knobs = set()
    for dirpath, _dirnames, filenames in os.walk(
            os.path.join(_REPO, "horovod_tpu")):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                knobs |= set(knob_re.findall(f.read()))
    assert {"HOROVOD_BUCKET_BYTES", "HOROVOD_OVERLAP",
            "HOROVOD_OVERLAP_BARRIER", "HOROVOD_PALLAS",
            "HOROVOD_XLA_FLAGS_PRESET", "HOROVOD_ENGINE_PAGE_SIZE",
            "HOROVOD_SERVING_CANARY_FRACTION", "HOROVOD_SLO",
            "HOROVOD_SLO_FAST_WINDOW", "HOROVOD_REQTRACE"} <= knobs
    doc = ""
    for name in ("performance.md", "serving.md", "observability.md",
                 "fault_tolerance.md", "running.md"):
        with open(os.path.join(_REPO, "docs", name)) as f:
            doc += f.read()
    missing = sorted(k for k in knobs if k not in doc)
    assert not missing, (
        f"env knobs named in code but absent from the docs/performance.md "
        f"/ docs/serving.md / docs/observability.md / "
        f"docs/fault_tolerance.md / docs/running.md knob tables: {missing}"
    )
