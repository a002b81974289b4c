"""The byte models of `tools/scaling_projection.py` that no other test
file pins: ZeRO-1's ring arithmetic, the int8 and PowerSGD wire formats, and
the int8 / PowerSGD models against the live `grad_sync_bytes_per_step`
gauge. The models are the tests' reference: counts from shapes, no time."""

import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(_REPO, "tools"))

def test_zero1_sync_byte_model():
    """RS+AG decomposition (ZeRO-1 sharded optimizer): the reduce-scatter
    leg moves exactly half the allreduce's gradient bytes, and the total
    (RS + update all-gather) is ring-equal at full precision."""
    from scaling_projection import zero1_sync_bytes

    B = 4 * 25_600_000  # fp32 ResNet-50-ish gradient volume
    n = 8
    m = zero1_sync_bytes(B, n)
    ring = (n - 1) / n
    assert m["allreduce"] == 2 * ring * B
    assert m["rs"] == ring * B == m["allreduce"] / 2
    assert m["ag"] == ring * B
    assert m["sharded_total"] == m["allreduce"]
    # fp16-compressed wire: RS rides 2-byte gradients, AG full fp32 updates
    c = zero1_sync_bytes(B, n, wire_bytes=B // 2)
    assert c["allreduce"] == ring * B
    assert c["rs"] == ring * B / 2
    assert c["sharded_total"] == ring * (B // 2 + B)
    # degenerate single rank: nothing moves
    z = zero1_sync_bytes(B, 1)
    assert z["allreduce"] == z["sharded_total"] == 0.0


@pytest.mark.compression
def test_int8_sync_byte_model():
    """Blockwise int8 byte model: per-leaf 1 byte/element + one bf16 scale
    per 256-block for leaves above the min-quantize floor, dense fp32
    below it; ring factors as allreduce/RS."""
    from scaling_projection import int8_sync_bytes

    shapes = [(784, 512), (512,), (512, 512), (512,), (512, 10), (10,)]
    m = int8_sync_bytes(shapes, 8)

    def size(s):
        return s[0] * (s[1] if len(s) == 2 else 1)

    elems = sum(size(s) for s in shapes)
    wire = sum(
        size(s) + -(-size(s) // 256) * 2 if size(s) >= 1024
        else 4 * size(s)
        for s in shapes
    )
    ring = 7 / 8
    assert m["wire_bytes"] == wire
    assert m["allreduce"] == pytest.approx(2 * ring * wire)
    assert m["rs"] == pytest.approx(ring * wire)
    assert m["fp32_allreduce"] == pytest.approx(2 * ring * 4 * elems)
    assert 0.25 < m["ratio_vs_fp32"] < 0.26  # ~25.8% incl. scale overhead
    # int shorthand: one flat leaf; a sub-floor leaf is billed dense
    assert int8_sync_bytes(2048, 8)["wire_bytes"] == 2048 + 8 * 2
    assert int8_sync_bytes(256, 8)["wire_bytes"] == 256 * 4


@pytest.mark.compression
def test_powersgd_sync_byte_model():
    from scaling_projection import powersgd_sync_bytes

    shapes = [(64, 192), (64, 64), (2048,)]
    m = powersgd_sync_bytes(shapes, 4, 8)
    factor = (64 + 192) * 4 * 4 + (64 + 64) * 4 * 4
    fb = 2048 + 8 * 2  # 1-D int8 fallback: bytes + scales
    assert m["factor_bytes"] == factor
    assert m["int8_fallback_bytes"] == fb
    assert m["wire_bytes"] == factor + fb
    # a sub-floor 1-D leaf rides (and bills) dense
    assert powersgd_sync_bytes([(192,)], 4, 8)["int8_fallback_bytes"] == 768
    # a tiny 2-D leaf fails the (d0+m)*r < d0*m crossover: factors would
    # cost MORE than the dense leaf, so it falls back (and bills dense)
    tiny = powersgd_sync_bytes([(2, 3)], 4, 8)
    assert tiny["factor_bytes"] == 0
    assert tiny["int8_fallback_bytes"] == 6 * 4


@pytest.mark.compression
def test_int8_model_matches_live_gauge():
    """The analytic model must equal the grad_sync_bytes_per_step gauge the
    instrumented optimizer reports — same hook, zero drift."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.compression import Compression
    from scaling_projection import int8_sync_bytes, powersgd_sync_bytes

    hvd.init()
    try:
        hvd.metrics.reset()
        n = hvd.size()
        params = {"w": jnp.ones((64, 48), jnp.float32),
                  "b": jnp.ones((29,), jnp.float32)}
        shapes = [(29,), (64, 48)]  # tree_leaves order: b, w
        g = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.5), params)

        tx = hvd.DistributedOptimizer(
            optax.sgd(0.1), compression=Compression.int8,
            error_feedback=True)
        s = tx.init(params)
        tx.update(g, s, params)
        gauge = hvd.metrics.value("grad_sync_bytes_per_step",
                                  mode="allreduce")
        assert gauge == pytest.approx(int8_sync_bytes(shapes, n)["allreduce"])

        tx = hvd.DistributedOptimizer(
            optax.sgd(0.1), compression=Compression.powersgd(4),
            error_feedback=True)
        s = tx.init(params)
        tx.update(g, s, params)
        gauge = hvd.metrics.value("grad_sync_bytes_per_step",
                                  mode="allreduce")
        assert gauge == pytest.approx(
            powersgd_sync_bytes(shapes, 4, n)["allreduce"])
    finally:
        hvd.shutdown()
