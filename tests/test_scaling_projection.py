"""Scaling-projection tool: HLO comm-byte extraction + end-to-end run.

The virtual CPU mesh cannot measure scaling efficiency (all devices share
one host core); `tools/scaling_projection.py` provides the relative signal
instead — comm bytes and FLOPs from the COMPILED step, rolled into the ring
roofline. These tests pin the extraction against ground truth (gradient
bytes == 4 B x param count for the fp32-gradient DP step)."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(_REPO, "tools"))
from scaling_projection import comm_bytes_from_hlo  # noqa: E402


def test_comm_bytes_extraction():
    hlo = """
  %ar0 = f32[1000,512] all-reduce(f32[1000,512] %p0), replica_groups={}
  %ar1 = bf16[256] all-reduce(bf16[256] %p1), replica_groups={}
  %t = (f32[10], s32[4]) all-reduce(%a, %b)
  %ag = f32[64,8] all-gather(f32[8,8] %p2), dimensions={0}
  %cp = bf16[4,128] collective-permute(bf16[4,128] %p3), source_target_pairs={{0,1}}
  %a2a = f32[16,2] all-to-all(f32[16,2] %p4), dimensions={0}
  %other = f32[999] add(f32[999] %x, f32[999] %y)
"""
    want = (1000 * 512 * 4 + 256 * 2 + (10 * 4 + 4 * 4) + 64 * 8 * 4
            + 4 * 128 * 2 + 16 * 2 * 4)
    assert comm_bytes_from_hlo(hlo) == want


def test_comm_bytes_async_pairs_counted_once():
    hlo = """
  %s = f32[100] all-reduce-start(f32[100] %p0)
  %d = f32[100] all-reduce-done(f32[100] %s)
  %cs = bf16[8] collective-permute-start(bf16[8] %p1)
  %cd = bf16[8] collective-permute-done(bf16[8] %cs)
  %ags = (f32[8,8], f32[64,8]) all-gather-start(f32[8,8] %p2), dimensions={0}
  %agd = f32[64,8] all-gather-done(%ags)
"""
    # tuple-shaped -start ops count only the result (largest) element
    assert comm_bytes_from_hlo(hlo) == 100 * 4 + 8 * 2 + 64 * 8 * 4


def test_comm_time_model():
    from scaling_projection import comm_ops_from_hlo, comm_time_s

    hlo = """
  %ar = f32[100] all-reduce(f32[100] %a), replica_groups={{0,1,2,3},{4,5,6,7}}
  %cp = f32[50] collective-permute(f32[50] %b), source_target_pairs={{0,1}}
  %ag = f32[80] all-gather(f32[20] %c), replica_groups=[2,4]<=[8], dimensions={0}
"""
    ops = comm_ops_from_hlo(hlo)
    assert [(o, g) for o, _, g in ops] == [
        ("all-reduce", 4), ("collective-permute", 0), ("all-gather", 4)]
    bw = 1e9
    t = comm_time_s(ops, bw, default_group=8)
    want = (2 * 3 / 4 * 400 + 50 * 4 + 3 / 4 * 320) / bw
    assert abs(t - want) < 1e-12


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


def test_zero1_hlo_rs_ag_priced_like_allreduce():
    """An HLO carrying the sharded step's reduce-scatter + all-gather pair
    must price the same wire time as one ring allreduce of the gradient
    volume: RS outputs the 1/g shard costed (g-1)·B_shard, AG outputs the
    full buffer costed (g-1)/g·B — their sum is the allreduce's 2(g-1)/g·B."""
    from scaling_projection import comm_ops_from_hlo, comm_time_s

    ar = """
  %ar = f32[80] all-reduce(f32[80] %g), replica_groups={{0,1,2,3,4,5,6,7}}
"""
    rsag = """
  %rs = f32[10] reduce-scatter(f32[80] %g), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %ag = f32[80] all-gather(f32[10] %u), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
"""
    bw = 1e9
    t_ar = comm_time_s(comm_ops_from_hlo(ar), bw, default_group=8)
    t_rsag = comm_time_s(comm_ops_from_hlo(rsag), bw, default_group=8)
    assert abs(t_ar - t_rsag) < 1e-15
    # and the RS leg alone is half the allreduce
    rs_only = comm_time_s(comm_ops_from_hlo(rsag)[:1], bw, default_group=8)
    assert abs(rs_only - t_ar / 2) < 1e-15


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


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["sp", "tp", "ep", "pp"])
def test_lm_comm_fraction_modes(mode):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "scaling_projection.py"),
         "--parallelism", mode, "--dim", "64", "--depth", "1",
         "--heads", "4", "--seq-len", "256", "--vocab", "512",
         "--mfu", "0.4"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == f"{mode}_comm_fraction"
    assert rec["comm_bytes_per_step"] > 0
    assert 0.0 < rec["comm_fraction_serial"] < 1.0
    assert 0.0 < rec["efficiency_overlapped"] <= 1.0


@pytest.mark.slow
def test_projection_end_to_end():
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "scaling_projection.py"),
         "--model", "resnet50", "--image-size", "64", "--batch-per-chip", "2",
         "--chips", "8", "--mfu", "0.4"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    # the DP step allreduces every fp32 gradient exactly once: comm bytes
    # must equal 4 B x params to within a few % (loss/batch-stat scalars)
    assert abs(rec["comm_bytes_per_step"] - 4 * rec["params"]) \
        < 0.05 * 4 * rec["params"], rec
    eff = rec["projection"]["8"]
    assert 0.0 < eff["efficiency_serial"] <= 1.0
    assert eff["efficiency_overlapped"] >= eff["efficiency_serial"]


@pytest.mark.slow
def test_hier_projection_end_to_end():
    """hier mode: the compiled step must decompose the gradient allreduce
    into local reduce-scatter + cross all-reduce on the 1/local shard +
    local all-gather (reference NCCLHierarchicalAllreduce,
    nccl_operations.cc:162-354), with each fabric's byte count pinned to
    the gradient volume."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "scaling_projection.py"),
         "--parallelism", "hier", "--image-size", "64",
         "--batch-per-chip", "2", "--mfu", "0.4"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "hier_comm_fraction"
    grad = 4 * rec["params"]
    local = rec["mesh"]["local"]
    tol = 0.06
    # DCN carries ONLY the 1/local cross shard — the whole point
    assert abs(rec["comm_bytes_by_fabric"]["dcn"] - grad / local) \
        < tol * grad, rec["comm_bytes_by_fabric"]
    # ICI carries the local reduce-scatter output (grad/local) plus the
    # local all-gather output (grad)
    assert abs(rec["comm_bytes_by_fabric"]["ici"] - (grad + grad / local)) \
        < tol * grad, rec["comm_bytes_by_fabric"]
    for cfg in rec["multi_host_projection"].values():
        assert cfg["hier_speedup"] > 1.0
