#!/usr/bin/env python
"""Synthetic image-model benchmark — the rebuild's analog of reference
``examples/tensorflow2_synthetic_benchmark.py`` (ResNet-50, synthetic images,
img/s). ``--model`` also covers the reference scaling table's resnet101 /
inception3 / vgg16 (``docs/benchmarks.rst:10-14``). Prints ONE JSON line:

    {"metric": "resnet50_images_per_sec_per_chip", "value": ..., "unit":
     "img/s/chip", "vs_baseline": ...}

Baseline: the reference's only published absolute number, 103.6 img/s/GPU
(tf_cnn_benchmarks ResNet-101, bs 64/GPU, 16 Pascal P100 over 25GbE —
``docs/benchmarks.rst:26-42``; see BASELINE.md).

The default mode runs the img/s workload in this process, on the chip:
it exits non-zero when the platform is not ``tpu`` (a CPU timing is not a
device number), and every result line names the ``device_kind`` it ran on.
"""

import argparse
import json
import os
import sys
import time

BASELINE_IMG_S_PER_CHIP = 103.6


# name -> (models attr, default image size, has reference baseline).
# resnet101/inception3/vgg16 are the reference's scaling-table workloads
# (docs/benchmarks.rst:10-14); its only *absolute* number is the ResNet-type
# 103.6 img/s/GPU, so vs_baseline is null for the other families.
_MODELS = {
    "resnet50": ("ResNet50", 224, True),
    "resnet101": ("ResNet101", 224, True),
    "inception3": ("InceptionV3", 299, False),
    "vgg16": ("VGG16", 224, False),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument(
        "--model",
        choices=sorted(_MODELS),
        default="resnet50",
        help="benchmark workload; the reference's scaling table covers "
        "resnet101, inception3 and vgg16 (docs/benchmarks.rst:10-14)",
    )
    p.add_argument("--batch-size", type=int, default=128, help="per-chip batch")
    p.add_argument(
        "--image-size", type=int, default=None,
        help="default: 299 for inception3, else 224",
    )
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument(
        "--shard-optimizer", action="store_true",
        help="ZeRO-1: reduce-scatter gradient sync + sharded optimizer "
        "state (DistributedOptimizer(shard_optimizer=True))",
    )
    p.add_argument(
        "--zero-ab", action="store_true",
        help="run the sharded-vs-allreduce A/B rung (small explicit-"
        "collective model, both sync modes) and print its JSON line; "
        "records zero1_ab_* gauges + grad_sync_bytes_per_step in the "
        "metrics registry. CPU-safe.",
    )
    p.add_argument(
        "--fsdp-ab", action="store_true",
        help="run the ZeRO-3-vs-ZeRO-1 A/B rung (gather-on-use param "
        "sharding vs sharded optimizer state on the same small model); "
        "records fsdp_ab_step_ratio plus the measured "
        "param_gather_bytes_per_step / grad_sync_bytes_per_step gauges "
        "and prints ONE JSON line with the analytic zero3_sync_bytes "
        "model. CPU-safe; with no healthy device it still emits the "
        "byte-model line.",
    )
    p.add_argument(
        "--publish-ab", action="store_true",
        help="run the weight-publication A/B rung (same small model with "
        "streaming publication to an in-process KV on vs off) and print "
        "its JSON line; records publish_ab_step_ratio + "
        "serving_publish_wire_bytes gauges plus the analytic "
        "delta+int8-vs-full-checkpoint byte model. CPU-safe; with no "
        "healthy device it still emits the byte-model line.",
    )
    p.add_argument(
        "--serving-ab", action="store_true",
        help="run the serving-engine A/B rung: the same ragged request "
        "set decoded by the continuous-batching paged engine vs one "
        "static right-padded generate() batch; records "
        "serving_ab_goodput_ratio and prints ONE JSON line with the "
        "analytic slot-token goodput model "
        "(tools/scaling_projection.py::serving_goodput). CPU-safe; with "
        "no healthy device it still emits the model line.",
    )
    p.add_argument(
        "--prefix-ab", action="store_true",
        help="run the prefix-cache A/B rung: the same ragged request set "
        "served cold vs prefix-cached through one engine; records "
        "prefix_ab_prefill_ratio and prints ONE JSON line with the "
        "analytic prefill-token model "
        "(tools/scaling_projection.py::prefix_prefill_flops); the "
        "measured serving_prefill_tokens deltas must match the model "
        "exactly. CPU-safe; with no healthy device it still emits the "
        "model line.",
    )
    p.add_argument(
        "--spec-ab", action="store_true",
        help="run the speculative-decoding A/B rung: the same ragged "
        "request set decoded plain vs with a full-depth draft (100%% "
        "acceptance by construction); records spec_ab_goodput_ratio and "
        "prints ONE JSON line with the analytic acceptance model "
        "(tools/scaling_projection.py::spec_decode_tokens); the measured "
        "spec_proposed/spec_accepted counters must match the model "
        "exactly. CPU-safe; with no healthy device it still emits the "
        "model line.",
    )
    p.add_argument(
        "--straggler-ab", action="store_true",
        help="run the straggler A/B rung: the same eager-collective step "
        "loop with and without an injected HOROVOD_CHAOS rank_slow charge, "
        "with the fleet aggregator attributing the straggler live; "
        "records straggler_ab_step_ratio and prints ONE JSON line with "
        "the detected rank + measured arrival spread. CPU-safe.",
    )
    p.add_argument(
        "--numerics-ab", action="store_true",
        help="run the numerics-guard A/B rung: the same guarded train "
        "loop clean vs under a HOROVOD_CHAOS grad_spike charge; records "
        "the numerics_ab_step_ratio gauge (guarded-spiked / clean step "
        "time — the guard's overhead plus the skipped step) and prints "
        "ONE JSON line with the detection step. CPU-safe.",
    )
    p.add_argument(
        "--input-ab", action="store_true",
        help="run the input-pipeline A/B rung: the same jitted step fed "
        "by a ResumableLoader with prefetch on vs off (synchronous host "
        "gather); records the input_ab_step_ratio gauge (serial / "
        "overlapped step time) and prints ONE JSON line with the "
        "measured compute/load split plus the analytic "
        "tools/scaling_projection.py::input_step_time model. CPU-safe; "
        "with no healthy device it still emits the analytic-model line.",
    )
    p.add_argument(
        "--elastic-chaos", action="store_true",
        help="run the elastic chaos soak rung: inject rank_fail mid-run "
        "(HOROVOD_CHAOS), let the elastic coordinator shrink + regrow the "
        "mesh, and report the recovery latency as the "
        "elastic_recovery_latency_seconds gauge + one JSON line. CPU-safe.",
    )
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument(
        "--compression",
        choices=["none", "fp16", "int8", "powersgd"],
        default=None,
        help="gradient wire compression for the measured workload "
        "(HOROVOD_COMPRESSION spelling; powersgd implies error feedback "
        "and the ZeRO-1 exchange). --fp16-allreduce is the legacy alias "
        "for --compression fp16.",
    )
    p.add_argument(
        "--powersgd-rank", type=int, default=None,
        help="rank for --compression powersgd (default: "
        "HOROVOD_POWERSGD_RANK, else 4)",
    )
    p.add_argument(
        "--compression-ab", action="store_true",
        help="run the compression A/B rung (same small model through "
        "none/fp16/int8/powersgd sync) and print its JSON line; records "
        "compression_ab_step_ratio gauges + measured wire-byte gauges. "
        "CPU-safe; with no healthy device it still emits the byte-model "
        "A/B line so the perf trajectory is never empty.",
    )
    p.add_argument(
        "--overlap-ab", action="store_true",
        help="run the comm/compute-overlap A/B rung (same small model "
        "through the explicit-collective ZeRO-1 step, bucketed vs "
        "monolithic gradient sync) and print its JSON line; records the "
        "overlap_ab_step_ratio gauge + per-mode grad_sync_bytes_per_step "
        "and grad_sync_buckets, plus the analytic "
        "tools/scaling_projection.py::overlap_step_time model. CPU-safe; "
        "with no healthy device it still emits the analytic-model line.",
    )
    p.add_argument(
        "--pallas-ab", action="store_true",
        help="run the Pallas-kernel A/B rung (the same small ZeRO-1 + "
        "int8 + fused-Adam step with HOROVOD_PALLAS=1 vs =0) and print "
        "its JSON line; records the pallas_ab_step_ratio gauge, both "
        "arms' billed wire bytes vs the ring model, and the analytic "
        "tools/scaling_projection.py::pallas_hot_path_bytes HBM model "
        "(wire INVARIANCE itself is pinned by the schedule-fingerprint "
        "tests, not this gauge). CPU-safe: off-TPU the fused arm runs "
        "the kernels in Pallas interpret mode (an equivalence surface, "
        "so the CPU time ratio is interpreter overhead, not a speedup); "
        "with no healthy device it still emits the analytic-model line.",
    )
    p.add_argument(
        "--bucket-bytes", type=int, default=None,
        help="bucket capacity for --overlap-ab / overlapped workloads "
        "(default: HOROVOD_BUCKET_BYTES, else 256 KiB for the A/B's "
        "small model — the 64 MB production default would leave it one "
        "bucket and measure nothing)",
    )
    p.add_argument(
        "--trace-dir",
        default=None,
        help="after the timed loop, capture an XLA device trace of a few "
        "extra train steps into this dir (the real-workload overlap "
        "artifact; reference docs/timeline.rst analog)",
    )
    args = p.parse_args()
    if args.iters < 1 or args.batch_size < 1:
        p.error("--iters and --batch-size must be >= 1")
    if args.image_size is None:
        args.image_size = _MODELS[args.model][1]

    if args.zero_ab:
        return _run_zero_ab(args)

    if args.fsdp_ab:
        return _run_fsdp_ab(args)

    if args.compression_ab:
        return _run_compression_ab(args)

    if args.overlap_ab:
        return _run_overlap_ab(args)

    if args.pallas_ab:
        return _run_pallas_ab(args)

    if args.publish_ab:
        return _run_publish_ab(args)

    if args.serving_ab:
        return _run_serving_ab(args)

    if args.prefix_ab:
        return _run_prefix_ab(args)

    if args.spec_ab:
        return _run_spec_ab(args)

    if args.straggler_ab:
        return _run_straggler_ab(args)

    if args.numerics_ab:
        return _run_numerics_ab(args)

    if args.input_ab:
        return _run_input_ab(args)

    if args.elastic_chaos:
        return _run_elastic_chaos(args)

    return _run_benchmark(args)


def _run_zero_ab(args):
    """Sharded-vs-allreduce A/B rung: train the same small MLP through the
    explicit-collective (shard_map) step twice — gradient allreduce vs the
    ZeRO-1 reduce-scatter/all-gather DistributedOptimizer — and record the
    step-time ratio plus both modes' ``grad_sync_bytes_per_step`` in the
    metrics registry. Prints ONE JSON line. Runs anywhere (CPU mesh
    included); on a no-overlap host the ratio is a floor, the bytes model
    is exact either way."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )
    from horovod_tpu.profiler import timed_steps

    hvd.init()
    n = hvd.size()

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    rng = jax.random.PRNGKey(0)
    batch = max(n * 8, 32)
    x_np = np.random.RandomState(0).rand(batch, 28, 28).astype(np.float32)
    y_np = np.random.RandomState(1).randint(0, 10, batch)
    sample = jnp.zeros((1, 28, 28), jnp.float32)
    variables = model.init(rng, sample)
    params0 = variables.get("params", variables)
    iters = max(args.iters, 5)

    def run(mode):
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        if mode == "sharded":
            tx = hvd.DistributedOptimizer(
                optax.adam(1e-3), shard_optimizer=True)
            step = make_shardmap_train_step(
                model, tx, loss_fn=softmax_xent, shard_optimizer=True,
                instrument=False)
        else:
            tx = optax.adam(1e-3)
            step = make_shardmap_train_step(
                model, tx, loss_fn=softmax_xent, instrument=False)
        opt_state = tx.init(params)
        if mode != "sharded":
            opt_state = replicate(opt_state)
        xs, ys = shard_batch(x_np), shard_batch(y_np)
        state = [params, {}, opt_state]
        for _ in range(3):  # warmup / compile
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
        jax.block_until_ready(state[0])

        def one():
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
            return loss

        losses, dt = timed_steps(one, iters)
        assert all(np.isfinite(l) for l in losses), losses[-3:]
        bytes_now = hvd.metrics.value(
            "grad_sync_bytes_per_step", mode=mode)
        return dt / iters, bytes_now

    t_ar, b_ar = run("allreduce")
    t_sh, b_sh = run("sharded")
    ratio = t_sh / t_ar if t_ar else None
    if hvd.metrics.enabled():
        hvd.metrics.gauge(
            "zero1_ab_step_ratio",
            help="sharded / allreduce step time (explicit-collective A/B)",
        ).set(ratio)
    out = {
        "metric": "zero1_sharded_vs_allreduce_step_ratio",
        "value": round(ratio, 4) if ratio is not None else None,
        "unit": "x",
        "n_chips": n,
        "allreduce_step_s": round(t_ar, 6),
        "sharded_step_s": round(t_sh, 6),
        "grad_sync_bytes_per_step": {"allreduce": b_ar, "sharded": b_sh},
        "grad_bytes_halved": (
            bool(b_ar and b_sh and b_sh <= 0.55 * b_ar)
        ),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _fsdp_byte_model(n: int) -> dict:
    """Analytic ZeRO-3-vs-ZeRO-1 wire bytes for the A/B MLP — emitted even
    when no device comes up (the byte model is exact on any mesh; only the
    step-time ratio needs live hardware)."""
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    from scaling_projection import zero3_sync_bytes

    fp32 = zero3_sync_bytes(_AB_SHAPES, n)
    i8 = zero3_sync_bytes(_AB_SHAPES, n, wire="int8")
    return {
        "zero3_total_bytes": {"none": fp32["zero3_total"],
                              "int8": i8["zero3_total"]},
        "param_gather_bytes": {"none": fp32["param_gather"],
                               "int8": i8["param_gather"]},
        "grad_reduce_scatter_bytes": fp32["grad_reduce_scatter"],
        "zero1_total_bytes": fp32["zero1_total"],
        "wire_ratio_vs_zero1": {
            "none": round(fp32["zero3_total"] / fp32["zero1_total"], 4)
            if fp32["zero1_total"] else 0.0,
            "int8": round(i8["zero3_total"] / fp32["zero1_total"], 4)
            if fp32["zero1_total"] else 0.0,
        },
    }


def _run_fsdp_ab(args):
    """ZeRO-3 vs ZeRO-1 A/B rung: the same small MLP through the explicit-
    collective step with gather-on-use param sharding
    (``DistributedOptimizer(shard_params=True)``) vs the ZeRO-1 sharded
    optimizer, plus the measured ``param_gather_bytes_per_step`` /
    ``grad_sync_bytes_per_step`` gauges and the analytic
    ``zero3_sync_bytes`` model. Records ``fsdp_ab_step_ratio`` and prints
    ONE JSON line. CPU-safe; with no healthy device it still emits the
    byte-model line."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    def _emit_model_only(reason, n=8):
        out = {
            "metric": "fsdp_ab_step_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "byte_model": _fsdp_byte_model(n),
        }
        print(json.dumps(out), flush=True)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.profiler import timed_steps
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0
    n = hvd.size()

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    batch = max(n * 8, 32)
    x_np = np.random.RandomState(0).rand(batch, 28, 28).astype(np.float32)
    y_np = np.random.RandomState(1).randint(0, 10, batch)
    sample = jnp.zeros((1, 28, 28), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), sample)
    params0 = variables.get("params", variables)
    iters = max(args.iters, 5)

    def run(mode):
        params = jax.tree_util.tree_map(jnp.array, params0)
        if mode == "zero3":
            params = hvd.fsdp_pack_params(params)
            tx = hvd.DistributedOptimizer(
                optax.adam(1e-3), shard_params=True)
            step = make_shardmap_train_step(
                model, tx, loss_fn=softmax_xent, shard_params=True,
                instrument=False)
        else:
            tx = hvd.DistributedOptimizer(
                optax.adam(1e-3), shard_optimizer=True)
            step = make_shardmap_train_step(
                model, tx, loss_fn=softmax_xent, shard_optimizer=True,
                instrument=False)
            params = replicate(params)
        opt_state = tx.init(params)
        xs, ys = shard_batch(x_np), shard_batch(y_np)
        state = [params, {}, opt_state]
        for _ in range(3):  # warmup / compile
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
        jax.block_until_ready(jax.tree_util.tree_leaves(state[0]))

        def one():
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
            return loss

        losses, dt = timed_steps(one, iters)
        assert all(np.isfinite(l) for l in losses), losses[-3:]
        metric_mode = "zero3" if mode == "zero3" else "sharded"
        return dt / iters, hvd.metrics.value(
            "grad_sync_bytes_per_step", mode=metric_mode)

    t_z1, b_z1 = run("zero1")
    t_z3, b_z3 = run("zero3")
    gather_bytes = hvd.metrics.value(
        "param_gather_bytes_per_step", mode="zero3")
    ratio = t_z3 / t_z1 if t_z1 else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "fsdp_ab_step_ratio",
            help="ZeRO-3 / ZeRO-1 step time (explicit-collective A/B)",
        ).set(ratio)
    out = {
        "metric": "fsdp_ab_step_ratio",
        "value": round(ratio, 4) if ratio is not None else None,
        "unit": "x",
        "n_chips": n,
        "zero1_step_s": round(t_z1, 6),
        "zero3_step_s": round(t_z3, 6),
        "grad_sync_bytes_per_step": {"zero1": b_z1, "zero3": b_z3},
        "param_gather_bytes_per_step": gather_bytes,
        "byte_model": _fsdp_byte_model(n),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _resolve_compression(args):
    """(compressor, error_feedback, name) from --compression /
    --fp16-allreduce. int8 and powersgd pair with error feedback — the
    convergence-safe configuration the docs recommend; fp16 keeps its
    historical EF-less spelling for baseline comparability."""
    from horovod_tpu.compression import Compression

    name = args.compression or ("fp16" if args.fp16_allreduce else "none")
    if name == "powersgd":
        return Compression.powersgd(args.powersgd_rank), True, name
    comp = {"none": Compression.none, "fp16": Compression.fp16,
            "int8": Compression.int8}[name]
    return comp, name == "int8", name


#: param shapes of the compression-ab MLP (28*28 -> 512 -> 512 -> 10), the
#: input to the byte models when no device ever comes up
_AB_SHAPES = [(784, 512), (512,), (512, 512), (512,), (512, 10), (10,)]


def _compression_byte_model(n: int, rank: int) -> dict:
    """Analytic per-mode wire bytes for the A/B model — emitted even when
    the device never produces a healthy window, so the round's perf
    trajectory records the byte A/B regardless (the CPU-mesh model is
    exact; only the step-time ratio needs a live mesh)."""
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    from scaling_projection import (
        int8_sync_bytes, powersgd_sync_bytes, zero1_sync_bytes,
    )

    import numpy as _np

    elems = sum(int(_np.prod(s)) for s in _AB_SHAPES)
    fp32 = zero1_sync_bytes(4 * elems, n)
    fp16 = zero1_sync_bytes(4 * elems, n, wire_bytes=2 * elems)
    i8 = int8_sync_bytes(_AB_SHAPES, n)
    ps = powersgd_sync_bytes(_AB_SHAPES, rank, n)
    return {
        "grad_elems": elems,
        "rs_bytes": {
            "none": fp32["rs"], "fp16": fp16["rs"], "int8": i8["rs"],
            # P/Q ride full ring allreduces — the model's allreduce figure
            "powersgd": ps["allreduce"],
        },
        "wire_ratio_vs_fp32": {
            "none": 1.0, "fp16": 0.5,
            "int8": round(i8["ratio_vs_fp32"], 4),
            # powersgd vs the fp32 RS leg: its allreduce total over fp32's
            # one-way reduce-scatter bytes
            "powersgd": round(ps["allreduce"] / fp32["rs"], 4)
            if fp32["rs"] else 0.0,
        },
        "powersgd_rank": rank,
    }


def _run_compression_ab(args):
    """Compression A/B rung: the same small MLP through the ZeRO-1
    explicit-collective step under none / fp16 / int8 / powersgd wire
    compression. Records per-mode ``compression_ab_step_ratio`` gauges
    (mode step time / uncompressed step time) plus the measured
    ``grad_sync_bytes_per_step`` gauges, and prints ONE JSON line. Runs
    anywhere (CPU mesh included: the byte model is exact there, the time
    ratio a floor); if no backend comes up at all, the byte-model line is
    emitted anyway so the perf trajectory is never empty."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    rank = args.powersgd_rank or int(
        os.environ.get("HOROVOD_POWERSGD_RANK", "4"))

    def _emit_model_only(reason, n=8):
        out = {
            "metric": "compression_ab_step_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "byte_model": _compression_byte_model(n, rank),
        }
        print(json.dumps(out), flush=True)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.compression import Compression
    from horovod_tpu.profiler import timed_steps
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0
    n = hvd.size()

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    batch = max(n * 8, 32)
    x_np = np.random.RandomState(0).rand(batch, 28, 28).astype(np.float32)
    y_np = np.random.RandomState(1).randint(0, 10, batch)
    sample = jnp.zeros((1, 28, 28), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), sample)
    params0 = variables.get("params", variables)
    iters = max(args.iters, 5)
    modes = {
        "none": (Compression.none, False),
        "fp16": (Compression.fp16, True),
        "int8": (Compression.int8, True),
        "powersgd": (Compression.powersgd(rank), True),
    }

    def run(comp, ef):
        tx = hvd.DistributedOptimizer(
            optax.adam(1e-3), shard_optimizer=True, compression=comp,
            error_feedback=ef)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, shard_optimizer=True,
            instrument=False)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        opt_state = tx.init(params)
        xs, ys = shard_batch(x_np), shard_batch(y_np)
        state = [params, {}, opt_state]
        for _ in range(3):  # warmup / compile
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
        jax.block_until_ready(state[0])

        def one():
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
            return loss

        losses, dt = timed_steps(one, iters)
        assert all(np.isfinite(l) for l in losses), losses[-3:]
        return dt / iters, hvd.metrics.value(
            "grad_sync_bytes_per_step", mode="sharded")

    step_s, sync_bytes, ratios = {}, {}, {}
    for name, (comp, ef) in modes.items():
        step_s[name], sync_bytes[name] = run(comp, ef)
        ratios[name] = (
            round(step_s[name] / step_s["none"], 4)
            if step_s.get("none") else None
        )
        if hvd.metrics.enabled() and ratios[name] is not None:
            hvd.metrics.gauge(
                "compression_ab_step_ratio",
                help="compressed / uncompressed step time "
                     "(explicit-collective ZeRO-1 A/B)",
                compression=name,
            ).set(ratios[name])
    out = {
        "metric": "compression_ab_step_ratio",
        "value": ratios.get("int8"),
        "unit": "x",
        "n_chips": n,
        "step_s": {k: round(v, 6) for k, v in step_s.items()},
        "step_ratio_vs_none": ratios,
        "grad_sync_bytes_per_step": sync_bytes,
        "byte_model": _compression_byte_model(n, rank),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _overlap_model(n: int, bucket_bytes: int, batch: int) -> dict:
    """Analytic overlap model for the A/B MLP — emitted even when no
    device comes up. Byte side (exact on any mesh): bucketing moves the
    same gradient bytes as the monolithic packing (per-bucket ZeRO
    padding is the only delta, reported). Time side (a projection, not a
    measurement): ``overlap_step_time`` evaluated at the TPU v4
    operating point — ring comm time for the model's gradient bytes over
    ICI vs its fwd+bwd FLOPs at peak."""
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    from scaling_projection import _HW, overlap_step_time, zero1_sync_bytes

    from horovod_tpu.ops.overlap import BucketPlan

    import jax as _jax
    import numpy as _np

    leaves = [_jax.ShapeDtypeStruct(s, _np.float32) for s in _AB_SHAPES]
    elems = sum(int(_np.prod(s)) for s in _AB_SHAPES)
    grad_bytes = 4 * elems
    plan1 = BucketPlan.build(leaves, n=1, bucket_bytes=bucket_bytes)
    plan_n = BucketPlan.build(leaves, n=n, bucket_bytes=bucket_bytes)
    mono = zero1_sync_bytes(grad_bytes, n)
    # per-bucket ZeRO padding: the only wire-byte delta bucketing adds
    pad_bytes = 4 * sum(b.Lp - b.L for b in plan_n.buckets) \
        - 4 * ((-elems) % n)
    hw = _HW["tpu-v4"]
    flops = 6 * batch * sum(
        int(_np.prod(s)) for s in _AB_SHAPES if len(s) == 2)
    t_compute = flops / hw["peak_flops"]
    t_comm = mono["allreduce"] / hw["ici_bw"]
    return {
        "grad_bytes": grad_bytes,
        "bucketed_bytes": 4 * sum(b.L for b in plan1.buckets),
        "bucket_pad_bytes_vs_monolithic": pad_bytes,
        "n_buckets": len(plan1.buckets),
        "bucket_bytes": bucket_bytes,
        "projection_v4": overlap_step_time(
            t_compute, t_comm, len(plan1.buckets), latency_s=1e-6),
    }


def _run_overlap_ab(args):
    """Comm/compute-overlap A/B rung: the same small MLP through the
    explicit-collective ZeRO-1 step with bucketed (overlap) vs
    monolithic gradient sync. Records the ``overlap_ab_step_ratio``
    gauge (bucketed / monolithic step time), both modes' measured
    ``grad_sync_bytes_per_step`` + the ``grad_sync_buckets`` gauge, and
    prints ONE JSON line with the analytic
    ``overlap_step_time`` model. Runs anywhere — the 8-device CPU mesh
    timeshares one core, so the measured ratio there is an overhead
    floor (~1.0), never a speedup; the byte parity and the bucket count
    are exact on any mesh, and with no backend at all the analytic line
    is still emitted."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    bucket_bytes = args.bucket_bytes or int(os.environ.get(
        "HOROVOD_BUCKET_BYTES", str(256 * 1024)))

    def _emit_model_only(reason, n=8, batch=64):
        out = {
            "metric": "overlap_ab_step_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "overlap_model": _overlap_model(n, bucket_bytes, batch),
        }
        print(json.dumps(out), flush=True)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.profiler import timed_steps
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0
    n = hvd.size()

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    batch = max(n * 8, 32)
    x_np = np.random.RandomState(0).rand(batch, 28, 28).astype(np.float32)
    y_np = np.random.RandomState(1).randint(0, 10, batch)
    sample = jnp.zeros((1, 28, 28), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), sample)
    params0 = variables.get("params", variables)
    iters = max(args.iters, 5)

    def run(overlap):
        # overlap=False explicitly: with HOROVOD_OVERLAP=1 exported (the
        # very knob this rung documents) an unset kwarg would bucket the
        # BASELINE arm too and the A/B would measure nothing
        kw = dict(shard_optimizer=True, overlap=False)
        if overlap:
            kw.update(overlap=True, bucket_bytes=bucket_bytes)
        tx = hvd.DistributedOptimizer(optax.adam(1e-3), **kw)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, shard_optimizer=True,
            instrument=False)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        opt_state = tx.init(params)
        xs, ys = shard_batch(x_np), shard_batch(y_np)
        state = [params, {}, opt_state]
        for _ in range(3):  # warmup / compile
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
        jax.block_until_ready(state[0])

        def one():
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
            return loss

        losses, dt = timed_steps(one, iters)
        assert all(np.isfinite(l) for l in losses), losses[-3:]
        return dt / iters, hvd.metrics.value(
            "grad_sync_bytes_per_step", mode="sharded"), hvd.metrics.value(
            "grad_sync_buckets", mode="sharded")

    t_mono, b_mono, k_mono = run(False)
    t_ov, b_ov, k_ov = run(True)
    ratio = t_ov / t_mono if t_mono else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "overlap_ab_step_ratio",
            help="bucketed / monolithic step time (explicit-collective "
                 "ZeRO-1 A/B)",
        ).set(ratio)
    out = {
        "metric": "overlap_ab_step_ratio",
        "value": round(ratio, 4) if ratio is not None else None,
        "unit": "x",
        "n_chips": n,
        "monolithic_step_s": round(t_mono, 6),
        "bucketed_step_s": round(t_ov, 6),
        "grad_sync_bytes_per_step": {"monolithic": b_mono, "bucketed": b_ov},
        "grad_sync_buckets": {"monolithic": k_mono, "bucketed": k_ov},
        "overlap_model": _overlap_model(n, bucket_bytes, batch),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


#: the --pallas-ab workload tree: one fat f32 matrix + biases, small
#: enough that the off-TPU interpret-mode arm stays in CI budget while
#: the flat ZeRO packing still quantizes (above the 1024-element floor)
_PALLAS_AB_SHAPES = [(784, 64), (64,), (64, 10), (10,)]


def _pallas_byte_model(n: int = 8) -> dict:
    """Analytic HBM-traffic model for the Pallas A/B — emitted even when
    no device comes up (exact on any mesh: it depends only on shapes)."""
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    from scaling_projection import pallas_hot_path_bytes

    return pallas_hot_path_bytes(
        _PALLAS_AB_SHAPES, n, error_feedback=True, epilogue="scatter")


def _run_pallas_ab(args):
    """Pallas-kernel A/B rung: the same small MLP through the ZeRO-1 +
    int8 + error-feedback + fused-Adam step with ``HOROVOD_PALLAS=1``
    (fused kernels) vs ``=0`` (discrete HLO). Records the
    ``pallas_ab_step_ratio`` gauge (fused / discrete step time), both
    arms' billed ``grad_sync_bytes_per_step``, and prints ONE JSON line
    with the analytic ``pallas_hot_path_bytes`` HBM model plus the
    ring-model wire bytes the gauges should equal. The byte gauges are
    the trace-time per-leaf wire-pricing model, identical across arms
    by construction — they pin that both programs BILL the same wire,
    not that the compiled wire is unchanged; the schedule-fingerprint
    matrix (tests/test_pallas.py) is what pins wire invariance. Runs
    anywhere: off-TPU the fused arm executes the kernels in Pallas
    INTERPRET mode — the equivalence surface, so the CPU time ratio
    measures interpreter overhead plus millisecond-scale timing noise
    (usually > 1, occasionally < 1 on the timeshared mesh) and is never
    a perf signal either way — and with no backend at all the analytic
    line still emits."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    def _emit_model_only(reason, n=8):
        out = {
            "metric": "pallas_ab_step_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "pallas_model": _pallas_byte_model(n),
        }
        print(json.dumps(out), flush=True)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.compression import Compression, Int8Compressor
    from horovod_tpu.ops.collective import _smap, allreduce, Average
    from horovod_tpu.profiler import timed_steps
    from horovod_tpu.training import shard_batch
    from jax.sharding import PartitionSpec as P

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0
    n = hvd.size()
    ax = hvd.data_axis()
    mesh = hvd.mesh()

    rng = np.random.RandomState(0)
    params0 = {
        "w1": jnp.asarray(rng.randn(784, 64).astype(np.float32) * 0.05),
        "b1": jnp.zeros((64,), jnp.float32),
        "w2": jnp.asarray(rng.randn(64, 10).astype(np.float32) * 0.05),
        "b2": jnp.zeros((10,), jnp.float32),
    }
    x_np = rng.rand(max(n * 4, 16), 784).astype(np.float32)
    y_np = rng.randn(x_np.shape[0], 10).astype(np.float32)
    # interpret mode pays per-grid-step interpreter overhead, so the
    # measured arm stays short OFF-TPU only; a TPU run honors --iters
    iters = max(args.iters, 3)
    if jax.default_backend() != "tpu":
        iters = min(iters, 10)

    def loss_fn(p, x, y):
        h = jnp.maximum(x @ p["w1"] + p["b1"][None], 0.0)
        return jnp.mean((h @ p["w2"] + p["b2"][None] - y) ** 2)

    def run(pallas: str):
        prev = os.environ.get("HOROVOD_PALLAS")
        os.environ["HOROVOD_PALLAS"] = pallas
        try:
            tx = hvd.DistributedOptimizer(
                hvd.fused_adam(1e-3), compression=Compression.int8,
                error_feedback=True, shard_optimizer=True)
            params = jax.tree_util.tree_map(jnp.array, params0)
            state = tx.init(params)

            def step(p, s, x, y):
                l, g = jax.value_and_grad(loss_fn)(p, x, y)
                u, s = tx.update(g, s, p)
                p = optax.apply_updates(p, u)
                return p, s, allreduce(l, Average, axis=ax)

            sm = jax.jit(_smap(
                step, mesh, (P(), P(ax), P(ax), P(ax)), (P(), P(ax), P())
            ))
            xs, ys = shard_batch(x_np), shard_batch(y_np)
            box = [params, state]
            for _ in range(2):  # warmup / compile
                box[0], box[1], loss = sm(box[0], box[1], xs, ys)
            jax.block_until_ready(box[0])

            def one():
                box[0], box[1], loss = sm(box[0], box[1], xs, ys)
                return loss

            losses, dt = timed_steps(one, iters)
            assert all(np.isfinite(l) for l in losses), losses[-3:]
            return dt / iters, hvd.metrics.value(
                "grad_sync_bytes_per_step", mode="sharded")
        finally:
            if prev is None:
                os.environ.pop("HOROVOD_PALLAS", None)
            else:
                os.environ["HOROVOD_PALLAS"] = prev

    t_disc, b_disc = run("0")
    t_fused, b_fused = run("1")
    ratio = t_fused / t_disc if t_disc else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "pallas_ab_step_ratio",
            help="fused-Pallas / discrete-HLO step time (ZeRO-1 + int8 + "
                 "fused-Adam A/B; interpreter overhead off-TPU)",
        ).set(ratio)
    # the ring-model wire bytes both gauges should equal: ONE f32 flat
    # group of Lp = E padded to the axis size, priced by the compressor
    elems = sum(
        int(np.prod(s)) for s in _PALLAS_AB_SHAPES)
    lp = elems + ((-elems) % n)
    ring = (n - 1) / n if n > 1 else 0.0
    wire_model = ring * Int8Compressor.wire_bytes((lp,), jnp.float32)
    out = {
        "metric": "pallas_ab_step_ratio",
        "value": round(ratio, 4) if ratio is not None else None,
        "unit": "x",
        "n_chips": n,
        "discrete_step_s": round(t_disc, 6),
        "fused_step_s": round(t_fused, 6),
        "interpret": jax.default_backend() != "tpu",
        "grad_sync_bytes_per_step": {
            "discrete": b_disc, "fused": b_fused,
            "ring_model": wire_model,
        },
        "pallas_model": _pallas_byte_model(n),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _publish_byte_model(keyframe_every: int = 8) -> dict:
    """Analytic publish bytes for the A/B model — emitted even when no
    device comes up, so the round's perf trajectory always records the
    delta+int8 vs full-checkpoint comparison (exact on any mesh)."""
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    from scaling_projection import publish_bytes

    return publish_bytes(_AB_SHAPES, keyframe_every=keyframe_every)


def _run_publish_ab(args):
    """Weight-publication A/B rung: the same small MLP stepped with
    streaming weight publication ON (every step, int8 deltas + periodic
    keyframes to an in-process KV) vs OFF. Records the
    ``publish_ab_step_ratio`` gauge (published / bare step time), the
    measured ``serving_publish_wire_bytes`` gauges, and ONE JSON line with
    the analytic delta-vs-full-checkpoint byte model. A subscriber polls
    every generation and the run asserts it reconstructs the trainer's
    weights — the rung doubles as an end-to-end protocol check. Runs
    anywhere (CPU mesh included; the byte model is exact there, the time
    ratio an upper bound — publication is host-side work)."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    keyframe_every = 8

    def _emit_model_only(reason):
        out = {
            "metric": "publish_ab_step_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "byte_model": _publish_byte_model(keyframe_every),
        }
        print(json.dumps(out), flush=True)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import checkpoint as _ckpt
    from horovod_tpu.profiler import timed_steps
    from horovod_tpu.run.rendezvous import KVStoreServer
    from horovod_tpu.serving import WeightPublisher, WeightSubscriber
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0
    n = hvd.size()

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            x = nn.Dense(512)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    batch = max(n * 8, 32)
    x_np = np.random.RandomState(0).rand(batch, 28, 28).astype(np.float32)
    y_np = np.random.RandomState(1).randint(0, 10, batch)
    sample = jnp.zeros((1, 28, 28), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), sample)
    params0 = variables.get("params", variables)
    iters = max(args.iters, 5)
    server = KVStoreServer()

    def run(publisher):
        tx = hvd.DistributedOptimizer(optax.adam(1e-3))
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, instrument=False)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        opt_state = tx.init(params)
        xs, ys = shard_batch(x_np), shard_batch(y_np)
        state = [params, {}, opt_state]
        for _ in range(3):  # warmup / compile
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
        jax.block_until_ready(state[0])
        counter = {"step": 0}

        def one():
            state[0], state[1], state[2], loss = step(
                state[0], state[1], state[2], xs, ys)
            counter["step"] += 1
            if publisher is not None:
                publisher.publish({"params": state[0]}, counter["step"])
            else:
                float(loss)  # fence: match the publisher's D2H sync cost
            return loss

        losses, dt = timed_steps(one, iters)
        assert all(np.isfinite(l) for l in losses), losses[-3:]
        return dt / iters, state[0]

    bare_s, _ = run(None)
    pub = WeightPublisher(
        server, keyframe_every=keyframe_every, register=False)
    pub_s, final_params = run(pub)
    ratio = round(pub_s / bare_s, 4) if bare_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "publish_ab_step_ratio",
            help="published / bare step time (streaming weight "
                 "publication every step)",
        ).set(ratio)

    # protocol self-check: a subscriber reconstructs the trainer's weights
    sub = WeightSubscriber(server)
    tree = sub.wait_for_generation(pub.generation, timeout=30)
    for got, want in zip(
        jax.tree_util.tree_leaves(tree),
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            np.asarray, final_params)),
    ):
        np.testing.assert_allclose(got, want, atol=5e-2)

    ckpt_bytes = _ckpt.state_nbytes(final_params)
    out = {
        "metric": "publish_ab_step_ratio",
        "value": ratio,
        "unit": "x",
        "n_chips": n,
        "step_s": {"bare": round(bare_s, 6), "published": round(pub_s, 6)},
        "generations": pub.generation,
        "subscriber_generation": sub.generation,
        "publish_wire_bytes": {
            "key": hvd.metrics.value(
                "serving_publish_wire_bytes", kind="key"),
            "delta": hvd.metrics.value(
                "serving_publish_wire_bytes", kind="delta"),
        },
        "checkpoint_bytes": ckpt_bytes,
        "byte_model": _publish_byte_model(keyframe_every),
        "device_kind": jax.devices()[0].device_kind,
    }
    server.close()
    print(json.dumps(out), flush=True)
    return 0


def _run_serving_ab(args):
    """Serving-engine A/B rung: one ragged request set decoded twice —
    (a) through the continuous-batching paged engine (sequences join at
    iteration boundaries, finished slots readmit immediately, prefill
    chunked into the decode schedule) and (b) as one static right-padded
    ``generate()`` batch that holds every row until the whole wave
    finishes. Records ``serving_ab_goodput_ratio`` (engine goodput /
    static goodput, generated tokens per second) and prints ONE JSON line
    beside the analytic slot-token model
    (``tools/scaling_projection.py::serving_goodput``). Both arms run
    compile-warm (the engine is reused across runs; the static waves are
    jitted per shape), so the measured CPU ratio is an honest FLOOR: on
    millisecond steps the engine's per-iteration host scheduling and
    logits readback dominate and the ratio lands well under 1 — the
    padded-work saving the model prices needs accelerator-scale step
    times to show up. The run also asserts the engine's greedy tokens
    match ``generate()`` exactly — the rung doubles as an end-to-end
    parity check."""
    import numpy as np

    from tools.scaling_projection import serving_goodput

    max_new = 8
    max_batch = 4
    prefill_chunk = 8
    rng = np.random.RandomState(0)
    prompt_lens = [int(x) for x in rng.randint(4, 25, size=12)]

    def _emit_model_only(reason):
        out = {
            "metric": "serving_ab_goodput_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "goodput_model": serving_goodput(
                prompt_lens, max_new, max_batch=max_batch,
                prefill_chunk=prefill_chunk),
        }
        print(json.dumps(out), flush=True)

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0

    from horovod_tpu.models.transformer import TransformerLM, generate
    from horovod_tpu.serving.engine import InferenceEngine

    model = TransformerLM(vocab=256, dim=64, depth=2, heads=4, mlp_ratio=2,
                          max_len=64, dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [rng.randint(1, 256, size=l).astype(np.int32)
               for l in prompt_lens]

    # static arm: ceil(R / B) right-padded generate() waves. The wave fn
    # is jitted (one compile per wave shape, cached across runs) so BOTH
    # arms are compile-warm in the timed passes and the ratio measures
    # scheduling, not trace/lowering overhead.
    static_fns = {}

    def _static_fn(shape):
        if shape not in static_fns:
            static_fns[shape] = jax.jit(
                lambda p, pad, lens: generate(
                    model, p, pad, max_new_tokens=max_new,
                    prompt_lens=lens))
        return static_fns[shape]

    def run_static():
        outs = []
        for i in range(0, len(prompts), max_batch):
            wave = prompts[i:i + max_batch]
            tmax = max(len(p) for p in wave)
            pad = np.zeros((len(wave), tmax), np.int32)
            for j, p in enumerate(wave):
                pad[j, :len(p)] = p
            lens = np.asarray([len(p) for p in wave], np.int32)
            toks = np.asarray(_static_fn(pad.shape)(
                params, jnp.asarray(pad), jnp.asarray(lens)))
            outs.extend(
                toks[j, lens[j]:lens[j] + max_new]
                for j in range(len(wave)))
        return outs

    # ONE engine across warmup + timed runs: a fresh engine per run would
    # carry a fresh jit cache, so the timed arm would re-trace and
    # re-compile while the static arm stays warm — deflating the ratio
    eng = InferenceEngine(
        model, page_size=8, num_pages=64, max_batch=max_batch,
        prefill_chunk=prefill_chunk, max_seq_len=40)
    eng.set_weights(params, generation=1)

    def run_engine():
        reqs = [eng.submit(p, max_new, rid=f"ab-{i}")
                for i, p in enumerate(prompts)]
        eng.run_until_idle()
        return [np.asarray(r.generated) for r in reqs]

    # warmup both arms (compiles dominate a first pass)
    static_out = run_static()
    engine_out = run_engine()
    for a, b in zip(engine_out, static_out):
        np.testing.assert_array_equal(a, b)

    total_new = len(prompts) * max_new
    t0 = time.perf_counter()
    run_static()
    static_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_engine()
    engine_s = time.perf_counter() - t0
    ratio = round((total_new / engine_s) / (total_new / static_s), 4) \
        if engine_s and static_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "serving_ab_goodput_ratio",
            help="continuous-batching engine goodput / static batched "
                 "generate() goodput (tokens per second)",
        ).set(ratio)
    out = {
        "metric": "serving_ab_goodput_ratio",
        "value": ratio,
        "unit": "x",
        "n_requests": len(prompts),
        "max_new_tokens": max_new,
        "wall_s": {"static": round(static_s, 6),
                   "engine": round(engine_s, 6)},
        "goodput_tokens_per_s": {
            "static": round(total_new / static_s, 2),
            "engine": round(total_new / engine_s, 2),
        },
        "goodput_model": serving_goodput(
            prompt_lens, max_new, max_batch=max_batch,
            prefill_chunk=prefill_chunk),
        "parity": "token-identical",
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_prefix_ab(args):
    """Prefix-cache A/B rung: the same ragged request set served twice
    through ONE engine — first cold (every prompt pays full prefill, and
    its full prompt pages enter the refcounted index at finish), then
    cached (admission aliases the resident pages and prefills only the
    non-shared tail). Records ``prefix_ab_prefill_ratio`` (cold wall /
    cached wall for the full drain) and prints ONE JSON line beside the
    analytic ``tools/scaling_projection.py::prefix_prefill_flops``
    model. The measured ``serving_prefill_tokens`` deltas must match the
    model EXACTLY — the model replicates the engine's hit rounding
    (lcm(page, chunk) alignment, capped below the prompt end), so any
    drift is a real caching bug. Tokens from the cached pass must be
    bit-identical to the cold pass (and both to ``generate()`` — the
    cold pass rides the same parity-pinned engine)."""
    import numpy as np

    from tools.scaling_projection import prefix_prefill_flops

    max_new = 8
    max_batch = 4
    prefill_chunk = 8
    page_size = 8
    rng = np.random.RandomState(0)
    prompt_lens = [int(x) for x in rng.randint(10, 33, size=10)]
    model_line = prefix_prefill_flops(
        prompt_lens, prompt_lens, page_size=page_size,
        prefill_chunk=prefill_chunk)

    def _emit_model_only(reason):
        out = {
            "metric": "prefix_ab_prefill_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "prefill_model": model_line,
        }
        print(json.dumps(out), flush=True)

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0

    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.serving.engine import InferenceEngine

    model = TransformerLM(vocab=256, dim=64, depth=2, heads=4,
                          mlp_ratio=2, max_len=64, dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [rng.randint(1, 256, size=l).astype(np.int32)
               for l in prompt_lens]
    eng = InferenceEngine(
        model, page_size=page_size, num_pages=128, max_batch=max_batch,
        prefill_chunk=prefill_chunk, max_seq_len=48, prefix_cache=True)
    eng.set_weights(params, generation=1)

    def run(batch, tag):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new, rid=f"{tag}-{i}")
                for i, p in enumerate(batch)]
        eng.run_until_idle()
        return (time.perf_counter() - t0,
                [np.asarray(r.generated) for r in reqs])

    # compile warmup on a DIFFERENT prompt set (same lengths): both
    # measured passes run compile-warm, and the warmup prompts share no
    # prefix with the measured ones, so the measured cold pass is cold
    warmup = [rng.randint(1, 256, size=l).astype(np.int32)
              for l in prompt_lens]
    run(warmup, "warm")

    def tokens_counter():
        return hvd.metrics.value("serving_prefill_tokens") \
            if hvd.metrics.enabled() else None

    before = tokens_counter()
    cold_s, cold_toks = run(prompts, "cold")
    mid = tokens_counter()
    cached_s, cached_toks = run(prompts, "cached")
    after = tokens_counter()
    for a, b in zip(cached_toks, cold_toks):
        np.testing.assert_array_equal(a, b)
    measured_cold = measured_cached = None
    if before is not None:
        measured_cold = int(mid - before)
        measured_cached = int(after - mid)
        assert measured_cold == model_line["cold_prefill_tokens"], (
            measured_cold, model_line)
        assert measured_cached == model_line["cached_prefill_tokens"], (
            measured_cached, model_line)
    ratio = round(cold_s / cached_s, 4) if cached_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "prefix_ab_prefill_ratio",
            help="cold drain wall / prefix-cached drain wall for the "
                 "same request set (one engine, warm jit cache)",
        ).set(ratio)
    out = {
        "metric": "prefix_ab_prefill_ratio",
        "value": ratio,
        "unit": "x",
        "n_requests": len(prompts),
        "wall_s": {"cold": round(cold_s, 6),
                   "cached": round(cached_s, 6)},
        "measured_prefill_tokens": {"cold": measured_cold,
                                    "cached": measured_cached},
        "prefill_model": model_line,
        "parity": "token-identical",
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_spec_ab(args):
    """Speculative-decoding A/B rung: the same ragged request set decoded
    by a plain engine and by one speculating with a FULL-DEPTH draft —
    draft argmax ≡ target argmax, so acceptance is deterministically
    100% and the ``spec_proposed`` / ``spec_accepted`` counters must
    match ``tools/scaling_projection.py::spec_decode_tokens`` EXACTLY
    (each request: ``(max_new−1) // (K+1)`` speculative iterations of
    ``K+1`` tokens, remainder decoded plain). Records
    ``spec_ab_goodput_ratio`` (spec tokens/s over plain tokens/s; on CPU
    the draft's extra forwards usually land it under 1 — the model's
    ``decode_goodput_ratio`` prices the real win at ``draft_cost < 1``)
    and prints ONE JSON line. Both arms must be token-identical."""
    import numpy as np

    from tools.scaling_projection import spec_decode_tokens

    max_new = 10
    lookahead = 3
    n_requests = 8
    model_line = spec_decode_tokens(
        max_new, lookahead, acceptance_rate=1.0, draft_cost=1.0,
        n_requests=n_requests)

    def _emit_model_only(reason):
        out = {
            "metric": "spec_ab_goodput_ratio",
            "value": None,
            "unit": "x",
            "skipped": reason,
            "spec_model": model_line,
        }
        print(json.dumps(out), flush=True)

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    try:
        hvd.init()
    except Exception as e:
        _emit_model_only(f"tpu-unavailable: {type(e).__name__}")
        return 0

    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.serving.engine import InferenceEngine

    model = TransformerLM(vocab=256, dim=64, depth=2, heads=4,
                          mlp_ratio=2, max_len=64, dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=int(l)).astype(np.int32)
               for l in rng.randint(4, 21, size=n_requests)]
    plain = InferenceEngine(
        model, page_size=8, num_pages=64, max_batch=4,
        prefill_chunk=8, max_seq_len=40)
    plain.set_weights(params, generation=1)
    # full-depth draft: acceptance is 100% by construction, making the
    # counter pin exact; a REAL deployment uses draft_depth << depth
    spec = InferenceEngine(
        model, page_size=8, num_pages=64, max_batch=4,
        prefill_chunk=8, max_seq_len=40, draft_depth=model.depth,
        spec_lookahead=lookahead)
    spec.set_weights(params, generation=1)

    def run(eng, tag):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new, rid=f"{tag}-{i}")
                for i, p in enumerate(prompts)]
        eng.run_until_idle()
        return (time.perf_counter() - t0,
                [np.asarray(r.generated) for r in reqs])

    run(plain, "warm-p")
    run(spec, "warm-s")

    def cval(name):
        return hvd.metrics.value(name) if hvd.metrics.enabled() else None

    p0, a0 = cval("spec_proposed"), cval("spec_accepted")
    plain_s, plain_toks = run(plain, "plain")
    spec_s, spec_toks = run(spec, "spec")
    for a, b in zip(spec_toks, plain_toks):
        np.testing.assert_array_equal(a, b)
    measured_proposed = measured_accepted = None
    if p0 is not None:
        measured_proposed = int(cval("spec_proposed") - p0)
        measured_accepted = int(cval("spec_accepted") - a0)
        assert measured_proposed == model_line["proposed"], (
            measured_proposed, model_line)
        assert measured_accepted == model_line["accepted"], (
            measured_accepted, model_line)
    total_new = len(prompts) * max_new
    ratio = round((total_new / spec_s) / (total_new / plain_s), 4) \
        if spec_s and plain_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "spec_ab_goodput_ratio",
            help="speculative-decode goodput / plain-decode goodput "
                 "(tokens per second, full-depth draft)",
        ).set(ratio)
    out = {
        "metric": "spec_ab_goodput_ratio",
        "value": ratio,
        "unit": "x",
        "n_requests": len(prompts),
        "max_new_tokens": max_new,
        "lookahead": lookahead,
        "wall_s": {"plain": round(plain_s, 6),
                   "spec": round(spec_s, 6)},
        "measured": {"proposed": measured_proposed,
                     "accepted": measured_accepted},
        "spec_model": model_line,
        "parity": "token-identical",
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_straggler_ab(args):
    """Straggler A/B rung: time the same eager-collective step loop with
    and without an injected ``rank_slow`` chaos charge while the fleet
    aggregation plane (publisher → KV → rank-0 aggregator) attributes the
    straggler live. Records the ``straggler_ab_step_ratio`` gauge
    (slowed / clean step time — on a per-collective delay of D with C
    collectives per step the analytic expectation is
    ``1 + C·D/clean_step``) and prints ONE JSON line carrying the detected
    rank + measured arrival spread, so the rung doubles as an end-to-end
    check of the detection path. Runs anywhere (CPU mesh included)."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.observability import aggregate, straggler
    from horovod_tpu.resilience import chaos, health
    from horovod_tpu.run.rendezvous import KVStoreServer

    hvd.init()
    n = hvd.size()
    slow_rank = min(3, n - 1)
    delay = 0.05
    iters = max(args.iters, 5)
    collectives_per_step = 2
    x = np.random.RandomState(0).rand(256, 64).astype(np.float32)

    server = KVStoreServer()
    try:
        pub = aggregate.MetricsPublisher(server, rank=0, interval=60.0)
        agg = aggregate.FleetAggregator(server, register=False)

        def run(with_chaos):
            chaos.configure(
                f"rank_slow={slow_rank}:{delay}" if with_chaos else None
            )
            straggler.reset()
            health.reset()
            detected = None
            t0 = time.time()
            for step in range(iters):
                straggler.set_step(step)
                for _ in range(collectives_per_step):
                    np.asarray(hvd.allreduce(x, hvd.Sum))
                pub.publish_once()
                out = agg.collect()
                if out["straggler"] is not None and detected is None:
                    detected = dict(out["straggler"], at_step=step)
            return (time.time() - t0) / iters, detected

        clean_s, _ = run(False)
        slow_s, detected = run(True)
    finally:
        chaos.reset()
        server.close()
    ratio = round(slow_s / clean_s, 4) if clean_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "straggler_ab_step_ratio",
            help="rank_slow-injected / clean step time (straggler A/B)",
        ).set(ratio)
    out = {
        "metric": "straggler_ab_step_ratio",
        "value": ratio,
        "unit": "x",
        "n_chips": n,
        "clean_step_s": round(clean_s, 6),
        "slowed_step_s": round(slow_s, 6),
        "injected": {"rank": slow_rank, "seconds": delay},
        "expected_ratio": round(
            1.0 + collectives_per_step * delay / clean_s, 4
        ) if clean_s else None,
        "detected_rank": None if detected is None else detected["rank"],
        "detected_at_step": (
            None if detected is None else detected["at_step"]
        ),
        "detected_spread_s": (
            None if detected is None
            else round(detected["spread_seconds"], 6)
        ),
        "health": health.health_state().name,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_numerics_ab(args):
    """Numerics-guard A/B rung: run the same guarded explicit-collective
    train loop clean and under an injected ``grad_spike_at_step`` chaos
    charge. Records the ``numerics_ab_step_ratio`` gauge (spiked / clean
    step time — the guard's fused-reduction overhead is symmetric, so the
    expected ratio is ~1.0; the spiked run additionally proves the
    detector by reporting which step was marked BAD and skipped) and
    prints ONE JSON line with the detection step. Runs anywhere (CPU mesh
    included)."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.resilience import chaos, numerics
    from horovod_tpu.training import (
        make_shardmap_train_step, shard_batch, softmax_xent,
    )
    import flax.linen as nn

    hvd.init()

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(8)(nn.relu(nn.Dense(32)(x)))

    n = hvd.size()
    iters = max(args.iters, 10)
    spike_at = 7  # past the guard's 5-step EWMA warmup (+1 warmup call)
    spike_scale = 1e4
    model = Tiny()
    rng = np.random.RandomState(0)
    x = shard_batch(rng.rand(4 * n, 16).astype(np.float32))
    y = shard_batch(rng.randint(0, 8, 4 * n))
    params0 = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))["params"]

    def run(with_chaos):
        chaos.configure(
            f"grad_spike_at_step={spike_at}:{spike_scale}"
            if with_chaos else None
        )
        tx = hvd.DistributedOptimizer(
            optax.adam(1e-2), shard_optimizer=True, numerics_guard=True)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, shard_optimizer=True,
            instrument=False)
        params = jax.tree_util.tree_map(jnp.array, params0)
        opt_state = tx.init(params)
        # compile outside the clock (the step donates its inputs, so the
        # warmup's outputs become the loop's inputs)
        params, _, opt_state, _ = step(params, {}, opt_state, x, y)
        detected = None
        t0 = time.time()
        for i in range(iters):
            params, _, opt_state, loss = step(params, {}, opt_state, x, y)
            v = numerics.note_step(i, opt_state)
            if v is not None and v["last_bad"] and detected is None:
                # report on the guard-count clock — the charge's own
                # grammar: the out-of-clock warmup call consumed count 0,
                # so loop iteration i runs at guard count i+1 and a
                # correct detection equals `injected.step`
                detected = i + 1
        return (time.time() - t0) / iters, detected, numerics.verdict(
            opt_state)

    try:
        clean_s, _, _ = run(False)
        spiked_s, detected, v = run(True)
    finally:
        chaos.reset()
    ratio = round(spiked_s / clean_s, 4) if clean_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "numerics_ab_step_ratio",
            help="grad_spike-injected / clean guarded step time "
                 "(numerics A/B)",
        ).set(ratio)
    out = {
        "metric": "numerics_ab_step_ratio",
        "value": ratio,
        "unit": "x",
        "n_chips": n,
        "clean_step_s": round(clean_s, 6),
        "spiked_step_s": round(spiked_s, 6),
        "injected": {"step": spike_at, "scale": spike_scale},
        "detected_at_step": detected,
        "bad_steps": None if v is None else v["bad_count"],
        "grad_norm_ewma": None if v is None else round(v["ewma"], 6),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_input_ab(args):
    """Input-pipeline A/B rung: the same jitted step fed by a
    ResumableLoader with the prefetch thread on vs off (synchronous host
    gather per batch). The source charges a deterministic per-batch host
    load cost so the rung measures the *overlap machinery*, not tmpfs
    speed; the analytic ``input_step_time`` model (serial = compute +
    load, overlapped = max(compute, load)) is emitted beside the
    measurement — and alone when no device comes up. Records the
    ``input_ab_step_ratio`` gauge (serial / overlapped step time; >= 1
    when prefetch wins) and prints ONE JSON line."""
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "tools"))
    from scaling_projection import input_step_time

    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    load_cost_s = 0.002
    model_only = {
        "metric": "input_ab_step_ratio",
        "unit": "x",
        "input_model": input_step_time(0.004, load_cost_s, 2),
    }

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()

    import jax
    import jax.numpy as jnp

    from horovod_tpu.data import ResumableLoader
    from horovod_tpu.data.loader import _ArraySource

    n = hvd.size()
    iters = max(args.iters, 10)
    rows, feat = 64 * n, 256
    rng = np.random.RandomState(0)
    X = rng.rand(rows, feat).astype(np.float32)
    Y = rng.randint(0, 8, rows).astype(np.int32)
    W = jnp.asarray(rng.rand(feat, feat).astype(np.float32))

    class _CostedSource(_ArraySource):
        """Array source with a deterministic per-gather host cost — the
        stand-in for a real storage read on the tmpfs-backed CI host."""

        def gather(self, indices):
            time.sleep(load_cost_s)
            return super().gather(indices)

    @jax.jit
    def step(w, xb):
        h = xb @ w
        for _ in range(8):
            h = jnp.tanh(h @ w)
        return h.sum()

    def run(prefetch):
        loader = ResumableLoader(
            _CostedSource((X, Y)), 8 * n, seed=0, prefetch=prefetch,
            name=f"input-ab-{prefetch}", register=False,
        )
        try:
            xb, _ = loader.next_batch()  # warm the jit outside the clock
            float(step(W, xb))
            t0 = time.time()
            for _ in range(iters):
                xb, _ = loader.next_batch()
                float(step(W, xb))
            return (time.time() - t0) / iters
        finally:
            loader.close()

    serial_s = run(0)
    overlapped_s = run(2)
    # the compute half alone (loader out of the loop), for the model
    xb, _ = ResumableLoader(
        (X, Y), 8 * n, seed=0, prefetch=0, name="input-ab-probe",
        register=False,
    ).next_batch()
    t0 = time.time()
    for _ in range(iters):
        float(step(W, xb))
    compute_s = (time.time() - t0) / iters

    ratio = round(serial_s / overlapped_s, 4) if overlapped_s else None
    if hvd.metrics.enabled() and ratio is not None:
        hvd.metrics.gauge(
            "input_ab_step_ratio",
            help="prefetch-off / prefetch-on step time (input A/B)",
        ).set(ratio)
    out = {
        "metric": "input_ab_step_ratio",
        "value": ratio,
        "unit": "x",
        "n_chips": n,
        "serial_step_s": round(serial_s, 6),
        "overlapped_step_s": round(overlapped_s, 6),
        "compute_step_s": round(compute_s, 6),
        "load_cost_s": load_cost_s,
        "input_model": input_step_time(compute_s, load_cost_s, 2),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_elastic_chaos(args):
    """Elastic chaos soak: train a small ZeRO-1 explicit-collective model
    under ``rank_fail``/``rank_join`` chaos — the coordinator shrinks the
    mesh mid-run and grows it back — and report the measured recovery
    latency (rollback + mesh re-formation + reshard + epoch barrier) as
    the ``elastic_recovery_latency_seconds`` gauge plus ONE JSON line.
    Runs anywhere (CPU mesh included)."""
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.resilience import chaos, elastic
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, shard_batch, softmax_xent,
    )

    hvd.init()
    n0 = hvd.size()
    if n0 < 3:
        raise SystemExit(f"--elastic-chaos needs >= 3 ranks, have {n0}")

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(256)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    model = MLP()
    sample = jnp.zeros((1, 28, 28), jnp.float32)
    params0 = model.init(jax.random.PRNGKey(0), sample).get("params")
    # batch divisible by every world size the soak visits
    batch = n0 * (n0 - 1) * 2

    def batch_for(step):
        rng = np.random.RandomState(step)
        x = rng.rand(batch, 28, 28).astype(np.float32)
        y = rng.randint(0, 10, batch)
        return x, y

    def step_builder(world):
        tx = hvd.DistributedOptimizer(optax.adam(1e-3), shard_optimizer=True)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, shard_optimizer=True,
            instrument=False)

        def step_fn(state, i):
            x, y = batch_for(i)
            p, _, os_, loss = step(
                state["params"], {}, state["opt_state"],
                shard_batch(x), shard_batch(y))
            return {"params": p, "opt_state": os_}

        return step_fn

    tx0 = hvd.DistributedOptimizer(optax.adam(1e-3), shard_optimizer=True)
    params = replicate(jax.tree_util.tree_map(jnp.array, params0))
    state = {"params": params, "opt_state": tx0.init(params)}

    iters = max(args.iters, 10)
    fail_at = max(2, iters // 3)
    join_at = max(fail_at + 2, 2 * iters // 3)
    chaos.configure(
        f"rank_fail=1,rank_fail_at_step={fail_at},"
        f"rank_join_at_step={join_at}")
    t0 = time.time()
    try:
        state = elastic.run(
            step_builder, state, num_steps=iters, snapshot_every=1)
    finally:
        chaos.reset()
    wall = time.time() - t0

    hist = hvd.metrics.value("resilience_elastic_resize_seconds") or {}
    count = int(hist.get("count", 0) or 0)
    total = float(hist.get("sum", 0.0) or 0.0)
    latency = total / count if count else None
    if latency is not None and hvd.metrics.enabled():
        hvd.metrics.gauge(
            "elastic_recovery_latency_seconds",
            help="mean wall time of one elastic membership change",
        ).set(latency)
    out = {
        "metric": "elastic_recovery_latency",
        "value": round(latency, 4) if latency is not None else None,
        "unit": "s",
        "n_chips": n0,
        "resizes": count,
        "generations": hvd.metrics.value("resilience_elastic_generation"),
        "soak_wall_s": round(wall, 3),
        "steps": iters,
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(out), flush=True)
    return 0


def _run_benchmark(args):
    from horovod_tpu.run.env_util import install_sigterm_exit

    install_sigterm_exit()  # watchdog SIGTERM -> clean device teardown

    from horovod_tpu import tuning

    tuning.enable_compile_cache()  # before the first backend touch

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py: platform={platform} — img/s and MFU are device "
            f"numbers; run it on a TPU host")

    import horovod_tpu as hvd
    import horovod_tpu.models as models
    from horovod_tpu.training import (
        init_model,
        make_jit_train_step,
        replicate,
        shard_batch,
    )

    hvd.init()
    n_chips = hvd.size()
    model = getattr(models, _MODELS[args.model][0])(num_classes=1000)
    compression, error_feedback, comp_name = _resolve_compression(args)
    # resolve once: the flag OR the env fallback the optimizer itself honors
    # (HOROVOD_SHARD_OPTIMIZER=1 without --shard-optimizer must not clobber
    # the sharded state layout below or misreport the sync mode)
    from horovod_tpu.optim import _env_true

    sharded = bool(args.shard_optimizer) or _env_true("HOROVOD_SHARD_OPTIMIZER")
    tx = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), compression=compression,
        error_feedback=error_feedback, shard_optimizer=sharded,
    )

    rng = jax.random.PRNGKey(0)
    global_batch = args.batch_size * n_chips
    sample = jnp.zeros((1, args.image_size, args.image_size, 3), jnp.float32)
    params, batch_stats = init_model(model, rng, sample)
    params = replicate(params)
    batch_stats = replicate(batch_stats)
    # sharded mode: init already placed the [N, shard] state P(data) —
    # replicate() here would clobber the ZeRO-1 layout
    opt_state = (
        tx.init(params) if sharded else replicate(tx.init(params))
    )

    # instrument=False: the AOT-compiled executable below is wrapped with
    # the measured per-step FLOPs instead (double-wrapping would double
    # count train_steps)
    step = make_jit_train_step(model, tx, instrument=False)

    images_np = np.random.RandomState(0).rand(
        global_batch, args.image_size, args.image_size, 3
    ).astype(np.float32)
    labels_np = np.random.RandomState(1).randint(0, 1000, global_batch)
    images = shard_batch(images_np)
    labels = shard_batch(labels_np)

    # AOT-compile once and run the loop through the compiled executable: the
    # same compile serves execution and cost analysis (a separate
    # lower().compile() would not populate jit's dispatch cache and would
    # compile ResNet-50 twice)
    step = step.lower(
        params, batch_stats, opt_state, images, labels
    ).compile()
    ca = step.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    step_flops = float(ca["flops"])
    # feed the metrics registry too (train_steps / train_step_seconds /
    # train_mfu): the benchmark exercises the same observability surface a
    # real training job gets, and the summary rides stderr for debugging
    from horovod_tpu.training import instrument_step

    step = instrument_step(step, batch_arg=3, flops_per_step=step_flops)

    for _ in range(args.warmup):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels
        )
    jax.block_until_ready((params, loss))

    from horovod_tpu.profiler import timed_steps

    state = [params, batch_stats, opt_state]

    def run_one():
        state[0], state[1], state[2], loss = step(
            state[0], state[1], state[2], images, labels
        )
        return loss

    losses, dt = timed_steps(run_one, args.iters)
    if not all(np.isfinite(l) for l in losses):
        raise SystemExit(f"bench.py: non-finite loss: {losses[-5:]}")

    img_per_sec = global_batch * args.iters / dt
    per_chip = img_per_sec / n_chips

    device_kind = jax.devices()[0].device_kind
    result = {
        "metric": f"{args.model}_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": (
            round(per_chip / BASELINE_IMG_S_PER_CHIP, 3)
            if _MODELS[args.model][2] else None
        ),
        "n_chips": n_chips,
        "platform": platform,
        "device_kind": device_kind,
    }
    sync_mode = "sharded" if sharded else "allreduce"
    sync_bytes = hvd.metrics.value("grad_sync_bytes_per_step", mode=sync_mode)
    if sync_bytes is not None:
        result["grad_sync_mode"] = sync_mode
        result["grad_sync_bytes_per_step"] = sync_bytes
    if comp_name != "none":
        result["compression"] = comp_name
    from horovod_tpu.profiler import device_peak_flops

    achieved = step_flops * args.iters / dt
    result["mfu"] = round(
        achieved / (n_chips * device_peak_flops(device_kind)), 4)
    result["model_tflops_per_step"] = round(step_flops / 1e12, 3)
    print(json.dumps(result), flush=True)
    print("metrics snapshot:\n" + hvd.metrics.summary(),
          file=sys.stderr, flush=True)
    if args.trace_dir:
        # after the timed loop so tracing overhead never pollutes img/s;
        # the real-workload overlap artifact (reference docs/timeline.rst)
        from horovod_tpu.profiler import timeline

        with timeline(args.trace_dir):
            for _ in range(3):
                run_one()
            jax.block_until_ready(state[0])
        print(f"trace written to {args.trace_dir}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
