#!/usr/bin/env python
"""Synthetic benchmark for the TF2 frontend — the rebuild's analog of the
reference's flagship benchmark (``examples/tensorflow2_synthetic_benchmark.py``,
BASELINE config 2): Keras application model, synthetic images,
``DistributedGradientTape`` + optional fp16 compression, img/s per iter.

The TF2 path exercises the frontend end-to-end (gradient tape wrapping,
compression, broadcast_variables); the TPU number for the same workload is
the ``resnet50_train_1chip`` cell of ``benchmarks/run.py``, which drives the
same collective layer from a jitted XLA training step.
"""

import argparse
import time

import numpy as np
import tensorflow as tf

import horovod_tpu.tensorflow as hvd


def build_model(name: str):
    if name == "tiny":
        # smoke-test model: same topology class (conv -> pool -> dense)
        return tf.keras.Sequential([
            tf.keras.layers.Conv2D(16, 3, strides=2, activation="relu"),
            tf.keras.layers.Conv2D(32, 3, strides=2, activation="relu"),
            tf.keras.layers.GlobalAveragePooling2D(),
            tf.keras.layers.Dense(10),
        ])
    return getattr(tf.keras.applications, name)(weights=None)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="ResNet50",
                   help="tf.keras.applications model name, or 'tiny'")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=3)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    hvd.init()
    model = build_model(args.model)
    opt = tf.optimizers.SGD(0.01)
    compression = (
        hvd.Compression.fp16 if args.fp16_allreduce else hvd.Compression.none
    )

    size = args.image_size if args.model != "tiny" else 32
    data = tf.random.uniform([args.batch_size, size, size, 3])
    target = tf.random.uniform(
        [args.batch_size], minval=0, maxval=10, dtype=tf.int64
    )

    def benchmark_step():
        with tf.GradientTape() as tape:
            probs = model(data, training=True)
            loss = tf.losses.sparse_categorical_crossentropy(
                target, probs, from_logits=True
            )
        tape = hvd.DistributedGradientTape(tape, compression=compression)
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))

    # warmup (builds variables), then sync initial state across ranks
    for _ in range(args.num_warmup_batches):
        benchmark_step()
    hvd.broadcast_variables(model.variables, root_rank=0)
    # Keras 3 made optimizer.variables a property; Keras 2 had a method
    opt_vars = opt.variables() if callable(opt.variables) else opt.variables
    hvd.broadcast_variables(opt_vars, root_rank=0)

    if hvd.rank() == 0:
        print(f"Model: {args.model}")
        print(f"Batch size: {args.batch_size}")
        print(f"Number of workers: {hvd.size()}")

    img_secs = []
    for x in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            benchmark_step()
        dt = time.perf_counter() - t0
        img_sec = args.batch_size * args.num_batches_per_iter / dt
        if hvd.rank() == 0:
            print(f"Iter #{x}: {img_sec:.1f} img/sec per worker")
        img_secs.append(img_sec)

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print(f"Img/sec per worker: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
        print(
            f"Total img/sec on {hvd.size()} worker(s): "
            f"{hvd.size() * img_sec_mean:.1f} +-{hvd.size() * img_sec_conf:.1f}"
        )


if __name__ == "__main__":
    main()
