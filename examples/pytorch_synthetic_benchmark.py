#!/usr/bin/env python
"""Synthetic benchmark for the torch frontend — analog of reference
``examples/pytorch_synthetic_benchmark.py`` (img/s with allreduced grads).
The model is a small conv net (torch runs on host CPU here; what is
measured on the TPU is ``benchmarks/run.py``'s cells)."""

import argparse
import time

import numpy as np
import torch
import torch.nn.functional as F

import horovod_tpu.torch as hvd


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-warmup", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    hvd.init()
    model = torch.nn.Sequential(
        torch.nn.Conv2d(3, 32, 3, stride=2), torch.nn.ReLU(),
        torch.nn.Conv2d(32, 64, 3, stride=2), torch.nn.ReLU(),
        torch.nn.AdaptiveAvgPool2d(1), torch.nn.Flatten(),
        torch.nn.Linear(64, 1000),
    )
    compression = (
        hvd.Compression.fp16 if args.fp16_allreduce else hvd.Compression.none
    )
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01),
        named_parameters=model.named_parameters(),
        compression=compression,
    )
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    data = torch.randn(args.batch_size, 3, 64, 64)
    target = torch.randint(0, 1000, (args.batch_size,))

    def step():
        opt.zero_grad()
        loss = F.cross_entropy(model(data), target)
        loss.backward()
        opt.step()

    for _ in range(args.num_warmup):
        step()
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        step()
    dt = time.perf_counter() - t0
    img_sec = args.batch_size * args.num_iters / dt
    total = hvd.size() * img_sec
    if hvd.rank() == 0:
        print(f"Img/sec per rank: {img_sec:.1f}")
        print(f"Total img/sec on {hvd.size()} rank(s): {total:.1f}")


if __name__ == "__main__":
    main()
