"""The reference benchmark's synthetic data (Horovod's
``tensorflow2_synthetic_benchmark.py``: random images, random labels), from
a seed: a pool of float32 image batches, uniform in [0, 1), and int32
labels."""

import numpy as np


def make(params, cfg, seed, global_batch):
    rng = np.random.default_rng(seed)
    s = cfg["image_size"]
    return [(rng.random((global_batch, s, s, 3), dtype=np.float32),
             rng.integers(0, cfg["num_classes"], global_batch,
                          dtype=np.int32))
            for _ in range(params["pool"])]
