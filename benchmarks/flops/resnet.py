"""Model FLOPs of a ``resnet``-family training step, from shapes alone:
3 x the forward's convolution and classifier FLOPs (2 per multiply-add) per
image: forward once, backward twice. BatchNorm, ReLU and pooling are not
counted; nothing is recomputed."""


def _out(size, stride):
    return -(-size // stride)


def forward_flops_per_image(cfg):
    f, size = cfg["num_filters"], cfg["image_size"]
    size = _out(size, 2)
    flops = 2 * size * size * 7 * 7 * 3 * f
    size = _out(size, 2)               # max-pool
    cin = f
    for s, n in enumerate(cfg["stage_sizes"]):
        w = f * 2 ** s
        for j in range(n):
            stride = 2 if s > 0 and j == 0 else 1
            out = _out(size, stride)
            flops += 2 * size * size * cin * w            # 1x1, before stride
            flops += 2 * out * out * 9 * w * w            # 3x3, strided
            flops += 2 * out * out * w * 4 * w            # 1x1
            if j == 0:
                flops += 2 * out * out * cin * 4 * w      # projection
            size, cin = out, 4 * w
    return flops + 2 * cin * cfg["num_classes"]


def model_flops_per_example(cfg, traffic):
    return 3 * forward_flops_per_image(cfg)
