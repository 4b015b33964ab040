"""Operations the ResNet family *needs* for one image, from shapes alone.

A multiply-add is two operations; only convolutions and the head count
(weight standardisation, ReLU and pooling are elementwise). A training step
is forward, the gradient to the input and the gradient to the weights of
every convolution — except the stem, whose input is the image and gets no
gradient. Recomputation does not count.
"""

from __future__ import annotations


def conv_layers(cfg: dict):
    """``(name, macs, has_input_grad, stride)`` of every convolution and the
    head, for one image. ResNet v1.5 bottlenecks: the stride sits in the
    3x3."""
    side, width = cfg["image_size"], cfg["width"]
    s = -(-side // 2)                                   # stem, stride 2
    layers = [("conv_stem", s * s * 7 * 7 * 3 * width, False, 2)]
    s = -(-s // 2)                                      # 3x3 max pool, /2
    ch = width
    for i, blocks in enumerate(cfg["stage_sizes"]):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            s_out = -(-s // stride)
            name = f"stage{i}_block{j}"
            layers.append((f"{name}.conv1", s * s * ch * f, True, 1))
            layers.append((f"{name}.conv2", s_out * s_out * 9 * f * f, True,
                           stride))
            layers.append((f"{name}.conv3", s_out * s_out * f * 4 * f, True,
                           1))
            if stride != 1 or ch != 4 * f:
                layers.append((f"{name}.proj",
                               s_out * s_out * ch * 4 * f, True, stride))
            s, ch = s_out, 4 * f
    layers.append(("head", ch * cfg["num_classes"], True, 1))
    return layers


def forward_flops_per_sample(cfg: dict) -> float:
    return 2.0 * sum(layer[1] for layer in conv_layers(cfg))


def train_flops_per_sample(cfg: dict) -> float:
    return 2.0 * sum(macs * (3 if dgrad else 2)
                     for _, macs, dgrad, _ in conv_layers(cfg))


def train_flops_as_lowered(cfg: dict) -> float:
    """What a jaxpr walker counts for the same step: JAX lowers the input
    gradient of a convolution of stride ``s`` to a convolution over the
    input dilated by ``s``, which multiplies ``s * s`` times as many
    numbers, most of them the inserted zeros. Kept only so that
    ``perf/selftest.py`` can check the closed form against
    ``observability.count_flops``; utilisation uses the needed count."""
    return 2.0 * sum(macs * ((2 + stride * stride) if dgrad else 2)
                     for _, macs, dgrad, stride in conv_layers(cfg))


def train_flops_per_step(cfg: dict) -> float:
    """One optimizer step of one worker (``batch_size`` images)."""
    return cfg["trainer"]["batch_size"] * train_flops_per_sample(cfg)
