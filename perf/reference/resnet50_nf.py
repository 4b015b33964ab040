"""Plain reference of the norm-free ResNet forward pass: float32
``jax.numpy``/``lax`` at ``precision=highest``, nothing imported from the
program. ResNet-50 v1.5 (He et al. 2015; stride 2 in the 3x3 convolution of
a down-sampling bottleneck) with Scaled Weight Standardization in place of
normalisation (Brock, De, Smith 2021): every convolution's kernel is
standardised over its fan-in, scaled by ``1/sqrt(fan_in)`` and a learned
per-channel gain; ReLUs inside a branch are followed by the variance-
restoring gain ``sqrt(2 / (1 - 1/pi))``. It reads the parameter tree under
the names models/resnet.py gives it.

Departures from Brock et al., as the program has them: no alpha/beta
residual scaling and no stochastic depth; the last convolution of a branch
starts at zero gain; uint8 inputs are normalised as ``(x - 127.5) / 58``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RELU_GAIN = math.sqrt(2.0 / (1.0 - 1.0 / math.pi))


def _ws_conv(x, p, stride=1, padding="SAME"):
    k = p["kernel"]
    fan_in = k.shape[0] * k.shape[1] * k.shape[2]
    mean = jnp.mean(k, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=(0, 1, 2), keepdims=True)
    w = (k - mean) / jnp.sqrt(var * fan_in + 1e-4) * p["gain"]
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_ws_conv(x, p["conv1"])) * RELU_GAIN
    y = jax.nn.relu(_ws_conv(y, p["conv2"], stride)) * RELU_GAIN
    y = _ws_conv(y, p["conv3"])
    if "proj" in p:
        x = _ws_conv(x, p["proj"], stride)
    return jax.nn.relu(x + y)


def forward(params, images, cfg: dict):
    """uint8 (or float) images ``[n, h, w, 3]`` -> float32 logits."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jnp.asarray(images)
    if x.dtype == jnp.uint8:
        x = (x.astype(jnp.float32) - 127.5) / 58.0
    x = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = _ws_conv(x, params["conv_stem"], 2, ((3, 3), (3, 3)))
        x = jax.nn.relu(x) * RELU_GAIN
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)))
        for i, blocks in enumerate(cfg["stage_sizes"]):
            for j in range(blocks):
                x = _bottleneck(x, params[f"stage{i}_block{j}"],
                                2 if i > 0 and j == 0 else 1)
        x = jnp.mean(x, axis=(1, 2))
        return x @ params["head"]["kernel"] + params["head"]["bias"]
