"""Shared on-device input normalization for the image model zoo.

The staged-data contract: image trainers stage RAW uint8 bytes (4x fewer
host->device and HBM bytes than f32) and the model normalizes on device as
``(x - 127.5) / 58`` — approximately (x - mean) / std for natural images,
fused by XLA into the stem conv. One definition, used by ResNet, the CIFAR
CNN, and ViT, so the magic constants (which README and tests
all rely on) cannot drift apart between models.
"""

from __future__ import annotations

import jax.numpy as jnp


def normalize_image_input(x, dtype, normalize_uint8: bool = True):
    """Cast ``x`` to ``dtype``; uint8 inputs are first normalized on device
    (unless ``normalize_uint8`` is False — e.g. masks or pre-scaled bytes).
    Float inputs pass through with only the dtype cast."""
    if x.dtype == jnp.uint8 and normalize_uint8:
        return (x.astype(dtype) - 127.5) / 58.0
    return x.astype(dtype)
