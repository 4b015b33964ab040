"""ResNet family — the flagship model (BASELINE config 3: ResNet-50/ImageNet).

The reference has no in-tree model zoo (models live in Keras example
scripts); the north-star benchmark nevertheless names ResNet-50/ImageNet with
ADAG at >=50% MFU, so this is the flagship.

TPU-first design choices:
- NHWC layout, 3x3/1x1 convs — XLA tiles these straight onto the MXU.
- **GroupNorm instead of BatchNorm.** BatchNorm needs mutable running stats
  (impure step, host round-trips on sync) and cross-replica stat all-reduces;
  GroupNorm is stateless, batch-size independent, and fuses into the conv
  epilogue. This keeps every train step a pure function — the property the
  whole substrate (shard_map + scanned rounds) relies on.
- **Norm-free variant (``norm="nf"``)**: the round-3 profile (DESIGN.md)
  showed the GN step is HBM-bandwidth-bound — activation-norm traffic rides
  fused into the convs and caps MFU at ~38% even though the MXU is half
  idle. Scaled Weight Standardization (NF-ResNet / NFNet recipe: standardize
  the ~25M weights per fan-in, ~100MB of traffic, instead of re-reading GBs
  of activations) removes that entirely; measured +10 MFU points on v5e.
  Blocks stay identity-at-init via a zero-init gain on the last branch conv
  (the analogue of the GN variant's zero-init scale).
- bfloat16 compute / float32 params; float32 classifier head.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distkeras_tpu import precision as precision_lib
from distkeras_tpu.models.input_norm import normalize_image_input
from distkeras_tpu.models.remat import remat_wrap

ModuleDef = Any


#: opt-in toggle for the fused pallas GroupNorm kernel
#: (ops/pallas/groupnorm.py). Default OFF — measured on v5e (ResNet-50
#: bench): the per-sample-grid kernel LOST to XLA's native lowering
#: (20.9% vs 34.7% MFU) because the custom call breaks fusion with the
#: surrounding convs and the VMEM-overflow backward path costs extra
#: passes. The round-3 profile (DESIGN.md §4b) retired the kernel
#: approach entirely: XLA already fuses GN stats into the producer convs,
#: so no standalone kernel can win — use ``norm="nf"`` when norm traffic
#: matters. Kept as an experimental path (numerics fully tested).
USE_FUSED_GROUPNORM = False


def group_norm(channels: int, dtype, name: str, **kw):
    """GroupNorm with a group count that always divides ``channels``
    (32 groups at ImageNet widths, fewer for tiny test models). Uses the
    fused pallas kernel on TPU (profiled: GroupNorm was ~17% of the ResNet-50
    step under XLA's two-pass lowering)."""
    groups = math.gcd(32, channels)
    if USE_FUSED_GROUPNORM:
        from distkeras_tpu.ops.pallas.groupnorm import FusedGroupNorm

        return FusedGroupNorm(num_groups=groups, dtype=dtype, name=name,
                              **kw)
    return nn.GroupNorm(num_groups=groups, dtype=dtype, name=name, **kw)


#: variance compensation applied after branch-internal ReLUs of norm-free
#: blocks. Mean-zero (weight-standardized) kernels propagate only the
#: input's variance, and Var[relu(z)] = (1 - 1/pi)/2 for unit-normal z, so
#: the NF-ResNet/NFNet gain is sqrt(2/(1 - 1/pi)) — not sqrt(2), which
#: preserves the second moment rather than the variance.
_RELU_GAIN = 1.7128585504496627


class ScaledWSConv(nn.Module):
    """Conv with Scaled Weight Standardization (NF-ResNet / NFNet recipe).

    The kernel is standardized per output channel over its fan-in and scaled
    by ``1/sqrt(fan_in)`` so unit-variance input yields ~unit-variance output
    (gain 1); a learnable per-channel gain restores expressivity. All weight
    math runs in f32 on the ~O(params) tensors, then the standardized kernel
    is cast to the compute dtype — this replaces GroupNorm's per-step passes
    over GBs of activations with ~100MB of weight traffic, which is what the
    round-3 profile showed the step was bound by (DESIGN.md).
    """

    features: int
    kernel_size: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: Any = "SAME"
    dtype: jnp.dtype = jnp.bfloat16
    use_bias: bool = True
    gain_init: Any = nn.initializers.ones
    precision: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        dtype, _, _, act_quant = precision_lib.resolve(self.precision,
                                                       self.dtype)
        kh, kw = self.kernel_size
        in_ch = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.normal(1.0),
                            (kh, kw, in_ch, self.features), jnp.float32)
        fan_in = kh * kw * in_ch
        mu = jnp.mean(kernel, axis=(0, 1, 2), keepdims=True)
        var = jnp.var(kernel, axis=(0, 1, 2), keepdims=True)
        w = (kernel - mu) * jax.lax.rsqrt(var * fan_in + 1e-4)
        gain = self.param("gain", self.gain_init, (self.features,),
                          jnp.float32)
        w = w * gain
        # quantize AFTER standardization: the conv consumes exactly what a
        # low-precision conv would see (weight standardization itself stays
        # in f32 on the O(params) tensors)
        y = jax.lax.conv_general_dilated(
            act_quant(x.astype(dtype)), act_quant(w.astype(dtype)),
            window_strides=self.strides, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            b = self.param("bias", nn.initializers.zeros,
                           (self.features,), jnp.float32)
            y = y + b.astype(dtype)
        return y


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut on shape change."""

    filters: int  # bottleneck width; block output is 4*filters
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    norm: str = "gn"  # "gn" | "nf" (norm-free, scaled-WS convs)
    precision: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        dtype, _, conv_kw, _ = precision_lib.resolve(self.precision,
                                                     self.dtype)
        if self.norm == "nf":
            conv = partial(ScaledWSConv, dtype=self.dtype,
                           precision=self.precision)
            residual = x
            y = conv(self.filters, (1, 1), name="conv1")(x)
            y = nn.relu(y) * _RELU_GAIN
            y = conv(self.filters, (3, 3),
                     strides=(self.strides, self.strides),
                     name="conv2")(y)
            y = nn.relu(y) * _RELU_GAIN
            # zero-init gain: the block starts as identity, same role as
            # the GN variant's zero-init norm3 scale
            y = conv(4 * self.filters, (1, 1), name="conv3",
                     gain_init=nn.initializers.zeros)(y)
            if residual.shape != y.shape:
                residual = conv(4 * self.filters, (1, 1),
                                strides=(self.strides, self.strides),
                                name="proj")(residual)
            return nn.relu(residual + y)
        conv = partial(nn.Conv, use_bias=False, dtype=dtype, **conv_kw)
        norm = partial(group_norm, dtype=dtype)
        residual = x
        y = conv(self.filters, (1, 1), name="conv1")(x)
        y = norm(self.filters, name="norm1")(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 padding="SAME", name="conv2")(y)
        y = norm(self.filters, name="norm2")(y)
        y = nn.relu(y)
        y = conv(4 * self.filters, (1, 1), name="conv3")(y)
        # zero-init the last norm's scale so blocks start as identity
        y = norm(4 * self.filters, name="norm3",
                 scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(4 * self.filters, (1, 1),
                            strides=(self.strides, self.strides),
                            name="proj")(residual)
            residual = norm(4 * self.filters, name="norm_proj")(residual)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 block (ResNet-18/34)."""

    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    norm: str = "gn"
    precision: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        dtype, _, conv_kw, _ = precision_lib.resolve(self.precision,
                                                     self.dtype)
        if self.norm == "nf":
            conv = partial(ScaledWSConv, dtype=self.dtype,
                           precision=self.precision)
            residual = x
            y = conv(self.filters, (3, 3),
                     strides=(self.strides, self.strides),
                     name="conv1")(x)
            y = nn.relu(y) * _RELU_GAIN
            y = conv(self.filters, (3, 3), name="conv2",
                     gain_init=nn.initializers.zeros)(y)
            if residual.shape != y.shape:
                residual = conv(self.filters, (1, 1),
                                strides=(self.strides, self.strides),
                                name="proj")(residual)
            return nn.relu(residual + y)
        conv = partial(nn.Conv, use_bias=False, dtype=dtype, **conv_kw)
        norm = partial(group_norm, dtype=dtype)
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 padding="SAME", name="conv1")(x)
        y = norm(self.filters, name="norm1")(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), padding="SAME", name="conv2")(y)
        y = norm(self.filters, name="norm2",
                 scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            strides=(self.strides, self.strides),
                            name="proj")(residual)
            residual = norm(self.filters, name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5 (stride-2 in the 3x3 conv of downsampling bottlenecks)."""

    stage_sizes: Sequence[int]
    block: ModuleDef = BottleneckBlock
    num_classes: int = 1000
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    norm: str = "gn"  # "gn" | "nf" (norm-free: scaled-WS convs, no GN)
    #: uint8 inputs are normalized on device (models/input_norm.py) —
    #: staging raw bytes is 4x cheaper than f32 and the cast fuses into the
    #: stem. Set False when uint8 inputs are already in the model's
    #: expected range (masks, pre-scaled data); no effect on float inputs.
    normalize_uint8: bool = True
    #: MXU-friendly stem: rearrange the image H x W x C -> H/2 x W/2 x 4C
    #: (space-to-depth) and use a 4x4 stride-1 conv instead of 7x7 stride-2
    #: — same output resolution and receptive-field class, but the conv's
    #: contraction dim grows 3 -> 12, which packs the MXU's lanes far
    #: better than a 3-channel input (the classic MLPerf ResNet trick).
    #: Requires even H and W.
    space_to_depth: bool = False
    #: activation rematerialization policy (models/remat.py): "blocks"
    #: checkpoints each residual block, "full" also wraps the stem conv
    #: (whose [B, 112, 112, 64] output is the single largest activation).
    remat: str = "none"
    #: mixed-precision policy (distkeras_tpu/precision.py), the ``remat=``
    #: -style plumbing: overrides ``dtype`` for conv/matmul compute, f32
    #: classifier head stays f32
    precision: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        del train  # stateless norms: train/eval forward passes are identical
        dtype, _, conv_kw, _ = precision_lib.resolve(self.precision,
                                                     self.dtype)
        block_cls = remat_wrap(self.block, self.remat)
        x = normalize_image_input(x, dtype, self.normalize_uint8)
        if self.space_to_depth:
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
                n, h // 2, w // 2, 4 * c)
            stem_kernel, stem_strides, stem_pad = (4, 4), (1, 1), "SAME"
        else:
            stem_kernel, stem_strides = (7, 7), (2, 2)
            stem_pad = ((3, 3), (3, 3))
        if self.norm == "nf":
            stem_conv = remat_wrap(ScaledWSConv, self.remat, stem=True)
            x = stem_conv(self.width, stem_kernel, strides=stem_strides,
                          padding=stem_pad, dtype=self.dtype,
                          precision=self.precision, name="conv_stem")(x)
            x = nn.relu(x) * _RELU_GAIN
        elif self.space_to_depth:
            stem_conv = remat_wrap(nn.Conv, self.remat, stem=True)
            x = stem_conv(self.width, stem_kernel, strides=stem_strides,
                          padding=stem_pad, use_bias=False, dtype=dtype,
                          name="conv_stem", **conv_kw)(x)
            x = group_norm(self.width, dtype=dtype, name="norm_stem")(x)
            x = nn.relu(x)
        else:
            stem_conv = remat_wrap(nn.Conv, self.remat, stem=True)
            x = stem_conv(self.width, (7, 7), strides=(2, 2),
                          padding=[(3, 3), (3, 3)],
                          use_bias=False, dtype=dtype,
                          name="conv_stem", **conv_kw)(x)
            x = group_norm(self.width, dtype=dtype, name="norm_stem")(x)
            x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, num_blocks in enumerate(self.stage_sizes):
            for j in range(num_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = block_cls(filters=self.width * 2 ** i, strides=strides,
                              dtype=self.dtype, norm=self.norm,
                              precision=self.precision,
                              name=f"stage{i}_block{j}")(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def resnet18(**kw) -> ResNet:
    return ResNet(stage_sizes=(2, 2, 2, 2), block=BasicBlock, **kw)


def resnet34(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BasicBlock, **kw)


def resnet50(**kw) -> ResNet:
    """BASELINE config-3 / north-star flagship.

    The default ``norm="gn"`` (GroupNorm) variant measures ~36-42% MFU on
    v5e — HBM-bound on activation-norm traffic (DESIGN.md §4b). For the
    ≥50%-MFU recipe use :func:`resnet50_nf`.
    """
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BottleneckBlock, **kw)


def resnet50_nf(**kw) -> ResNet:
    """The ≥50%-MFU flagship recipe: norm-free ResNet-50 (Scaled Weight
    Standardization instead of GroupNorm) + on-device uint8 normalization.

    ``chip_smoke.py``'s train leg runs it at batch 128. The round-3 profile
    (DESIGN.md §4b) showed the GN step is HBM-bandwidth-bound on activation
    norm traffic, which the NF parameterization removes entirely. Stage
    uint8 images (the model normalizes on device, 4x fewer staged bytes)
    and prefer long scanned device calls (e.g. ``communication_window=8``,
    ``staging_rounds=24``) so dispatch amortizes. Trade-off: NF nets need
    the prescribed init discipline (carried by ScaledWSConv) and can be
    slightly less forgiving of exotic learning-rate schedules than GN.
    """
    kw.setdefault("norm", "nf")
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BottleneckBlock, **kw)


def resnet101(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 23, 3), block=BottleneckBlock, **kw)
