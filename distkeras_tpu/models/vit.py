"""Vision Transformer (BASELINE config 5: ViT-L, pjit data-parallel).

Standard ViT: conv patch embedding (a strided conv = one big MXU matmul per
patch grid), learned position embeddings, CLS token, pre-LN encoder, fp32
classifier head.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from distkeras_tpu import precision as precision_lib
from distkeras_tpu.models.input_norm import normalize_image_input
from distkeras_tpu.models.remat import remat_wrap
from distkeras_tpu.models.transformer import Encoder


class ViT(nn.Module):
    num_classes: int = 1000
    patch_size: int = 16
    num_layers: int = 24
    num_heads: int = 16
    width: int = 1024
    mlp_dim: int = 4096
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    #: uint8 inputs are normalized on device (models/input_norm.py) —
    #: staging raw bytes is 4x cheaper than f32, which matters doubly here
    #: because config 5's end-to-end number is bound by image staging over
    #: the host->device link. No effect on float inputs.
    normalize_uint8: bool = True
    #: activation rematerialization policy for the encoder blocks
    #: (models/remat.py); "full" also wraps the patch embedding.
    remat: str = "none"
    #: mixed-precision policy (distkeras_tpu/precision.py); f32 head stays
    #: f32
    precision: Optional[str] = None
    #: "xla" | "flash" — attention kernel dispatch (ops/attention.py);
    #: ViT attention is bidirectional, so "flash" needs the in-repo
    #: kernel's non-causal path (and raises until its flag is on)
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        dtype, _, conv_kw, _ = precision_lib.resolve(self.precision,
                                                     self.dtype)
        x = normalize_image_input(x, dtype, self.normalize_uint8)
        p = self.patch_size
        patch_conv = remat_wrap(nn.Conv, self.remat, stem=True)
        x = patch_conv(self.width, (p, p), strides=(p, p), padding="VALID",
                       dtype=dtype, name="patch_embed", **conv_kw)(x)
        b, h, w, c = x.shape
        x = x.reshape((b, h * w, c))
        cls = self.param("cls", nn.initializers.zeros, (1, 1, self.width))
        x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, c)).astype(dtype),
                             x], axis=1)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, h * w + 1, self.width))
        x = x + pos.astype(dtype)
        x = Encoder(self.num_layers, self.num_heads, self.mlp_dim,
                    self.dropout_rate, self.dtype, remat=self.remat,
                    precision=self.precision, attention=self.attention,
                    name="encoder")(x, train=train)
        cls_out = x[:, 0]
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(cls_out).astype(jnp.float32)


def vit_base(**kw) -> ViT:
    defaults = dict(num_layers=12, num_heads=12, width=768, mlp_dim=3072)
    defaults.update(kw)
    return ViT(**defaults)


def vit_large(**kw) -> ViT:
    """BASELINE config-5 model (ViT-L/16)."""
    return ViT(**kw)


def vit_tiny(**kw) -> ViT:
    """Test-sized ViT for CI and CPU runs."""
    defaults = dict(num_classes=10, patch_size=4, num_layers=2, num_heads=2,
                    width=32, mlp_dim=64, dtype=jnp.float32)
    defaults.update(kw)
    return ViT(**defaults)
