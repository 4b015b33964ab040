"""Attention ops — the compute core of the transformer model family.

The reference has no attention anywhere (MLPs/convnets only, SURVEY.md §2);
BASELINE configs 4-5 (BERT-base MLM, ViT-L) require it, and the task spec
makes long-context first-class. This module holds the single-device paths:

- ``dot_product_attention``: einsum attention, bf16-friendly, fp32 softmax.
  XLA fuses the scale/mask/softmax chain into the two MXU matmuls.
- ``MultiHeadAttention``: flax module with fused QKV projection (one matmul
  instead of three — fewer, larger MXU ops).

The distributed path (ring attention over a sequence-parallel mesh axis)
lives in ``ops/ring_attention.py``.

Kernel dispatch (DESIGN.md §23): every attention call site routes through
``apply_attention(..., attention=)`` — a ``precision.resolve()``-style
switch. ``"xla"`` (default) is the einsum path below. ``"flash"`` names a
fused Pallas TPU kernel and runs one or raises with the reason: the
in-repo kernel (``ops/pallas/flash_attention.py``) when its ablation flag
is on and ``fits()`` accepts the shape, else the upstream pallas kernel
(causal only). It never substitutes the XLA path in silence — off-TPU,
under a padding mask, or on a shape neither kernel takes, it raises.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distkeras_tpu import precision as precision_lib

# Large-but-finite mask value (flax convention): keeps softmax defined (and
# its gradient zero, not NaN) even for rows whose keys are ALL masked — e.g.
# an all-padding row from ModelPredictor's static-shape tail padding.
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_attention_causal(q: jax.Array, k: jax.Array, v: jax.Array
                           ) -> jax.Array:
    """Fused causal attention via the upstream pallas TPU kernel
    (``jax.experimental.pallas.ops.tpu.flash_attention``).

    [batch, seq, heads, head_dim] in/out (transposed to the kernel's BHTD
    internally). O(seq) memory instead of materializing the [seq, seq]
    score matrix — the single-chip long-context path, complementing ring
    attention's cross-chip sequence parallelism. Constraints inherited
    from the kernel: TPU only, seq a multiple of its block size (powers of
    two >= 128 are safe).
    """
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    scale = q.shape[-1] ** -0.5
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))  # -> [b, h, t, d]
    out = fa.flash_attention(qt, kt, vt, causal=True, sm_scale=scale)
    return out.swapaxes(1, 2).astype(q.dtype)


#: legal values for the attention= switch threaded through the model
#: families (transformer/bert/vit/moe encoders; gpt has its own field
#: whose "flash" value routes through the same dispatch)
ATTENTION_MODES = ("xla", "flash")


def resolve_attention(attention: Optional[str]) -> str:
    """Normalize the ``attention=`` model field (None -> ``"xla"``)."""
    mode = attention or "xla"
    if mode not in ATTENTION_MODES:
        raise ValueError(
            f"attention={attention!r}; expected one of {ATTENTION_MODES}")
    return mode


def apply_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None,
                    causal: bool = False,
                    attention: Optional[str] = None) -> jax.Array:
    """Dispatch one attention call per the resolved mode.

    ``"flash"`` runs a fused kernel or raises: the in-repo kernel when
    its default-off ablation flag is on and ``fits()`` takes the shape,
    else the upstream pallas kernel (causal only). Both are TPU kernels
    that know only the causal mask, so another platform or a padding
    mask is an error naming the reason, never a quiet XLA run.
    """
    mode = resolve_attention(attention)
    if mode == "xla":
        return dot_product_attention(q, k, v, mask=mask, causal=causal)
    from distkeras_tpu.ops.pallas import flash_attention as _fa

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"attention='flash' names a Pallas TPU kernel but this process "
            f"runs on {platform!r}; use attention='xla' here")
    if mask is not None:
        raise ValueError(
            "attention='flash': the fused kernels know only the causal "
            "mask, not a padding mask; use attention='xla'")
    if _fa.USE_FLASH_ATTENTION and _fa.fits(q.shape):
        return _fa.flash_attention(q, k, v, causal=causal)
    if not causal:
        raise ValueError(
            f"attention='flash' on a bidirectional call of shape "
            f"{q.shape}: the upstream kernel is causal-only and the in-repo "
            f"kernel declined (USE_FLASH_ATTENTION="
            f"{_fa.USE_FLASH_ATTENTION}, fits={_fa.fits(q.shape)}); use "
            f"attention='xla'")
    return flash_attention_causal(q, k, v)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None,
                          causal: bool = False) -> jax.Array:
    """Attention over [batch, seq, heads, head_dim] tensors.

    Softmax runs in float32 regardless of input dtype (bf16 logits overflow
    long-sequence softmax); the output is cast back to the input dtype.
    """
    dtype = q.dtype
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = jnp.arange(q.shape[1])[:, None]
        k_pos = jnp.arange(k.shape[1])[None, :]
        logits = jnp.where(q_pos >= k_pos, logits, MASK_VALUE)
    if mask is not None:
        # mask: [batch, kv_seq] (padding) or broadcastable to [b, h, q, k]
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        logits = jnp.where(mask, logits, MASK_VALUE)
    weights = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


class MultiHeadAttention(nn.Module):
    """MHA with fused QKV projection. Input/output: [batch, seq, width]."""

    num_heads: int
    qkv_features: Optional[int] = None
    dtype: jnp.dtype = jnp.bfloat16
    causal: bool = False
    #: mixed-precision policy for the qkv/out projections
    #: (distkeras_tpu/precision.py); attention itself stays fp32-softmax
    precision: Optional[str] = None
    #: "xla" | "flash" — kernel dispatch for the attention op itself
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None):
        dtype, dense_kw, _, _ = precision_lib.resolve(self.precision,
                                                      self.dtype)
        width = x.shape[-1]
        features = self.qkv_features or width
        head_dim = features // self.num_heads
        assert features % self.num_heads == 0

        qkv = nn.Dense(3 * features, dtype=dtype, name="qkv", **dense_kw)(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda t: t.reshape(t.shape[:2] + (self.num_heads, head_dim))
        out = apply_attention(split(q), split(k), split(v),
                              mask=mask, causal=self.causal,
                              attention=self.attention)
        out = out.reshape(out.shape[:2] + (features,))
        return nn.Dense(width, dtype=dtype, name="out", **dense_kw)(out)
