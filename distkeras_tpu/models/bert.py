"""BERT — masked-language-model family (BASELINE config 4: BERT-base MLM).

The reference has no transformer models; this fulfils the benchmark config,
not a file-level parity obligation. Forward signature follows the framework
convention ``model.apply(vars, features, train=...)`` where ``features`` is
the int32 token-id matrix [batch, seq]; padding (token id 0) is masked out of
attention automatically. Pair with the ``masked_lm`` loss (labels < 0 are
ignored positions).

TPU notes: vocab rounded to a multiple of 128 by default (MXU lane width for
the embedding/logit matmuls), bf16 compute, fp32 LayerNorm/softmax/head.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from distkeras_tpu import precision as precision_lib
from distkeras_tpu.models.transformer import Encoder


class BertMLM(nn.Module):
    vocab_size: int = 30592  # 30522 rounded up to a multiple of 128
    max_len: int = 512
    num_layers: int = 12
    num_heads: int = 12
    width: int = 768
    mlp_dim: int = 3072
    num_segments: int = 2
    dropout_rate: float = 0.0
    pad_id: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    #: activation rematerialization policy for the encoder blocks
    #: (models/remat.py)
    remat: str = "none"
    #: mixed-precision policy (distkeras_tpu/precision.py); f32 MLM head
    #: stays f32
    precision: Optional[str] = None
    #: "xla" | "flash" — attention kernel dispatch (ops/attention.py);
    #: note "flash" raises on a call that carries a padding mask until
    #: the fused kernel learns key-side masks
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, input_ids, train: bool = False, segment_ids=None):
        dtype, dense_kw, _, _ = precision_lib.resolve(self.precision,
                                                      self.dtype)
        ids = input_ids.astype(jnp.int32)
        b, seq = ids.shape
        tok = nn.Embed(self.vocab_size, self.width, dtype=dtype,
                       name="tok_embed")(ids)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.max_len, self.width))[:seq]
        x = tok + pos.astype(dtype)
        if segment_ids is not None:
            x = x + nn.Embed(self.num_segments, self.width, dtype=dtype,
                             name="seg_embed")(segment_ids.astype(jnp.int32))
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_embed")(x)
        x = x.astype(dtype)

        mask = ids != self.pad_id  # [b, seq] key-side padding mask
        x = Encoder(self.num_layers, self.num_heads, self.mlp_dim,
                    self.dropout_rate, self.dtype, remat=self.remat,
                    precision=self.precision, attention=self.attention,
                    name="encoder")(x, mask=mask, train=train)

        # MLM head: transform + tied-style output projection
        x = nn.Dense(self.width, dtype=dtype, name="mlm_dense",
                     **dense_kw)(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(x)
        logits = nn.Dense(self.vocab_size, dtype=jnp.float32,
                          name="mlm_head")(x)
        return logits.astype(jnp.float32)


def bert_base(**kw) -> BertMLM:
    """BASELINE config-4 model (BERT-base: 12L/12H/768)."""
    return BertMLM(**kw)


def bert_tiny(**kw) -> BertMLM:
    """Test-sized BERT (2L/2H/64) for CI and CPU runs."""
    defaults = dict(vocab_size=256, max_len=64, num_layers=2, num_heads=2,
                    width=64, mlp_dim=128, dtype=jnp.float32)
    defaults.update(kw)
    return BertMLM(**defaults)
