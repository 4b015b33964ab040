"""Ring attention vs full attention on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from distkeras_tpu.ops.attention import dot_product_attention
from distkeras_tpu.ops.ring_attention import ring_attention_sharded


@pytest.fixture
def seq_mesh(devices):
    return Mesh(np.array(devices[:8]), ("seq",))


def _qkv(b=2, t=32, h=2, d=4, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


def test_ring_matches_full(seq_mesh):
    q, k, v = _qkv()
    ring = ring_attention_sharded(q, k, v, seq_mesh)
    full = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_ring_causal_matches_full(seq_mesh):
    q, k, v = _qkv(seed=1)
    ring = ring_attention_sharded(q, k, v, seq_mesh, causal=True)
    full = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_ring_padding_mask_matches_full(seq_mesh):
    q, k, v = _qkv(seed=2)
    rng = np.random.default_rng(3)
    kv_mask = jnp.asarray(rng.random((2, 32)) > 0.3)
    ring = ring_attention_sharded(q, k, v, seq_mesh, kv_mask=kv_mask)
    full = dot_product_attention(q, k, v, mask=kv_mask)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_dtype_preserved(seq_mesh):
    q, k, v = _qkv(seed=4)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = ring_attention_sharded(q, k, v, seq_mesh)
    assert out.dtype == jnp.bfloat16
    full = dot_product_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                 v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(full),
                               rtol=0.05, atol=0.05)


def test_ring_grads_finite(seq_mesh):
    q, k, v = _qkv(seed=5)

    def loss(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, seq_mesh,
                                              causal=True) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


def test_flash_attention_option_raises_off_tpu():
    """attention="flash" plumbs through the GPT family to the kernel
    dispatch, which refuses to run a TPU kernel's name on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest

    from distkeras_tpu.models.gpt import gpt_tiny

    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)),
                      jnp.int32)
    params = gpt_tiny(attention="full").init(jax.random.key(0),
                                             ids)["params"]
    with pytest.raises(RuntimeError, match="'cpu'"):
        gpt_tiny(attention="flash").apply({"params": params}, ids)
