"""Fused scaled-int8 matmul-dequant kernel (ops/pallas/int8_matmul.py).

The kernel is DEFAULT OFF (the groupnorm lesson: a custom call is a
fusion fence). Tier-1 pins three things on CPU: the default stays off,
the dispatch predicate is honest, and interpret-mode execution is
bit-exact against the pure-XLA fallback (same int32 accumulate, same
final f32 scale multiply). The compiled (non-interpret) kernel is checked
against the same fallback on the chip by ``chip_smoke.py``'s kernels leg.
"""

import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.pallas import int8_matmul as k


def test_kernel_is_default_off():
    assert k.USE_FUSED_INT8_MATMUL is False
    # and therefore never dispatched, on any backend
    assert k.kernel_enabled() is False


def test_fits_predicate():
    assert k.fits((512, 512), (512, 512))
    assert k.fits((256, 768), (768, 256))
    assert not k.fits((100, 512), (512, 512))   # ragged M
    assert not k.fits((512, 512), (512, 100))   # ragged N
    assert not k.fits((512, 100), (100, 512))   # ragged K
    assert not k.fits((2, 512, 512), (512, 512))  # batched lhs
    assert not k.fits((512, 512), (256, 512))   # K mismatch


def test_interpret_mode_bit_exact_vs_xla_fallback():
    """Same math, two lowerings: the int32 accumulate is exact in both, so
    the only float op is the final scale multiply — results must agree to
    the bit, not to a tolerance."""
    for qx, qw, sxw in k.reference_rows(sizes=((512, 512, 512),
                                               (256, 768, 256))):
        ref = np.asarray(k.xla_int8_matmul_dequant(
            jnp.asarray(qx), jnp.asarray(qw), sxw))
        out = np.asarray(k.int8_matmul_dequant(
            jnp.asarray(qx), jnp.asarray(qw), sxw, interpret=True))
        np.testing.assert_array_equal(ref, out)


def test_precision_path_uses_xla_fallback_while_off():
    """scaled_int8_matmul must produce the XLA-fallback numbers while the
    kernel is off — the trace-time dispatch can't silently engage."""
    from distkeras_tpu.precision import quantize_int8, scaled_int8_matmul

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((512, 512)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((512, 512)).astype(np.float32))
    qx, sx = quantize_int8(x)
    qw, sw = quantize_int8(w)
    ref = k.xla_int8_matmul_dequant(qx, qw, sx * sw).astype(x.dtype)
    np.testing.assert_array_equal(np.asarray(scaled_int8_matmul(x, w)),
                                  np.asarray(ref))
