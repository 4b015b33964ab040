"""FLOPs counter + MFU math tests."""

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import observability as obs


def test_count_flops_matmul():
    a = jnp.zeros((8, 16))
    b = jnp.zeros((16, 32))
    flops = obs.count_flops(lambda a, b: a @ b, a, b)
    assert flops == 2 * 8 * 16 * 32


def test_count_flops_scan_multiplies():
    a = jnp.zeros((4, 4))

    def f(a):
        def body(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(body, a, None, length=10)
        return out

    assert obs.count_flops(f, a) == 10 * 2 * 4 * 4 * 4


def test_count_flops_conv():
    x = jnp.zeros((1, 8, 8, 3))
    k = jnp.zeros((3, 3, 3, 16))
    f = lambda x, k: jax.lax.conv_general_dilated(
        x, k, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # out 1x8x8x16, each output = 2 * 3*3*3 MACs
    assert obs.count_flops(f, x, k) == 2 * 8 * 8 * 16 * 27


def test_count_flops_through_jit_and_grad():
    a = jnp.zeros((8, 8))

    @jax.jit
    def loss(a):
        return jnp.sum((a @ a) ** 2)

    fwd = obs.count_flops(loss, a)
    assert fwd == 2 * 8 * 8 * 8
    both = obs.count_flops(jax.grad(loss), a)
    assert both >= 3 * fwd  # fwd + two backward matmuls


def test_count_flops_resnet_tiny_close_to_known_shape():
    from distkeras_tpu.models.resnet import resnet50

    model = resnet50(num_classes=1000)
    x = jnp.zeros((1, 224, 224, 3))
    shapes = jax.eval_shape(
        lambda k: model.init(k, x, train=False), jax.random.key(0))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)["params"]
    flops = obs.count_flops(
        lambda p: model.apply({"params": p}, x, train=False), params)
    # published ResNet-50 forward ~4.1 GMACs at 224x224 -> 2*MACs ~ 8.2 GFLOPs
    assert 7.6e9 < flops < 8.7e9, flops


def test_mfu_math():
    assert obs.mfu(1e12, 0.01, num_chips=1, peak_per_chip=1e15) == 0.1
    assert obs.mfu(0, 0.01) is None


def test_calibrate_peak_off_tpu_returns_none():
    """On the CPU mesh there is no peak table entry — calibration must
    decline rather than fabricate a ratio (None means 'cannot check',
    not 'ok')."""
    assert obs.calibrate_peak(size=64, chain=2, repeats=1) is None


def test_calibrate_peak_math_with_patched_peak(monkeypatch):
    """With a fake peak entry the calibration runs end-to-end on CPU and
    returns a consistent achieved/peak/ratio triple."""
    monkeypatch.setattr(obs, "device_peak_flops", lambda device=None: 1e12)
    cal = obs.calibrate_peak(size=64, chain=4, repeats=1)
    assert set(cal) == {"achieved", "peak", "ratio"}
    assert cal["peak"] == 1e12
    assert cal["achieved"] > 0
    assert cal["ratio"] == cal["achieved"] / cal["peak"]


def test_step_timer():
    t = obs.StepTimer()
    with t.measure(4):
        pass
    assert t.mean_step_s >= 0 and t.steps == 4


def test_pallas_call_flops_scale_with_grid():
    """A pallas kernel's body jaxpr is ONE grid cell's work; the counter
    must multiply by the grid size (counting it once undercounted the
    flash-attention probe ~4x per head-batch — BASELINE.md gpt row)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from distkeras_tpu import observability

    def kernel(x_ref, y_ref, o_ref):
        o_ref[...] = jnp.dot(x_ref[...], y_ref[...])

    def f(x, y):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            grid=(4,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (0, 0)),
                      pl.BlockSpec((128, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
        )(x, y)

    x = jnp.ones((128, 128), jnp.float32)
    flops = observability.count_flops(f, x, x)
    assert flops == 4 * 2 * 128 ** 3  # grid cells x 2*MACs per cell


def test_hbm_stats_cpu_returns_none_without_phantom_gauges():
    """CPU has no PJRT allocator stats: hbm_stats must return None AND not
    publish stale observability.hbm_* gauges for the health digest."""
    from distkeras_tpu import telemetry

    reg = telemetry.reset()
    try:
        assert obs.hbm_stats() is None
        gauges = reg.snapshot().get("gauges", {})
        assert not any(k.startswith("observability.hbm_") for k in gauges)
    finally:
        telemetry.reset()


def test_hbm_stats_publishes_gauges_with_fake_device():
    from distkeras_tpu import telemetry

    class FakeDevice:
        def memory_stats(self):
            return {"peak_bytes_in_use": 2048, "bytes_in_use": 1024,
                    "bytes_limit": 4096}

    reg = telemetry.reset()
    try:
        out = obs.hbm_stats(FakeDevice())
        assert out == {"peak_bytes": 2048, "allocated_bytes": 1024,
                       "limit_bytes": 4096}
        gauges = reg.snapshot()["gauges"]
        assert gauges["observability.hbm_peak_bytes"] == 2048.0
        assert gauges["observability.hbm_allocated_bytes"] == 1024.0
        assert gauges["observability.hbm_limit_bytes"] == 4096.0
    finally:
        telemetry.reset()


def test_compiled_memory_bytes_reports_temp_scratch():
    """memory_analysis works on CPU — the remat acceptance tests lean on
    temp_bytes, so its plumbing is guarded here."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.sum(jnp.tanh(x @ x.T) @ x)

    compiled = jax.jit(f).lower(jnp.ones((64, 64))).compile()
    mem = obs.compiled_memory_bytes(compiled)
    assert mem is not None
    assert mem["temp_bytes"] > 0
    assert mem["argument_bytes"] >= 64 * 64 * 4
    assert set(mem) == {"temp_bytes", "argument_bytes", "output_bytes",
                        "generated_code_bytes"}


def test_compiled_memory_bytes_bad_object_is_none():
    assert obs.compiled_memory_bytes(object()) is None
