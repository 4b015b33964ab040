"""Norm-free (scaled-WS) ResNet variant: init invariants + trainability.

The NF variant is the TPU-perf answer the round-3 profile demanded
(activation-norm traffic was the step's HBM bottleneck — DESIGN.md). These
tests pin its algebra on CPU: standardized-weight statistics, identity-at-
init blocks, uint8 input normalization, and that the thing actually trains.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.resnet import (BasicBlock, BottleneckBlock, ResNet,
                                         ScaledWSConv)


def test_ws_conv_output_unit_variance():
    """Unit-normal input through a gain-1 WS conv gives ~unit-variance output
    (the signal-propagation property the standardization exists for)."""
    conv = ScaledWSConv(features=64, kernel_size=(3, 3),
                        dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 16, 16, 32)), jnp.float32)
    params = conv.init(jax.random.key(1), x)["params"]
    y = conv.apply({"params": params}, x)
    assert 0.8 < float(jnp.var(y)) < 1.25
    assert abs(float(jnp.mean(y))) < 0.1


def test_ws_conv_standardization_is_shift_scale_invariant():
    """Adding a constant to (or scaling) the raw kernel leaves the effective
    conv unchanged — the defining property of weight standardization."""
    conv = ScaledWSConv(features=8, kernel_size=(1, 1), dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 4, 4, 6)),
                    jnp.float32)
    params = conv.init(jax.random.key(0), x)["params"]
    y0 = conv.apply({"params": params}, x)
    shifted = dict(params, kernel=params["kernel"] * 3.0 + 1.5)
    y1 = conv.apply({"params": shifted}, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-4, atol=1e-4)


def test_nf_bottleneck_block_identity_at_init():
    """Zero-init gain on the last branch conv: block == relu(x) at init when
    shapes match (same role as the GN variant's zero-init norm3 scale)."""
    block = BottleneckBlock(filters=4, strides=1, dtype=jnp.float32,
                            norm="nf")
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 8, 8, 16)),
                    jnp.float32)
    params = block.init(jax.random.key(0), x)["params"]
    y = block.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(y), np.maximum(np.asarray(x), 0),
                               rtol=1e-5, atol=1e-5)


def test_nf_basic_block_identity_at_init():
    block = BasicBlock(filters=16, strides=1, dtype=jnp.float32, norm="nf")
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 8, 8, 16)),
                    jnp.float32)
    params = block.init(jax.random.key(0), x)["params"]
    y = block.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(y), np.maximum(np.asarray(x), 0),
                               rtol=1e-5, atol=1e-5)


def test_nf_resnet_uint8_input_matches_normalized_float():
    """The on-device uint8 path equals feeding pre-normalized floats."""
    model = ResNet(stage_sizes=(1, 1), block=BasicBlock, width=8,
                   num_classes=5, dtype=jnp.float32, norm="nf")
    rng = np.random.default_rng(5)
    u8 = rng.integers(0, 256, (2, 16, 16, 3), dtype=np.uint8)
    params = model.init(jax.random.key(0), jnp.asarray(u8),
                        train=False)["params"]
    y_u8 = model.apply({"params": params}, jnp.asarray(u8), train=False)
    xf = (u8.astype(np.float32) - 127.5) / 58.0
    y_f = model.apply({"params": params}, jnp.asarray(xf), train=False)
    np.testing.assert_allclose(np.asarray(y_u8), np.asarray(y_f),
                               rtol=1e-5, atol=1e-5)


def test_nf_resnet_trains():
    """Loss decreases on a tiny overfit task — the NF recipe is trainable,
    not just fast."""
    import optax

    from distkeras_tpu import engine

    model = ResNet(stage_sizes=(1, 1), block=BottleneckBlock, width=8,
                   num_classes=4, dtype=jnp.float32, norm="nf")
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((16, 16, 16, 3)), jnp.float32)
    labels = jnp.asarray(np.eye(4, dtype=np.float32)[
        rng.integers(0, 4, 16)])
    batch = {"features": x, "labels": labels}
    tx = optax.sgd(0.05, momentum=0.9)
    state = engine.create_train_state(model, jax.random.key(0), batch, tx)
    step = engine.make_train_step(model, "categorical_crossentropy", tx,
                                  with_metrics=False)
    losses = []
    for _ in range(40):
        state, ms = step(state, batch)
        losses.append(float(ms["loss"]))
    assert losses[-1] < 0.5 * losses[0], losses[::10]
    assert np.isfinite(losses).all()


def test_space_to_depth_stem_shapes_and_grads():
    """space_to_depth=True (MXU-friendly stem rearrange) preserves output
    shape and trains, for both norm variants."""
    for norm in ("nf", "gn"):
        model = ResNet(stage_sizes=(1, 1), block=BasicBlock, width=8,
                       num_classes=5, dtype=jnp.float32, norm=norm,
                       space_to_depth=True)
        x = jnp.asarray(
            np.random.default_rng(8).standard_normal((2, 32, 32, 3)),
            jnp.float32)
        params = model.init(jax.random.key(0), x, train=False)["params"]
        y = model.apply({"params": params}, x, train=False)
        assert y.shape == (2, 5)
        assert params["conv_stem"]["kernel"].shape[:3] == (4, 4, 12)

        def loss(p):
            return jnp.mean(
                model.apply({"params": p}, x, train=True) ** 2)

        grads = jax.grad(loss)(params)
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(grads))


def test_resnet50_nf_is_the_bench_recipe():
    """The public norm-free constructor (README quickstart, chip_smoke.py):
    norm-free blocks + on-device uint8 normalization, overridable kwargs."""
    from distkeras_tpu.models import resnet50_nf

    m = resnet50_nf()
    assert m.norm == "nf" and m.normalize_uint8
    assert m.stage_sizes == (3, 4, 6, 3)
    assert resnet50_nf(num_classes=10).num_classes == 10
