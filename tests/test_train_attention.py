"""The gpt training path's attention: which form runs, and that the fused
kernel trains the model as the XLA form does.

``models/gpt.py`` attends a no-cache causal call with the fused training
kernel (``ops/pallas/flash_attention.py``) where the backend is a TPU, the
call runs on one device as traced (a ``shard_map`` body, a mesh of one
device) and the kernel takes the shape, and with ``dot_product_attention``
everywhere else. Here the backend is the CPU, so the TPU cases answer "is
the backend a TPU" for a chip and run the kernel through the Pallas
interpreter. Which form ran is read from the program's counters
(``model.attention.calls``, ``model.attention.fused_calls``) and from a
spy on the kernel's entry.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distkeras_tpu import telemetry
from distkeras_tpu.models.gpt import CausalLM
from distkeras_tpu.ops.pallas import flash_attention as fa

#: NUMERICS.md "Flash-attention equivalence": gradients through the
#: recompute backward within 1e-4 of the reference at float32
GRAD_RTOL = 1e-4


def _tiny(num_layers=1, remat="none", max_len=256):
    """Two heads of 64 (gpt2's head size) over a width of 128."""
    return CausalLM(vocab_size=64, max_len=max_len, num_layers=num_layers,
                    num_heads=2, width=128, mlp_dim=256, dtype=jnp.float32,
                    remat=remat)


def _ids(b, t, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(1, 64, (b, t)),
                       jnp.int32)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("workers",))


def _one_device():
    """A mesh of one device: what a one-chip trainer runs under."""
    return jax.set_mesh(_mesh(1))


def _as_on_a_chip(monkeypatch):
    """The backend answers "TPU", and the kernel runs interpreted; returns
    the list of the shapes the kernel is called with."""
    calls = []
    kernel = fa.flash_attention

    def spy(q, k, v, **kw):
        calls.append(q.shape)
        return kernel(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", spy)
    return calls


def _counts(registry):
    rows = {r["name"]: r.get("value") for r in registry.rows()}
    return (rows.get("model.attention.calls", 0),
            rows.get("model.attention.fused_calls", 0))


@pytest.mark.parametrize("case,t,fused", [
    ("tpu", 256, True),
    ("cpu", 256, False),
    ("tpu", 200, False),           # not a whole number of 128-blocks
    ("tpu", 64, False),            # shorter than a block
    ("tpu_cache", 128, False),     # a prefill into a cache
    ("tpu_shard_map", 256, True),  # a shard_map body over two devices
    ("tpu_gspmd", 256, False),     # GSPMD over a mesh of two devices
    ("tpu_no_mesh", 256, False),   # eight devices, and no mesh to say
])
def test_fused_kernel_chosen_by_backend_and_shape(monkeypatch, case, t,
                                                  fused):
    calls = []
    if case.startswith("tpu"):
        calls = _as_on_a_chip(monkeypatch)
    model = _tiny()
    ids = _ids(2, t)
    params = model.init(jax.random.key(0), ids[:, :8])["params"]
    registry = telemetry.reset()
    forward = lambda ids: model.apply({"params": params}, ids)
    where = {"tpu_gspmd": lambda: jax.set_mesh(_mesh(2)),
             "tpu_shard_map": contextlib.nullcontext,
             "tpu_no_mesh": contextlib.nullcontext}.get(case, _one_device)
    with where():
        if case == "tpu_cache":
            logits, _ = model.apply({"params": params}, ids,
                                    cache=model.init_cache(2),
                                    cache_index=jnp.zeros((2,), jnp.int32))
        elif case == "tpu_shard_map":
            # unchecked, as the trainers' bodies are: the interpreter
            # cannot follow the varying axes through the kernel's slices
            # (tests/test_decode_layout.py compiles the checked form)
            logits = jax.jit(jax.shard_map(
                forward, mesh=_mesh(2), in_specs=P("workers"),
                out_specs=P("workers"), check_vma=False))(ids)
        else:
            logits = jax.jit(forward)(ids)
        # a cache call's queries fit the kernel: the cache alone declines it
        assert fa.train_dispatch(jax.ShapeDtypeStruct(
            (2, t, 2, 64), jnp.bfloat16)) is (
                fused and case != "tpu_shard_map" or case == "tpu_cache")
    assert logits.shape == (2, t, 64)
    want_calls = 0 if case == "tpu_cache" else 1   # a cache call decides
    assert _counts(registry) == (want_calls, int(fused))
    rows = 1 if case == "tpu_shard_map" else 2
    assert calls == ([(rows, t, 2, 64)] if fused else [])


def test_fused_training_step_matches_xla(monkeypatch):
    """Loss and every parameter's gradient of a two-layer model under
    ``remat="blocks"``, through the kernel against the XLA form."""
    calls = _as_on_a_chip(monkeypatch)
    model = _tiny(num_layers=2, remat="blocks")
    ids = _ids(2, 256, seed=1)
    params = model.init(jax.random.key(1), ids[:, :8])["params"]

    def loss(p):
        logits = model.apply({"params": p}, ids, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], ids[:, 1:]).mean()

    step = functools.partial(jax.value_and_grad(loss), params)
    with _one_device():
        fused_loss, fused_grads = jax.jit(step)()
        assert len(calls) == 2     # one trace a layer
        monkeypatch.setattr(fa, "_on_tpu", lambda: False)
        xla_loss, xla_grads = jax.jit(step)()
    assert len(calls) == 2
    np.testing.assert_allclose(float(fused_loss), float(xla_loss),
                               rtol=1e-6)
    for path, got in jax.tree_util.tree_leaves_with_path(fused_grads):
        want = np.asarray(
            functools.reduce(lambda t, k: t[k.key], path, xla_grads))
        scale = np.max(np.abs(want))
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=GRAD_RTOL, atol=GRAD_RTOL * scale,
            err_msg=jax.tree_util.keystr(path))
