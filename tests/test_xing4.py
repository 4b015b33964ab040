"""A residual of several streams (models/latent_moe.py with ``hc_mult > 1``:
Xing4.0-29B-A4B's manifold-constrained hyper-connections) on latent
attention, a leading dense layer and sigmoid-routed experts, against the
plain reference (perf/reference/xing4.py: float32, expanded attention, each
expert on the tokens routed to it, no cache, nothing of the program), at
the rehearsal configuration's sizes in float32 on the CPU with seeded random
weights: four streams, 20 Sinkhorn iterations, the clip at +-30, YaRN past
an original length of 16.

Tolerance: the float32 contract of NUMERICS.md ("Decode-step
equivalence"): the program and the reference differ in the order of their
sums, so logits agree to ``1e-5`` of the largest. Each mutation of the
mechanism below moves them by more than a tenth of it.
"""

import functools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import latent_moe as lm
from distkeras_tpu.serving import GenerationEngine, KVCachePool
from distkeras_tpu.serving.generation import (GHOST_TOKEN, make_decode_fn,
                                              make_prefill_fn)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perf"))
import harness  # noqa: E402
from builders import xing4 as builder  # noqa: E402
from reference import xing4 as ref  # noqa: E402

TOL = 1e-5
CFG = dict(harness.load_json("configs", "xing4_29b_a4b_tiny.json"),
           dtype="float32")


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


@functools.lru_cache(maxsize=None)
def _compiled(model, what):
    if what == "init":
        return jax.jit(lambda key: model.init(
            key, jnp.zeros((1, 8), jnp.int32))["params"])
    if what == "forward":
        return jax.jit(lambda p, i: model.apply({"params": p}, i))
    if what == "prefill":
        return jax.jit(make_prefill_fn(model))
    if what == "decode":
        return jax.jit(make_decode_fn(model))
    return jax.jit(lambda p, i: ref.forward(p, i, CFG))


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def reference(params, ids):
    return _compiled(None, "reference")(params, jnp.asarray(ids))


@pytest.fixture(scope="module")
def tiny():
    model = builder.build_model(CFG, "serve")
    return model, _compiled(model, "init")(jax.random.key(0))


def test_the_builder_makes_the_configuration_s_model(tiny):
    model, params = tiny
    assert (model.hc_mult, model.hc_sinkhorn_iters, model.hc_eps,
            model.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert model.dense_layers == 1 and model.scoring == "sigmoid"
    assert model.experts_held == 8 and model.routed_scaling == 2
    # a mapping around each of the 3 layers' 2 sub-layers
    hc = {name for name in params if name.startswith("hc_")}
    assert hc == {f"hc_{part}_{i}" for part in ("attn", "mlp")
                  for i in range(3)}
    assert {k: v.shape for k, v in params["hc_mlp_2"].items()} == {
        "phi_pre": (256, 4), "phi_post": (256, 4), "phi_res": (256, 16),
        "b_pre": (4,), "b_post": (4,), "b_res": (4, 4), "alpha_pre": (),
        "alpha_post": (), "alpha_res": ()}
    assert all(a.dtype == jnp.float32 for a in
               jax.tree.leaves(params["hc_attn_0"]))


@pytest.mark.parametrize("t", [100, 40, 5],
                         ids=["expanded_in_blocks", "expanded", "absorbed"])
def test_forward_matches_the_reference(tiny, t):
    model, params = tiny
    ids = jax.random.randint(jax.random.key(1), (2, t), 0, model.vocab_size)
    got = _compiled(model, "forward")(params, ids)
    assert got.dtype == jnp.float32 and got.shape == (2, t, model.vocab_size)
    assert rel(got, reference(params, ids)) < TOL


def _halved_post(next_fun, args, kwargs, context):
    out = next_fun(*args, **kwargs)
    if isinstance(context.module, lm.HyperConnection):
        pre, post, res = out
        return pre, post / 2, res
    return out


@pytest.mark.parametrize("mutation", ["one_sinkhorn_iteration",
                                      "post_without_its_2", "stream_0",
                                      "one_stream"])
def test_each_mutation_fails_the_comparison(tiny, mutation, monkeypatch):
    """The program with one part of the mechanism wrong, on the same
    weights: 1 Sinkhorn iteration in place of 20, ``H_post`` without its
    factor 2, the head reading stream 0 in place of the streams' sum, one
    stream (``hc_mult`` 1, the mappings' weights unread)."""
    model, params = tiny
    ids = jax.random.randint(jax.random.key(1), (1, 40), 0, model.vocab_size)
    want = reference(params, ids)
    forward = lambda m: jax.jit(
        lambda p, i: m.apply({"params": p}, i))(params, ids)
    if mutation == "one_sinkhorn_iteration":
        got = forward(model.clone(hc_sinkhorn_iters=1))
    elif mutation == "post_without_its_2":
        with nn.intercept_methods(_halved_post):
            got = forward(model)
    elif mutation == "stream_0":
        monkeypatch.setattr(lm, "_merged", lambda x, n: lm._streams(x, n)[0])
        got = forward(model)
    else:
        got = forward(model.clone(hc_mult=1))
    assert rel(got, want) > 1e4 * TOL


def test_h_res_is_doubly_stochastic_with_the_clip_active():
    """A mapping with a logit of 40 planted in ``H_res``'s first cell: the
    program's ``H_res`` is the 20 iterations of a float64 Sinkhorn over the
    CLIPPED logits (at 30: unclipped, the rest of the first column would be
    e^10 times smaller), its rows sum to 1 but for ``hc_eps``, and its
    columns are as near 1 as 20 iterations bring them (a cell this large
    slows the iterations: 200 bring the columns five times nearer)."""
    n, width, t = 4, 16, 6
    layer = lm.HyperConnection(n, 20, 1e-6, (-30.0, 30.0), 1e-6)
    x = jax.random.normal(jax.random.key(4), (1, t, n * width))
    params = layer.init(jax.random.key(5), x)["params"]
    params = dict(params, b_res=params["b_res"].at[0, 0].set(40.0),
                  phi_res=params["phi_res"] * 0.1)
    pre, post, res = jax.jit(layer.apply)({"params": params}, x)
    assert pre.shape == post.shape == (n, 1, t) and res.shape == (n, n, 1, t)
    assert bool(jnp.all((pre > 0) & (pre < 1) & (post > 0) & (post < 2)))
    h = np.moveaxis(np.asarray(res, np.float64), (2, 3), (0, 1))[0]

    v = np.asarray(x, np.float64)[0]
    v = v / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + 1e-6)
    logits = (v @ np.asarray(params["phi_res"], np.float64)).reshape(
        t, n, n) + np.asarray(params["b_res"], np.float64)
    assert (logits[:, 0, 0] > 30).all()

    def sinkhorn(m, iters):
        for _ in range(iters):
            m = m / (m.sum(axis=-2, keepdims=True) + 1e-6)
            m = m / (m.sum(axis=-1, keepdims=True) + 1e-6)
        return m

    clipped = sinkhorn(np.exp(np.clip(logits, -30, 30)), 20)
    np.testing.assert_allclose(h, clipped, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(h[:, 1:, 0], clipped[:, 1:, 0], rtol=1e-3)
    unclipped = sinkhorn(np.exp(logits), 20)
    assert (unclipped[:, 1:, 0] < 1e-3 * clipped[:, 1:, 0]).all()
    np.testing.assert_allclose(h.sum(axis=-1), 1.0, atol=1e-5)
    residual = np.abs(clipped.sum(axis=-2) - 1).max()
    assert np.abs(h.sum(axis=-2) - 1).max() <= residual + 1e-5
    further = sinkhorn(np.exp(np.clip(logits, -30, 30)), 200)
    assert np.abs(further.sum(axis=-2) - 1).max() < residual / 5


def test_a_mapping_is_counted_as_it_is_traced(tiny):
    model, params = tiny
    jax.jit(lambda p, i: model.apply({"params": p}, i)).lower(
        params, jnp.zeros((1, 8), jnp.int32))
    rows = {r["name"]: r.get("value")
            for r in telemetry.get_registry().rows()}
    assert rows["model.hc.sublayers"] == 2 * model.num_layers


def _prefill(model, params, pool, seq, n, slot, bucket):
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = seq[:n]
    new_pool, logits = _compiled(model, "prefill")(
        params, pool.pool, ids, np.int32(slot), np.int32(n))
    pool.swap(new_pool)
    pool.lengths[slot] = n
    return logits


def test_prefill_then_decode_through_the_pool_matches_every_position(tiny):
    """Two sequences prefilled into pool rows (buckets 16 and 96: an
    absorbed and an expanded block), then 30 decode steps on a 4-lane
    executable (two padding lanes on the scratch row): every position's
    logits against the reference's full forward. The pool holds one latent
    line a position a layer, as without streams."""
    model, params = tiny
    pool = KVCachePool(model, num_slots=3)
    assert [{k: v.shape for k, v in leaf.items()} for leaf in pool.pool] == \
        [{"kv": (4, 128, 128)}] * 3
    rng = np.random.default_rng(3)
    seqs = rng.integers(1, model.vocab_size, (2, 70 + 30)).astype(np.int32)
    prompts, slots, buckets = (5, 70), (2, 0), (16, 96)
    want = np.asarray(reference(params, seqs))
    for which in range(2):
        logits = _prefill(model, params, pool, seqs[which], prompts[which],
                          slots[which], buckets[which])
        assert rel(logits, want[which][prompts[which] - 1]) < TOL
    scratch = pool.scratch_slot
    for step in range(30):
        slot_ids = np.array([slots[0], scratch, slots[1], scratch], np.int32)
        tokens = np.array([seqs[0][prompts[0] + step], GHOST_TOKEN,
                           seqs[1][prompts[1] + step], GHOST_TOKEN], np.int32)
        lengths = np.array([prompts[0] + step, 0, prompts[1] + step, 0],
                           np.int32)
        new_pool, logits, held = _compiled(model, "decode")(
            params, pool.pool, slot_ids, tokens, lengths)
        pool.swap(new_pool)
        for lane, which in ((0, 0), (2, 1)):
            assert rel(logits[lane], want[which][prompts[which] + step]) \
                < TOL, (step, lane)
        assert held.shape == (2, model.experts_held)     # the expert layers


@pytest.mark.parametrize("length", [1, 50, 96],
                         ids=["one", "middle", "whole_bucket"])
def test_prefill_returns_the_reference_row_at_its_length(tiny, length):
    """A prefill in a 96-token bucket, told the prompt's length, sums the
    four streams, norms and computes the head at position ``length - 1``
    alone: the reference's logits there, whatever the padding holds."""
    model, params = tiny
    seq = np.random.default_rng(length).integers(
        1, model.vocab_size, 96).astype(np.int32)
    want = np.asarray(reference(params, seq[None, :length]))[0, -1]
    pool = KVCachePool(model, num_slots=2)
    ids = seq[None].copy()
    _, got = _compiled(model, "prefill")(params, pool.pool, ids,
                                         np.int32(1), np.int32(length))
    assert got.shape == (model.vocab_size,)
    assert rel(got, want) < TOL


def test_told_its_real_length_the_model_computes_one_row_of_the_head(tiny):
    model, params = tiny
    row = jax.eval_shape(lambda: model.init_cache(1, positions=96))
    call = lambda **told: jax.eval_shape(
        lambda p, c, ids: model.apply(
            {"params": p}, ids, cache=c,
            cache_index=jnp.zeros((1,), jnp.int32), **told),
        params, row, jax.ShapeDtypeStruct((1, 96), jnp.int32))[0].shape
    assert call(real_len=jnp.array([40])) == (1, 1, model.vocab_size)
    assert call() == (1, 96, model.vocab_size)


def test_engine_tokens_are_the_reference_argmax_and_counters_add_up(tiny):
    """Through ``GenerationEngine`` with no keyword of its own: prompts in
    padded buckets, 12 tokens each, three at once on four slots; every
    emitted token's reference logit is its position's largest, to the
    tolerance, and the counters hold the steps' tokens and assignments."""
    model, params = tiny
    rng = np.random.default_rng(7)
    sizes = (5, 75, 9)
    prompts = [rng.integers(1, model.vocab_size, n).astype(np.int32)
               for n in sizes]
    with GenerationEngine(model, params, num_slots=4, slot_ladder=(2, 4),
                          prefill_buckets=(16, 96)) as eng:
        assert eng.compiled_executables == {"prefill": (16, 96),
                                            "decode": (2, 4)}
        futures = [eng.generate(p, max_new_tokens=12) for p in prompts]
        outs = [f.result(timeout=120).tokens for f in futures]
    for prompt, out in zip(prompts, outs):
        assert len(out) == 12
        full = np.concatenate([prompt, out])
        logits = np.asarray(reference(params, full[None]))[0]
        at = logits[len(prompt) - 1:len(full) - 1]
        gaps = at.max(axis=-1) - at[np.arange(12), out]
        assert gaps.max() <= TOL * np.abs(logits).max(), gaps
    counters = telemetry.get_registry().snapshot()["counters"]
    assert counters["serving.decode.tokens"] == 33
    assert counters["serving.moe.assignments"] == \
        33 * model.experts_per_token * 2      # the two expert layers
    # every executable the engine compiled traced the 6 mappings once
    assert counters["model.hc.sublayers"] % (2 * model.num_layers) == 0
    assert counters["model.hc.sublayers"] >= 4 * 2 * model.num_layers
