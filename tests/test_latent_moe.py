"""The latent-attention, routed-expert decoder (models/latent_moe.py)
against its plain reference (perf/reference/latent_moe.py: float32,
expanded attention, no cache, nothing of the program), at a small size on
the CPU with seeded random weights, and the cache protocol it shares with
``CausalLM`` (the model owns its cache's leaves; ``KVCachePool`` asks).

Tolerances. In float32 the program and the reference differ only in the
order of their sums: 1e-5 of the largest logit (measured: under 1e-6).
With bfloat16 parameters and products, measured against the reference on
the same bfloat16 weights, see ``BF16_TOLERANCE`` below.
"""

import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import latent_moe as lm
from distkeras_tpu.models.gpt import gpt_tiny
from distkeras_tpu.serving import GenerationEngine, KVCachePool
from distkeras_tpu.serving.generation import (GHOST_TOKEN, make_decode_fn,
                                              make_prefill_fn)
from distkeras_tpu.serving.kv_cache import PagedKVCachePool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perf"))
from reference import dots3_note as ref_dots3  # noqa: E402
from reference import latent_moe as ref  # noqa: E402
from reference import nemotron_h as ref_nemotron  # noqa: E402

F32_TOLERANCE = 1e-5
#: the program in bfloat16 (parameters and products; float32 router, norms,
#: softmax, logits and residual stream) against the float32 reference on
#: the same weights: per position, the largest |program - reference| logit
#: over the largest |reference| logit of all; then the 90th percentile over
#: the 384 positions. The percentile, because a token whose fourth and
#: fifth gates nearly tie goes to another expert in either precision (its
#: error, up to 0.12 here, is a choice and not rounding) while rounding
#: moves every position. Measured at this file's sizes and seeds: 0.0059,
#: 0.0049, 0.0049; the reference computed wholly in bfloat16 (router,
#: norms, softmax, logits and stream as well): 0.0110, 0.0077, 0.0086.
BF16_TOLERANCE_P90 = 0.0068
#: and nothing anywhere near a wrong mask, position or weight (O(1))
BF16_TOLERANCE_MAX = 0.25


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def config_of(model) -> dict:
    """The reference reads a configuration file's keys (the source's)."""
    return {
        "num_hidden_layers": model.num_layers,
        "num_attention_heads": model.num_heads,
        "kv_lora_rank": model.kv_lora_rank,
        "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim,
        "v_head_dim": model.v_head_dim,
        "rms_norm_eps": model.rms_eps,
        "num_experts_per_tok": model.experts_per_token,
        "routed_scaling_factor": model.routed_scaling,
        "expert_share": {"index": model.expert_share[0],
                         "of": model.expert_share[1]},
        "rope_parameters": {
            "rope_theta": model.rope_theta, "factor": model.rope_factor,
            "beta_fast": model.rope_beta_fast,
            "beta_slow": model.rope_beta_slow,
            "original_max_position_embeddings": model.rope_original_max_len,
            "mscale_all_dim": model.rope_mscale_all_dim,
            "llama_4_scaling_beta": model.position_beta}}


@functools.lru_cache(maxsize=None)
def _compiled(model, what, dtype=None):
    """One jitted function a model and kind: the seeds share a compile."""
    if what == "init":
        return jax.jit(lambda key: model.init(
            key, jnp.zeros((1, 8), jnp.int32))["params"])
    if what == "forward":
        return jax.jit(lambda p, i: model.apply({"params": p}, i))
    cfg = config_of(model)
    return jax.jit(lambda p, i: ref.forward(p, i, cfg, dtype))


def init(model, seed=0):
    return _compiled(model, "init")(jax.random.key(seed))


def forward(model, params, ids):
    return _compiled(model, "forward")(params, ids)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def reference(model, params, ids, dtype=jnp.float32):
    """The reference's logits ``[batch, t, vocab]``, as one compiled call."""
    return _compiled(model, "reference", dtype)(params, jnp.asarray(ids))


@pytest.fixture(scope="module")
def tiny():
    model = lm.latent_moe_tiny(max_len=96)
    return model, init(model)


# ------------------------------------------------------------ full forward

@pytest.mark.parametrize("t", [512, 300, 40], ids=[
    "expanded_in_blocks", "expanded_ragged", "absorbed"])
def test_forward_matches_the_reference(t):
    """Cache-less forward, both forms of the layer (a 512-token block
    takes its queries in two blocks of 256, a 300-token one in two with
    the second padded and cut; 40 tokens are absorbed), positions past the original length (16) so the query's position
    factor leaves 1."""
    model = lm.latent_moe_tiny(max_len=512)
    params = init(model)
    ids = jax.random.randint(jax.random.key(1), (1, t), 0, model.vocab_size)
    got = forward(model, params, ids)
    assert got.dtype == jnp.float32 and got.shape == (1, t, model.vocab_size)
    assert rel(got, reference(model, params, ids)) < F32_TOLERANCE


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_program_is_inside_and_bfloat16_router_outside(seed):
    """The served precision stays inside ``BF16_TOLERANCE_P90`` of the
    float32 reference on the same weights; the same equations computed
    wholly in bfloat16 (a bfloat16 router, softmax, norms) do not."""
    model = lm.latent_moe_tiny(max_len=128, dtype=jnp.bfloat16, width=64,
                               moe_width=32, num_experts=16,
                               experts_per_token=4, expert_share=(0, 2))
    params = init(model, seed)
    assert params["moe_0"]["gate"].dtype == jnp.bfloat16
    assert params["moe_0"]["router"].dtype == jnp.float32
    ids = jax.random.randint(jax.random.key(seed + 7), (4, 96), 0,
                             model.vocab_size)
    want = reference(model, params, ids)

    def errors(got):
        e = jnp.max(jnp.abs(got.astype(jnp.float32) - want), axis=-1)
        e = np.asarray(e).ravel() / float(jnp.max(jnp.abs(want)))
        return np.percentile(e, 90), e.max()

    p90, worst = errors(forward(model, params, ids))
    assert p90 < BF16_TOLERANCE_P90 and worst < BF16_TOLERANCE_MAX
    p90, _ = errors(reference(model, params, ids, jnp.bfloat16))
    assert p90 > BF16_TOLERANCE_P90


# ------------------------------------------------- through the cache pool

def test_prefill_then_decode_through_the_pool_matches_every_position(tiny):
    """Two sequences prefilled into pool rows (bucket 16, fresh rows as
    long as the bucket), then 16 decode steps on a 4-lane executable (two
    padding lanes on the scratch row): every position's logits against
    the reference's full forward of the whole sequence."""
    model, params = tiny
    pool = KVCachePool(model, num_slots=3)
    assert [leaf["kv"].shape for leaf in pool.pool] == \
        [(4, 96, 128)] * model.num_layers         # 24 + 8 -> one 128 tile
    assert pool.cache_bytes == 4 * model.cache_bytes_per_row()
    prefill = jax.jit(make_prefill_fn(model))
    decode = jax.jit(make_decode_fn(model))
    rng = np.random.default_rng(3)
    seqs = rng.integers(1, model.vocab_size, (2, 14 + 16)).astype(np.int32)
    prompts, slots = (9, 14), (2, 0)
    want = np.asarray(reference(model, params, seqs))
    for which, (n, slot) in enumerate(zip(prompts, slots)):
        ids = np.zeros((1, 16), np.int32)
        ids[0, :n] = seqs[which][:n]
        new_pool, logits = prefill(params, pool.pool, ids, np.int32(slot),
                                   np.int32(n))
        pool.swap(new_pool)
        pool.lengths[slot] = n
        assert rel(logits, want[which][n - 1]) < F32_TOLERANCE
    scratch = pool.scratch_slot
    for step in range(16):
        slot_ids = np.array([slots[0], scratch, slots[1], scratch], np.int32)
        tokens = np.array([seqs[0][prompts[0] + step], GHOST_TOKEN,
                           seqs[1][prompts[1] + step], GHOST_TOKEN], np.int32)
        lengths = np.array([prompts[0] + step, 0, prompts[1] + step, 0],
                           np.int32)
        new_pool, logits, held = decode(params, pool.pool, slot_ids, tokens,
                                        lengths)
        pool.swap(new_pool)
        for lane, which in ((0, 0), (2, 1)):
            assert rel(logits[lane], want[which][prompts[which] + step]) \
                < F32_TOLERANCE, (step, lane)
        # tokens per held expert: the two live lanes' real position only
        assert held.shape == (model.num_layers, model.experts_held)
        assert held.dtype == jnp.int32
        assert (np.asarray(held).sum(axis=1)
                <= 2 * model.experts_per_token).all()


@functools.lru_cache(maxsize=None)
def _prefill(model):
    return jax.jit(make_prefill_fn(model))


@pytest.mark.parametrize("length", [1, 50, 80],
                         ids=["one", "middle", "whole_bucket"])
def test_prefill_returns_the_reference_row_at_its_length(tiny, length):
    """A prefill in an 80-token bucket (the expanded form), told the
    prompt's length, hands back the reference's logits at position ``length
    - 1``: the head's one row, whatever the bucket's padding holds."""
    model, params = tiny
    ids = np.asarray(jax.random.randint(jax.random.key(length), (1, 80), 1,
                                        model.vocab_size), np.int32)
    want = np.asarray(reference(model, params, ids[:, :length]))[0, -1]
    pool = KVCachePool(model, num_slots=2)
    _, got = _prefill(model)(params, pool.pool, ids, np.int32(1),
                             np.int32(length))
    assert got.shape == (model.vocab_size,)
    assert rel(got, want) < F32_TOLERANCE


@pytest.mark.parametrize("t", [16, 80], ids=["absorbed", "expanded"])
def test_told_its_real_length_the_model_computes_one_row_of_the_head(
        tiny, t):
    model, params = tiny
    row = jax.eval_shape(lambda: model.init_cache(1, positions=t))
    call = lambda **told: jax.eval_shape(
        lambda p, c, ids: model.apply(
            {"params": p}, ids, cache=c,
            cache_index=jnp.zeros((1,), jnp.int32), **told),
        params, row, jax.ShapeDtypeStruct((1, t), jnp.int32))[0].shape
    assert call(real_len=jnp.array([t - 3])) == (1, 1, model.vocab_size)
    assert call() == (1, t, model.vocab_size)


def test_absorbed_form_equals_expanded_form_at_equal_inputs():
    heads, rank, nope, rope, v_dim = 4, 24, 8, 8, 12
    dims = (rank, nope, rope, v_dim, heads)
    keys = jax.random.split(jax.random.key(5), 4)
    b, t, r = 3, 6, 40
    q = jax.random.normal(keys[0], (b, t, heads, nope + rope))
    rows = jax.random.normal(keys[1], (b, r, rank + rope))
    w_kvb = jax.random.normal(keys[2], (rank, heads * (nope + v_dim))) * 0.2
    pos = jnp.array([[5], [20], [33]]) + jnp.arange(t)[None, :]
    scale = 0.3 * (1.0 + 0.1 * jax.random.uniform(keys[3], (b, t)))
    form = lambda fn, rows: jax.jit(
        lambda *a: fn(*a, dims, scale))(q, rows, pos, w_kvb)
    a = form(lm._attend_absorbed, rows)
    e = form(lm._attend_expanded, rows)
    assert a.shape == e.shape == (b, t, heads, v_dim)
    assert rel(a, e) < F32_TOLERANCE
    # a line padded to whole tiles reads the same
    padded = jnp.pad(rows, ((0, 0), (0, 0), (0, 96)))
    assert rel(form(lm._attend_absorbed, padded), e) < F32_TOLERANCE


# ------------------------------------------------------- the expert layer

def _layer(num_experts, share, k=2):
    return lm.ExpertShare(width=16, num_experts=num_experts,
                          experts_per_token=k, expert_share=share,
                          dtype=jnp.float32)


def _apply(layer, params, x):
    return jax.jit(lambda p, x: layer.apply({"params": p}, x))(params, x)


def _ref_moe(params, x, share, k=2):
    z = {"k": k, "index": share[0], "routed_scaling": 1.0}
    return jax.jit(lambda p, x: ref.moe(p, x, z))(params, x)


#: few tokens go through every held expert, many through a grouped product
TOKENS = pytest.mark.parametrize("tokens", [20, 300],
                                 ids=["masked_dense", "grouped"])


@TOKENS
def test_the_four_shares_add_up_to_the_uncut_layer(tokens):
    """The routed parts that shares 0..3 compute, plus the shared expert
    once, equal the uncut reference's layer (all 8 experts in one)."""
    assert 20 <= lm._DENSE_MAX_TOKENS < 300
    x = jax.random.normal(jax.random.key(2), (tokens, 32))
    whole = jax.jit(_layer(8, (0, 1)).init)(jax.random.key(3), x)["params"]
    want = _ref_moe(whole, x, (0, 1))
    shared = ref.swiglu(x, whole["shared_gate"], whole["shared_up"],
                        whole["shared_down"])
    total = shared
    sent = 0
    for index in range(4):
        part = dict(whole, **{name: whole[name][2 * index:2 * index + 2]
                              for name in ("gate", "up", "down")})
        out, routed = _apply(_layer(8, (index, 4)), part, x)
        total = total + (out - shared)
        sent = sent + int(routed.sum())
        # and each share is what the reference gives for that share
        assert rel(out, _ref_moe(part, x, (index, 4))) < F32_TOLERANCE
    assert rel(total, want) < F32_TOLERANCE
    assert sent == tokens * 2       # every assignment is held by one share


@TOKENS
def test_router_ties_and_an_expert_without_a_token(tokens):
    """Two experts with the same router column tie on every token (the
    lower index wins, in the program and the reference alike), and an
    expert whose column is far below the rest receives no token: its
    group is empty and the result is still the reference's."""
    x = jax.random.normal(jax.random.key(4), (tokens, 32)).at[:, 0].set(1.0)
    layer = _layer(4, (0, 1), k=2)
    params = jax.jit(layer.init)(jax.random.key(6), x)["params"]
    router = params["router"]
    router = router.at[:, 1].set(router[:, 0])        # 0 and 1 tie
    router = router.at[:, 3].set(0.0).at[0, 3].set(-50.0)   # 3 is never near
    params = dict(params, router=router)
    logits = x @ router
    assert (logits[:, 0] == logits[:, 1]).all() and (logits[:, 3] < -40).all()
    out, routed = _apply(layer, params, x)
    assert not routed[:, 3].any()
    assert (routed[:, 0] & routed[:, 1]).any()        # tied, both among the 2
    assert (routed[:, 0] & ~routed[:, 1]).any()       # tied for the 2nd place
    assert not (routed[:, 1] & ~routed[:, 0]).any()   # the lower index first
    assert bool(jnp.isfinite(out).all())
    assert rel(out, _ref_moe(params, x, (0, 1))) < F32_TOLERANCE


#: scoring rule, expert form and the reference that writes them down
FAMILIES = {"softmax_swiglu": ("softmax", "swiglu", ref),
            "sigmoid_relu2": ("sigmoid", "relu2", ref_nemotron),
            "sigmoid_swiglu": ("sigmoid", "swiglu", ref_dots3)}


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("tokens,k", [(300, 2), (515, 8)],
                         ids=["300x2", "515x8"])
@pytest.mark.parametrize("routing",
                         ["every_held", "none_held", "one_takes_all"])
def test_many_token_path_is_dropless_for_any_routing(routing, tokens, k,
                                                     family):
    """The loop over blocks computes every assignment to a held expert and
    nothing else, whatever the router does: every choice held (share 0 of
    1: a token's k parts all add into its row); none held (this share's
    router outputs far below the rest: no block in use, the shared expert
    alone, nothing read from a row that is not the token's); one held
    expert taking every token and the other three none (two or three
    blocks of one expert, the last ragged). Neither ``tokens`` nor
    ``tokens * k`` is a multiple of the block."""
    scoring, activation, reference = FAMILIES[family]
    assert tokens > lm._DENSE_MAX_TOKENS and tokens % lm._EXPERT_BLOCK \
        and (tokens * k) % lm._EXPERT_BLOCK
    share = (0, 1) if routing == "every_held" else (1, 4)
    layer = lm.ExpertShare(
        width=16, num_experts=16, experts_per_token=k, expert_share=share,
        routed_scaling=2.5, dtype=jnp.float32, scoring=scoring,
        activation=activation)
    x = jax.random.normal(jax.random.key(tokens), (tokens, 32)
                          ).at[:, 0].set(1.0)
    params = jax.jit(layer.init)(jax.random.key(k), x)["params"]
    # router outputs 4..7 are this share's (the second of four)
    column = lambda r, e, logit: r.at[:, e].set(0.0).at[0, e].set(logit)
    router = params["router"]
    if routing == "none_held":
        for e in range(4, 8):
            router = column(router, e, -50.0)
    elif routing == "one_takes_all":
        for e in (4, 6, 7):
            router = column(router, e, -50.0)
        router = column(router, 5, 50.0)
    params = dict(params, router=router)
    if scoring == "sigmoid" and routing != "every_held":
        bias = params["router_bias"].at[4:8].set(-9.0)
        if routing == "one_takes_all":
            bias = bias.at[5].set(9.0)
        params = dict(params, router_bias=bias)
    held = 16 // share[1]
    part = dict(params, **{
        name: params[name][:held] for name in ("gate", "up", "down")
        if name in params})
    out, routed = _apply(layer, part, x)
    z = {"k": k, "index": share[0], "routed_scaling": 2.5}
    want = jax.jit(lambda p, x: reference.moe(p, x, z))(part, x)
    assert bool(jnp.isfinite(out).all())
    assert rel(out, want) < F32_TOLERANCE
    sent = np.asarray(routed).sum(axis=0)
    if routing == "every_held":
        assert sent.sum() == tokens * k
    elif routing == "none_held":
        assert sent.sum() == 0      # and what is wanted is the shared alone
        assert not np.asarray(reference.routed_part(part, x, z)).any()
    else:
        assert sent.tolist() == [0, tokens, 0, 0]


#: sha256 (first 16 digits) of what the layer below lowers to at 200
#: tokens, the few-token branch, at the commit before the many-token path
#: stopped laying out rows (PR 35)
PARENT_DENSE_TEXT = "45d91866c0bcdc0c"


def test_many_token_path_lays_out_indices_and_not_rows():
    """512 tokens, top 8 of 32 experts, 4 of them held, rows of 64: the
    lowered layer has no array of ``n * k`` or ``n * k + held * 256`` rows
    of the model's width and no ``[n, k, d]`` pass (the parent's text had
    all three); at 200 tokens, the other branch, the text is the
    parent's."""
    layer = lm.ExpertShare(width=16, num_experts=32, experts_per_token=8,
                           expert_share=(0, 8))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((8, 64)))["params"])
    lowered = lambda n: jax.jit(
        lambda p, x: layer.apply({"params": p}, x)).lower(
            params, f32(n, 64)).as_text()
    text = lowered(512)
    assert "tensor<256x64x" in text and "tensor<768x64xf32>" in text
    for rows in ("tensor<4096x64x", "tensor<5120x64x", "tensor<512x8x64x"):
        assert rows not in text, rows
    assert hashlib.sha256(lowered(200).encode()).hexdigest()[:16] == \
        PARENT_DENSE_TEXT


# ------------------------------------------------------ rotary embedding

def test_yarn_frequencies_against_closed_form():
    """dim 8, theta 10000, factor 4, beta 32 / 1, original length 16: the
    corners are 8 ln(16 / (32 * 2 pi)) / (2 ln 1e4) = -1.099 -> floor,
    clamped to 0, and 8 ln(16 / (2 pi)) / (2 ln 1e4) = 0.406 -> ceil 1:
    the ramp is 0 at pair 0 and 1 from pair 1 on."""
    got = lm.yarn_inv_freq(8, 10000.0, 4.0, 32.0, 1.0, 16)
    plain = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(
        got, [plain[0], plain[1] / 4, plain[2] / 4, plain[3] / 4], rtol=1e-6)
    # the published sizes: pairs that turn more than 32 times in 8192
    # positions keep theta^(-2j/64), the slowest are divided by 128
    big = lm.yarn_inv_freq(64, 10000.0, 128.0, 32.0, 1.0, 8192)
    full = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    low = np.floor(64 * np.log(8192 / (32 * 2 * np.pi)) / (2 * np.log(1e4)))
    high = np.ceil(64 * np.log(8192 / (2 * np.pi)) / (2 * np.log(1e4)))
    assert (low, high) == (12, 25)
    np.testing.assert_allclose(big[:13], full[:13], rtol=1e-6)
    np.testing.assert_allclose(big[25:], full[25:] / 128, rtol=1e-6)
    mid = (18 - 12) / (25 - 12)
    np.testing.assert_allclose(
        big[18], full[18] / 128 * mid + full[18] * (1 - mid), rtol=1e-6)
    # the reference's own copy agrees
    z = {"rope": 64, "theta": 10000.0, "factor": 128.0, "beta_fast": 32.0,
         "beta_slow": 1.0, "original": 8192}
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(z)), big,
                               rtol=1e-6)


def test_rotation_position_factor_and_softmax_scale_closed_form():
    # pairs (1, 0) at position 3 with frequency 0.5 turn by 1.5 radians
    x = jnp.array([[[1.0, 0.0, 0.0, 2.0]]])
    got = lm.rope_interleaved(x, jnp.array([[3]]), jnp.array([0.5, 0.25]))
    np.testing.assert_allclose(
        got[0, 0], [np.cos(1.5), np.sin(1.5),
                    -2 * np.sin(0.75), 2 * np.cos(0.75)], rtol=1e-6)
    # original length 16: 1 below it, 1 + 0.1 ln 2 at 16..31, ln 3 at 32..
    pos = jnp.array([0, 15, 16, 31, 32, 50])
    np.testing.assert_allclose(
        lm.position_scale(pos, 0.1, 16),
        [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(2),
         1 + 0.1 * np.log(3), 1 + 0.1 * np.log(4)], rtol=1e-6)
    assert abs(lm.softmax_scale(128, 128.0)
               - 128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2) < 1e-9
    assert lm.softmax_scale(128, 1.0) == 128 ** -0.5


# ------------------------------------------- the cache protocol, both ways

def test_pool_over_causal_lm_builds_the_leaves_it_built_before():
    """``KVCachePool`` asks the model for its leaves; for ``CausalLM``
    they are, bit for bit, what ``gpt.init_cache`` made: ``{"k", "v"}``
    zeros of ``[slots + 1, max_len, width]`` in the compute dtype, and
    the same bytes a row."""
    for model in (gpt_tiny(), gpt_tiny(dtype=jnp.bfloat16, max_len=64)):
        pool = KVCachePool(model, num_slots=3)
        assert len(pool.pool) == model.num_layers
        for layer in pool.pool:
            assert sorted(layer) == ["k", "v"]
            for leaf in layer.values():
                assert leaf.shape == (4, model.max_len, model.width)
                assert leaf.dtype == model.dtype
                assert not np.asarray(leaf, np.float32).any()
        itemsize = np.dtype(model.dtype).itemsize
        assert pool.cache_bytes == 4 * (2 * model.num_layers * model.max_len
                                        * model.width * itemsize)
        assert model.prefill_row_len(16) == model.max_len


def test_causal_lm_steps_lower_to_the_program_they_were():
    """The decode and prefill steps over ``CausalLM`` written as they
    were before the model owned its cache (the verify step and a slice;
    a fresh ``[1, max_len, width]`` row, told its real length and handing
    back its one row of logits) lower to the same program text as
    ``make_decode_fn`` / ``make_prefill_fn`` give now."""
    model = gpt_tiny()
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    pool = jax.eval_shape(lambda: model.init_cache(5))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)

    def decode(params, pool, slot_ids, tokens, lengths):
        ids = jnp.stack([tokens, jnp.full_like(tokens, GHOST_TOKEN)], axis=1)
        logits, pool = model.apply({"params": params}, ids, cache=pool,
                                   cache_index=lengths, cache_rows=slot_ids)
        return pool, logits[:, 0, :]

    def prefill(params, pool, ids, slot, length):
        row = jax.tree.map(
            lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype), pool)
        real_len = jnp.reshape(length, (1,))
        logits, new_row = model.apply(
            {"params": params}, ids, cache=row,
            cache_index=jnp.zeros((1,), jnp.int32), real_len=real_len)
        pool = jax.tree.map(
            lambda p, c: jax.lax.dynamic_update_slice_in_dim(
                p, c, slot, axis=0), pool, new_row)
        return pool, logits[0, 0]

    text = lambda fn, *args: jax.jit(fn).lower(params, pool, *args).as_text()
    strip = lambda s: s.replace("jit_decode", "").replace("jit_prefill", "")
    assert strip(text(decode, i32(4), i32(4), i32(4))) == \
        strip(text(make_decode_fn(model), i32(4), i32(4), i32(4)))
    assert strip(text(prefill, i32(1, 16), i32(), i32())) == \
        strip(text(make_prefill_fn(model), i32(1, 16), i32(), i32()))


def test_paged_pool_refuses_a_family_without_a_paged_form(tiny):
    model, _ = tiny
    with pytest.raises(TypeError, match="rectangular KVCachePool"):
        PagedKVCachePool(model, num_slots=2, page_size=16)
    with pytest.raises(ValueError, match="no paged form"):
        model.apply({"params": tiny[1]}, jnp.zeros((1, 2), jnp.int32),
                    cache=model.init_cache(1),
                    cache_index=jnp.zeros(1, jnp.int32),
                    page_table=jnp.zeros((1, 6), jnp.int32))


# ---------------------------------------------- through GenerationEngine

def test_engine_serves_the_family_and_its_counters_add_up(tiny):
    """Greedy generation through ``GenerationEngine`` + ``KVCachePool``
    gives the tokens the reference's full forward would choose, and the
    ``serving.moe.*`` counters the decode step feeds add up."""
    model, params = tiny
    with GenerationEngine(model, params, num_slots=4, slot_ladder=(2, 4),
                          prefill_buckets=(8, 16)) as eng:
        assert eng.compiled_executables == {"prefill": (8, 16),
                                            "decode": (2, 4)}
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, model.vocab_size, n).tolist()
                   for n in (5, 12, 16, 3, 9, 7)]
        futures = [eng.generate(p, max_new_tokens=10) for p in prompts]
        results = [f.result(timeout=120) for f in futures]
    whole = np.zeros((6, 32), np.int32)
    for row, prompt, res in zip(whole, prompts, results):
        assert len(res.tokens) == 10
        row[:len(prompt) + 10] = list(prompt) + list(res.tokens)
    logits = np.asarray(reference(model, params, whole))
    for row, prompt, logit in zip(whole, prompts, logits):
        for p in range(len(prompt) - 1, len(prompt) + 9):
            assert logit[p].max() - logit[p, row[p + 1]] < 1e-4
    snap = telemetry.get_registry().snapshot()
    assigned = snap["counters"]["serving.moe.assignments"]
    held = snap["counters"]["serving.moe.assignments_held"]
    tokens = snap["counters"]["serving.decode.tokens"]
    assert tokens == 6 * 9          # the first token comes from the prefill
    assert assigned == tokens * model.experts_per_token * model.num_layers
    assert 0 < held <= assigned
    steps = snap["counters"]["serving.decode.steps"]
    active = snap["histograms"]["serving.moe.experts_active"]
    load = snap["histograms"]["serving.moe.load_max_over_mean"]
    assert active["count"] == steps and load["count"] == steps
    assert 0 < active["max"] <= model.experts_held
    assert load["min"] >= 1.0
    # a family that attends all it holds counts no positions attended
    assert not any(name.startswith("serving.sparse.")
                   for name in snap["counters"])


def test_decode_step_counts_sum_to_the_assignments_held(tiny):
    """Tokens per held expert, as the decode step returns them, against
    the router worked by hand from the reference: per layer they sum to
    the live lanes' assignments that fell on held experts."""
    model, params = tiny
    pool = model.init_cache(3)
    decode = jax.jit(make_decode_fn(model))
    slot_ids = np.array([0, 2, 1, 2], np.int32)          # row 2 is scratch
    tokens = np.array([5, GHOST_TOKEN, 9, GHOST_TOKEN], np.int32)
    _, _, held = decode(params, pool, slot_ids, tokens,
                        np.zeros(4, np.int32))
    # the same two tokens at position 0 through the reference's router
    z = ref.sizes(config_of(model))
    x = jnp.asarray(params["tok_embed"])[jnp.array([5, 9])]
    want = []
    for i in range(model.num_layers):
        y = ref.rms_norm(x, params[f"attn_norm_{i}"], z["eps"])
        x = x + jnp.stack([ref.attention(params[f"attn_{i}"], row[None],
                                         jnp.arange(1), z)[0] for row in y])
        y = ref.rms_norm(x, params[f"moe_norm_{i}"], z["eps"])
        _, chosen = jax.lax.top_k(
            jax.nn.softmax(y @ params[f"moe_{i}"]["router"]), z["k"])
        want.append([int((chosen == e).sum())
                     for e in range(model.experts_held)])
        x = x + ref.moe(params[f"moe_{i}"], y, z)
    np.testing.assert_array_equal(np.asarray(held), want)
