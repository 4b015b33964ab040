"""Long-context serving economics tests (ISSUE 20).

Three compounding accelerations, each pinned to the same exactness
standard the serving stack already carries:

- **chunked prefill** equals one-shot prefill, and the full forward, at
  the decode-step tolerance (test_generation.TOL) at every chunk
  boundary and mid-sequence position, the engine's chunked path is
  token-identical to the unchunked engine, and the chunk executable is
  declared up front — the compile cache still never grows under
  traffic;
- **int8 KV pages** reuse the wire codec's affine quantizer (the same
  qparams rule ``precision.py`` shares), hold a per-cell round-trip
  error bound of scale/2, shrink the page pool below 1/1.8 of native,
  and survive a prefix-cache host round trip token-identically;
- **sampled speculative decoding** with the min(1, p/q) accept rule is
  STREAM-IDENTICAL to plain target sampling under a shared seed — for
  the repo's deterministic (point-mass) drafts the residual resample
  coincides with the mismatch draw, so equality is exact, not merely
  distributional (NUMERICS.md "Sampled speculative equivalence").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.comms import codec
from distkeras_tpu.models import gpt as gpt_lib
from distkeras_tpu.models.gpt import (
    KV_QUANT_LEVELS,
    dequantize_kv_page,
    gpt_tiny,
    page_bytes,
    quantize_kv_page,
)
from distkeras_tpu.serving import (
    GenerationEngine,
    ModelDraft,
    NgramDraft,
    PagedKVCachePool,
)
from distkeras_tpu.serving.generation import make_paged_step_fn
from distkeras_tpu import precision
from distkeras_tpu.utils import fault
from test_generation import TOL, _prompt


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    fault.clear_chaos()
    yield
    telemetry.reset()
    fault.clear_chaos()


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _tokens(eng, prompts, max_new=16, timeout=120):
    futs = [eng.generate(p, max_new_tokens=max_new) for p in prompts]
    return [f.result(timeout=timeout).tokens.tolist() for f in futs]


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_parity_at_every_boundary(lm):
    """Feeding a 29-token prompt in 8-token chunks through the paged
    step family yields the one-shot bucket-32 prefill's logits, and the
    full forward's, at the decode-step tolerance at every covered
    position — including the mid-sequence chunk starts at 8, 16, 24."""
    model, params = lm
    step = jax.jit(make_paged_step_fn(model), donate_argnums=(1,))
    seq = _prompt(29, seed=5)
    chunk = 8

    def run(feed_sizes):
        pool = PagedKVCachePool(model, num_slots=1, page_size=16)
        slot = pool.allocate()
        assert pool.reserve(slot, model.max_len)
        pts = pool.page_table_row(slot)[None, :]
        rows, pos = [], 0
        for size in feed_sizes:
            ids = np.zeros((1, size), np.int32)
            take = seq[pos:pos + size]
            ids[0, :len(take)] = take
            new_pool, logits = step(params, pool.pool, pts, ids,
                                    np.full(1, pos, np.int32))
            pool.swap(new_pool)
            rows.append(np.asarray(logits)[0, :len(take)])
            pos += len(take)
        return np.concatenate(rows, axis=0)

    one_shot = run([32])[:29]
    chunked = run([chunk] * 4)[:29]
    pad = np.zeros((1, model.max_len), np.int32)
    pad[0, :29] = seq
    full = np.asarray(model.apply({"params": params}, pad))[0, :29]
    np.testing.assert_allclose(chunked, one_shot, **TOL)
    np.testing.assert_allclose(chunked, full, **TOL)


def test_chunked_engine_token_identical_and_cache_fixed(lm):
    """The chunked engine emits exactly the unchunked engine's tokens,
    declares the prefill_chunk executable up front, and adds ZERO
    executables under mixed chunked traffic."""
    model, params = lm
    prompts = [_prompt(n, seed=40 + n) for n in (5, 20, 31, 12, 27)]
    with GenerationEngine(model, params, num_slots=2,
                          page_size=16) as eng:
        want = _tokens(eng, prompts)
    with GenerationEngine(model, params, num_slots=2, page_size=16,
                          prefill_chunk=8) as eng:
        assert eng.compiled_executables["prefill_chunk"] == (8,)
        compiles = telemetry.counter("serving.decode.compiles").value
        declared = dict(eng.compiled_executables)
        got = _tokens(eng, prompts)
        assert eng.compiled_executables == declared
        assert telemetry.counter(
            "serving.decode.compiles").value == compiles
        assert telemetry.counter(
            "serving.decode.chunk.admitted").value >= 1
        hs = eng.health_status()["chunked_prefill"]
        assert hs["prefill_chunk"] == 8 and hs["chunk_steps"] >= 1
    assert got == want


def test_chunk_size_matching_bucket_shares_executable(lm):
    """prefill_chunk equal to a prefill bucket reuses that executable
    instead of compiling a new one."""
    model, params = lm
    with GenerationEngine(model, params, num_slots=2, page_size=16,
                          prefill_buckets=(8, 32),
                          prefill_chunk=8) as eng:
        # 2 prefill + 2 decode (no prefix cache => no swap execs),
        # and NO extra chunk compile
        assert telemetry.counter("serving.decode.compiles").value == 4
        assert eng.compiled_executables["prefill_chunk"] == (8,)
        got = _tokens(eng, [_prompt(20, seed=9)], max_new=8)
    with GenerationEngine(model, params, num_slots=2,
                          page_size=16) as eng:
        assert got == _tokens(eng, [_prompt(20, seed=9)], max_new=8)


def test_chunked_composes_with_prefix_and_spec(lm):
    """chunked prefill + prefix cache + speculative decoding together
    still emit the plain paged engine's exact tokens."""
    model, params = lm
    shared = _prompt(24, seed=77)
    prompts = [shared, _prompt(9, seed=78), shared]
    with GenerationEngine(model, params, num_slots=2,
                          page_size=16) as eng:
        want = _tokens(eng, prompts, max_new=10)
    with GenerationEngine(model, params, num_slots=2, page_size=16,
                          prefill_chunk=8, prefix_cache_bytes=4 << 20,
                          draft=NgramDraft(ngram=2), spec_k=3) as eng:
        got = _tokens(eng, prompts, max_new=10)
        assert eng.health_status()["prefix_cache"]["hits"] >= 1
    assert got == want


# ---------------------------------------------------------------------------
# int8 KV pages
# ---------------------------------------------------------------------------


def test_kv_quantizer_qparams_match_codec_and_precision_rule(lm):
    """quantize_kv_page derives its scale from the SAME affine rule the
    wire codec and precision.py share, and its codes equal
    precision.quantize_int8 on the flattened page."""
    rng = np.random.default_rng(0)
    page = jnp.asarray(rng.normal(size=(3, 16, 2, 16)).astype(np.float32))
    codes, scale = quantize_kv_page(page)
    amax = np.max(np.abs(np.asarray(page)), axis=(1, 2, 3))
    np.testing.assert_allclose(
        np.asarray(scale), precision.symmetric_int8_qparams(amax))
    np.testing.assert_allclose(
        np.asarray(scale),
        codec.affine_qparams(-amax, amax, KV_QUANT_LEVELS))
    want, pscale = precision.quantize_int8(np.asarray(page[0]).ravel())
    np.testing.assert_allclose(float(scale[0]), pscale)
    np.testing.assert_array_equal(
        np.asarray(codes[0]).ravel(), want)


def test_kv_page_roundtrip_error_bound(lm):
    """Per-cell dequant error <= scale/2 on random pages; the all-zero
    page round-trips exactly with scale 0."""
    rng = np.random.default_rng(1)
    for i in range(4):
        page = jnp.asarray(
            rng.normal(scale=10.0 ** (i - 2),
                       size=(2, 16, 2, 16)).astype(np.float32))
        codes, scale = quantize_kv_page(page)
        back = np.asarray(dequantize_kv_page(codes, scale))
        err = np.abs(back - np.asarray(page))
        bound = np.asarray(scale)[:, None, None, None] / 2
        assert np.all(err <= bound + 1e-7), err.max()
    codes, scale = quantize_kv_page(jnp.zeros((1, 16, 2, 16)))
    assert float(scale[0]) == 0.0
    np.testing.assert_array_equal(np.asarray(codes), 0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_kv_page(codes, scale)), 0.0)


def test_int8_pool_accounting_and_engine_generates(lm):
    """int8 pages cost < native/1.8 bytes, the engine reports the
    format in health_status, and generation completes."""
    model, params = lm
    native = page_bytes(model, 16)
    quant = page_bytes(model, 16, kv_dtype="int8")
    assert quant * 1.8 < native
    with GenerationEngine(model, params, num_slots=2, page_size=16,
                          kv_dtype="int8") as eng:
        assert eng.pool.kv_dtype == "int8"
        assert eng.pool.page_bytes == quant
        out = _tokens(eng, [_prompt(20, seed=3), _prompt(7, seed=4)])
        assert all(len(t) > 0 for t in out)
        paged = eng.health_status()["paged"]
        assert paged["kv_dtype"] == "int8"
        assert paged["kv_quant_bytes_saved"] == (
            (native - quant) * (eng.pool.num_pages + 1))
        assert telemetry.gauge(
            "serving.decode.paged.kv_quant_bytes_saved").value > 0


def test_int8_prefix_hit_roundtrip_token_identical(lm):
    """A prefix-cache full hit on an int8 pool — quantized blobs
    swapped out to host and back — replays the cold run's tokens
    exactly (the host copy stores the codes, so no second
    quantization error accrues)."""
    model, params = lm
    prompt = _prompt(22, seed=11)
    with GenerationEngine(model, params, num_slots=2, page_size=16,
                          kv_dtype="int8",
                          prefix_cache_bytes=4 << 20) as eng:
        cold = _tokens(eng, [prompt], max_new=12)
        warm = _tokens(eng, [prompt], max_new=12)
        assert eng.health_status()["prefix_cache"]["hits"] >= 1
    assert warm == cold


def test_int8_decode_close_to_native(lm):
    """int8 KV is lossy by design: a cell is off by at most ``scale / 2``,
    one half-step of a 254-level grid over its page's range. Step by step
    the int8 pool's logits stay within eight such half-steps of the native
    pool's (relative to the largest native logit), both pools fed the
    NATIVE greedy stream so that one argmax flip cannot cascade, and the
    greedy token is the native one wherever the native top-two gap exceeds
    twice that bound."""
    model, params = lm
    step = jax.jit(make_paged_step_fn(model))

    def feed(pool, ids, length):
        pts = pool.page_table_row(0)[None, :]
        new_pool, logits = step(params, pool.pool, pts, ids,
                                np.array([length], np.int32))
        pool.swap(new_pool)
        return np.asarray(logits)[0]

    for seq in (_prompt(20, seed=6), _prompt(13, seed=8)):
        native, quant = (PagedKVCachePool(model, num_slots=1, page_size=16,
                                          kv_dtype=kv)
                         for kv in (None, "int8"))
        for pool in (native, quant):
            assert pool.reserve(pool.allocate(), model.max_len)
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(seq)] = seq
        want = feed(native, ids, 0)[len(seq) - 1]
        got = feed(quant, ids, 0)[len(seq) - 1]
        for length in range(len(seq), len(seq) + 10):
            bound = 8 * np.max(np.abs(want)) / KV_QUANT_LEVELS
            np.testing.assert_allclose(got, want, rtol=0, atol=bound)
            top = np.sort(want)[-2:]
            if top[1] - top[0] > 2 * bound:
                assert np.argmax(got) == np.argmax(want)
            tok = np.array([[np.argmax(want), 0]], np.int32)  # + ghost
            want = feed(native, tok, length)[0]
            got = feed(quant, tok, length)[0]


# ---------------------------------------------------------------------------
# sampled speculative decoding
# ---------------------------------------------------------------------------


def test_sampled_spec_stream_identical_ngram(lm):
    """Seeded sampled engine with an n-gram draft emits EXACTLY the
    plain sampled engine's stream — the accept/resample coupling
    consumes one uniform per emitted token in emission order."""
    model, params = lm
    prompts = [_prompt(n, seed=50 + n) for n in (5, 18, 30)]
    kw = dict(num_slots=2, sampling=True, temperature=0.7, seed=321)
    with GenerationEngine(model, params, **kw) as eng:
        want = _tokens(eng, prompts, max_new=24)
    with GenerationEngine(model, params, draft=NgramDraft(ngram=2),
                          spec_k=3, **kw) as eng:
        got = _tokens(eng, prompts, max_new=24)
        assert eng.health_status()["speculative"]["sampling"] is True
        assert telemetry.counter(
            "serving.decode.spec.proposed").value > 0
    assert got == want


def test_sampled_spec_stream_identical_model_draft(lm):
    """Same identity with a ModelDraft (self-draft): its greedy
    proposals disagree with sampled draws often, so the resample path
    is exercised, yet the stream never diverges."""
    model, params = lm
    prompts = [_prompt(12, seed=91), _prompt(25, seed=92)]
    kw = dict(num_slots=2, sampling=True, temperature=0.5, seed=99)
    with GenerationEngine(model, params, **kw) as eng:
        want = _tokens(eng, prompts, max_new=20)
    with GenerationEngine(model, params,
                          draft=ModelDraft(model, params), spec_k=2,
                          **kw) as eng:
        got = _tokens(eng, prompts, max_new=20)
        assert telemetry.counter(
            "serving.decode.spec.sampled_resamples").value >= 0
    assert got == want


def test_sampled_paged_chunked_spec_composition(lm):
    """Paged + chunked prefill + sampling + spec (native KV) emits the
    same stream as the identically configured engine without spec —
    both engines chunk alike and the accept coupling is exact, so the
    identity receipt survives the composition."""
    model, params = lm
    prompts = [_prompt(21, seed=70), _prompt(9, seed=71)]
    base = dict(num_slots=2, page_size=16, prefill_chunk=8,
                sampling=True, temperature=0.6, seed=13)
    with GenerationEngine(model, params, **base) as eng:
        want = _tokens(eng, prompts, max_new=14)
    with GenerationEngine(model, params, draft=NgramDraft(ngram=2),
                          spec_k=3, **base) as eng:
        got = _tokens(eng, prompts, max_new=14)
    assert got == want


def test_int8_sampled_spec_runs_and_is_deterministic(lm):
    """int8 KV forfeits the spec-vs-plain identity receipt (the page
    requantization history depends on the step pattern — plain decode
    re-encodes per token, verify per k+1 block — so the lossy cache
    contents themselves differ), but the full stack still runs and
    stays deterministic: two identically configured int8 spec engines
    replay each other exactly."""
    model, params = lm
    prompts = [_prompt(21, seed=70), _prompt(9, seed=71)]
    base = dict(num_slots=2, page_size=16, kv_dtype="int8",
                prefill_chunk=8, sampling=True, temperature=0.6,
                seed=13, draft=NgramDraft(ngram=2), spec_k=3)
    with GenerationEngine(model, params, **base) as eng:
        a = _tokens(eng, prompts, max_new=14)
    with GenerationEngine(model, params, **base) as eng:
        b = _tokens(eng, prompts, max_new=14)
    assert a == b
    assert all(len(t) == 14 for t in a)


def test_sampled_same_seed_deterministic_across_engines(lm):
    """Two engines with the same seed replay each other; a different
    seed diverges (so the determinism is the seed's doing)."""
    model, params = lm
    prompts = [_prompt(16, seed=60)]
    kw = dict(num_slots=2, sampling=True, temperature=1.0)
    with GenerationEngine(model, params, seed=5, **kw) as eng:
        a = _tokens(eng, prompts, max_new=24)
    with GenerationEngine(model, params, seed=5, **kw) as eng:
        b = _tokens(eng, prompts, max_new=24)
    with GenerationEngine(model, params, seed=6, **kw) as eng:
        c = _tokens(eng, prompts, max_new=24)
    assert a == b
    assert a != c


def test_constructor_validation_new_kwargs(lm):
    model, params = lm
    with pytest.raises(ValueError, match="prefill_chunk requires"):
        GenerationEngine(model, params, prefill_chunk=8)
    with pytest.raises(ValueError, match="prefill_chunk must be >= 2"):
        GenerationEngine(model, params, page_size=16, prefill_chunk=1)
    with pytest.raises(ValueError, match="exceeds model max_len"):
        GenerationEngine(model, params, page_size=16,
                         prefill_chunk=256)
    with pytest.raises(ValueError, match="kv_dtype requires"):
        GenerationEngine(model, params, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype must be"):
        GenerationEngine(model, params, page_size=16, kv_dtype="fp4")
    with pytest.raises(ValueError, match="temperature must be"):
        GenerationEngine(model, params, sampling=True, temperature=0.0)
