"""Paged KV, prefix cache, and speculative decoding tests (DESIGN.md §19).

The load-bearing guarantees, each a superset of the rectangular-pool
contract test_generation.py pins:

- the paged step's logits equal the full-prefix forward's at the
  decode-step tolerance (test_generation.TOL) at every position,
  including across page boundaries, and a host swap-out/swap-in round
  trip returns the parked pages bit for bit;
- a prefix-cache hit (full or partial) produces token-identical output
  to a cold engine, and a full hit runs ZERO prefill forwards;
- speculative decoding emits exactly the plain greedy token sequence
  for ANY draft (a self-draft accepts everything; a bad draft merely
  proposes in vain);
- a torn host restore (``kv.swap_in`` chaos) degrades that request to a
  cold prefill and evicts the entry — slower, never a corrupted lane;
- page reservation is all-or-nothing, exhaustion is backpressure, and a
  long-tail mix whose rectangular reservation exceeds the page budget
  still completes;
- the compile cache holds exactly the declared executables and never
  grows under mixed hit/miss/speculative traffic.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models.gpt import gpt_tiny, page_bytes
from distkeras_tpu.serving import (
    GenerationEngine,
    ModelDraft,
    NgramDraft,
    PagedKVCachePool,
    PrefixCache,
)
from distkeras_tpu.serving.generation import (
    make_paged_step_fn,
    make_swap_in_fn,
    make_swap_out_fn,
)
from distkeras_tpu.utils import fault
from test_generation import TOL, _prompt, _ref_fn


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


def _greedy_ref(model, params, prompt, steps):
    ref = _ref_fn(model, params)
    seq, out = list(prompt), []
    for _ in range(steps):
        tok = int(np.argmax(ref(seq)))
        out.append(tok)
        seq.append(tok)
    return out


# ---------------------------------------------------------------- numerics

def test_paged_step_equals_full_forward_every_position(lm):
    """Paged prefill + 40 decode steps on an interleaved (non-identity)
    page table: every step's logits are the padded full forward's at the
    decode-step tolerance, across the page boundaries at 16, 32 and
    beyond."""
    model, params = lm
    ref = _ref_fn(model, params)
    pool = PagedKVCachePool(model, num_slots=2, page_size=16)
    step = jax.jit(make_paged_step_fn(model), donate_argnums=(1,))
    a, b = pool.allocate(), pool.allocate()
    # interleave reservations so slot a's pages are NOT contiguous
    assert pool.reserve(a, 16) and pool.reserve(b, 16)
    assert pool.reserve(a, model.max_len) and pool.reserve(b, model.max_len)
    assert sorted(pool.page_table_row(a).tolist()
                  + pool.page_table_row(b).tolist()) == list(range(16))
    assert pool.page_table_row(a)[1] != pool.page_table_row(a)[0] + 1

    seq = _prompt(5)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :5] = seq
    pts = pool.page_table_row(a)[None, :]
    new_pool, logits = step(params, pool.pool, pts, ids,
                            np.zeros(1, np.int32))
    pool.swap(new_pool)
    pool.lengths[a] = 5
    np.testing.assert_allclose(np.asarray(logits)[0, 4], ref(seq), **TOL)
    tok = int(np.argmax(np.asarray(logits)[0, 4]))
    for _ in range(40):
        feed = np.array([[tok, 0]], np.int32)  # token + ghost
        new_pool, logits = step(params, pool.pool, pts, feed,
                                pool.lengths[a:a + 1].copy())
        pool.swap(new_pool)
        pool.lengths[a] += 1
        seq.append(tok)
        row = np.asarray(logits)[0, 0]
        np.testing.assert_allclose(row, ref(seq), **TOL)
        tok = int(np.argmax(row))


def test_host_swap_roundtrip_is_bitwise_lossless(lm):
    """swap_out -> clobber the device pages -> swap_in: the pages come
    back bit for bit (pure copies), so parking KV in host RAM is free of
    numerical consequence, and decode resumes on the full forward's
    logits."""
    model, params = lm
    ref = _ref_fn(model, params)
    pool = PagedKVCachePool(model, num_slots=1, page_size=16)
    step = jax.jit(make_paged_step_fn(model), donate_argnums=(1,))
    swap_out = jax.jit(make_swap_out_fn())
    swap_in = jax.jit(make_swap_in_fn())  # no donation: test keeps refs

    seq = _prompt(20, seed=3)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :20] = seq
    pts = pool.page_table_row(0)[None, :]
    assert pool.reserve((slot := pool.allocate()), model.max_len)
    pts = pool.page_table_row(slot)[None, :]
    new_pool, logits = step(params, pool.pool, pts, ids,
                            np.zeros(1, np.int32))
    pool.swap(new_pool)
    pool.lengths[slot] = 20
    tok = int(np.argmax(np.asarray(logits)[0, 19]))

    page_ids = pool.page_table_row(slot)
    parked = jax.tree.map(np.asarray, swap_out(pool.pool, page_ids))
    pool.swap(jax.tree.map(jnp.zeros_like, pool.pool))  # clobber
    pool.swap(swap_in(pool.pool, page_ids, parked))     # restore
    for was, now in zip(jax.tree.leaves(parked),
                        jax.tree.leaves(swap_out(pool.pool, page_ids))):
        assert np.any(was != 0)
        np.testing.assert_array_equal(np.asarray(now), was)

    seq.append(tok)
    feed = np.array([[tok, 0]], np.int32)
    new_pool, logits = step(params, pool.pool, pts, feed,
                            np.array([20], np.int32))
    pool.swap(new_pool)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], ref(seq), **TOL)


def test_engine_paged_matches_rect_and_reference(lm):
    model, params = lm
    prompts = [_prompt(3, 3), _prompt(8, 4), _prompt(20, 5)]
    want = [_greedy_ref(model, params, p, 12) for p in prompts]
    with GenerationEngine(model, params, num_slots=4,
                          prefill_buckets=(8, 32),
                          page_size=16) as eng:
        futs = [eng.generate(p, max_new_tokens=12) for p in prompts]
        got = [f.result(timeout=60).tokens.tolist() for f in futs]
    assert got == want


# ------------------------------------------------------------ prefix cache

def test_prefix_full_hit_identical_output_zero_prefills(lm):
    model, params = lm
    prompt = _prompt(12, 7)
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 32), page_size=16,
                          prefix_cache_bytes=4 << 20) as eng:
        cold = eng.generate(prompt,
                            max_new_tokens=8).result(timeout=60)
        prefills_after_cold = telemetry.counter(
            "serving.decode.prefills").value
        warm = eng.generate(prompt,
                            max_new_tokens=8).result(timeout=60)
        assert warm.tokens.tolist() == cold.tokens.tolist()
        # the warm request's first token came from parked logits: the
        # prefill counter did not move
        assert telemetry.counter(
            "serving.decode.prefills").value == prefills_after_cold
        assert telemetry.counter(
            "serving.decode.prefix.full_hits").value == 1
        h = eng.health_status()["prefix_cache"]
        assert h["hits"] == 1 and h["misses"] == 1
        assert h["hit_rate"] == 0.5 and h["entries"] >= 1


def test_prefix_partial_hit_matches_cold_engine(lm):
    """An extended prompt rides the cached prefix through a suffix
    prefill; tokens must equal a cache-less engine's bit-for-bit."""
    model, params = lm
    base = _prompt(12, 8)
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 32), page_size=16,
                          prefix_cache_bytes=4 << 20) as eng:
        first = eng.generate(base, max_new_tokens=6).result(timeout=60)
        extended = base + first.tokens.tolist()[:3]
        got = eng.generate(extended,
                           max_new_tokens=6).result(timeout=60)
        assert eng.health_status()["prefix_cache"]["hits"] >= 1
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 32),
                          page_size=16) as cold_eng:
        cold = cold_eng.generate(extended,
                                 max_new_tokens=6).result(timeout=60)
    assert got.tokens.tolist() == cold.tokens.tolist()


def test_prefix_cache_lru_eviction_under_budget(lm):
    model, _ = lm
    data = lambda: {"k": np.zeros((2, 16, 2, 16), np.float32)}
    per = 2 * 16 * 2 * 16 * 4
    cache = PrefixCache(budget_bytes=2 * per)
    a, b, c = (tuple(_prompt(6, s)) for s in (1, 2, 3))
    cache.insert(a, data())
    cache.insert(b, data())
    assert cache.lookup(a) is not None  # refresh a: b is now LRU
    cache.insert(c, data())
    assert cache.bytes <= cache.budget_bytes
    assert cache.evictions == 1
    assert cache.lookup(b) is None and cache.lookup(a) is not None
    assert cache.lookup(c) is not None
    # an entry bigger than the whole budget is refused outright
    big = {"k": np.zeros((8, 16, 2, 16), np.float32)}
    cache.insert(tuple(_prompt(6, 4)), big)
    assert len(cache) == 2 and cache.evictions == 2


def test_prefix_hash_collision_degrades_to_miss(lm):
    """Equal (length, hash) with different tokens must verify token
    equality and miss, never serve the wrong KV."""
    cache = PrefixCache(budget_bytes=1 << 20)
    a = tuple(_prompt(6, 1))
    cache.insert(a, {"k": np.zeros(4, np.float32)})
    b = tuple(t + 1 for t in a)
    assert cache.lookup(b) is None
    assert cache.misses == 1


# ------------------------------------------------------------- speculative

def test_speculative_ngram_draft_exact_tokens_paged(lm):
    model, params = lm
    prompts = [_prompt(4, 11), _prompt(9, 12), _prompt(16, 13)]
    want = [_greedy_ref(model, params, p, 24) for p in prompts]
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 32), page_size=16,
                          draft=NgramDraft(ngram=2), spec_k=3) as eng:
        futs = [eng.generate(p, max_new_tokens=24) for p in prompts]
        got = [f.result(timeout=60).tokens.tolist() for f in futs]
        sp = eng.health_status()["speculative"]
    assert got == want
    assert sp["proposed"] > 0 and 0.0 <= sp["accept_rate"] <= 1.0


def test_speculative_self_draft_accepts_everything_rect(lm):
    """A ModelDraft wrapping the TARGET model proposes exactly the
    greedy continuation, so every speculative iteration accepts all
    spec_k tokens — and the output is still the plain greedy string.
    max_new=21 makes the 20 post-prefill tokens exactly 5 full
    iterations, so the tail cap never truncates an accepted run."""
    model, params = lm
    prompt = _prompt(6, 14)
    want = _greedy_ref(model, params, prompt, 21)
    with GenerationEngine(model, params, num_slots=1,
                          prefill_buckets=(8, 32),
                          draft=ModelDraft(model, params),
                          spec_k=3) as eng:
        got = eng.generate(prompt,
                           max_new_tokens=21).result(timeout=60)
        sp = eng.health_status()["speculative"]
        assert "draft_prefill" in eng.compiled_executables
    assert got.tokens.tolist() == want
    assert sp["proposed"] > 0
    assert sp["accept_rate"] == 1.0


# ------------------------------------------------------- fault degradation

def test_torn_swap_in_degrades_to_cold_prefill(lm):
    model, params = lm
    prompt = _prompt(12, 9)
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 32), page_size=16,
                          prefix_cache_bytes=4 << 20) as eng:
        cold = eng.generate(prompt, max_new_tokens=8).result(timeout=60)
        fault.inject_chaos("kv.swap_in", "drop", count=1)
        torn = eng.generate(prompt, max_new_tokens=8).result(timeout=60)
        assert torn.tokens.tolist() == cold.tokens.tolist()
        assert telemetry.counter(
            "serving.decode.paged.swap_in_failures").value == 1
        # the torn entry was evicted, the request re-prefilled cold and
        # re-parked its prefix — the NEXT identical request hits clean
        assert telemetry.counter(
            "fault.chaos", site="kv.swap_in", action="drop").value == 1
        again = eng.generate(prompt,
                             max_new_tokens=8).result(timeout=60)
        assert again.tokens.tolist() == cold.tokens.tolist()
        assert telemetry.counter(
            "serving.decode.paged.swap_in_failures").value == 1


# ----------------------------------------------- paged pool + backpressure

def test_paged_pool_reservation_all_or_nothing(lm):
    model, _ = lm
    pool = PagedKVCachePool(model, num_slots=4, page_size=16,
                            num_pages=10)
    assert pool.cache_bytes == 11 * page_bytes(model, 16)
    a, b = pool.allocate(), pool.allocate()
    assert pool.reserve(a, 100)            # 7 pages
    assert pool.pages_in_use == 7
    assert not pool.reserve(b, 64)         # needs 4, only 3 free
    assert pool.pages_in_use == 7          # nothing partially claimed
    assert pool.reserve(b, 48)             # 3 pages fit
    assert pool.free_pages == 0
    with pytest.raises(ValueError, match="table width"):
        pool.reserve(b, model.max_len + 1)
    pool.free(a)
    assert pool.pages_in_use == 3 and pool.free_pages == 7
    assert (pool.page_table_row(a) == pool.scratch_page).all()
    # growing an existing reservation only claims the delta
    assert pool.reserve(b, 64)
    assert pool.pages_in_use == 4


def test_longtail_mix_exceeding_rect_budget_completes(lm):
    """num_pages=8 backs ONE near-max_len request at a time; the
    rectangular reservation for the same 4 slots would be 32 pages.
    Four long requests all complete via head-of-line backpressure."""
    model, params = lm
    with GenerationEngine(model, params, num_slots=4,
                          prefill_buckets=(8,), page_size=16,
                          num_pages=8, queue_capacity=16) as eng:
        futs = [eng.generate(_prompt(4, 20 + s), max_new_tokens=100)
                for s in range(4)]
        for f in futs:
            assert f.result(timeout=120).tokens.size == 100
        assert eng.pool.pages_in_use == 0
        assert eng.health_status()["paged"]["num_pages"] == 8


# ------------------------------------------------- compile-cache discipline

def test_compile_cache_fixed_under_mixed_decode_traffic(lm):
    """Prefix hits, misses, partial hits, page swaps, and speculative
    iterations together add ZERO executables after __init__."""
    model, params = lm
    with GenerationEngine(model, params, num_slots=3, slot_ladder=(1, 3),
                          prefill_buckets=(8, 32), page_size=16,
                          prefix_cache_bytes=4 << 20,
                          draft=NgramDraft(ngram=2), spec_k=3,
                          queue_capacity=32) as eng:
        declared = {"prefill": (8, 32), "decode": (1, 3),
                    "verify": (1, 3), "swap": ("in", "out")}
        assert eng.compiled_executables == declared
        compiles = telemetry.counter("serving.decode.compiles").value
        assert compiles == 8  # 2 prefill + 2 decode + 2 verify + 2 swap
        shared = _prompt(10, 30)
        futs = [eng.generate(p, max_new_tokens=m)
                for p, m in [(shared, 6), (_prompt(3, 31), 9),
                             (shared, 6), (_prompt(20, 32), 4),
                             (shared + [5, 6], 5), (_prompt(6, 33), 12)]]
        for f in futs:
            f.result(timeout=60)
        assert eng.compiled_executables == declared
        assert telemetry.counter(
            "serving.decode.compiles").value == compiles
        assert eng.health_status()["prefix_cache"]["hits"] >= 2


def test_engine_constructor_validation(lm):
    model, params = lm
    with pytest.raises(ValueError, match="requires page_size"):
        GenerationEngine(model, params, prefix_cache_bytes=1 << 20)
    with pytest.raises(ValueError, match="BOTH draft"):
        GenerationEngine(model, params, spec_k=3)
    with pytest.raises(ValueError, match="BOTH draft"):
        GenerationEngine(model, params, draft=NgramDraft())
    with pytest.raises(ValueError, match="page_size must divide"):
        GenerationEngine(model, params, page_size=24)
    with pytest.raises(ValueError, match="cannot back"):
        PagedKVCachePool(model, 2, page_size=16, num_pages=4)
