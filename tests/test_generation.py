"""Generative serving tests: KV-cache decode parity, slot pool,
continuous-batching scheduler (ISSUE 9 acceptance).

The load-bearing guarantees:

- decode-step logits equal the float32 full-prefix forward at the
  model's max_len-padded shape at ``rtol=atol=1e-5`` (``TOL``), at every
  generated position — prefill, solo decode, and batched lanes alike
  (NUMERICS.md "Decode-step equivalence"; the paged, chunked and
  kernel tests import ``TOL`` from here);
- the compile cache holds exactly one executable per declared prefill
  bucket + decode-ladder entry and never grows under mixed traffic;
- iteration-level scheduling: a short request admitted after a long one
  finishes first, and a freed slot is reused mid-flight;
- slot exhaustion surfaces as QueueFull backpressure, never an OOM;
- a deadline expiring mid-generation fails that request and frees its
  slot for the next one;
- what a client sees of step *k* (its token on the stream, a result)
  is handed over after step *k + 1* has been dispatched, in the order it
  always had, and nothing stays owed when the engine idles, expires a
  request, fails or shuts down (ISSUE 33).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models.gpt import gpt_tiny
from distkeras_tpu.serving import (
    DeadlineExceeded,
    EngineClosed,
    GenerationEngine,
    KVCachePool,
    QueueFull,
)
from distkeras_tpu.serving.generation import (make_decode_fn,
                                              make_prefill_fn,
                                              make_verify_fn)
from test_sched_phases import ENGINES


@pytest.fixture(autouse=True)
def fresh_registry():
    """Engines capture metric objects at construction: install a clean
    registry per test so counters/cache assertions are not cross-polluted."""
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, size=n,
                                                dtype=np.int64).tolist()


def _ref_fn(model, params):
    """Golden reference: the standard full forward at the model's FIXED
    max_len-padded shape (NUMERICS.md "Decode-step equivalence"). Returns
    seq -> logits row for the last real position."""
    full = jax.jit(lambda p, ids: model.apply({"params": p}, ids))

    def ref(seq):
        pad = np.zeros((1, model.max_len), np.int32)
        pad[0, :len(seq)] = seq
        return np.asarray(full(params, pad))[0, len(seq) - 1]

    return ref


# ---------------------------------------------------------------- numerics

# The decode-step contract's tolerance, for every pool and attention
# form: the sums are the full forward's up to the order the backend adds
# them in, and whether that order gives the same bits depends on the
# machine. The rectangular pool's leaves are [rows, max_len, width]; a
# step writes its K/V lines into the pool in place first and attends the
# lanes' rows where they lie (models/gpt.py).
TOL = dict(rtol=1e-5, atol=1e-5)


def _prefilled(model, params, seqs, num_slots, bucket=8):
    """A pool with ``seqs`` prefilled into slots 0.., and each one's
    first-token logits."""
    pool = KVCachePool(model, num_slots=num_slots)
    prefill = jax.jit(make_prefill_fn(model))
    slots, lasts = [], []
    for seq in seqs:
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(seq)] = seq
        slot = pool.allocate()
        new_pool, last = prefill(params, pool.pool, ids, np.int32(slot),
                                 np.int32(len(seq)))
        pool.swap(new_pool)
        pool.lengths[slot] = len(seq)
        slots.append(slot)
        lasts.append(np.asarray(last))
    return pool, slots, lasts


def test_pool_leaves_are_rows_by_positions_by_width(lm):
    model, _ = lm
    pool = KVCachePool(model, num_slots=3)
    assert len(pool.pool) == model.num_layers
    for layer in pool.pool:
        assert sorted(layer) == ["k", "v"]
        for leaf in layer.values():
            assert leaf.shape == (4, model.max_len, model.width)
            assert leaf.dtype == jnp.float32


@pytest.mark.parametrize("bucket,steps,prompts,pads", [
    pytest.param(8, 24, [(5, 0)], 0, id="bucket8"),
    pytest.param(32, 6, [(5, 0)], 0, id="bucket32"),
    pytest.param(128, 6, [(5, 0)], 0, id="bucket128"),
    pytest.param(8, 40, [(5, 0)], 0, id="solo_lane_every_step"),
    pytest.param(8, 10, [(5, 1), (7, 2)], 2, id="two_lanes_two_pads"),
])
def test_prefill_then_decode_matches_full_forward(lm, bucket, steps,
                                                  prompts, pads):
    """The decode-step contract (NUMERICS.md "Decode-step equivalence"):
    prefill, then every decode step, equals the float32 full forward at
    TOL. Bucket 8 and 32 prefill through the spread-query form, bucket 128
    through the reshape-to-heads form (128 positions x 2 heads > 128
    rows); every decode step through the spread form over the pool. The
    last case decodes two live lanes beside two pads on the scratch row
    in one 4-wide step, each lane held to its own sequence's reference."""
    model, params = lm
    ref = _ref_fn(model, params)
    seqs = [_prompt(n, seed) for n, seed in prompts]
    pool, slots, lasts = _prefilled(model, params, seqs, len(seqs), bucket)
    for seq, last in zip(seqs, lasts):
        np.testing.assert_allclose(last, ref(seq), **TOL)
    decode = jax.jit(make_decode_fn(model), donate_argnums=(1,))
    toks = [int(np.argmax(last)) for last in lasts]
    for _ in range(steps):
        new_pool, logits = decode(
            params, pool.pool,
            np.array(slots + [pool.scratch_slot] * pads, np.int32),
            np.array(toks + [0] * pads, np.int32),
            np.array([pool.lengths[s] for s in slots] + [0] * pads,
                     np.int32))
        pool.swap(new_pool)
        logits = np.asarray(logits)
        for j, seq in enumerate(seqs):
            pool.lengths[slots[j]] += 1
            seq.append(toks[j])
            np.testing.assert_allclose(logits[j], ref(seq), **TOL)
            toks[j] = int(np.argmax(logits[j]))


@pytest.mark.parametrize("t", [2, 4])
def test_verify_agrees_with_decode_step_by_step(lm, t):
    """One verify call over T fed tokens gives, row by row, the logits of
    T decode steps, and leaves the same K/V lines in the pool."""
    model, params = lm
    seqs = [_prompt(5, seed=1), _prompt(7, seed=2)]
    pool, slots, lasts = _prefilled(model, params, seqs, 2)
    decode = jax.jit(make_decode_fn(model))
    verify = jax.jit(make_verify_fn(model))
    slot_ids = np.array(slots, np.int32)
    start = pool.lengths[slots].copy()
    fed = np.zeros((2, t), np.int32)
    stepwise = np.zeros((2, t, model.vocab_size), np.float32)
    seq_pool, toks = pool.pool, [int(np.argmax(r)) for r in lasts]
    for j in range(t):
        fed[:, j] = toks
        seq_pool, logits = decode(params, seq_pool, slot_ids,
                                  np.array(toks, np.int32),
                                  (start + j).astype(np.int32))
        stepwise[:, j] = np.asarray(logits)
        toks = [int(np.argmax(r)) for r in stepwise[:, j]]
    ver_pool, ver_logits = verify(params, pool.pool, slot_ids, fed, start)
    np.testing.assert_allclose(np.asarray(ver_logits), stepwise, **TOL)
    for want, got in zip(jax.tree.leaves(seq_pool),
                         jax.tree.leaves(ver_pool)):
        for i, s in enumerate(slots):
            np.testing.assert_allclose(
                np.asarray(got)[s, :start[i] + t],
                np.asarray(want)[s, :start[i] + t], **TOL)


def test_last_cell_is_written_and_its_ghost_writes_nothing(lm, monkeypatch):
    """A lane at max_len - 1 writes cell max_len - 1; its ghost, at
    max_len, is dropped: whatever token the ghost feeds, the pool comes
    back the same, and no other row changes."""
    from distkeras_tpu.serving import generation

    model, params = lm
    last = model.max_len - 1
    pools = []
    for ghost in (0, 5):
        monkeypatch.setattr(generation, "GHOST_TOKEN", ghost)
        pool = KVCachePool(model, num_slots=2)
        decode = jax.jit(make_decode_fn(model))
        new_pool, logits = decode(
            params, pool.pool, np.array([0], np.int32),
            np.array([7], np.int32), np.array([last], np.int32))
        assert np.isfinite(np.asarray(logits)).all()
        pools.append(jax.tree.map(np.asarray, new_pool))
    for a, b in zip(jax.tree.leaves(pools[0]), jax.tree.leaves(pools[1])):
        np.testing.assert_array_equal(a, b)
        assert np.abs(a[0, last]).max() > 0          # the real cell
        assert not a[0, :last].any()                 # nothing clamped back
        assert not a[1:].any()                       # nor wrapped forward
    # one position earlier the ghost does land, past the new length
    monkeypatch.setattr(generation, "GHOST_TOKEN", 0)
    pool = KVCachePool(model, num_slots=2)
    new_pool, _ = jax.jit(make_decode_fn(model))(
        params, pool.pool, np.array([0], np.int32),
        np.array([7], np.int32), np.array([last - 1], np.int32))
    for leaf in jax.tree.leaves(new_pool):
        written = np.abs(np.asarray(leaf)[0]).max(axis=-1) > 0
        assert written.nonzero()[0].tolist() == [last - 1, last]


def test_padded_lanes_on_scratch_disturb_no_live_row(lm):
    """The same two live lanes through a 2-wide step and through a 4-wide
    step padded with scratch lanes: the live rows come back the same and
    cells no lane wrote are untouched, bit for bit."""
    model, params = lm
    seqs = [_prompt(5, seed=1), _prompt(7, seed=2)]
    pool, slots, lasts = _prefilled(model, params, seqs, 2)
    decode = jax.jit(make_decode_fn(model))
    before = jax.tree.map(np.asarray, pool.pool)
    toks = [int(np.argmax(r)) for r in lasts]
    lens = [int(pool.lengths[s]) for s in slots]
    scratch = pool.scratch_slot
    two, logits2 = decode(params, pool.pool, np.array(slots, np.int32),
                          np.array(toks, np.int32),
                          np.array(lens, np.int32))
    four, logits4 = decode(
        params, pool.pool, np.array(slots + [scratch] * 2, np.int32),
        np.array(toks + [0, 0], np.int32), np.array(lens + [0, 0], np.int32))
    np.testing.assert_allclose(np.asarray(logits4)[:2], np.asarray(logits2),
                               **TOL)
    for old, a, b in zip(jax.tree.leaves(before), jax.tree.leaves(two),
                         jax.tree.leaves(four)):
        a, b = np.asarray(a), np.asarray(b)
        for s, n in zip(slots, lens):
            np.testing.assert_allclose(b[s], a[s], **TOL)
            # the step wrote cells n (token) and n + 1 (ghost) only
            np.testing.assert_array_equal(b[s, :n], old[s, :n])
            np.testing.assert_array_equal(b[s, n + 2:], old[s, n + 2:])
        np.testing.assert_array_equal(a[scratch], old[scratch])


def test_model_draft_proposes_the_greedy_continuation(lm):
    """A ModelDraft on the target's own weights keeps its own pool
    through the same three functions: its k proposals are the reference's
    greedy continuation, token for token."""
    from distkeras_tpu.serving.generation import ModelDraft

    model, params = lm
    ref = _ref_fn(model, params)
    prompt = _prompt(6, seed=14)
    seq, want = list(prompt), []
    for _ in range(5):
        want.append(int(np.argmax(ref(seq))))
        seq.append(want[-1])
    draft = ModelDraft(model, params)
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 32), draft=draft,
                          spec_k=4) as eng:
        assert "draft_prefill" in eng.compiled_executables
        draft.begin(0, prompt, want[0])
        got = draft.propose([0], [want[0]], [len(prompt)], 4)
    assert got[0].tolist() == want[1:]


def test_engine_matches_padded_full_forward_greedy(lm):
    """End-to-end through the scheduler: greedy continuations equal the
    golden reference's, for prompts landing in different buckets."""
    model, params = lm
    ref = _ref_fn(model, params)
    with GenerationEngine(model, params, num_slots=4,
                          prefill_buckets=(8, 32),
                          queue_capacity=16) as eng:
        prompts = [_prompt(3, 3), _prompt(8, 4), _prompt(20, 5)]
        futs = [eng.generate(p, max_new_tokens=12) for p in prompts]
        for p, f in zip(prompts, futs):
            got = f.result(timeout=60).tokens.tolist()
            seq, want = list(p), []
            for _ in range(12):
                tok = int(np.argmax(ref(seq)))
                want.append(tok)
                seq.append(tok)
            assert got == want


# ------------------------------------------------------------ slot pool

def test_kv_cache_pool_accounting(lm):
    model, _ = lm
    pool = KVCachePool(model, num_slots=3)
    assert pool.scratch_slot == 3
    assert pool.cache_bytes == 4 * model.cache_bytes_per_row()  # 3 + scratch
    got = [pool.allocate() for _ in range(3)]
    assert sorted(got) == [0, 1, 2]
    assert pool.allocate() is None  # exhausted, not an error
    pool.free(got[1])
    assert pool.num_free == 1 and pool.num_active == 2
    assert pool.allocate() == got[1]
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(99)


def test_pool_free_resets_length(lm):
    model, _ = lm
    pool = KVCachePool(model, num_slots=1)
    slot = pool.allocate()
    pool.lengths[slot] = 17
    pool.free(slot)
    assert pool.lengths[slot] == 0


# ------------------------------------------------- compile-cache discipline

def test_compile_cache_exactly_declared_and_never_grows(lm):
    model, params = lm
    with GenerationEngine(model, params, num_slots=3, slot_ladder=(1, 3),
                          prefill_buckets=(4, 16),
                          queue_capacity=32) as eng:
        declared = {"prefill": (4, 16), "decode": (1, 3)}
        assert eng.compiled_executables == declared
        assert telemetry.counter("serving.decode.compiles").value == 4
        # mixed traffic: both prompt buckets, every in-flight width 1..3
        futs = [eng.generate(_prompt(n, seed=n), max_new_tokens=m)
                for n, m in [(2, 3), (10, 9), (3, 5), (12, 2), (16, 7),
                             (4, 4), (9, 11), (2, 2)]]
        for f in futs:
            f.result(timeout=60)
        assert eng.compiled_executables == declared  # never grew
        assert telemetry.counter("serving.decode.compiles").value == 4


def test_engine_rejects_undeclared_shapes(lm):
    model, params = lm
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8,)) as eng:
        with pytest.raises(ValueError, match="largest prefill bucket"):
            eng.generate(_prompt(9))
        with pytest.raises(ValueError, match="max_len"):
            eng.generate(_prompt(8), max_new_tokens=model.max_len)
    with pytest.raises(ValueError, match="top out at"):
        GenerationEngine(model, params, num_slots=4, slot_ladder=(1, 2))
    with pytest.raises(ValueError, match=">= 2"):
        GenerationEngine(model, params, num_slots=2, prefill_buckets=(1, 8))


# ------------------------------------------------ iteration-level scheduling

def test_short_request_admitted_midflight_finishes_first(lm):
    """slots=2: a long generation holds one slot; two short requests
    share the other, the second admitted only when the first retires —
    both finish while the long one is still decoding."""
    model, params = lm
    done_order = []
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8,), queue_capacity=16) as eng:
        long_f = eng.generate(_prompt(4, 1), max_new_tokens=110)
        long_f.add_done_callback(lambda f: done_order.append("long"))
        s1 = eng.generate(_prompt(5, 2), max_new_tokens=2)
        s1.add_done_callback(lambda f: done_order.append("s1"))
        s2 = eng.generate(_prompt(6, 3), max_new_tokens=2)
        s2.add_done_callback(lambda f: done_order.append("s2"))
        assert s1.result(timeout=60).tokens.size == 2
        assert s2.result(timeout=60).tokens.size == 2
        assert long_f.result(timeout=60).tokens.size == 110
    assert done_order == ["s1", "s2", "long"]
    retired = telemetry.counter("serving.decode.retired", reason="length")
    assert retired.value == 3


def test_slot_exhaustion_is_queue_full_backpressure(lm):
    model, params = lm
    eng = GenerationEngine(model, params, num_slots=1,
                           prefill_buckets=(8,), queue_capacity=2)
    try:
        futs = []
        with pytest.raises(QueueFull):
            for _ in range(50):
                futs.append(eng.generate(_prompt(4), max_new_tokens=100))
        assert telemetry.counter("serving.decode.rejected").value >= 1
    finally:
        eng.shutdown(drain=False, timeout=30.0)
    # non-draining shutdown fails what was still in flight, typed
    for f in futs:
        if f.done() and f.exception() is not None:
            assert isinstance(f.exception(), EngineClosed)


def test_deadline_expiry_midgeneration_frees_slot(lm):
    """A slow stream consumer + tight deadline: the request fails with
    DeadlineExceeded after SOME tokens, and the single slot is free for
    the next request."""
    model, params = lm
    with GenerationEngine(model, params, num_slots=1,
                          prefill_buckets=(8,)) as eng:
        got = []

        def slow_consumer(tok):
            got.append(tok)
            time.sleep(0.02)

        fut = eng.generate(_prompt(4), max_new_tokens=110, timeout_ms=60,
                           stream=slow_consumer)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        assert 0 < len(got) < 110  # genuinely mid-generation
        # the slot came back: a fresh request runs to completion
        res = eng.generate(_prompt(5), max_new_tokens=3).result(timeout=60)
        assert res.tokens.size == 3 and res.reason == "length"
        dl = telemetry.counter("serving.decode.retired", reason="deadline")
        assert dl.value == 1


def test_deadline_checked_at_admission_too(lm):
    model, params = lm
    with GenerationEngine(model, params, num_slots=1,
                          prefill_buckets=(8,), queue_capacity=8) as eng:
        # occupy the only slot, then queue a request that expires waiting
        blocker = eng.generate(_prompt(4, 1), max_new_tokens=60,
                               stream=lambda t: time.sleep(0.005))
        doomed = eng.generate(_prompt(4, 2), max_new_tokens=2,
                              timeout_ms=20)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
        assert blocker.result(timeout=60).tokens.size == 60


# --------------------------------------------------------------- lifecycle

def test_eos_retirement_and_streaming_order(lm):
    """Pick the eos id the model will actually emit (its first greedy
    token) so the sequence retires on EOS, and the stream saw every
    token in order including it."""
    model, params = lm
    ref = _ref_fn(model, params)
    prompt = _prompt(6, 9)
    eos = int(np.argmax(ref(prompt)))
    seen = []
    with GenerationEngine(model, params, num_slots=1,
                          prefill_buckets=(8,)) as eng:
        res = eng.generate(prompt, max_new_tokens=50, eos_id=eos,
                           stream=seen.append).result(timeout=60)
    assert res.reason == "eos"
    assert res.tokens[-1] == eos
    assert seen == res.tokens.tolist()


def test_shutdown_drains_by_default(lm):
    model, params = lm
    eng = GenerationEngine(model, params, num_slots=2,
                           prefill_buckets=(8,), queue_capacity=16)
    futs = [eng.generate(_prompt(4, s), max_new_tokens=5)
            for s in range(6)]
    eng.shutdown()  # drain=True: everything queued still completes
    assert all(f.result(timeout=1).tokens.size == 5 for f in futs)
    with pytest.raises(EngineClosed):
        eng.generate(_prompt(4))


def test_health_status_shape(lm):
    model, params = lm
    with GenerationEngine(model, params, num_slots=2, slot_ladder=(1, 2),
                          prefill_buckets=(8,)) as eng:
        h = eng.health_status()
        assert h["num_slots"] == 2 and h["slots_free"] == 2
        assert h["decode_ladder"] == [1, 2]
        assert h["compiled"] == {"prefill": [8], "decode": [1, 2]}
        assert h["cache_bytes"] == eng.pool.cache_bytes


# ------------------------------------------- delivery after the dispatch

def _note_dispatches(eng, log):
    """Wrap every executable the scheduler dispatches (prefill buckets,
    the chunk step, the decode and verify ladders) so that its call
    appends ``"D"`` to ``log`` first. Called before the first request:
    the scheduler is waiting and reads the tables at each call."""
    def noting(ex):
        def call(*args):
            log.append("D")
            return ex(*args)
        return call

    for table in (eng._prefill_exec, eng._decode_exec, eng._verify_exec):
        for key in list(table):
            table[key] = noting(table[key])
    if eng._chunk_exec is not None:
        eng._chunk_exec = noting(eng._chunk_exec)


def _counters():
    return telemetry.get_registry().snapshot()["counters"]


@pytest.mark.parametrize("flavour", sorted(ENGINES))
def test_a_steps_tokens_follow_the_next_steps_dispatch(lm, flavour):
    """One request on one lane, its executables and its stream writing
    one log: step k + 1's call comes before the stream callback of step
    k's tokens, every dispatch once decoding began hands something over,
    and the last step's tokens and the result arrive with no further
    dispatch, because the engine idles with nothing owed."""
    model, params = lm
    log, streamed = [], []
    with GenerationEngine(model, params, num_slots=1,
                          prefill_buckets=(8, 32), **ENGINES[flavour]()
                          ) as eng:
        _note_dispatches(eng, log)
        fut = eng.generate(_prompt(20, 3), max_new_tokens=9,
                           stream=lambda t: (streamed.append(t),
                                             log.append("t")))
        fut.add_done_callback(lambda f: log.append("R"))
        res = fut.result(timeout=60)
        assert not eng._owed
        counters, seen = _counters(), list(log)
        assert eng.generate(_prompt(4), max_new_tokens=1).result(
            timeout=60).tokens.size == 1    # one token: no decode step
    assert streamed == res.tokens.tolist() and len(streamed) == 9
    steps = counters["serving.decode.steps"]
    prefill_calls = counters.get("serving.decode.chunk.steps", 1)
    assert seen.count("D") == prefill_calls + steps
    # nothing reaches the client before the first decode step is with the
    # device, the prefill's own token included
    first = seen.index("t")
    assert seen[:first] == ["D"] * (prefill_calls + 1)
    rest = "".join(seen[first:])
    # then runs of tokens, one dispatch between two runs, and the last
    # two runs together: the flush follows the last delivery directly
    assert rest.endswith("tR") and "DD" not in rest and "Dt" in rest
    assert rest.count("D") == steps - 1 and rest.count("t") == 9
    if flavour != "speculative":    # one token a step
        assert rest == "tD" * (steps - 1) + "ttR" and steps == 8
    assert counters["serving.sched.delivered"] == 9 + 1
    after = counters["serving.sched.delivered_after_dispatch"]
    assert 1 <= counters["serving.sched.delivered"] - after <= 1 + 3 + 1


def test_expiry_hands_over_the_owed_token_before_the_error(lm):
    model, params = lm
    seen = []

    def stream(tok):
        seen.append(tok)
        if len(seen) == 2:
            # handed over with step 2 launched: the deadline passes here,
            # step 2 lands, and its token is owed when the request expires
            time.sleep(max(0.0, t_late - time.monotonic()))

    with GenerationEngine(model, params, num_slots=1,
                          prefill_buckets=(8,)) as eng:
        t_late = time.monotonic() + 1.05
        fut = eng.generate(_prompt(4), max_new_tokens=50, timeout_ms=1000.0,
                           stream=stream)
        fut.add_done_callback(lambda f: seen.append("failed"))
        with pytest.raises(DeadlineExceeded, match="after 3 tokens"):
            fut.result(timeout=60)
        assert not eng._owed and eng.pool.num_free == 1
    assert len(seen) == 4 and seen[-1] == "failed"
    counters = _counters()
    assert counters["serving.sched.delivered"] == 3 + 1
    assert counters["serving.sched.delivered_after_dispatch"] == 2


@pytest.mark.filterwarnings(     # the scheduler re-raises what killed it
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("how", ["no_drain", "failure"])
def test_what_is_owed_is_flushed_before_the_rest_is_failed(lm, how):
    """A shutdown that does not drain and a scheduler failure both find a
    token owed (step 2's): it reaches the stream, then the future fails."""
    model, params = lm
    seen, closing = [], threading.Event()
    eng = GenerationEngine(model, params, num_slots=1, prefill_buckets=(8,))
    try:
        if how == "failure":
            real, calls = eng._decode_exec[1], []

            def third_call_fails(*args):
                calls.append(1)
                if len(calls) == 3:
                    raise RuntimeError("injected")
                return real(*args)

            eng._decode_exec[1] = third_call_fails

        def stream(tok):
            seen.append(tok)
            if how == "no_drain" and len(seen) == 2:
                closing.set()       # step 2 is launched; wait for the close
                while not eng._closed:
                    time.sleep(0.001)

        fut = eng.generate(_prompt(4), max_new_tokens=50, stream=stream)
        fut.add_done_callback(lambda f: seen.append("failed"))
        if how == "no_drain":
            assert closing.wait(timeout=60)
            eng.shutdown(drain=False, timeout=30.0)
        with pytest.raises(EngineClosed):
            fut.result(timeout=60)
    finally:
        eng.shutdown(drain=False, timeout=30.0)
    assert not eng._thread.is_alive() and not eng._owed
    assert len(seen) == 4 and seen[-1] == "failed"
    counters = _counters()
    assert counters["serving.sched.delivered"] == 3
    assert counters["serving.sched.delivered_after_dispatch"] == 2
    if how == "failure":
        assert counters["serving.decode.loop_errors"] == 1


@pytest.mark.parametrize("lanes_left", [0, 1])
def test_a_done_callback_may_call_generate(lm, lanes_left):
    """The result is set from the delivery walk, outside the engine's
    condition: a callback that submits the next request does not deadlock,
    whether the walk is a flush (no lane left) or follows a dispatch."""
    model, params = lm
    follow = []
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8,)) as eng:
        if lanes_left:
            other = eng.generate(_prompt(5, 1), max_new_tokens=60)
        fut = eng.generate(_prompt(4, 2), max_new_tokens=3)
        fut.add_done_callback(lambda f: follow.append(
            eng.generate(_prompt(6, 3), max_new_tokens=3)))
        assert fut.result(timeout=60).tokens.size == 3
        t_end = time.monotonic() + 60
        while not follow and time.monotonic() < t_end:
            time.sleep(0.001)
        assert follow[0].result(timeout=60).tokens.size == 3
        if lanes_left:
            assert other.result(timeout=60).tokens.size == 60


def test_delivered_counts_what_was_owed_and_most_follows_a_dispatch(lm):
    """A queue that is never empty until the end: every traced request is
    owed its tokens, two rows through the queue (queue_wait, prefill) and
    one result; only the last retirement's items are flushed."""
    model, params = lm
    n, answer = 8, 24
    streams = [[] for _ in range(n)]
    with GenerationEngine(model, params, num_slots=2, queue_capacity=16,
                          prefill_buckets=(8,)) as eng:
        futs = [eng.generate(_prompt(4 + k % 3, k), max_new_tokens=answer,
                             stream=streams[k].append,
                             trace=telemetry.TraceContext.new_root())
                for k in range(n)]
        results = [f.result(timeout=120) for f in futs]
        silent = eng.generate(_prompt(4), max_new_tokens=5).result(
            timeout=60)     # no stream, no trace: its result alone is owed
    assert [r.tokens.tolist() for r in results] == streams
    counters = _counters()
    delivered = counters["serving.sched.delivered"]
    assert delivered == n * (answer + 2 + 1) + 1 and silent.tokens.size == 5
    assert counters["serving.sched.delivered_after_dispatch"] >= \
        0.9 * delivered
    assert counters["serving.decode.trace_rows"] == 4 * n


# ------------------------------------------------------ admission spacing

def _log_dispatches(eng, log, gate):
    """``"P"`` / ``"S"`` into ``log`` at every prefill / decode dispatch;
    the first prefill waits for ``gate``, so that a burst is queued whole
    before the scheduler admits its first request."""
    def noting(ex, mark):
        def call(*args):
            gate.wait(timeout=60)
            log.append(mark)
            return ex(*args)
        return call

    for table, mark in ((eng._prefill_exec, "P"), (eng._decode_exec, "S")):
        for key in list(table):
            table[key] = noting(table[key], mark)


def _burst(lm, n=4, answer=12, **kw):
    """``n`` requests queued together on ``n`` slots: the dispatch log and
    the answers."""
    model, params = lm
    log, gate = [], threading.Event()
    with GenerationEngine(model, params, num_slots=n, queue_capacity=16,
                          prefill_buckets=(8,), **kw) as eng:
        _log_dispatches(eng, log, gate)
        futs = [eng.generate(_prompt(4 + k % 3, k), max_new_tokens=answer)
                for k in range(n)]
        gate.set()
        answers = [f.result(timeout=120).tokens.tolist() for f in futs]
    return "".join(log), answers


def test_by_default_a_burst_is_prefilled_back_to_back(lm):
    log, answers = _burst(lm)
    assert log.startswith("PPPPS") and log.count("P") == 4
    assert all(len(a) == 12 for a in answers)


@pytest.mark.parametrize("shares, steps", [(0.5, 2), (1.0, 3), (2.0, 6)])
def test_admit_spacing_puts_decode_steps_between_a_bursts_prefills(
        lm, shares, steps):
    """One admission, then ``shares`` times the request's share of the
    pool's steps (12 tokens on 4 slots: 3 steps, a part of a step counting
    whole), then the next: no lane waits out two prefills in a row. The
    answers are the unspaced engine's: when a request is admitted changes
    no token of it."""
    log, answers = _burst(lm, admit_spacing=shares)
    assert log.startswith(("P" + "S" * steps) * 3 + "P")
    assert log.count("P") == 4
    assert answers == _burst(lm)[1]


def test_a_longer_answer_buys_more_steps_before_the_next_admission(lm):
    """The spacing is the admitted request's own: 24 tokens on 3 slots
    keep 8 steps to themselves, the 6 tokens admitted next keep 2."""
    model, params = lm
    log, gate = [], threading.Event()
    with GenerationEngine(model, params, num_slots=3, queue_capacity=16,
                          prefill_buckets=(8,), admit_spacing=1.0) as eng:
        _log_dispatches(eng, log, gate)
        futs = [eng.generate(_prompt(5, k), max_new_tokens=n)
                for k, n in enumerate((24, 6, 6))]
        gate.set()
        assert [f.result(timeout=60).tokens.size for f in futs] == [24, 6, 6]
    assert "".join(log).startswith("P" + "S" * 8 + "P" + "SS" + "P")


def test_admit_spacing_counts_only_while_a_lane_decodes(lm):
    """An engine whose lanes have all retired admits the next request at
    once, whatever the spacing: with one slot every admission finds no
    lane decoding."""
    model, params = lm
    log, gate = [], threading.Event()
    gate.set()
    with GenerationEngine(model, params, num_slots=1, queue_capacity=16,
                          prefill_buckets=(8,), admit_spacing=50.0) as eng:
        _log_dispatches(eng, log, gate)
        futs = [eng.generate(_prompt(5, k), max_new_tokens=3)
                for k in range(3)]
        assert all(f.result(timeout=60).tokens.size == 3 for f in futs)
        assert eng.health_status()["admit_spacing"] == 50
    assert "".join(log) == "PSS" * 3


def test_admit_spacing_is_not_negative(lm):
    model, params = lm
    with pytest.raises(ValueError, match="admit_spacing"):
        GenerationEngine(model, params, num_slots=1, prefill_buckets=(8,),
                         admit_spacing=-1)
