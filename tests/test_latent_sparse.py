"""Latent attention of two kinds in one model (models/latent_moe.py with
``layer_kinds``: dots3-note-prev's layers): full layers under a learned
sparse indexer and window layers whose cache is a ring, headwise gates and
the rank rescale, against the plain reference (perf/reference/dots3_note.py:
float32, expanded attention, the selection by a stable sort, the window by
a mask, no cache, nothing of the program), at a small size on the CPU with
seeded random weights. ``index_topk`` (8), the window (9) and the ring (80
cells) are all SMALLER than the contexts, so the selection bites, the
window forgets and the ring wraps.

A top-k chooses before any mask: what the length mask used to hide (a ghost
position, bucket padding, a freed slot's stale lines, the scratch row) has
to score ``-inf`` before the selection, and no length mask lies over a ring
at all: arithmetic on the lane's length says what each cell holds. Each
trap gets a test that plants LARGE values there and shows that none is
attended.

Tolerance: in float32 the program and the reference differ in the order of
their sums (measured under 4e-6 of the largest logit); a selection is
discrete, so two scores within that of each other at the k-th place would
flip a position. At this file's sizes and seeds none does.
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
from distkeras_tpu.serving import GenerationEngine, KVCachePool
from distkeras_tpu.serving.generation import (GHOST_TOKEN, make_decode_fn,
                                              make_prefill_fn,
                                              make_verify_fn)
from distkeras_tpu.serving.kv_cache import select_leaves, state_leaves

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perf"))
from reference import dots3_note as ref  # noqa: E402

TOL = 1e-5
TOPK, WINDOW, RING = 8, 9, 80
KINDS = ("F", "F", "S", "S")


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def dots_tiny(**kw):
    """Every mechanism of the family at test size: a leading dense layer
    and three expert layers (share 1 of 2, sigmoid scores), two full layers
    whose three index heads choose 8 positions, two window layers of their
    own sizes (window 9 in a ring of 80), gates, rescale, plain rotary
    frequencies, float32."""
    defaults = dict(
        max_len=160, num_layers=4, layer_kinds=KINDS, dense_layers=1,
        dense_width=24, scoring="sigmoid", expert_share=(1, 2),
        rope_factor=1.0, position_beta=0.0, rope_theta=8e7, rms_eps=1e-5,
        index_heads=3, index_dim=16, index_topk=TOPK,
        window_sizes=lm.WindowSizes(
            window=WINDOW, ring=RING, num_heads=2, q_lora_rank=16,
            kv_lora_rank=40, qk_nope_head_dim=12, qk_rope_head_dim=8,
            v_head_dim=12, rope_theta=5e4),
        head_gate=True, rank_rescale=True)
    defaults.update(kw)
    return lm.latent_moe_tiny(**defaults)


def config_of(model) -> dict:
    """The reference reads a configuration file's keys (the source's)."""
    w = model.window_sizes
    return {
        "layer_types": [{"F": "full_attention", "S": "sliding_attention"}[k]
                        for k in model.kinds],
        "first_k_dense_replace": model.dense_layers,
        "num_attention_heads": model.num_heads,
        "q_lora_rank": model.q_lora_rank,
        "kv_lora_rank": model.kv_lora_rank,
        "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim,
        "v_head_dim": model.v_head_dim, "rope_theta": model.rope_theta,
        "swa_num_attention_heads": w.num_heads,
        "swa_q_lora_rank": w.q_lora_rank, "swa_kv_lora_rank": w.kv_lora_rank,
        "swa_qk_nope_head_dim": w.qk_nope_head_dim,
        "swa_qk_rope_head_dim": w.qk_rope_head_dim,
        "swa_v_head_dim": w.v_head_dim, "swa_rope_theta": w.rope_theta,
        "sliding_window_size": w.window,
        "rms_norm_eps": model.rms_eps,
        "num_experts_per_tok": model.experts_per_token,
        "routed_scaling_factor": model.routed_scaling,
        "expert_share": {"index": model.expert_share[0],
                         "of": model.expert_share[1]},
        "index_n_heads": model.index_heads,
        "index_head_dim": model.index_dim, "index_topk": model.index_topk}


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
    if what == "verify":
        return jax.jit(make_verify_fn(model))
    cfg = config_of(model)
    return jax.jit(lambda p, i: ref.forward(p, i, cfg))


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def reference(model, params, ids):
    return _compiled(model, "reference")(params, jnp.asarray(ids))


@pytest.fixture(scope="module")
def tiny():
    model = dots_tiny()
    return model, _compiled(model, "init")(jax.random.key(0))


def _prefill(model, params, pool, seq, n, slot, bucket):
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = seq[:n]
    new_pool, logits = _compiled(model, "prefill")(
        params, pool.pool, ids, np.int32(slot), np.int32(n))
    pool.swap(new_pool)
    pool.lengths[slot] = n
    return logits


def _plant(pool, slot, start, scale=1e3):
    """LARGE values in row ``slot`` of every leaf a query reads before any
    length mask: the index keys from position ``start`` on, and every cell
    of a ring that holds no position up to ``start - 1`` (what was there
    times ``scale``, and where nothing was, ``scale`` itself)."""
    grown = lambda a: jnp.where(a == 0, scale, a * scale)

    def grow(layer):
        if "ik" in layer:
            ik = layer["ik"]
            return dict(layer, ik=ik.at[slot, start:].set(
                grown(ik[slot, start:])))
        ring = layer["ring"]
        stale = np.asarray(lm.ring_holds(
            jnp.array([start - 1]), ring.shape[1]))[0] < 0
        return {"ring": ring.at[slot].set(
            jnp.where(stale[:, None], grown(ring[slot]), ring[slot]))}
    pool.swap(tuple(grow(layer) for layer in pool.pool))


# ------------------------------------------------------------ full forward

@pytest.mark.parametrize("t, r", [(640, 640), (600, 640), (300, 256)],
                         ids=["four_stretches", "ragged_end", "short_rows"])
def test_a_whole_prompt_in_stretches_is_the_whole_square_s_numbers(
        t, r, monkeypatch):
    """A whole prompt under the selection meets, a stretch of queries, only
    the rows up to the stretch's end: the same sets and the same numbers as
    every query over all the rows, since a query chooses nothing past its
    own position. Query blocks of 32 make the stretches 160 positions."""
    monkeypatch.setattr(lm, "_QUERY_BLOCK", 32)
    heads, nope, rope, v, rank, ih, idim, k = 2, 8, 4, 6, 12, 3, 8, 24
    keys = iter(jax.random.split(jax.random.key(3), 8))
    normal = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)
    q, rows = normal(2, t, heads, nope + rope), normal(2, r, rank + rope)
    w_kvb = normal(rank, heads * (nope + v)) * rank ** -0.5
    index = (normal(2, t, ih, idim), normal(2, t, ih), normal(2, r, idim), k)
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (2, t))
    scale = jnp.full((2, t), (nope + rope) ** -0.5, jnp.float32)
    args = (q, rows, pos, w_kvb, (rank, nope, rope, v, heads), scale, index)
    whole = lm._attend_expanded(*args)
    met, scores = [], lm.index_scores
    monkeypatch.setattr(lm, "index_scores", lambda iq, iw, keys, at: (
        met.append(keys.shape[1]), scores(iq, iw, keys, at))[1])
    got = lm._attend_expanded(*args, from_zero=True)
    assert got.shape == whole.shape == (2, t, heads, v)
    assert rel(got, whole) < TOL
    # the rows each stretch's queries are scored against: 10/16 of the
    # square when the rows are as long as the block
    assert met == {(640, 640): [160, 320, 480, 640],
                   (600, 640): [160, 320, 480, 640],
                   (300, 256): [96, 192, 256, 256]}[t, r]


@pytest.mark.parametrize("t", [300, 100, 40, 5], ids=[
    "expanded_in_blocks", "expanded", "absorbed",
    "fewer_than_topk_and_window"])
def test_forward_matches_the_reference(tiny, t):
    """Cache-less forward in both forms of each kind of layer: a long
    block masks its query blocks' scores by the selection (full) or attends
    a band of keys (window); a short one gathers the chosen lines or masks
    the block by the window. Five positions are fewer than ``index_topk``
    and than the window."""
    model, params = tiny
    model = model.clone(max_len=512)
    ids = jax.random.randint(jax.random.key(1), (2, t), 0, model.vocab_size)
    got = _compiled(model, "forward")(params, ids)
    assert got.dtype == jnp.float32 and got.shape == (2, t, model.vocab_size)
    assert rel(got, reference(model, params, ids)) < TOL


@pytest.mark.parametrize("off", ["selection", "window", "gate", "rescale"])
def test_each_mechanism_moves_the_logits(tiny, off):
    """The same weights with one mechanism taken away (the selection as
    wide as the context, the window as wide, no gate, no rescale) give the
    same logits while the mechanism cannot bite and other logits after: the
    comparison above can tell each of them skipped."""
    model, params = tiny
    ids = jax.random.randint(jax.random.key(1), (1, 40), 0, model.vocab_size)
    whole = _compiled(model, "forward")(params, ids)
    without = {
        "selection": lambda: model.clone(index_topk=160),
        "window": lambda: model.clone(
            window_sizes=model.window_sizes._replace(window=160, ring=240)),
        "gate": lambda: model.clone(head_gate=False),
        "rescale": lambda: model.clone(rank_rescale=False)}[off]()
    if off == "gate":
        params = jax.tree.map(lambda a: a, params)
        for i in range(model.num_layers):
            params[f"attn_{i}"] = {k: v for k, v in
                                   params[f"attn_{i}"].items()
                                   if k != "o_gate"}
    other = _compiled(without, "forward")(params, ids)
    same = {"selection": TOPK, "window": WINDOW}.get(off, 0)
    if same:
        assert rel(whole[:, :same], other[:, :same]) < TOL
    assert rel(whole[:, same:], other[:, same:]) > 1e-2


# ------------------------------------------------- the selection, set for set

def _recorded(monkeypatch):
    """``select_top`` and ``select_mask`` as they are, noting what they
    chose (run without ``jit``, so that the values are there to note)."""
    top, masks = [], []

    def note_top(scores, k):
        at, valid = lm_select_top(scores, k)
        top.append((np.asarray(at), np.asarray(valid)))
        return at, valid

    def note_mask(scores, k):
        mask = lm_select_mask(scores, k)
        masks.append(np.asarray(mask))
        return mask

    lm_select_top, lm_select_mask = lm.select_top, lm.select_mask
    monkeypatch.setattr(lm, "select_top", note_top)
    monkeypatch.setattr(lm, "select_mask", note_mask)
    return top, masks


def test_the_selected_sets_are_the_reference_s(tiny, monkeypatch):
    """``S_t`` of the full layers, set for set: a 70-token prefill (the
    mask of the long form) and six decode steps through the cache (the
    positions the short form gathers) against the reference's stable sort;
    ``attended`` counts them, and the window layers' windows."""
    model, params = tiny
    cfg = config_of(model)
    seq = np.random.default_rng(2).integers(
        1, model.vocab_size, 76).astype(np.int32)
    full = [i for i, kind in enumerate(model.kinds) if kind == "F"]
    want = {i: np.asarray(ref.selection(params, jnp.asarray(seq), cfg, i)[1])
            for i in range(model.num_layers)}
    sizes = np.stack([np.minimum(np.arange(76) + 1,
                                 TOPK if kind == "F" else WINDOW)
                      for kind in model.kinds])
    for i in range(model.num_layers):
        assert (want[i].sum(axis=1) == sizes[i]).all()
    top, masks = _recorded(monkeypatch)
    with jax.disable_jit():
        _, cache, _, attended = model.apply(
            {"params": params}, seq[None, :70], cache=model.init_cache(1),
            cache_index=jnp.zeros(1, jnp.int32))
        assert len(masks) == len(full) and not top
        for layer, mask in zip(full, masks):
            np.testing.assert_array_equal(mask[0, :, :70],
                                          want[layer][:70, :70])
            assert not mask[0, :, 70:].any()
        np.testing.assert_array_equal(attended[:, 0], sizes[:, :70])
        for p in range(70, 76):
            del top[:]
            _, cache, _, attended = model.apply(
                {"params": params}, seq[None, p:p + 1], cache=cache,
                cache_index=jnp.full(1, p, jnp.int32))
            assert len(top) == len(full)
            for layer, (at, valid) in zip(full, top):
                assert valid.all() and at.shape == (1, 1, TOPK)
                assert sorted(at[0, 0]) == \
                    np.flatnonzero(want[layer][p]).tolist()
            np.testing.assert_array_equal(attended[:, 0, 0], sizes[:, p])


@pytest.mark.parametrize("k", [1, 4, 7])
def test_ties_go_to_the_lower_position(k):
    """Equal scores, planted: both forms of the selection take the lower
    positions, as the reference's stable sort does; ``-inf`` is never
    chosen, and a place that holds nothing is not valid."""
    scores = jnp.array([[3., 1., 3., 1., 1., 3., 1., 0., -jnp.inf],
                        [2., 2., 2., 2., 2., 2., 2., 2., 2.],
                        [5., -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf,
                         -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf]])
    want = np.asarray(ref.selected(scores, k))
    np.testing.assert_array_equal(np.asarray(lm.select_mask(scores, k)), want)
    at, valid = lm.select_top(scores, k)
    for row in range(3):
        chosen = sorted(np.asarray(at[row])[np.asarray(valid[row])])
        assert chosen == np.flatnonzero(want[row]).tolist()
    assert np.flatnonzero(want[0]).tolist() == \
        sorted([0, 2, 5, 1, 3, 4, 6][:k])
    assert np.flatnonzero(want[1]).tolist() == list(range(k))
    assert np.flatnonzero(want[2]).tolist() == [0]


@pytest.mark.parametrize("r, k", [(700, 130), (384, 128), (130, 129),
                                  (1000, 7)])
def test_the_search_and_the_count_give_a_full_sort_s_set(r, k):
    """Neither form of the selection sorts: the k-th largest value is
    searched for bit by bit and the positions are counted out in blocks of
    128. On scores with many equal values, negatives, zeros of both signs
    and ``-inf`` tails of every length, both give the set of a stable
    descending sort (``jax.lax.top_k``'s, and the reference's), the
    positions ascending and in range."""
    rows = 9
    scores = jnp.round(jax.random.normal(jax.random.key(r), (rows, r)) * 4) / 4
    scores = scores.at[:, ::7].multiply(-0.0)
    held = jnp.array([1, 2, k - 1, k, k + 1, r // 2, r - 1, r, r])[:, None]
    scores = jnp.where(jnp.arange(r) < held, scores, -jnp.inf)
    want = np.asarray(ref.selected(scores, k))
    # (top_k orders -0.0 under 0.0; numerically they tie)
    top, at_sorted = jax.lax.top_k(scores + 0.0, k)
    for row in range(rows):
        assert sorted(np.asarray(at_sorted[row])[np.asarray(top[row])
                                                 > -np.inf]) \
            == np.flatnonzero(want[row]).tolist()
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda s: lm.select_mask(s, k))(scores)), want)
    at, valid = jax.jit(lambda s: lm.select_top(s, k))(scores)
    at, valid = np.asarray(at), np.asarray(valid)
    assert at.shape == valid.shape == (rows, k)
    assert at.min() >= 0 and at.max() < r
    for row in range(rows):
        assert at[row][valid[row]].tolist() \
            == np.flatnonzero(want[row]).tolist()
        assert valid[row].sum() == min(int(held[row, 0]), k)


# --------------------------------------------------------------- the ring

@pytest.mark.parametrize("last, want", [
    (3, [0, 1, 2, 3, -4, -3, -2, -1]),       # never written: negative
    (7, [0, 1, 2, 3, 4, 5, 6, 7]),           # full, not yet wrapped
    (10, [8, 9, 10, 3, 4, 5, 6, 7]),         # wrapped: 8..10 over 0..2
    (16, [16, 9, 10, 11, 12, 13, 14, 15])])  # wrapped twice
def test_a_ring_s_cells_hold_what_the_arithmetic_says(last, want):
    """Position ``p`` lies in cell ``p mod cells``; a window of 5 over a
    ring of 8 attends the five latest of them and nothing unwritten."""
    held = lm.ring_holds(jnp.array([last]), 8)
    assert np.asarray(held)[0].tolist() == want
    mask = np.asarray(lm.window_mask(held, jnp.array([[last]]), 5))[0, 0]
    assert sorted(np.asarray(held)[0][mask]) == \
        list(range(max(0, last - 4), last + 1))


@pytest.mark.parametrize("n, bucket", [(5, 16), (70, 96), (100, 128)],
                         ids=["shorter_than_the_window", "long_block",
                              "longer_than_the_ring"])
def test_a_prefill_builds_the_ring_from_its_real_positions(tiny, n, bucket):
    """The ring a prefill leaves holds the lines of the last real
    positions, not of the bucket's tail: cell for cell (where a cell holds
    a position at all) it is the ring that the same tokens leave when they
    are written one decode step at a time."""
    model, params = tiny
    seq = np.random.default_rng(4).integers(
        1, model.vocab_size, n).astype(np.int32)
    pool = KVCachePool(model, num_slots=2)
    _prefill(model, params, pool, seq, n, 0, bucket)
    _prefill(model, params, pool, seq, 1, 1, 16)
    for p in range(1, n):
        new_pool, *_ = _compiled(model, "decode")(
            params, pool.pool, np.array([1], np.int32), seq[p:p + 1],
            np.array([p], np.int32))
        pool.swap(new_pool)
    held = np.asarray(lm.ring_holds(jnp.array([n - 1]), RING))[0]
    assert (held >= 0).sum() == min(n, RING)
    for layer, kind in zip(pool.pool, model.kinds):
        if kind == "S":
            ring = np.asarray(layer["ring"])
            np.testing.assert_allclose(ring[0][held >= 0],
                                       ring[1][held >= 0], atol=1e-5)


# ------------------------------------------------- through the cache pool

def test_prefill_then_decode_through_the_pool_matches_every_position(tiny):
    """Two sequences prefilled into pool rows (buckets 16 and 96: a short
    and a long block), then 30 decode steps on a 4-lane executable (two
    padding lanes on the scratch row): every position's logits against the
    reference's full forward. The contexts pass ``index_topk`` (5 -> 35
    over 8), the window (over 9) and the ring's length (70 -> 100 over
    80); the step's fourth value counts what the two live lanes attend."""
    model, params = tiny
    pool = KVCachePool(model, num_slots=3)
    assert [{k: v.shape for k, v in leaf.items()} for leaf in pool.pool] == \
        [{"kv": (4, 160, 128), "ik": (4, 160, 16)}] * 2 \
        + [{"ring": (4, RING, 128)}] * 2
    assert pool.cache_bytes == 4 * model.cache_bytes_per_row() \
        == 4 * 4 * (2 * 160 * (128 + 16) + 2 * RING * 128)
    assert pool.state_bytes == 4 * 4 * 2 * RING * 128
    rng = np.random.default_rng(3)
    seqs = rng.integers(1, model.vocab_size, (2, 70 + 30)).astype(np.int32)
    prompts, slots, buckets = (5, 70), (2, 0), (16, 96)
    want = np.asarray(reference(model, params, seqs))
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
        new_pool, logits, held, attended = _compiled(model, "decode")(
            params, pool.pool, slot_ids, tokens, lengths)
        pool.swap(new_pool)
        for lane, which in ((0, 0), (2, 1)):
            assert rel(logits[lane], want[which][prompts[which] + step]) \
                < TOL, (step, lane)
        assert held.shape == (3, model.experts_held)     # the expert layers
        short = prompts[0] + step + 1
        assert attended.dtype == jnp.int32 and int(attended) == \
            2 * (min(short, TOPK) + TOPK) + 2 * (min(short, WINDOW) + WINDOW)


@pytest.mark.parametrize("trap", ["ghost", "bucket", "reused_slot",
                                  "scratch_row"])
def test_what_the_length_mask_used_to_hide_is_never_attended(tiny, trap,
                                                             monkeypatch):
    """Large index keys and ring cells past a lane's length, the four ways
    they get there. ``ghost``: a two-position block ``[token, ghost]`` (the
    verify step's shape) writes the ghost's key and line before the token's
    query selects. ``bucket``: a 9-token prompt in a bucket of 16 leaves
    seven padded keys and ring cells. ``reused_slot``: a 9-token request in
    the row a 40-token one left. ``scratch_row``: padded lanes beside the
    live one, the scratch row full of large values. In each the logits are
    the reference's, and with the mask of the index scores moved (one
    position late for the ghost, which then fills a free place of the
    selection; away for the others) they are not: the trap is live."""
    model, params = tiny
    seq = np.random.default_rng(5).integers(
        1, model.vocab_size, 48).astype(np.int32)
    want = np.asarray(reference(model, params, seq[None]))[0]
    pool = KVCachePool(model, num_slots=2)
    n = 3 if trap == "ghost" else 9     # 4 .. 7 positions: fewer than TOPK
    if trap == "reused_slot":
        other = np.random.default_rng(6).integers(
            1, model.vocab_size, 40).astype(np.int32)
        _prefill(model, params, pool, other, 40, 1, 40)
    _prefill(model, params, pool, seq, n, 1, 16)
    if trap != "ghost":
        _plant(pool, pool.scratch_slot if trap == "scratch_row" else 1,
               0 if trap == "scratch_row" else n)
    scratch = pool.scratch_slot

    late = 0

    def steps():
        """Logits of positions ``n .. n + 3`` through the pool as planted."""
        state, out = pool.pool, []
        for p in range(n, n + 4):
            if trap == "ghost":
                # the ghost's own key, grown: the model writes it first
                state, logits = _compiled(model, "verify")(
                    params, state, np.array([1], np.int32),
                    np.array([[seq[p], GHOST_TOKEN]], np.int32),
                    np.array([p], np.int32))
                state = tuple(
                    dict(layer, ik=layer["ik"].at[1, p + 1].mul(1e3))
                    if "ik" in layer else layer for layer in state)
                out.append(logits[0, 0])
                continue
            lanes = [1, scratch, scratch, scratch] \
                if trap == "scratch_row" else [1, scratch]
            feed = np.zeros(len(lanes), np.int32)
            feed[0], at = seq[p], np.zeros(len(lanes), np.int32)
            at[0] = p
            state, logits, _, attended = jax.jit(make_decode_fn(model))(
                params, state, np.array(lanes, np.int32), feed, at)
            assert late or int(attended) == \
                2 * min(p + 1, TOPK) + 2 * min(p + 1, WINDOW)
            out.append(logits[0])
        return jnp.stack(out)

    assert rel(steps(), want[n:n + 4]) < TOL
    if trap == "scratch_row":
        return      # a live lane never reads the scratch row: nothing to spring
    masked, late = lm.index_scores, 1 if trap == "ghost" else model.max_len
    monkeypatch.setattr(lm, "index_scores", lambda iq, iw, keys, pos:
                        masked(iq, iw, keys, pos + late))
    _compiled.cache_clear()
    try:
        assert rel(steps(), want[n:n + 4]) > 1e-3
    finally:
        _compiled.cache_clear()


def test_a_ring_s_stale_cells_would_be_attended_without_the_arithmetic(
        tiny, monkeypatch):
    """The ring's own trap: a 9-token request in the row a 40-token one
    left, the ring's unwritten cells grown large. The logits are the
    reference's; with every cell taken as held (no arithmetic) they are
    not."""
    model, params = tiny
    seq = np.random.default_rng(5).integers(
        1, model.vocab_size, 16).astype(np.int32)
    want = np.asarray(reference(model, params, seq[None]))[0]
    pool = KVCachePool(model, num_slots=1)
    _prefill(model, params, pool, seq, 6, 0, 16)
    _plant(pool, 0, 6)

    def step():
        _, logits, *_ = jax.jit(make_decode_fn(model))(
            params, pool.pool, np.array([0], np.int32), seq[6:7],
            np.array([6], np.int32))
        return logits[0]

    assert rel(step(), want[6]) < TOL
    monkeypatch.setattr(lm, "window_mask", lambda held, pos, window:
                        jnp.ones(pos.shape + held.shape[-1:], bool))
    assert rel(step(), want[6]) > 1e-3


# ---------------------------------------------- through GenerationEngine

def test_engine_tokens_are_the_reference_argmax_and_counters_add_up(tiny):
    """Through ``GenerationEngine``: prompts in padded buckets (one of them
    a long block whose answer wraps the ring), 12 tokens each, three at
    once on four slots; every emitted token's reference logit is its
    position's largest, to the tolerance, and ``serving.sparse.*`` hold
    what the steps attended."""
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
        logits = np.asarray(reference(model, params, full[None]))[0]
        at = logits[len(prompt) - 1:len(full) - 1]
        gaps = at.max(axis=-1) - at[np.arange(12), out]
        assert gaps.max() <= TOL * np.abs(logits).max(), gaps
    snap = telemetry.get_registry().snapshot()
    counters = snap["counters"]
    # a request's 11 decode steps hold n + 1 .. n + 11 positions
    cached = sum(n + step for n in sizes for step in range(1, 12))
    attended = sum(2 * min(n + step, TOPK) + 2 * min(n + step, WINDOW)
                   for n in sizes for step in range(1, 12))
    assert counters["serving.decode.tokens"] == 33
    assert counters["serving.sparse.positions_cached"] == \
        cached * model.num_layers
    assert counters["serving.sparse.positions_attended"] == attended
    assert counters["serving.moe.assignments"] == \
        33 * model.experts_per_token * 3      # the three expert layers
    assert snap["gauges"]["serving.decode.state_bytes"] == \
        5 * 4 * 2 * RING * 128               # the rings of 4 + 1 rows


@pytest.mark.parametrize("kw", [
    dict(page_size=8), dict(page_size=8, prefix_cache_bytes=1 << 20),
    dict(draft=object(), spec_k=2), dict(page_size=8, prefill_chunk=4)],
    ids=["paged_pool", "prefix_cache", "draft", "prefill_chunk"])
@pytest.mark.parametrize("leaf", ["ring", "ik"])
def test_engine_refuses_what_the_declared_leaves_cannot_take(tiny, kw, leaf):
    """One error, naming the leaf, for every feature no test has shown
    right under a ring (a state leaf: no length mask) or under a selection
    (the index keys: read before any mask); the leaves are what the model
    declares, and a family with neither declares none."""
    model, params = tiny
    assert select_leaves(model) == ("ik",)
    assert state_leaves(model) == ("ring",)
    plain = lm.latent_moe_tiny()
    assert select_leaves(plain) == state_leaves(plain) == ()
    if leaf == "ik":        # full layers alone: the selection's refusal
        model = model.clone(num_layers=2, layer_kinds=(), window_sizes=None)
        assert state_leaves(model) == ()
    with pytest.raises(ValueError, match=rf"cache leaf '{leaf}'.*no "
                       + next(iter(kw))):
        GenerationEngine(model, params, num_slots=2, **kw)


# --------------------------------------------- the share, and what stayed

@pytest.mark.parametrize("tokens", [20, 300],
                         ids=["masked_dense", "grouped"])
def test_the_eight_shares_add_up_to_the_uncut_layer(tokens):
    """The routed parts that shares 0..7 compute (4 of 32 sigmoid-scored
    experts each, top 8, a selection bias), plus the shared expert once,
    equal the uncut reference's layer (all 32 experts in one)."""
    layer = lambda share: lm.ExpertShare(
        width=16, num_experts=32, experts_per_token=8, expert_share=share,
        routed_scaling=1.0, dtype=jnp.float32, scoring="sigmoid")
    apply = lambda share, p: jax.jit(
        lambda p, x: layer(share).apply({"params": p}, x))(p, x)
    z = {"k": 8, "index": 0, "routed_scaling": 1.0}
    x = jax.random.normal(jax.random.key(2), (tokens, 32))
    whole = jax.jit(layer((0, 1)).init)(jax.random.key(3), x)["params"]
    want = jax.jit(lambda p, x: ref.moe(p, x, z))(whole, x)
    shared = ref.swiglu(x, whole["shared_gate"], whole["shared_up"],
                        whole["shared_down"])
    total, sent = shared, 0
    for index in range(8):
        part = dict(whole, **{name: whole[name][4 * index:4 * index + 4]
                              for name in ("gate", "up", "down")})
        out, routed = apply((index, 8), part)
        total = total + (out - shared)
        sent = sent + int(routed.sum())
    assert rel(total, want) < TOL
    assert sent == tokens * 8       # every assignment is held by one share


SCOPES = ("attn.index", "attn.select", "attn.sparse", "attn.window")


def _lowered(model, step):
    """A 16-lane decode in lane groups, a short prefill or a long one in
    query blocks, lowered from shapes alone."""
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    pool = jax.eval_shape(lambda: model.init_cache(17))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    fn, args = {
        "decode": (make_decode_fn(model), (i32(16), i32(16), i32(16))),
        "prefill16": (make_prefill_fn(model), (i32(1, 16), i32(), i32())),
        "prefill100": (make_prefill_fn(model), (i32(1, 100), i32(), i32())),
    }[step]
    return params, pool, jax.jit(fn).lower(params, pool, *args)


@pytest.mark.parametrize("step", ["decode", "prefill16", "prefill100"])
def test_a_model_with_nothing_configured_carries_nothing_of_it(step):
    """``LatentMoELM`` with no indexer, no window layer, no gate, no dense
    layer and softmax scores (``mistral_small_4``'s form): none of the new
    matrices among its parameters, one leaf a layer in its pool, no leaf
    declared, none of the four scopes in the program it lowers to, and a
    decode step of three values. The configured model, beside it, shows
    that each of these would tell."""
    plain, configured = lm.latent_moe_tiny(max_len=160), dots_tiny()
    for model, has in ((plain, False), (configured, True)):
        params, pool, lowered = _lowered(model, step)
        assert (select_leaves(model) == ("ik",)) is has
        assert (state_leaves(model) == ("ring",)) is has
        assert {name for layer in pool for name in layer} == \
            ({"kv", "ik", "ring"} if has else {"kv"})
        names = {path[-1].key for path, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]}
        assert any(name.startswith("index_") for name in names) is has
        assert ("o_gate" in names) is has
        text = lowered.as_text(debug_info=True)
        assert "attn.latent" in text
        # a long block masks the expanded form: nothing is gathered there
        found = {scope for scope in SCOPES if f"/{scope}" in text}
        want = set(SCOPES) - ({"attn.sparse"} if step == "prefill100"
                              else set())
        assert found == (want if has else set()), found
        if step == "decode":
            assert len(lowered.out_info) == (4 if has else 3)


#: sha256 (first 16 digits) of what ``latent_moe_tiny(max_len=96)`` lowers
#: to at the commit before this module had an indexer, layer kinds, gates or
#: the rescale (PR 33; ``jax.jit(...).lower(...).as_text()``, which carries
#: neither source locations nor scope names)
PARENT_TEXT = {"plain": "82448191ee5c0a29", "decode4": "a46cffab82e95205",
               "decode16": "23c7965911c327be", "prefill": "23725ad64c156f98"}


@pytest.mark.parametrize("what", sorted(PARENT_TEXT))
def test_with_nothing_configured_the_module_lowers_to_the_parent_s_text(what):
    """``mistral_small_4``'s programs did not change: the cache-less
    forward, a decode step in one lane group and in lane groups of 16, and
    a prefill into a fresh row lower, letter for letter, to what they
    lowered to before this family's mechanisms were added."""
    model = lm.latent_moe_tiny(max_len=96)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if what == "plain":
        fn, args = lambda p, ids: model.apply({"params": p}, ids), \
            (i32(2, 80),)
    elif what == "prefill":
        row = jax.eval_shape(lambda: model.init_cache(1, positions=80))
        fn = lambda p, c, ids, idx: model.apply(
            {"params": p}, ids, cache=c, cache_index=idx)
        args = (row, i32(1, 80), i32(1))
    else:
        lanes = int(what[len("decode"):])
        pool = jax.eval_shape(lambda: model.init_cache(17))
        fn = lambda p, c, ids, idx, rows: model.apply(
            {"params": p}, ids, cache=c, cache_index=idx, cache_rows=rows)
        args = (pool, i32(lanes, 2), i32(lanes), i32(lanes))
    text = jax.jit(fn).lower(params, *args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_TEXT[what]
