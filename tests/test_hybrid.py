"""The decoder assembled from layer kinds (models/hybrid.py: Mamba-2 blocks,
grouped-query attention without positions, sigmoid-routed relu^2 experts)
against its plain reference (perf/reference/nemotron_h.py: float32, the
recurrence one position at a time, no cache, nothing of the program), at a
small size on the CPU with seeded random weights; and the cache protocol as
it stands now that a model may declare leaves without a position axis.

Tolerance. In float32 the program and the reference differ in the order of
their sums, and in the chunked scan taking ``exp`` of a difference of
cumulative sums where the recurrence multiplies decays: 1e-5 of the
largest logit (``tests/test_generation.py``'s ``TOL``).
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import hybrid, latent_moe
from distkeras_tpu.models.gpt import gpt_tiny
from distkeras_tpu.serving import GenerationEngine, KVCachePool
from distkeras_tpu.serving.generation import (make_decode_fn,
                                              make_prefill_fn, state_leaves)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perf"))
from reference import nemotron_h as ref  # noqa: E402

TOL = 1e-5


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def config_of(model) -> dict:
    """The reference reads a configuration file's keys (the source's)."""
    return {
        "hybrid_override_pattern": model.pattern, "norm_eps": model.rms_eps,
        "mamba_num_heads": model.ssm_heads,
        "mamba_head_dim": model.ssm_head_dim, "n_groups": model.ssm_groups,
        "ssm_state_size": model.ssm_state, "conv_kernel": model.conv_kernel,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.num_kv_heads,
        "head_dim": model.head_dim,
        "num_experts_per_tok": model.experts_per_token,
        "routed_scaling_factor": model.routed_scaling,
        "expert_share": {"index": model.expert_share[0],
                         "of": model.expert_share[1]}}


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
    cfg = config_of(model)
    return jax.jit(lambda p, i: ref.forward(p, i, cfg))


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def reference(model, params, ids):
    return _compiled(model, "reference")(params, jnp.asarray(ids))


@pytest.fixture(scope="module")
def tiny():
    model = hybrid.hybrid_tiny()
    return model, _compiled(model, "init")(jax.random.key(0))


# ------------------------------------------------------------ full forward

@pytest.mark.parametrize("t", [40, 37, 3], ids=[
    "whole_chunks", "ragged_last_chunk", "a_position_at_a_time"])
def test_forward_matches_the_reference(tiny, t):
    """(a) The cache-less forward: five chunks of 8, four and a ragged
    fifth, and a block short enough to take the recurrence step by step."""
    model, params = tiny
    ids = jax.random.randint(jax.random.key(1), (2, t), 0, model.vocab_size)
    got = _compiled(model, "forward")(params, ids)
    assert got.dtype == jnp.float32 and got.shape == (2, t, model.vocab_size)
    assert rel(got, reference(model, params, ids)) < TOL


@pytest.mark.parametrize("t, chunk", [(16, 8), (13, 8), (24, 8), (5, 8),
                                      (9, 1)])
def test_chunked_scan_equals_the_recurrence(t, chunk):
    """(c) ``ssd_scan`` against ``ssm_steps`` for lengths that are and are
    not multiples of the chunk, from a state that is not zero, with the
    tail of one row masked (``dt = 0`` leaves its state as it was)."""
    b, g, r, p, n = 2, 2, 3, 4, 5
    keys = jax.random.split(jax.random.key(t), 6)
    x = jax.random.normal(keys[0], (b, t, g, r, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, t, g, r)) - 2.0)
    dt = dt.at[1, t - 2:].set(0.0)
    a = -jnp.exp(jax.random.normal(keys[2], (g, r)))
    b_mat = jax.random.normal(keys[3], (b, t, g, n))
    c_mat = jax.random.normal(keys[4], (b, t, g, n))
    h0 = jax.random.normal(keys[5], (b, g, r, p, n))
    y_s, h_s = jax.jit(hybrid.ssm_steps)(x, dt, a, b_mat, c_mat, h0)
    y_c, h_c = jax.jit(functools.partial(hybrid.ssd_scan, chunk=chunk))(
        x, dt, a, b_mat, c_mat, h0)
    assert y_c.shape == y_s.shape == (b, t, g, r, p)
    assert rel(y_c, y_s) < TOL and rel(h_c, h_s) < TOL
    # the masked tail moved nothing: row 1 ends where t - 2 positions end
    _, h_cut = jax.jit(hybrid.ssm_steps)(
        x[:, :t - 2], dt[:, :t - 2], a, b_mat[:, :t - 2], c_mat[:, :t - 2],
        h0)
    np.testing.assert_array_equal(np.asarray(h_s[1]), np.asarray(h_cut[1]))
    if t > chunk:       # the same chunks either way: the same bits
        _, h_cut = jax.jit(functools.partial(hybrid.ssd_scan, chunk=chunk))(
            x[:, :t - 2], dt[:, :t - 2], a, b_mat[:, :t - 2],
            c_mat[:, :t - 2], h0)
        np.testing.assert_array_equal(np.asarray(h_c[1]),
                                      np.asarray(h_cut[1]))


# ------------------------------------------------- through the cache pool

def _prefill(model, params, pool, seq, n, slot, bucket):
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = seq[:n]
    new_pool, logits = _compiled(model, "prefill")(
        params, pool.pool, ids, np.int32(slot), np.int32(n))
    pool.swap(new_pool)
    pool.lengths[slot] = n
    return logits


def _state_rows(pool, slot):
    """The slot's rows of every leaf without a position axis."""
    return [np.asarray(layer[name][slot]) for layer in pool.pool
            for name in ("ssm", "conv") if name in layer]


@pytest.mark.parametrize("num_slots", [3, 14], ids=["in_pool", "gathered"])
def test_prefill_then_decode_through_the_pool_matches_every_position(
        tiny, num_slots):
    """(b) Two sequences prefilled in a padded bucket into pool rows, then
    16 decode steps on a 4-lane executable (two padding lanes on the
    scratch row): every position's logits against the reference's full
    forward of the whole sequence. Four lanes of a 4-row pool advance the
    state where it lies, four of a 15-row pool gather and scatter it."""
    model, params = tiny
    assert 4 >= hybrid._IN_POOL_MIN_SHARE * 4 \
        and 4 < hybrid._IN_POOL_MIN_SHARE * 15
    rows = num_slots + 1
    pool = KVCachePool(model, num_slots=num_slots)
    kinds = [sorted(layer) for layer in pool.pool]
    assert kinds == [{"M": ["conv", "ssm"], "*": ["k", "v"], "E": []}[k]
                     for k in model.pattern]
    assert pool.pool[0]["ssm"].shape == (rows, 4, 8, 16)
    assert pool.pool[0]["ssm"].dtype == jnp.float32
    assert pool.pool[0]["conv"].shape == (rows, 3, 4 * 8 + 2 * 2 * 16)
    assert pool.pool[3]["k"].shape == (rows, model.max_len, 16)
    assert pool.cache_bytes == rows * model.cache_bytes_per_row()
    state_row = 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert pool.state_bytes == rows * state_row
    assert model.cache_bytes_per_row() == state_row \
        + 2 * model.max_len * 16 * 4
    gauges = telemetry.get_registry().snapshot()["gauges"]
    assert gauges["serving.decode.state_bytes"] == pool.state_bytes
    rng = np.random.default_rng(3)
    seqs = rng.integers(1, model.vocab_size, (2, 14 + 16)).astype(np.int32)
    prompts, slots = (9, 14), (2, 0)
    want = np.asarray(reference(model, params, seqs))
    for which, (n, slot) in enumerate(zip(prompts, slots)):
        logits = _prefill(model, params, pool, seqs[which], n, slot, 16)
        assert logits.shape == (model.vocab_size,)
        assert rel(logits, want[which][n - 1]) < TOL
    scratch = pool.scratch_slot
    for step in range(16):
        slot_ids = np.array([slots[0], scratch, slots[1], scratch], np.int32)
        tokens = np.array([seqs[0][prompts[0] + step], 0,
                           seqs[1][prompts[1] + step], 0], np.int32)
        lengths = np.array([prompts[0] + step, 0, prompts[1] + step, 0],
                           np.int32)
        new_pool, logits, held = _compiled(model, "decode")(
            params, pool.pool, slot_ids, tokens, lengths)
        pool.swap(new_pool)
        for lane, which in ((0, 0), (2, 1)):
            assert rel(logits[lane], want[which][prompts[which] + step]) \
                < TOL, (step, lane)
        assert held.shape == (model.pattern.count("E"), model.experts_held)
        assert (np.asarray(held).sum(axis=1)
                <= 2 * model.experts_per_token).all()


@pytest.mark.parametrize("branch", ["query_blocks", "lane_groups"])
def test_the_branches_only_the_published_sizes_take(tiny, monkeypatch, branch):
    """The attention block's two forms that the tiny sizes never reach,
    with their thresholds brought down to them: a long block's queries in
    blocks of ``_QUERY_BLOCK`` (a prefill of 40 in blocks of 16, the last
    ragged), and a decode step's lanes in groups of ``_LANE_GROUP`` (four
    lanes as two groups of two). Traced afresh: the module constants are
    read when the function is traced."""
    model, params = tiny
    seq = np.random.default_rng(11).integers(
        1, model.vocab_size, (2, 41)).astype(np.int32)
    want = np.asarray(reference(model, params, seq))
    pool = KVCachePool(model, num_slots=4)
    if branch == "query_blocks":
        monkeypatch.setattr(hybrid, "_QUERY_BLOCK", 16)
        got = jax.jit(lambda p, i: model.apply({"params": p}, i))(
            params, seq[:, :40])
        assert rel(got, want[:, :40]) < TOL
        ids = np.zeros((1, 48), np.int32)
        ids[0, :40] = seq[0, :40]
        _, logits = jax.jit(make_prefill_fn(model))(
            params, pool.pool, ids, np.int32(1), np.int32(40))
        assert rel(logits, want[0, 39]) < TOL
        return
    for which, slot in ((0, 3), (1, 1)):
        _prefill(model, params, pool, seq[which], 40, slot, 40)
    monkeypatch.setattr(hybrid, "_LANE_GROUP", 2)
    scratch = pool.scratch_slot
    _, logits, _ = jax.jit(make_decode_fn(model))(
        params, pool.pool, np.array([3, scratch, scratch, 1], np.int32),
        np.array([seq[0, 40], 0, 0, seq[1, 40]], np.int32),
        np.array([40, 0, 0, 40], np.int32))
    for lane, which in ((0, 0), (3, 1)):
        assert rel(logits[lane], want[which, 40]) < TOL


def _after_one_decode_step(model, params, seq, n, bucket):
    pool = KVCachePool(model, num_slots=2)
    _prefill(model, params, pool, seq, n, 1, bucket)
    scratch = pool.scratch_slot
    new_pool, *_ = _compiled(model, "decode")(
        params, pool.pool, np.array([1, scratch], np.int32),
        np.array([seq[n], 0], np.int32), np.array([n, 0], np.int32))
    pool.swap(new_pool)
    return _state_rows(pool, 1)


def _engine_tokens(model, params, prompts, **kw):
    """Serve ``prompts`` one after the other; the tokens of each and the
    state rows slot by slot when the last has left."""
    with GenerationEngine(model, params, prefill_buckets=(8, 16, 32),
                          **kw) as eng:
        out = [eng.generate(p, max_new_tokens=10).result(timeout=120).tokens
               for p in prompts]
        rows = [_state_rows(eng.pool, s) for s in range(eng.pool.num_slots)]
    return out, rows


@pytest.mark.parametrize("trap", ["ghost", "bucket", "reused_slot",
                                  "scratch_lanes"])
def test_what_no_length_mask_hides(tiny, trap):
    """(d) The three things that are harmless for keys and values and
    wrong for a state. ``ghost``: a lane's state after a decode step is
    the state after one token, not two. ``bucket``: a prompt of length L in
    a bucket of B > L leaves the state and the tail of L. ``reused_slot``
    and ``scratch_lanes``: a slot reused after a longer request, and a lane
    that sat beside padded scratch lanes, match a fresh engine."""
    model, params = tiny
    seq = np.random.default_rng(5).integers(
        1, model.vocab_size, 40).astype(np.int32)
    close = lambda got, want: all(
        rel(jnp.asarray(g), jnp.asarray(w)) < TOL for g, w in zip(got, want))
    if trap == "ghost":
        got = _after_one_decode_step(model, params, seq, 11, 16)
        pool = KVCachePool(model, num_slots=2)
        _prefill(model, params, pool, seq, 12, 1, 12)
        assert close(got, _state_rows(pool, 1))
        # and it is not the state two tokens on
        _prefill(model, params, pool, seq, 13, 0, 13)
        assert not close(got, _state_rows(pool, 0))
    elif trap == "bucket":
        padded, exact = (KVCachePool(model, num_slots=2) for _ in range(2))
        a = _prefill(model, params, padded, seq, 11, 1, 32)
        b = _prefill(model, params, exact, seq, 11, 1, 11)
        assert rel(a, b) < TOL
        assert close(_state_rows(padded, 1), _state_rows(exact, 1))
        # the tail is the last three real inputs, not the bucket's last
        tail = _state_rows(padded, 1)[1]
        assert tail.shape == (3, 96) and np.abs(tail).min(axis=1).max() > 0
    else:
        long, short = seq[:29], seq[30:37]
        if trap == "reused_slot":
            # one slot: the short request takes the long one's place
            (_, got), rows = _engine_tokens(model, params, [long, short],
                                            num_slots=1, slot_ladder=(1,))
        else:
            # a ladder of one entry: three padded lanes on the scratch
            # row beside the live one, every step
            (got,), rows = _engine_tokens(model, params, [short],
                                          num_slots=4, slot_ladder=(4,))
        (want,), fresh = _engine_tokens(model, params, [short], num_slots=1,
                                        slot_ladder=(1,))
        np.testing.assert_array_equal(got, want)
        used = [r for r in rows if np.abs(r[0]).max() > 0]
        assert len(used) == 1 and close(used[0], fresh[0])


def test_engine_tokens_are_the_reference_argmax(tiny):
    """(b) Through ``GenerationEngine``: prompts in padded buckets, 12
    tokens each, three at once on four slots; every emitted token's
    reference logit is its position's largest, to the tolerance."""
    model, params = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, model.vocab_size, n).astype(np.int32)
               for n in (5, 13, 9)]
    with GenerationEngine(model, params, num_slots=4, slot_ladder=(2, 4),
                          prefill_buckets=(8, 16)) as eng:
        assert eng.compiled_executables == {"prefill": (8, 16),
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
    counters = telemetry.get_registry().snapshot()["counters"]
    assert counters["serving.prefill.tokens"] == 5 + 13 + 9
    assert counters["serving.prefill.positions"] == 8 + 16 + 16
    assert counters["serving.moe.assignments"] == \
        counters["serving.decode.tokens"] * 3 * model.pattern.count("E")


def test_every_family_counts_its_prefill_padding():
    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8, 16)) as eng:
        eng.generate(np.arange(1, 11, dtype=np.int32),
                     max_new_tokens=2).result(timeout=120)
        assert eng.pool.state_bytes == 0
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["serving.prefill.tokens"] == 10
    assert snap["counters"]["serving.prefill.positions"] == 16
    assert snap["gauges"]["serving.decode.state_bytes"] == 0


# ----------------------------------------------------- what the engine refuses

@pytest.mark.parametrize("kw", [
    dict(page_size=8), dict(page_size=8, prefix_cache_bytes=1 << 20),
    dict(draft=object(), spec_k=2), dict(spec_k=2),
    dict(page_size=8, prefill_chunk=4), dict(prefill_chunk=4),
    dict(prefix_cache_bytes=1 << 20)],
    ids=["paged_pool", "prefix_cache", "draft", "spec_k", "prefill_chunk",
         "prefill_chunk_alone", "prefix_cache_alone"])
def test_engine_refuses_what_a_state_cannot_take(tiny, kw):
    """(g) One error, naming the leaf, for every feature that leans on the
    length mask: the paged pool, a prefix cache, speculative verify and
    chunked prefill."""
    model, params = tiny
    assert state_leaves(model) == ("ssm", "conv")
    assert state_leaves(gpt_tiny()) == ()
    with pytest.raises(ValueError, match=r"cache leaf 'ssm'.*no "
                       + next(iter(kw))):
        GenerationEngine(model, params, num_slots=2, **kw)
    with pytest.raises(ValueError, match="no paged form"):
        model.apply({"params": params}, jnp.zeros((1, 2), jnp.int32),
                    cache=model.init_cache(1),
                    cache_index=jnp.zeros(1, jnp.int32),
                    page_table=jnp.zeros((1, 6), jnp.int32))


# ------------------------------------------------------- the expert layer

def _layer(share, **kw):
    return latent_moe.ExpertShare(
        width=16, num_experts=8, experts_per_token=3, expert_share=share,
        routed_scaling=2.5, dtype=jnp.float32, scoring="sigmoid",
        activation="relu2", shared_width=24, **kw)


@pytest.mark.parametrize("tokens", [20, 300, 1100],
                         ids=["masked_dense", "in_blocks", "in_many_blocks"])
def test_the_two_shares_add_up_to_the_uncut_layer(tokens):
    """(e) The routed parts that shares 0 and 1 of 2 compute, plus the
    shared expert once, equal the uncut reference's layer (all 8 experts
    in one); each share is what the reference gives for that share. Both
    paths: every held expert on every token, and the loop over blocks (900
    assignments over 4 held experts and the absent ones, so blocks of 256
    that are ragged and empty; 3300, so that an expert has full blocks
    before its ragged one)."""
    assert 20 <= latent_moe._DENSE_MAX_TOKENS < 300
    x = jax.random.normal(jax.random.key(2), (tokens, 32))
    whole = jax.jit(_layer((0, 1)).init)(jax.random.key(3), x)["params"]
    assert sorted(whole) == ["down", "router", "router_bias", "shared_down",
                             "shared_up", "up"]
    assert whole["shared_up"].shape == (32, 24)
    assert float(jnp.abs(whole["router_bias"]).max()) > 0
    z = lambda index: {"k": 3, "index": index, "routed_scaling": 2.5}
    run = lambda p, z: jax.jit(lambda p, x: ref.moe(p, x, z))(p, x)
    shared = ref.relu2_expert(x, whole["shared_up"], whole["shared_down"])
    total, sent = shared, 0
    for index in range(2):
        part = dict(whole, **{name: whole[name][4 * index:4 * index + 4]
                              for name in ("up", "down")})
        layer = _layer((index, 2))
        out, routed = jax.jit(lambda p, x: layer.apply({"params": p}, x))(
            part, x)
        total = total + (out - shared)
        sent = sent + int(routed.sum())
        assert rel(out, run(part, z(index))) < TOL
    assert rel(total, run(whole, z(0))) < TOL
    assert sent == tokens * 3


def test_the_selection_bias_chooses_and_does_not_weigh():
    """A bias that lifts one expert over all the others sends every token
    there, at the weight its own sigmoid gives it."""
    x = jax.random.normal(jax.random.key(4), (10, 32))
    layer = _layer((0, 1))
    params = jax.jit(layer.init)(jax.random.key(6), x)["params"]
    lifted = dict(params, router_bias=params["router_bias"].at[5].set(9.0))
    _, routed = layer.apply({"params": lifted}, x)
    assert bool(routed[:, 5].all())
    z = {"k": 3, "index": 0, "routed_scaling": 2.5}
    out, _ = layer.apply({"params": lifted}, x)
    assert rel(out, ref.moe(lifted, x, z)) < TOL


def test_the_defaults_are_the_layer_the_latent_family_had():
    """(f) ``ExpertShare``'s new fields default to the scoring rule and the
    expert form ``LatentMoELM`` was built on: the same parameters' tree
    (no selection bias, a gate matrix, a shared expert of the routed
    width) and, given them explicitly, the same bits."""
    x = jax.random.normal(jax.random.key(8), (12, 32))
    plain = latent_moe.ExpertShare(width=16, num_experts=8,
                                   experts_per_token=2, expert_share=(0, 2),
                                   dtype=jnp.float32)
    assert (plain.scoring, plain.activation, plain.shared_width) == (
        "softmax", "swiglu", 0)
    params = plain.init(jax.random.key(9), x)["params"]
    assert sorted(params) == ["down", "gate", "router", "shared_down",
                              "shared_gate", "shared_up", "up"]
    assert params["shared_up"].shape == (32, 16)
    said = latent_moe.ExpertShare(
        width=16, num_experts=8, experts_per_token=2, expert_share=(0, 2),
        dtype=jnp.float32, scoring="softmax", activation="swiglu",
        shared_width=16)
    again = said.init(jax.random.key(9), x)["params"]
    assert jax.tree.structure(again) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(said.apply({"params": params}, x)[0]),
        np.asarray(plain.apply({"params": params}, x)[0]))
    model = latent_moe.latent_moe_tiny()
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sorted(tree["moe_0"]) == sorted(params)
    # the gated form through the loop over blocks: what the same tokens
    # get a few at a time, through every held expert
    many = jax.random.normal(jax.random.key(10), (300, 32))
    run = jax.jit(lambda p, x: plain.apply({"params": p}, x)[0])
    few = jnp.concatenate([run(params, many[:150]), run(params, many[150:])])
    assert rel(run(params, many), few) < TOL
