"""The pool attention kernel (ops/pallas/decode_attention.py), on the CPU
in interpret mode.

What the gpt decode step runs on a TPU in place of ``gather_rows`` +
``_attend_rows``: each lane's row read in the pool, block by block, up to
what the lane has written, with an online softmax. Held here to
``_attend_rows`` over the gathered rows of the same pool: float32 to the
decode-step tolerance (``test_generation.TOL``; the online softmax adds
the same terms in another order, NUMERICS.md "Decode-step equivalence"),
bfloat16 to ``BF16_TOL`` (the weights reach P.V rounded to 8 bits of
mantissa before they are normalised, not after). Every position past what
a lane has written is NaN in the kernel's pool and finite in the
reference's: a result without NaN that matches says those positions were
never read, or read and given no weight nor value.

The compiled kernel is held to the same reference on the chip by
``chip_smoke.py``'s kernels leg, and ``tests/test_decode_layout.py`` reads
what the TPU compiler makes of the step around it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import gpt as gpt_lib
from distkeras_tpu.ops.cache_rows import gather_rows
from distkeras_tpu.ops.pallas import decode_attention as da
from distkeras_tpu.serving import generation
from test_generation import TOL

#: bfloat16: one rounding of a weight (2**-9 relative) over sums of
#: O(1) values, and of the result
BF16_TOL = dict(rtol=2e-2, atol=2e-2)

MAX_LEN = 1024
WIDTH = 128
HEADS = 4


def _block(dtype, width=WIDTH, max_len=MAX_LEN):
    return da.block_positions(width, np.dtype(dtype).itemsize, max_len)


def _case(lengths, t, dtype, rows=None, width=WIDTH, heads=HEADS,
          max_len=MAX_LEN, seed=0):
    """Kernel and reference over one random pool: ``(out, ref)`` float32
    ``[lanes, t, width]``. Lane i reads row ``rows[i]`` (default: a
    permutation, lanes of length 0 on the scratch row, the last)."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    b = len(lengths)
    if rows is None:
        rows = rng.permutation(b).astype(np.int32)
        rows[lengths == 0] = b
    k, v = rng.standard_normal((2, b + 1, max_len, width)).astype(np.float32)
    q = rng.standard_normal((b, t, width)).astype(np.float32)
    unread = np.zeros((b + 1, max_len), bool)
    unread[np.setdiff1d(np.arange(b + 1), rows)] = True
    for row, n in zip(rows, lengths):
        unread[row, n + t:] = True
    cast = lambda a: jnp.asarray(a, dtype)
    pos = jnp.asarray(lengths[:, None] + np.arange(t)[None, :])
    ref = gpt_lib._attend_rows(
        cast(q), gather_rows(cast(k), jnp.asarray(rows)),
        gather_rows(cast(v), jnp.asarray(rows)), pos, heads)
    nan = lambda a: cast(np.where(unread[..., None], np.nan, a))
    out = da.pool_attention(cast(q), nan(k), nan(v), jnp.asarray(rows),
                            jnp.asarray(lengths), heads, interpret=True)
    assert out.shape == (b, t, width) and out.dtype == np.dtype(dtype)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


def _edge_lengths(t, block, lanes, seed):
    """Scratch (0), 1, one under / at / one over a block edge counted with
    the block the step writes, the last two a row allows (the ghost's
    write dropped at ``MAX_LEN - 1``), then random ones."""
    edges = [0, 1, block - t - 1, block - t, block - t + 1,
             2 * block - t, 2 * block - t + 1, MAX_LEN - 2, MAX_LEN - 1]
    rng = np.random.default_rng(seed)
    rest = rng.integers(0, MAX_LEN, max(0, lanes - len(edges)))
    return np.concatenate([edges, rest])[:lanes].astype(np.int32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL),
                                       (jnp.bfloat16, BF16_TOL)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [1, 2, 5])
@pytest.mark.parametrize("lanes", [8, 16, 32])
def test_matches_attend_rows_and_reads_nothing_unwritten(lanes, t, dtype,
                                                         tol):
    lengths = _edge_lengths(t, _block(dtype), lanes, seed=lanes + t)
    out, ref = _case(lengths, t, dtype, seed=lanes * 8 + t)
    assert not np.isnan(out).any()
    for lane in range(lanes):       # lane by lane: the message names one
        np.testing.assert_allclose(
            out[lane], ref[lane], **tol,
            err_msg=f"lane {lane} of length {lengths[lane]}")


@pytest.mark.parametrize("length", [
    "0", "1", "block-3", "block-2", "block-1", "block", "3*block-2",
    "max_len-2", "max_len-1"])
def test_every_lane_at_one_length(length):
    """The decode step's ``[token, ghost]`` with all eight lanes at one
    edge: ``block-2`` fills its first block exactly, ``block-1`` puts the
    ghost alone into a second, ``max_len-1`` has the ghost's write
    dropped and its query see the whole row."""
    block = _block(jnp.float32)
    n = eval(length, {"block": block, "max_len": MAX_LEN})
    rows = np.full(8, 8, np.int32) if n == 0 else None   # scratch, repeated
    out, ref = _case(np.full(8, n), 2, jnp.float32, rows=rows, seed=n)
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out, ref, **TOL)


def test_repeated_scratch_rows_and_a_permutation():
    """Padded lanes all point at the scratch row with length 0, between
    live lanes whose rows are not in lane order."""
    lengths = np.array([300, 0, 17, 0, 0, 700, 0, 128], np.int32)
    rows = np.array([5, 8, 0, 8, 8, 2, 8, 7], np.int32)
    out, ref = _case(lengths, 2, jnp.float32, rows=rows, seed=3)
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out, ref, **TOL)


def test_lane_i_reads_row_i_without_cache_rows():
    """``rows=None``: a fresh one-row cache (a short prefill bucket)."""
    rng = np.random.default_rng(5)
    k, v = rng.standard_normal((2, 2, 64, WIDTH)).astype(np.float32)
    q = rng.standard_normal((2, 5, WIDTH)).astype(np.float32)
    lengths = jnp.asarray([0, 40], jnp.int32)
    pos = lengths[:, None] + jnp.arange(5)[None, :]
    out = da.pool_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            None, lengths, HEADS, interpret=True)
    ref = gpt_lib._attend_rows(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), pos, HEADS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_heads_that_do_not_fill_a_tile_and_a_wider_line():
    """12 heads of 64 (gpt2-small's): padded to 16 query rows a
    position."""
    lengths = np.array([0, 5, 126, 127, 128, 600, 1022, 1023], np.int32)
    out, ref = _case(lengths, 2, jnp.float32, width=768, heads=12, seed=7)
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out, ref, **TOL)


# ------------------------------------------------------- what it declines

@pytest.mark.parametrize("width,itemsize,max_len,want", [
    (1024, 2, 1024, 128),       # gpt2-medium's pool
    (768, 2, 1024, 128),
    (128, 4, 1024, 128),        # the cap, not VMEM, bounds a narrow line
    (8192, 4, 4096, 16),        # a 32 KiB line: VMEM bounds it
    (16384, 4, 4096, 0),        # not a sublane tile of such lines
    (1024, 2, 1000, 0),         # no power of two of 16 or more divides
    (1024, 2, 48, 16),
])
def test_block_positions(width, itemsize, max_len, want):
    assert da.block_positions(width, itemsize, max_len) == want


@pytest.mark.parametrize("q,pool,heads,want", [
    ((32, 2, 1024), (33, 1024, 1024), 16, True),
    ((32, 8, 1024), (33, 1024, 1024), 16, True),     # 128 query rows
    ((32, 9, 1024), (33, 1024, 1024), 16, False),    # a long block
    ((1, 64, 1024), (1, 1024, 1024), 16, False),     # a prefill bucket
    ((8, 4, 768), (9, 1024, 768), 12, True),         # 12 heads pad to 16
    ((8, 2, 64), (9, 64, 64), 4, False),             # half a lane tile wide
    ((8, 2, 128), (9, 1000, 128), 4, False),         # no block divides
    ((8, 2, 16, 8), (9, 64, 16, 8), 16, False),      # heads apart
])
def test_fits(q, pool, heads, want):
    struct = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert da.fits(struct(q), struct(pool), heads) is want


def test_fits_wants_one_dtype_bf16_or_f32():
    q = jax.ShapeDtypeStruct((8, 2, 128), jnp.bfloat16)
    pool = lambda dtype: jax.ShapeDtypeStruct((9, 64, 128), dtype)
    assert da.fits(q, pool(jnp.bfloat16), 4)
    assert not da.fits(q, pool(jnp.float32), 4)
    f16 = jax.ShapeDtypeStruct((8, 2, 128), jnp.float16)
    assert not da.fits(f16, pool(jnp.float16), 4)
    with pytest.raises(ValueError, match="fits"):
        da.pool_attention(jnp.zeros((8, 2, 128), jnp.bfloat16),
                          jnp.zeros((9, 64, 128)), jnp.zeros((9, 64, 128)),
                          None, jnp.zeros(8, jnp.int32), 4, interpret=True)


def test_off_the_tpu_nothing_dispatches():
    """The CPU, and so the whole tier-1 suite, runs the code that was
    there; the model says so to whoever counts its reads."""
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert da.fits(struct(32, 2, 1024), struct(33, 1024, 1024), 16)
    assert not da.dispatch(struct(32, 2, 1024), struct(33, 1024, 1024), 16)
    model = gpt_lib.CausalLM(vocab_size=64, max_len=1024, num_layers=1,
                             num_heads=16, width=1024, mlp_dim=64)
    assert model.decode_read_block(2) == 0


@pytest.mark.parametrize("block,dtype,want", [
    (2, None, 128), (8, None, 128), (9, None, 0), (768, None, 0),
    (2, jnp.float32, 0),        # a float32 pool under bfloat16 queries
])
def test_model_says_what_its_step_reads_by(monkeypatch, block, dtype, want):
    monkeypatch.setattr(da, "_on_tpu", lambda: True)
    model = gpt_lib.CausalLM(vocab_size=64, max_len=1024, num_layers=1,
                             num_heads=16, width=1024, mlp_dim=64)
    assert model.decode_read_block(block, dtype) == want


# ------------------------------------------ inside the model, and counted

@pytest.fixture
def tiny():
    """Two layers of width 128 in float32 over 64 positions, four blocks
    of 16 (the test's steering, not an option of the program)."""
    model = gpt_lib.CausalLM(vocab_size=97, max_len=64, num_layers=2,
                             num_heads=4, width=128, mlp_dim=256,
                             dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture
def through_the_kernel(monkeypatch):
    """The model's cache branch as a TPU takes it, the kernel interpreted:
    calls counted by query-block length."""
    calls = []
    real = da.pool_attention

    def spied(q, *args, **kw):
        calls.append(q.shape[1])
        return real(q, *args, interpret=True, **kw)
    monkeypatch.setattr(da, "_on_tpu", lambda: True)
    monkeypatch.setattr(da, "_MAX_BLOCK", 16)
    monkeypatch.setattr(da, "pool_attention", spied)
    return calls


def test_decode_through_the_kernel_matches_the_full_forward(
        tiny, through_the_kernel):
    """Prefill (a long block: the present path), then decode steps and a
    verify step through the kernel, each lane against the full forward of
    its own sequence at the padded shape."""
    model, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, n) for n in (3, 14, 15, 33)]
    pool = model.init_cache(len(prompts) + 1)
    prefill = generation.make_prefill_fn(model)
    for slot, ids in enumerate(prompts):
        bucket = np.zeros((1, 48), np.int32)
        bucket[0, :len(ids)] = ids
        pool, _ = prefill(params, pool, jnp.asarray(bucket),
                          jnp.int32(slot), jnp.int32(len(ids)))
    assert through_the_kernel == []      # 48 x 4 query rows: a long block

    def full(seq):
        ids = np.zeros((1, model.max_len), np.int32)
        ids[0, :len(seq)] = seq
        return np.asarray(model.apply({"params": params},
                                      jnp.asarray(ids))[0, len(seq) - 1])
    decode = generation.make_decode_fn(model)
    seqs = [list(p) for p in prompts]
    slots = jnp.asarray([2, 0, 4, 3, 1], jnp.int32)    # lane 2: scratch
    for _ in range(3):
        fed = rng.integers(1, 97, len(seqs))
        lengths = [len(s) for s in seqs]
        tokens = lambda slot: 0 if slot == 4 else fed[slot]
        pool, logits = decode(
            params, pool, slots,
            jnp.asarray([tokens(int(s)) for s in slots], jnp.int32),
            jnp.asarray([0 if s == 4 else lengths[int(s)] for s in slots],
                        jnp.int32))
        for slot, tok in enumerate(fed):
            seqs[slot].append(tok)
        for lane, slot in enumerate(np.asarray(slots)):
            if slot != 4:
                np.testing.assert_allclose(np.asarray(logits[lane]),
                                           full(seqs[slot]), **TOL)
    assert through_the_kernel == [2] * 2 * 3        # two layers a step
    verify = generation.make_verify_fn(model)
    block = rng.integers(1, 97, (4, 4))
    pool, logits = verify(params, pool, jnp.arange(4, dtype=jnp.int32),
                          jnp.asarray(block, jnp.int32),
                          jnp.asarray([len(s) for s in seqs], jnp.int32))
    for slot in range(4):
        for j in range(4):
            np.testing.assert_allclose(
                np.asarray(logits[slot, j]),
                full(seqs[slot] + list(block[slot, :j + 1])), **TOL)
    assert through_the_kernel[-2:] == [4, 4]


def _engine_counts(model, params, prompts, new_tokens):
    telemetry.reset()
    with generation.GenerationEngine(model, params, num_slots=4,
                                     prefill_buckets=(16,)) as engine:
        futures = [engine.generate(p, max_new_tokens=new_tokens)
                   for p in prompts]
        for f in futures:
            f.result(timeout=120)
    counters = telemetry.get_registry().snapshot()["counters"]
    telemetry.reset()
    return counters


def test_engine_counts_whole_rows_where_nothing_bounds_the_read(tiny):
    """On the CPU the fixed-length path runs: every lane of every decode
    step reads its whole row, ladder padding included."""
    model, params = tiny
    counters = _engine_counts(model, params, [[5, 6, 7], [8, 9]], 4)
    row = counters["serving.decode.kv_positions_row"]
    assert row > 0 and row % model.max_len == 0
    assert counters["serving.decode.kv_positions_read"] == row
    for name in ("serving.decode.kv_positions_read",
                 "serving.decode.kv_positions_row"):
        assert telemetry.declared_kind(name) == "counter"


def test_engine_counts_lengths_rounded_up_to_the_models_block(
        tiny, monkeypatch):
    """A model that says its step reads by blocks of 16: a lone lane at
    lengths 3, 4, 5 with ``[token, ghost]`` reads one block a step of a
    64-position row (the counter is the scheduler's arithmetic; which path
    the device runs is the model's answer, here a stand-in)."""
    model, params = tiny
    monkeypatch.setattr(gpt_lib.CausalLM, "decode_read_block",
                        lambda self, block, dtype=None: 16)
    counters = _engine_counts(model, params, [[5, 6, 7]], 4)
    steps = counters["serving.decode.steps"]
    lanes = counters["serving.decode.kv_positions_row"] // model.max_len
    assert lanes % steps == 0               # one ladder rung throughout
    assert counters["serving.decode.kv_positions_read"] == 16 * lanes


def test_count_kv_read_rounds_and_cuts_at_the_row(tiny, monkeypatch):
    model, params = tiny
    monkeypatch.setattr(gpt_lib.CausalLM, "decode_read_block",
                        lambda self, block, dtype=None: 16)
    telemetry.reset()
    with generation.GenerationEngine(model, params, num_slots=4,
                                     prefill_buckets=(16,)) as engine:
        # held with the step's two: 2, 16, 17, 48, 49, 64, 65 (cut at 64)
        engine._count_kv_read(np.array([0, 14, 15, 46, 47, 62, 63],
                                       np.int32))
    counters = telemetry.get_registry().snapshot()["counters"]
    telemetry.reset()
    assert counters["serving.decode.kv_positions_read"] == \
        16 + 16 + 32 + 48 + 64 + 64 + 64
    assert counters["serving.decode.kv_positions_row"] == 7 * 64
