"""The greedy token chosen inside the decode executable (ISSUE 28).

A greedy ``GenerationEngine`` without a prefix cache compiles its decode
step inside ``generation.pick_on_device``: the step returns ``(pool',
tokens[n] int32, *routed)`` and the scheduler fetches one integer a lane
where it fetched ``[n, V]`` float32 logits and took ``np.argmax`` lane by
lane. What has to hold:

- the tokens are, step by step and lane by lane, the host argmax over the
  logits that the same model's ``make_decode_fn`` / ``make_paged_step_fn``
  gives for the same inputs (ragged lanes and a padded ladder entry
  included), and exact ties go to the lowest index as in ``np.argmax``;
- a sampled engine and one with a prefix cache still get logits from
  their step, and serve what they served: the seeded stream is the draw
  from the step's own logits, a full prefix hit's first token comes from
  the parked row, and the host-picked stream equals the device-picked one;
- ``serving.decode.device_picks`` says which of the two an engine is;
- the executables an engine compiles are the ones it compiled before.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models.gpt import gpt_tiny
from distkeras_tpu.models.latent_moe import latent_moe_tiny
from distkeras_tpu.serving import GenerationEngine
from distkeras_tpu.serving.generation import (make_decode_fn,
                                              make_paged_step_fn,
                                              pick_on_device)

BUCKETS = (8, 32)
LADDER = (2, 4)
#: (prompt length, max_new_tokens): three lanes on the 4-lane entry (one
#: padded), then two (an exact fit), then one on the 2-lane entry
REQUESTS = ((5, 4), (19, 9), (11, 14))

ENGINES = {
    "gpt_rect": ("gpt", dict()),
    "gpt_paged": ("gpt", dict(page_size=16)),
    "latent_rect": ("latent", dict()),
    "gpt_rect_sampled": ("gpt", dict(sampling=True, temperature=0.8,
                                     seed=24)),
    "gpt_paged_sampled": ("gpt", dict(page_size=16, sampling=True,
                                      temperature=0.8, seed=24)),
    "gpt_paged_prefix": ("gpt", dict(page_size=16,
                                     prefix_cache_bytes=4 << 20)),
}
GREEDY = ("gpt_rect", "gpt_paged", "latent_rect")
HOST_PICK = ("gpt_rect_sampled", "gpt_paged_sampled", "gpt_paged_prefix")


@functools.lru_cache(maxsize=None)
def _lm(family):
    model = gpt_tiny() if family == "gpt" else latent_moe_tiny(max_len=96)
    init = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])
    return model, init(jax.random.key(0))


def _prompts(vocab):
    rng = np.random.default_rng(28)
    return [rng.integers(1, vocab, n).tolist() for n, _ in REQUESTS]


@functools.lru_cache(maxsize=None)
def _served(name):
    """One engine of ``ENGINES`` serving ``REQUESTS`` at once, every
    decode executable spied on: before each call the model's own step
    function (compiled here, not donating) runs on the same arguments and
    its logits are kept per lane beside what the engine's executable
    returned. Run once a name; the tests read the record."""
    family, kwargs = ENGINES[name]
    model, params = _lm(family)
    paged = "page_size" in kwargs
    plain = jax.jit(make_paged_step_fn(model) if paged
                    else make_decode_fn(model))
    steps = []   # (lane keys, the plain step's logits [live, V], out [live])

    telemetry.reset()
    with GenerationEngine(model, params, num_slots=4, slot_ladder=LADDER,
                          prefill_buckets=BUCKETS, **kwargs) as eng:
        record = dict(
            executables=eng.compiled_executables,
            compiles=telemetry.counter("serving.decode.compiles").value,
            out={n: (ex.out_info[1].shape, str(ex.out_info[1].dtype))
                 for n, ex in eng._decode_exec.items()})
        for lane, ex in list(eng._decode_exec.items()):
            def spy(*args, _ex=ex):
                live = np.asarray(args[-1]) > 0      # padding has length 0
                keys = [np.asarray(row).tobytes()    # slot id / page table
                        for row in np.asarray(args[2])[live]]
                logits = np.asarray(plain(*args)[1])
                if logits.ndim == 3:
                    logits = logits[:, 0, :]
                got = _ex(*args)
                steps.append((keys, logits[live], np.asarray(got[1])[live]))
                return got
            eng._decode_exec[lane] = spy
        prompts = _prompts(model.vocab_size)
        futures = [eng.generate(p, max_new_tokens=m)
                   for p, (_, m) in zip(prompts, REQUESTS)]
        record["tokens"] = [f.result(timeout=120).tokens.tolist()
                            for f in futures]
        if "prefix_cache_bytes" in kwargs:
            prefills = telemetry.counter("serving.decode.prefills").value
            record["warm"] = eng.generate(
                prompts[1], max_new_tokens=REQUESTS[1][1]).result(
                    timeout=120).tokens.tolist()
            record["warm_prefills"] = telemetry.counter(
                "serving.decode.prefills").value - prefills
    record["steps"] = steps
    record["counters"] = telemetry.get_registry().snapshot()["counters"]
    telemetry.reset()
    return record


# --------------------------------------------------- (a) the host's argmax

@pytest.mark.parametrize("name", GREEDY)
def test_greedy_engine_emits_the_host_argmax_of_the_steps_logits(name):
    rec = _served(name)
    lanes_seen = set()
    streams = {}
    for keys, logits, out in rec["steps"]:
        lanes_seen.add(len(keys))
        assert out.dtype == np.int32 and out.shape == (len(keys),)
        np.testing.assert_array_equal(out, np.argmax(logits, axis=-1))
        for key, tok in zip(keys, out):
            streams.setdefault(key, []).append(int(tok))
    # ragged: three live lanes on the 4-lane entry, two, then one
    assert lanes_seen == {3, 2, 1}
    assert [len(t) for t in rec["tokens"]] == [m for _, m in REQUESTS]
    # each request's tokens after the prefill's are one lane's picks
    assert sorted(streams.values()) == sorted(t[1:] for t in rec["tokens"])


# ----------------------------------------------------------- (b) the ties

def _tied_logits():
    logits = np.zeros((5, 7), np.float32)
    logits[1, [2, 5]] = 3.0             # two maxima: the first
    logits[2, [6, 0]] = 1.5             # first and last
    logits[3] = -2.0                    # every entry the maximum
    logits[4, 3:] = np.float32(0.1)     # a run of equal maxima
    return logits


@pytest.mark.parametrize("form", ["rectangular", "paged", "routed"])
def test_wrapper_takes_the_first_maximum_as_np_argmax_does(form):
    logits = _tied_logits()
    want = np.argmax(logits, axis=-1)
    np.testing.assert_array_equal(want, [0, 2, 0, 0, 3])
    held = np.arange(6, dtype=np.int32).reshape(2, 3)
    if form == "paged":
        # [n, 2, V]: position 0 is the token's, position 1 the ghost's
        ghost = np.roll(logits, 1, axis=-1) + 9.0
        logits = np.stack([logits, ghost], axis=1)

    def decode(pool, shift):
        out = (pool + 1, jnp.asarray(logits) + shift)
        return out + (jnp.asarray(held),) if form == "routed" else out

    picked = pick_on_device(decode)
    assert picked.__name__ == "decode"
    pool, tokens, *routed = jax.jit(picked)(jnp.zeros(3), jnp.float32(0.0))
    assert tokens.dtype == jnp.int32 and tokens.shape == (5,)
    np.testing.assert_array_equal(np.asarray(tokens), want)
    np.testing.assert_array_equal(np.asarray(pool), np.ones(3))
    assert len(routed) == (form == "routed")
    for r in routed:
        np.testing.assert_array_equal(np.asarray(r), held)


# ------------------------------------------- (c) who keeps their logits

def _draw(logits_row, temperature, rng):
    """``GenerationEngine._pick_token``'s sampled branch, written again."""
    z = np.asarray(logits_row, np.float64) / temperature
    p = np.exp(z - z.max())
    cdf = np.cumsum(p / p.sum())
    return int(min(np.searchsorted(cdf, rng.random(), side="right"),
                   cdf.size - 1))


@pytest.mark.parametrize("name", HOST_PICK)
def test_sampled_and_prefix_engines_still_fetch_logits(name):
    rec = _served(name)
    vocab = _lm("gpt")[0].vocab_size
    paged = "page_size" in ENGINES[name][1]
    for n in LADDER:
        assert rec["out"][n] == ((n, 2, vocab) if paged else (n, vocab),
                                 "float32")
    for keys, logits, out in rec["steps"]:
        assert out.dtype == np.float32
        got = out[:, 0, :] if paged else out
        np.testing.assert_array_equal(got, logits)


@pytest.mark.parametrize("name", ["gpt_rect_sampled", "gpt_paged_sampled"])
def test_sampled_seeded_stream_is_the_draw_from_the_steps_logits(name):
    rec = _served(name)
    kwargs = ENGINES[name][1]
    rows = {}
    for keys, logits, _ in rec["steps"]:
        for key, row in zip(keys, logits):
            rows.setdefault(key, []).append(row)
    redrawn = []
    for index, tokens in enumerate(rec["tokens"]):
        # the request's own stream: (engine seed, submission index), one
        # uniform a token, the first spent on the prefill's row
        rng = np.random.default_rng([kwargs["seed"], index])
        rng.random()
        mine = [k for k, r in rows.items() if len(r) == len(tokens) - 1]
        assert len(mine) == 1
        redrawn.append([tokens[0]] + [
            _draw(row, kwargs["temperature"], rng) for row in rows[mine[0]]])
    assert redrawn == rec["tokens"]
    assert rec["tokens"] != _served(name.replace("_sampled", ""))["tokens"]


def test_prefix_engine_serves_the_device_picked_stream_and_its_full_hit():
    rec = _served("gpt_paged_prefix")
    # host argmax over fetched logits (the path as it was) and the
    # device's pick (gpt_paged) serve the same tokens
    assert rec["tokens"] == _served("gpt_paged")["tokens"]
    # the full hit: first token from the parked row, no prefill ran
    assert rec["warm"] == rec["tokens"][1]
    assert rec["warm_prefills"] == 0
    assert rec["counters"]["serving.decode.prefix.full_hits"] == 1


# ------------------------------------------------------- (d) the counter

@pytest.mark.parametrize("name", GREEDY + HOST_PICK)
def test_device_picks_counts_the_lanes_whose_token_the_device_chose(name):
    counters = _served(name)["counters"]
    tokens = counters["serving.decode.tokens"]
    extra = REQUESTS[1][1] - 1 if name.endswith("prefix") else 0
    assert tokens == sum(m - 1 for _, m in REQUESTS) + extra
    assert counters["serving.decode.device_picks"] == (
        tokens if name in GREEDY else 0)
    assert telemetry.declared_kind("serving.decode.device_picks") == "counter"


# --------------------------------------------------- (e) the executables

@pytest.mark.parametrize("name", GREEDY + HOST_PICK)
def test_engine_compiles_the_executables_it_compiled_before(name):
    rec = _served(name)
    want = {"prefill": BUCKETS, "decode": LADDER}
    compiles = len(BUCKETS) + len(LADDER)
    if name.endswith("prefix"):
        want["swap"] = ("in", "out")
        compiles += 2
    assert rec["executables"] == want
    assert rec["compiles"] == compiles
    if name in GREEDY:
        for n in LADDER:
            assert rec["out"][n] == ((n,), "int32")
