"""Continuous-batching generative serving (DESIGN.md §14).

The one-shot engine (engine.py) answers fixed-shape forwards; generating
T tokens through it costs T full-prefix forwards — O(T^2) attention
FLOPs recomputed per request and a compile-cache entry per observed
length. This module is the autoregressive path done properly:

- **prefill**: one bucketed forward (existing :class:`BucketSpec`
  ladder over prompt lengths) writes the whole prompt's K/V into a
  pool slot (serving/kv_cache.py) and yields the first token;
- **decode**: every iteration advances ALL in-flight sequences by one
  token in a single compiled step, the batch padded up to a declared
  **slot ladder** entry;
- **iteration-level scheduling** (the Orca/vLLM idea): new requests are
  admitted into the in-flight batch between decode steps, and finished
  sequences (EOS / ``max_new_tokens`` / deadline / context full) retire
  mid-flight, freeing their slot immediately — a short request admitted
  after a long one finishes first instead of waiting for the batch.

Compile-cache discipline survives verbatim from PR 2: exactly one
prefill executable per prompt bucket and one decode executable per
ladder entry, all AOT-compiled in ``__init__`` — the cache can never
grow under traffic (asserted in tests/test_generation.py).

Numerics, the contract (NUMERICS.md "Decode-step equivalence"): prefill
then decode, through every pool and attention form, equals the float32
full-prefix forward at the model's ``max_len``-padded shape at
``rtol=atol=1e-5`` at every position, and greedy streams are equal
wherever the reference's top-two logit gap exceeds that tolerance
(``tests/test_generation.py``'s ``TOL``). Equality is bit for bit only
where two results come from the same executable or a pure copy (a page
through a host swap, the paged kernel against its dense gather). Two
mechanisms below were built for bitwise equality on an earlier XLA:CPU
and are now implementation, kept until a ``perf_opt`` measures them away:
the attention contraction always runs over all ``max_len`` keys with an
exact-zero masked tail, and each decode step feeds a **ghost position**
— a T=2 block ``[token, 0]`` (hence the refusal of prefill buckets and
chunks below 2). The ghost's query output is discarded and its cache line
lands past the lane's length, masked until the next token overwrites it.

Where the greedy token is chosen: an engine that is greedy and keeps no
prefix cache wants an index from its decode step, not a distribution, so
the executable it compiles per ladder entry ends in the argmax over the
real position's float32 logits (:func:`pick_on_device` around the step
functions below) and returns ``(pool', tokens[n] int32, *routed)``: the
scheduler fetches one integer a lane and ``[n, V]`` never leaves the
device. ``jnp.argmax`` and ``np.argmax`` both take the first maximum, so
the stream is the host argmax's. ``sampling=True`` draws from a host
float64 CDF on the request's seeded stream and a prefix cache parks
every lane's last logits: both are fixed at construction, and their
steps keep returning logits. Verify and prefill keep their logits too.

Backpressure/deadline semantics are PR 2's, with the same typed errors:
bounded admission queue (:class:`QueueFull`, all-or-nothing), deadlines
checked at admission AND between decode steps (:class:`DeadlineExceeded`
mid-generation frees the slot), :class:`EngineClosed` after shutdown.

Three opt-in decode accelerations (DESIGN.md §19) layer on top without
changing any of the above:

- ``page_size=``: the slot pool becomes a :class:`PagedKVCachePool` —
  admission reserves only ``ceil((prompt + max_new) / page_size)``
  pages instead of a ``max_len`` rectangle, with the same logits at
  the decode-step tolerance (the paged forward attends over the same
  dense gathered view).
- ``prefix_cache_bytes=``: a host-RAM :class:`PrefixCache` keeps
  content-hashed KV prefixes; a full hit emits the first token with
  zero forward calls, a partial hit swaps the cached pages back in and
  prefills only the suffix. A failed swap-in (the ``"kv.swap_in"``
  chaos site) evicts the entry and degrades to a cold prefill.
- ``draft=``/``spec_k=``: speculative decoding — the draft proposes k
  tokens, one verify call scores them all, and the exact greedy
  accept/reject rule (NUMERICS.md "Speculative accept/reject
  exactness") emits a token stream identical to plain greedy decode
  regardless of draft quality.

Long-context serving economics (ISSUE 20) add three more opt-in
levers, each behind its own kwarg and composing with all of the above:

- ``prefill_chunk=``: **chunked prefill** — instead of one bucket-wide
  forward at admission, a long prompt is sliced into ``prefill_chunk``-
  token pieces ridden between decode iterations (one chunk per
  partially-prefilled slot per iteration). A slot carries a
  ``prefill_pos`` cursor and never enters a decode group until the
  cursor covers its prompt, so one user's TTFT stops taxing everyone
  else's tokens/s. Chunks reuse the paged step family at
  ``lengths=[cursor]`` (mid-sequence prefill), so every chunk's logits
  are the one-shot prefill's rows at the decode-step tolerance.
- ``kv_dtype="int8"``: **quantized KV pages** — the paged pool stores
  per-page symmetric int8 codes + f32 scales (models/gpt.py, the wire
  codec's affine rule), ~4x resident conversations per HBM byte at f32
  compute with a ``scale/2``-per-cell error bound; host swap, prefix
  cache, and fleet KV handoff ship the quantized blobs.
- ``sampling=True``: **temperature sampling** with a per-request
  seeded stream (``seed``/``temperature`` kwargs; one inverse-CDF
  uniform per emitted token), and — combined with ``draft=``/
  ``spec_k=`` — **sampling-capable speculative verification**: the
  standard target-vs-draft accept/reject rule, realized for this
  repo's deterministic (point-mass) drafts so the emitted stream is
  seeded-IDENTICAL to plain sampled decode (NUMERICS.md "Sampled
  speculative equivalence").

All executables (prefill x buckets, prefill-chunk, decode/verify x
ladder, page swap-in/out, draft prefill/decode) are still AOT-compiled
in ``__init__`` — the compile cache cannot grow under any traffic mix.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence, Tuple

import numpy as np

from distkeras_tpu import telemetry
from distkeras_tpu.serving.batching import (DeadlineExceeded, EngineClosed,
                                            QueueFull)
from distkeras_tpu.serving.buckets import BucketSpec
from distkeras_tpu.serving.kv_cache import (KVCachePool, PagedKVCachePool,
                                            PrefixCache, select_leaves,
                                            state_leaves)
from distkeras_tpu.utils import fault

#: the ``jax.named_scope`` names the step functions below declare for what
#: they add around the model's forward (profiling/scopes.py): the ids a
#: decode step stacks (``embed``), a prefill's row put into the pool
#: (``cache.write``), the one logits row a prefill hands back (``head``),
#: the on-device argmax (``pick``), the counts a step sums for the host's
#: counters (``step.count``), pages parked on the host and brought back
#: (``cache.swap``)
SCOPES = ("embed", "cache.write", "head", "pick", "step.count", "cache.swap")

#: token id fed at the decode step's ghost position (its output is
#: discarded and its cache line masked, so any valid id works)
GHOST_TOKEN = 0


def _default_ladder(num_slots: int) -> Tuple[int, ...]:
    """Powers of two up to ``num_slots``, always ending at ``num_slots``
    so every possible in-flight count has a lane bucket."""
    sizes = set()
    n = 1
    while n < num_slots:
        sizes.add(n)
        n *= 2
    sizes.add(num_slots)
    return tuple(sorted(sizes))


def _compile(fn, donate, args, **which):
    """One executable, ahead of time: ``fn`` traced, lowered for ``args``
    (shape structs) and compiled under a ``serving.decode.compile`` span
    that says ``which`` (``prefill=512``), counted, and handed to
    ``profiling/scopes.py`` for its scope table: an insert there, the
    executable's text is read only when somebody asks for tables. The
    compile's persistent-cache key holds the metadata (``own_metadata``):
    a hit on an entry that a program without these scopes wrote would give
    back an executable whose instructions name none."""
    import jax

    from distkeras_tpu.profiling import scopes

    with telemetry.span("serving.decode.compile", **which), \
            scopes.own_metadata():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    telemetry.counter("serving.decode.compiles").inc()
    scopes.register("jit_" + fn.__name__, ",".join(
        f"{k}={v}" for k, v in which.items()), compiled)
    return compiled


def make_prefill_fn(model, dtype=None):
    """Pure ``(params, pool, ids[1, Lb], slot, length) -> (pool',
    last_logits[V])``: run the prompt through a fresh one-row cache the
    model builds (``model.init_cache(1, dtype, positions=
    model.prefill_row_len(Lb))``: the whole ``max_len`` for ``CausalLM``,
    the bucket for a family that attends only what it wrote; ``dtype`` the
    pool's), put the row into pool row ``slot`` (the donated pool, one
    ``dynamic_update_slice`` a leaf: a leaf without a position axis is
    overwritten whole) and return the logits at position ``length - 1``
    (the first-token distribution). Bucket padding beyond ``length`` writes
    cells the length mask hides until real tokens overwrite them; a model
    that declares state leaves is told ``length`` (``real_len``), commits
    its state after position ``length - 1`` and hands back that position's
    logits alone."""
    import jax
    import jax.numpy as jnp

    stateful = bool(state_leaves(model))

    def prefill(params, pool, ids, slot, length):
        row = model.init_cache(
            1, dtype, positions=model.prefill_row_len(ids.shape[1]))
        told = {"real_len": jnp.reshape(length, (1,))} if stateful else {}
        logits, new_row, *_ = model.apply(
            {"params": params}, ids, cache=row,
            cache_index=jnp.zeros((1,), jnp.int32), **told)
        with jax.named_scope("cache.write"):
            pool = jax.tree.map(
                lambda p, c: jax.lax.dynamic_update_slice_in_dim(
                    p, c, slot, axis=0), pool, new_row)
        with jax.named_scope("head"):
            return pool, logits[0, 0 if stateful else length - 1]

    return prefill


def decode_block_tokens(model) -> int:
    """Positions a lane feeds a decode step: ``[token, ghost]``, or the
    token alone for a model that declares state leaves or selects what it
    reads (:func:`make_decode_fn`)."""
    return 1 if state_leaves(model) or select_leaves(model) else 2


def make_decode_fn(model):
    """Pure ``(params, pool, slot_ids[n], tokens[n], lengths[n]) ->
    (pool', logits[n, V])``: advance ``n`` lanes one token. Each lane
    feeds ``[token, GHOST_TOKEN]`` at positions ``[len, len+1]`` (the
    ghost keeps every matmul on the gemm path — see module docstring).
    The model writes both K/V lines into pool row ``slot_ids[i]`` in
    place and attends the lanes' rows (``cache_rows=slot_ids``): on a TPU
    in the pool itself, each row as far as its lane has written
    (``CausalLM``: ``ops/pallas/decode_attention.py``), elsewhere gathered
    whole; only the real position's logits return.
    The ghost's line sits past the lane's new length, masked until the
    next token overwrites it, and is dropped at ``max_len``. Padded
    lanes point at the pool's scratch row with length 0; their writes
    land in scratch and their outputs are discarded by the caller. A
    model that declares state leaves (:func:`state_leaves`) is fed
    ``[token]`` alone: its state advances by the real token, and the
    scratch row's state goes the way of the scratch row's lines.

    A model with routed experts hands back, token by token, which of the
    experts it holds each was sent to; the step then returns a third
    value, int32 ``[layers, experts_held]``: tokens per held expert,
    counted over the real position of the lanes that are not padding.
    A model without experts returns two values and pays nothing. A model
    whose attention selects what it reads (:func:`select_leaves`) hands
    back the positions each query attended, and the step a fourth value, an
    int32 scalar: those of the same lanes' real position, summed over lanes
    and layers. It is fed ``[token]`` alone as well: a ghost would cost
    every lane a second selection and gather.

    This is the step's contract whoever compiles it. A greedy
    :class:`GenerationEngine` compiles it inside :func:`pick_on_device`,
    which puts the token in the logits' place."""
    import jax
    import jax.numpy as jnp

    ghost = decode_block_tokens(model) == 2

    def decode(params, pool, slot_ids, tokens, lengths):
        # a state a row would be advanced by the ghost too, and a
        # selection made for it: such a model is fed its real token alone
        with jax.named_scope("embed"):
            ids = jnp.stack(
                [tokens, jnp.full_like(tokens, GHOST_TOKEN)], axis=1) \
                if ghost else tokens[:, None]
        logits, pool, *routed = model.apply(
            {"params": params}, ids, cache=pool, cache_index=lengths,
            cache_rows=slot_ids)
        with jax.named_scope("head"):
            logits = logits[:, 0, :]
        if not routed:
            return pool, logits
        with jax.named_scope("step.count"):
            scratch = jax.tree.leaves(pool)[0].shape[0] - 1
            live = (slot_ids != scratch)[None, :, None]
            return (pool, logits, jnp.sum(
                routed[0][:, :, 0, :] & live, axis=1, dtype=jnp.int32),
                *(jnp.sum(jnp.where(live[..., 0], attended[:, :, 0], 0))
                  for attended in routed[1:]))

    return decode


def make_verify_fn(model):
    """Pure ``(params, pool, slot_ids[n], tokens[n, T], lengths[n]) ->
    (pool', logits[n, T, V])``: the speculative verify step over the
    rectangular pool, decode's step at T positions. Each lane feeds
    ``[pending, d_1 .. d_{T-1}]`` at positions ``len .. len+T-1``; ALL T
    new K/V lines are written into the lane's pool row in place
    (accepted cells are exactly what sequential greedy would have
    written; rejected cells sit past the post-accept length, masked and
    overwritten before ever becoming visible) and all T logit rows
    return for the host-side accept/reject walk. T >= 2 keeps the gemm
    path, same as the decode ghost."""

    def verify(params, pool, slot_ids, tokens, lengths):
        logits, pool, *_ = model.apply(
            {"params": params}, tokens, cache=pool, cache_index=lengths,
            cache_rows=slot_ids)
        return pool, logits

    return verify


def make_paged_step_fn(model):
    """Pure ``(params, pages, page_tables[n, Pmax], tokens[n, T],
    lengths[n]) -> (pages', logits[n, T, V])`` — the ONE compiled shape
    family for every paged phase. Prefill is n=1/T=bucket at
    ``lengths=[start]`` (start > 0 = mid-sequence prefill: a suffix
    after a prefix-cache hit, or one chunk of a chunked prefill at its
    cursor), decode is T=2 (token + ghost), verify is T=spec_k+1. The
    model's paged write-back routes every cell to its physical page;
    ghost/overflow cells land in the scratch page."""

    def step(params, pages, page_tables, tokens, lengths):
        logits, new_pages = model.apply(
            {"params": params}, tokens, cache=pages, cache_index=lengths,
            page_table=page_tables)
        return new_pages, logits

    return step


def pick_on_device(step):
    """``step`` with the greedy token in the logits' place: ``(pool',
    tokens[n] int32, *routed)`` from a decode step that returns ``(pool',
    logits, *routed)``, the argmax taken inside the same jitted function
    over the real position's float32 logits (``[n, V]``, or position 0
    of the paged step's ``[n, 2, V]``). The first maximum wins, as in
    ``np.argmax``, so a greedy stream is the host argmax's. The wrapper
    keeps ``step``'s name: the executable is ``jit_decode`` (``jit_step``
    for the paged one) either way."""
    import jax
    import jax.numpy as jnp

    @functools.wraps(step)
    def picked(*args):
        pool, logits, *routed = step(*args)
        with jax.named_scope("pick"):
            if logits.ndim == 3:
                logits = logits[:, 0, :]
            return (pool, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    *routed)

    return picked


def make_swap_out_fn():
    """Pure ``(pages, page_ids[Pmax]) -> data``: gather the named pages
    (per leaf ``[Pmax, page_size, heads, head_dim]``) for host parking.
    NOT donating — the pool stays live; unused ids point at scratch."""
    import jax

    def swap_out(pages, page_ids):
        with jax.named_scope("cache.swap"):
            return jax.tree.map(lambda a: a[page_ids], pages)

    return swap_out


def make_swap_in_fn():
    """Pure ``(pages, page_ids[Pmax], data) -> pages'``: scatter parked
    page data back into the (donated) pool. Unused ids point at scratch,
    so their data rows collide only on the scratch page."""
    import jax

    def swap_in(pages, page_ids, data):
        with jax.named_scope("cache.swap"):
            return jax.tree.map(lambda a, d: a.at[page_ids].set(d),
                                pages, data)

    return swap_in


class NgramDraft:
    """Prompt-lookup drafting (host-only, zero device cost): propose the
    k tokens that followed the most recent earlier occurrence of the
    context's final ``ngram``-gram. Great on repetitive/structured
    output, useless on novel text — which is FINE: the verify step's
    exact accept/reject makes draft quality a throughput knob, never a
    correctness one. When no gram matches, the last token is repeated
    (proposals must always be exactly k — the verify shape is fixed)."""

    def __init__(self, ngram: int = 2):
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        self.ngram = int(ngram)
        self._ctx: dict = {}

    def bind(self, engine) -> None:  # noqa: ARG002 - uniform draft API
        """No executables to compile; the draft is pure host work."""

    def begin(self, slot: int, prompt, first_token: int) -> None:
        self._ctx[slot] = [int(t) for t in prompt] + [int(first_token)]

    def propose(self, slots, last_tokens, lengths, k: int) -> np.ndarray:
        del last_tokens, lengths  # the host context already ends on them
        out = np.zeros((len(slots), k), np.int32)
        for i, s in enumerate(slots):
            out[i] = self._propose_one(self._ctx[s], k)
        return out

    def _propose_one(self, ctx, k: int):
        n = self.ngram
        props: list = []
        if len(ctx) > n:
            tail = ctx[-n:]
            for start in range(len(ctx) - n - 1, -1, -1):
                if ctx[start:start + n] == tail:
                    props = ctx[start + n:start + n + k]
                    break
        while len(props) < k:
            props.append(props[-1] if props else ctx[-1])
        return np.asarray(props[:k], np.int32)

    def observe(self, slot: int, emitted) -> None:
        self._ctx[slot].extend(int(t) for t in emitted)

    def release(self, slot: int) -> None:
        self._ctx.pop(slot, None)


class ModelDraft:
    """Draft-model speculative proposals: a smaller ``CausalLM`` runs
    k+1 cheap decode steps to propose k tokens the target verifies in
    one call. The draft keeps its OWN rectangular KV pool indexed by the
    target's slot ids and always feeds at the target's lengths, so its
    cache tracks the true (post-accept) token sequence wherever the
    engine ran speculative iterations; iterations the engine gated off
    (e.g. near ``max_len``) leave a stale draft cell behind, which can
    only lower the accept rate — output exactness never depends on the
    draft cache (NUMERICS.md "Speculative accept/reject exactness").

    ``bind`` AOT-compiles one draft prefill per prompt bucket and one
    draft decode per ladder entry against the draft pool's shapes —
    fixed at construction, so the engine-wide compile-cache invariant
    holds with a draft attached."""

    def __init__(self, model, params, *, dtype=None):
        self.model = model
        self.params = params
        self._dtype = dtype
        self._cache = None

    def bind(self, engine) -> None:
        import jax

        if int(self.model.max_len) < engine.max_len:
            raise ValueError(
                f"draft max_len {self.model.max_len} < target max_len "
                f"{engine.max_len}; the draft must cover every position "
                f"the target can reach")
        self._buckets = engine._buckets
        self._ladder = engine._ladder
        self._scratch = engine.pool.num_slots
        if engine._device is not None:
            self.params = jax.device_put(self.params, engine._device)
        cache = self.model.init_cache(engine.pool.num_slots + 1,
                                      self._dtype)
        if engine._device is not None:
            cache = jax.device_put(cache, engine._device)
        self._cache = cache
        self._lengths = np.zeros(engine.pool.num_slots + 1, np.int32)
        sds = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        p_sds, c_sds = sds(self.params), sds(self._cache)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)
        prefill = make_prefill_fn(self.model, self._dtype)
        decode = make_decode_fn(self.model)
        self._prefill_exec = {}
        self._decode_exec = {}
        for lb in self._buckets:
            self._prefill_exec[lb] = _compile(
                prefill, (1,), (p_sds, c_sds, i32(1, lb), i32(), i32()),
                draft_prefill=lb)
        for n in self._ladder:
            self._decode_exec[n] = _compile(
                decode, (1,), (p_sds, c_sds, i32(n), i32(n), i32(n)),
                draft_lanes=n)
        # warm every executable against the draft scratch row
        scratch = np.int32(self._scratch)
        for lb, ex in self._prefill_exec.items():
            self._cache, _ = ex(self.params, self._cache,
                                np.zeros((1, lb), np.int32), scratch,
                                np.int32(lb))
        for n, ex in self._decode_exec.items():
            lanes = np.full(n, scratch, np.int32)
            zeros = np.zeros(n, np.int32)
            self._cache, *_ = ex(self.params, self._cache, lanes, zeros,
                                 zeros)

    @property
    def compiled_executables(self):
        return {"prefill": tuple(sorted(self._prefill_exec)),
                "decode": tuple(sorted(self._decode_exec))}

    def begin(self, slot: int, prompt, first_token: int) -> None:
        del first_token  # arrives as last_tokens at the next propose
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = prompt.size
        lb = self._buckets.bucket_for(n)
        ids = np.zeros((1, lb), np.int32)
        ids[0, :n] = prompt
        self._cache, _ = self._prefill_exec[lb](
            self.params, self._cache, ids, np.int32(slot), np.int32(n))
        self._lengths[slot] = n

    def propose(self, slots, last_tokens, lengths, k: int) -> np.ndarray:
        n = len(slots)
        lane = self._ladder.bucket_for(n)
        out = np.zeros((n, k), np.int32)
        feed = np.asarray(last_tokens, np.int32).copy()
        lens_live = np.asarray(lengths, np.int32).copy()
        # k proposal feeds + one cache-fill feed for the last draft
        # token, so a full accept leaves the draft cache complete
        for step in range(k + 1):
            slot_ids = np.full(lane, self._scratch, np.int32)
            toks = np.full(lane, GHOST_TOKEN, np.int32)
            lens = np.zeros(lane, np.int32)
            slot_ids[:n] = slots
            toks[:n] = feed
            lens[:n] = lens_live
            self._cache, logits, *_ = self._decode_exec[lane](
                self.params, self._cache, slot_ids, toks, lens)
            lens_live += 1
            if step < k:
                feed = np.argmax(np.asarray(logits)[:n], axis=-1)
                feed = feed.astype(np.int32)
                out[:, step] = feed
        self._lengths[list(slots)] = lens_live
        return out

    def observe(self, slot: int, emitted) -> None:
        """The draft feeds at the target's lengths, so acceptance needs
        no rollback bookkeeping here."""

    def release(self, slot: int) -> None:
        self._lengths[slot] = 0


class GenerationResult:
    """Terminal value of a finished generation.

    ``tokens``: int32 array of generated tokens (includes the EOS token
    when ``reason == "eos"``). ``reason``: ``"eos"`` | ``"length"``
    (hit ``max_new_tokens``) | ``"max_len"`` (context window full).
    """

    __slots__ = ("tokens", "reason")

    def __init__(self, tokens: np.ndarray, reason: str):
        self.tokens = tokens
        self.reason = reason

    def __repr__(self) -> str:
        return (f"GenerationResult(tokens={self.tokens.tolist()}, "
                f"reason={self.reason!r})")


class _GenRequest:
    __slots__ = ("prompt", "max_new_tokens", "eos_id", "stream", "future",
                 "t_submit", "deadline", "generated", "last_token",
                 "last_logits", "trace", "t_perf", "prefill_pos", "rng",
                 "decode_t0", "decode_end", "decode_steps", "decode_step_s")

    def __init__(self, prompt, max_new_tokens, eos_id, stream,
                 t_submit, deadline, trace=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.stream = stream
        self.future: Future = Future()
        self.t_submit = t_submit
        self.deadline = deadline
        self.generated: list = []
        self.last_token: int = 0
        #: chunked-prefill cursor: prompt positions [0, prefill_pos) are
        #: cached; the slot joins the decode set only at prompt.size
        self.prefill_pos: int = 0
        #: per-request sampled-decode stream (``sampling=True`` only):
        #: seeded from (engine seed, submission index), consumed one
        #: uniform per EMITTED token — the coupling that makes sampled
        #: speculative output stream-identical to plain sampling
        self.rng = None
        #: logits row that produced the newest token (kept only when a
        #: prefix cache is attached — retirement parks them so a resumed
        #: conversation's full hit can emit with zero forwards)
        self.last_logits = None
        #: TraceContext this request's spans chain under (None = untraced);
        #: t_perf is the submit instant on the span time base
        #: (perf_counter — t_submit stays monotonic for deadline math)
        self.trace = trace
        self.t_perf = time.perf_counter()
        #: what the request's one ``trace.decode`` row needs: the start of
        #: its first decode step, the end of its newest, how many it rode
        #: and the sum of their intervals
        self.decode_t0 = 0.0
        self.decode_end = 0.0
        self.decode_steps = 0
        self.decode_step_s = 0.0

    def rode_step(self, tp0: float, dt: float) -> None:
        """Note one decode step ``[tp0, tp0 + dt]`` this request shared:
        attribute writes only, the lane loop calls it once a lane."""
        if not self.decode_steps:
            self.decode_t0 = tp0
        self.decode_end = tp0 + dt
        self.decode_steps += 1
        self.decode_step_s += dt


class GenerationEngine:
    """Iteration-level continuous-batching decode loop over a slot pool.

    ``generate()`` is thread-safe and returns a Future of
    :class:`GenerationResult`; an optional ``stream`` callback receives
    each token as it is emitted (called on the scheduler thread — it
    must not block, or every in-flight sequence stalls).

    One scheduler thread owns the pool, the compiled executables, and
    all host-side accounting; every loop iteration admits queued
    requests into free slots (prefill), advances all active lanes one
    token (decode), and retires finished sequences.

    **One iteration** (DESIGN.md §14 "What a client sees, and when"):
    bookkeeping, dispatch, delivery, wait, copy. Everything the next
    launch needs (who retires, each lane's last token and length, which
    slots are free) is settled in pure Python as soon as a step's tokens
    are on the host; what a client or a handler thread can see (a stream
    callback, a ``trace.*`` row, a future's result or error) is only
    noted in ``self._owed``, and :meth:`_deliver` walks that queue right
    after the next executable's call returns, so the threads it wakes
    take the interpreter lock while the device works and never between
    "ready to launch" and "launched". A request's tokens still arrive in
    order and ahead of its result. Nothing stays owed: an iteration that
    ends with no lane left flushes before the scheduler waits or returns,
    and expiry, a scheduler error and a non-draining shutdown flush
    before they fail a request.

    **Admission spacing.** A whole-prompt prefill stalls every decoding
    lane for as long as it runs, and by default every free slot is filled
    as soon as a request waits: a burst of arrivals (or lanes admitted
    together retiring together) is then a run of prefills back to back,
    during which no lane emits. A request holds one of ``num_slots`` lanes
    for ``max_new_tokens`` steps, so in a full, steady pool one retires
    every ``max_new_tokens / num_slots`` steps: its share of the pool's
    steps. ``admit_spacing=c`` keeps ``c`` such shares of the request just
    admitted between its admission and the next while any lane decodes (an
    idle engine admits at once): a lane waits out one prefill in that many
    steps, never a run of them, and a burst is served as a steady stream
    of about ``num_slots / c`` lanes whatever the answers' lengths, since
    a long answer buys the steps it will hold its lane for. ``c`` a little
    over 1 leaves the few lanes empty that make admissions regular.
    """

    def __init__(self, model, params, *, num_slots: int = 4,
                 slot_ladder: Optional[Sequence[int]] = None,
                 prefill_buckets: Sequence[int] = (8, 32),
                 queue_capacity: int = 64,
                 default_max_new_tokens: int = 32,
                 eos_id: Optional[int] = None,
                 device=None, dtype=None, hbm_fraction: float = 0.8,
                 warmup: bool = True,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache_bytes: int = 0,
                 draft=None, spec_k: int = 0,
                 prefill_chunk: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 sampling: bool = False, temperature: float = 1.0,
                 seed: int = 0, admit_spacing: float = 0.0):
        import jax

        from distkeras_tpu.utils.jax_compat import enable_compilation_cache

        enable_compilation_cache()  # _compile_all() compiles every bucket
        self.model = model
        self.max_len = int(model.max_len)
        self._buckets = BucketSpec(prefill_buckets)
        if self._buckets.sizes[0] < 2:
            # Lb=1 would put the prefill Dense on the M=1 gemv path the
            # ghost position exists to avoid (module docstring)
            raise ValueError(
                f"prefill buckets must be >= 2, got {self._buckets.sizes}")
        if self._buckets.max_size > self.max_len:
            raise ValueError(
                f"largest prefill bucket {self._buckets.max_size} exceeds "
                f"model max_len {self.max_len}")
        self._ladder = BucketSpec(
            _default_ladder(num_slots) if slot_ladder is None
            else slot_ladder)
        if self._ladder.max_size != num_slots:
            raise ValueError(
                f"slot ladder {self._ladder.sizes} must top out at "
                f"num_slots={num_slots} so every in-flight count has a "
                f"compiled lane width")
        self._paged = page_size is not None
        asked = [name for name, on in (
            ("page_size", self._paged),
            ("prefix_cache_bytes", prefix_cache_bytes),
            ("draft", draft is not None), ("spec_k", spec_k),
            ("prefill_chunk", prefill_chunk is not None)) if on]
        if state_leaves(model) and asked:
            raise ValueError(
                f"{type(model).__name__} keeps a state a row (cache leaf "
                f"{state_leaves(model)[0]!r}: no position axis, so no "
                f"length mask hides what a step writes there); it is "
                f"served from the rectangular pool, whole prompts, one "
                f"token a step: no {', '.join(asked)}")
        if select_leaves(model) and asked:
            raise ValueError(
                f"{type(model).__name__} attends a selection of its cache "
                f"(cache leaf {select_leaves(model)[0]!r} is read before "
                f"any mask); it is served from the rectangular pool, whole "
                f"prompts, one token a step: no {', '.join(asked)}")
        if prefix_cache_bytes and not self._paged:
            raise ValueError(
                "prefix_cache_bytes requires page_size: the prefix cache "
                "parks/restores KV at page granularity")
        if (draft is None) != (spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH draft= and spec_k >= 1")
        if spec_k < 0 or spec_k >= self.max_len - 1:
            raise ValueError(f"spec_k must be in [0, max_len-1), got "
                             f"{spec_k}")
        self._draft = draft
        self._spec_k = int(spec_k)
        self._chunk = None if prefill_chunk is None else int(prefill_chunk)
        if self._chunk is not None:
            if not self._paged:
                raise ValueError(
                    "prefill_chunk requires page_size: chunked prefill "
                    "rides the paged step family's mid-sequence prefill")
            if self._chunk < 2:
                # a 1-token chunk would put the chunk call on the M=1
                # gemv path the ghost position exists to avoid (module
                # docstring)
                raise ValueError(
                    f"prefill_chunk must be >= 2, got {prefill_chunk}")
            if self._chunk > self.max_len:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} exceeds model "
                    f"max_len {self.max_len}")
        if kv_dtype is not None and not self._paged:
            raise ValueError(
                "kv_dtype requires page_size: quantized KV is a "
                "page-pool format")
        self._sampling = bool(sampling)
        self._temperature = float(temperature)
        if self._sampling and self._temperature <= 0:
            raise ValueError(
                f"temperature must be > 0, got {temperature}")
        self._seed = int(seed)
        if admit_spacing < 0:
            raise ValueError(
                f"admit_spacing must be >= 0, got {admit_spacing}")
        # decode steps between two admissions while a lane decodes, in
        # shares of the admitted request (class docstring, "Admission
        # spacing"); 0: every free slot at once
        self._admit_spacing = float(admit_spacing)
        self._admit_wait = 0.0          # steps the last admission bought
        self._steps_since_admit = 0
        self._req_seq = 0  # submission index: per-request stream ids
        # a greedy engine that parks no logits wants an index from its
        # decode step: the token is chosen inside it (pick_on_device)
        self._device_pick = not self._sampling and not prefix_cache_bytes
        if self._paged:
            self.pool = PagedKVCachePool(
                model, num_slots, page_size=page_size, num_pages=num_pages,
                device=device, dtype=dtype, kv_dtype=kv_dtype,
                hbm_fraction=hbm_fraction)
        else:
            self.pool = KVCachePool(model, num_slots, device=device,
                                    dtype=dtype, hbm_fraction=hbm_fraction)
        self._pool_dtype = dtype
        self._prefix = (PrefixCache(prefix_cache_bytes)
                        if prefix_cache_bytes else None)
        if device is not None:
            params = jax.device_put(params, device)
        self._device = device
        self._params = params
        # live-rollout state (serving/rollout.py, DESIGN.md §18): the
        # scheduler thread owns installation; in-flight sequences finish
        # on the version they started (pinned per slot at prefill), so
        # several versions can be live at once until their slots retire
        self.model_version = 0
        self.last_swap_time: Optional[float] = None
        self._versions = {0: params}       # version -> params (pinnable)
        self._slot_version: dict = {}      # slot -> version pinned at prefill
        self._pending_swap = None          # (version, params, Event, errbox)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.eos_id = eos_id
        self.queue_capacity = int(queue_capacity)
        self._dq: "collections.deque[_GenRequest]" = collections.deque()
        # cross-host prefix traffic (serving/fleet.py KV handoff,
        # DESIGN.md §22): import/export requests from server handler
        # threads, applied by the scheduler thread between iterations so
        # the prefix cache keeps its single-owner (no-lock) contract
        self._host_ops: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._closed = False
        self._drain = True
        # what the scheduler owes its clients, in order: (phase, call,
        # arguments), handed over by _deliver once the next dispatch is
        # with the device (class docstring, "One iteration")
        self._owed: collections.deque = collections.deque()

        self._admitted_c = telemetry.counter("serving.decode.admitted")
        self._rejected_c = telemetry.counter("serving.decode.rejected")
        self._expired_c = telemetry.counter("serving.decode.deadline_exceeded")
        self._prefills_c = telemetry.counter("serving.decode.prefills")
        # real prompt tokens, and the bucket or chunk positions computed
        # for them (what padding costs: a padded position is as dear as a
        # real one to a layer that scans)
        self._prefill_tokens_c = telemetry.counter("serving.prefill.tokens")
        self._prefill_positions_c = telemetry.counter(
            "serving.prefill.positions")
        self._steps_c = telemetry.counter("serving.decode.steps")
        self._tokens_c = telemetry.counter("serving.decode.tokens")
        self._trace_rows_c = telemetry.counter("serving.decode.trace_rows")
        self._device_picks_c = telemetry.counter(
            "serving.decode.device_picks")
        # K/V positions a decode step's attention reads, beside the rows'
        # whole length: the model says by what block its step bounds the
        # read (0: it reads whole rows, as every paged step does)
        self._kv_read_c = telemetry.counter(
            "serving.decode.kv_positions_read")
        self._kv_row_c = telemetry.counter("serving.decode.kv_positions_row")
        self._step_tokens = decode_block_tokens(model)
        bounded = getattr(model, "decode_read_block", None)
        self._kv_read_block = 0 if self._paged or bounded is None else \
            bounded(self._step_tokens, dtype)
        self._stream_err_c = telemetry.counter("serving.decode.stream_errors")
        self._loop_err_c = telemetry.counter("serving.decode.loop_errors")
        self._prefill_h = telemetry.histogram("serving.decode.prefill_s")
        self._step_h = telemetry.histogram("serving.decode.step_s")
        self._ttft_h = telemetry.histogram("serving.decode.ttft_s")
        self._padded_h = telemetry.histogram("serving.decode.padded_lanes")
        self._tps_g = telemetry.gauge("serving.decode.tokens_per_s")
        self._active_g = telemetry.gauge("serving.decode.slots_active")
        self._depth_g = telemetry.gauge("serving.decode.queue_depth")
        # the scheduler iteration partitioned on the host clock; each
        # phase is also a profiler annotation (module docstring of
        # telemetry.PhaseTimer, DESIGN.md §5b)
        self._sched = telemetry.PhaseTimer(
            "serving.sched.",
            ("control", "admit", "prefill_wait", "launch", "wait", "copy",
             "pick", "stream", "retire"), whole="iter")
        self._delivered_c = telemetry.counter("serving.sched.delivered")
        self._delivered_after_c = telemetry.counter(
            "serving.sched.delivered_after_dispatch")
        self._spec_proposed_c = telemetry.counter(
            "serving.decode.spec.proposed")
        self._spec_accepted_c = telemetry.counter(
            "serving.decode.spec.accepted")
        self._spec_iters_c = telemetry.counter(
            "serving.decode.spec.iterations")
        self._spec_rate_g = telemetry.gauge("serving.decode.spec.accept_rate")
        self._swapped_in_c = telemetry.counter(
            "serving.decode.paged.swapped_in")
        self._swapped_out_c = telemetry.counter(
            "serving.decode.paged.swapped_out")
        self._swap_fail_c = telemetry.counter(
            "serving.decode.paged.swap_in_failures")
        self._prefix_full_c = telemetry.counter(
            "serving.decode.prefix.full_hits")
        self._prefix_imports_c = telemetry.counter(
            "serving.decode.prefix.imports")
        self._prefix_exports_c = telemetry.counter(
            "serving.decode.prefix.exports")
        if self._chunk is not None:
            # created only when chunking is on so the health CLI's
            # DECODE line gains the field exactly when it means something
            self._chunk_admits_c = telemetry.counter(
                "serving.decode.chunk.admitted")
            self._chunk_steps_c = telemetry.counter(
                "serving.decode.chunk.steps")
            self._chunk_depth_g = telemetry.gauge(
                "serving.decode.chunk.queue_depth")
            self._chunk_depth_g.set(0)
        if hasattr(model, "experts_per_token"):
            # a model with routed experts: its decode step hands back
            # tokens per held expert beside the logits (make_decode_fn)
            self._moe_assign_c = telemetry.counter("serving.moe.assignments")
            self._moe_held_c = telemetry.counter(
                "serving.moe.assignments_held")
            self._moe_active_h = telemetry.histogram(
                "serving.moe.experts_active")
            self._moe_load_h = telemetry.histogram(
                "serving.moe.load_max_over_mean")
        if select_leaves(model):
            # its decode step hands back the positions attended
            # (make_decode_fn's fourth value)
            self._sparse_cached_c = telemetry.counter(
                "serving.sparse.positions_cached")
            self._sparse_attended_c = telemetry.counter(
                "serving.sparse.positions_attended")
        if self._sampling and self._spec_k:
            self._spec_s_accepts_c = telemetry.counter(
                "serving.decode.spec.sampled_accepts")
            self._spec_s_resamples_c = telemetry.counter(
                "serving.decode.spec.sampled_resamples")

        self._compile_all()
        if self._draft is not None:
            self._draft.bind(self)
        if warmup:
            self._warmup()
        self._thread = threading.Thread(target=self._scheduler_loop,
                                        name="generation-scheduler",
                                        daemon=True)
        self._thread.start()

    # -- AOT compilation ---------------------------------------------------

    def _compile_all(self) -> None:
        """Compile exactly one executable per prefill bucket, one per
        slot-ladder entry, one verify per ladder entry (speculative
        only), and the fixed-shape page swap pair (prefix cache only),
        up front. Nothing compiles after __init__ — the cache cannot
        grow under traffic (asserted by test)."""
        import jax

        sds = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        p_sds, pool_sds = sds(self._params), sds(self.pool.pool)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)
        self._prefill_exec = {}
        self._decode_exec = {}
        self._verify_exec = {}
        self._chunk_exec = None
        self._swap_out_exec = None
        self._swap_in_exec = None
        pick = pick_on_device if self._device_pick else (lambda fn: fn)
        if self._paged:
            step = make_paged_step_fn(self.model)
            pmax = self.pool.pages_per_slot
            for lb in self._buckets:
                self._prefill_exec[lb] = _compile(
                    step, (1,), (p_sds, pool_sds, i32(1, pmax), i32(1, lb),
                                 i32(1)), prefill=lb)
            if self._chunk is not None:
                if self._chunk in self._prefill_exec:
                    # a chunk the width of a prefill bucket is the SAME
                    # compiled shape — share the executable (both calls
                    # donate the pool; the executable is stateless)
                    self._chunk_exec = self._prefill_exec[self._chunk]
                else:
                    self._chunk_exec = _compile(
                        step, (1,), (p_sds, pool_sds, i32(1, pmax),
                                     i32(1, self._chunk), i32(1)),
                        prefill_chunk=self._chunk)
            for n in self._ladder:
                self._decode_exec[n] = _compile(
                    pick(step), (1,), (p_sds, pool_sds, i32(n, pmax),
                                       i32(n, 2), i32(n)), lanes=n)
                if self._spec_k:
                    self._verify_exec[n] = _compile(
                        step, (1,), (p_sds, pool_sds, i32(n, pmax),
                                     i32(n, self._spec_k + 1), i32(n)),
                        verify=n)
            if self._prefix is not None:
                data_sds = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        (pmax,) + a.shape[1:], a.dtype), pool_sds)
                self._swap_out_exec = _compile(
                    make_swap_out_fn(), (), (pool_sds, i32(pmax)),
                    swap="out")
                self._swap_in_exec = _compile(
                    make_swap_in_fn(), (0,), (pool_sds, i32(pmax), data_sds),
                    swap="in")
            return
        prefill = make_prefill_fn(self.model, self._pool_dtype)
        decode = pick(make_decode_fn(self.model))
        for lb in self._buckets:
            self._prefill_exec[lb] = _compile(
                prefill, (1,), (p_sds, pool_sds, i32(1, lb), i32(), i32()),
                prefill=lb)
        for n in self._ladder:
            self._decode_exec[n] = _compile(
                decode, (1,), (p_sds, pool_sds, i32(n), i32(n), i32(n)),
                lanes=n)
            if self._spec_k:
                self._verify_exec[n] = _compile(
                    make_verify_fn(self.model), (1,),
                    (p_sds, pool_sds, i32(n), i32(n, self._spec_k + 1),
                     i32(n)), verify=n)

    def _warmup(self) -> None:
        """Run every executable once against the scratch slot/page so no
        request pays first-execution costs. Scratch garbage is fine:
        reads are masked by per-slot lengths."""
        with telemetry.span("serving.decode.warmup"):
            scratch = np.int32(self.pool.scratch_slot)
            if self._paged:
                pmax = self.pool.pages_per_slot
                spt = self.pool.page_tables[self.pool.scratch_slot]
                for lb, ex in self._prefill_exec.items():
                    new_pool, _ = ex(self._params, self.pool.pool,
                                     spt[None, :],
                                     np.zeros((1, lb), np.int32),
                                     np.zeros(1, np.int32))
                    self.pool.swap(new_pool)
                for n, ex in self._decode_exec.items():
                    pts = np.tile(spt, (n, 1))
                    zeros = np.zeros(n, np.int32)
                    new_pool, _ = ex(self._params, self.pool.pool, pts,
                                     np.zeros((n, 2), np.int32), zeros)
                    self.pool.swap(new_pool)
                for n, ex in self._verify_exec.items():
                    pts = np.tile(spt, (n, 1))
                    zeros = np.zeros(n, np.int32)
                    new_pool, _ = ex(
                        self._params, self.pool.pool, pts,
                        np.zeros((n, self._spec_k + 1), np.int32), zeros)
                    self.pool.swap(new_pool)
                if (self._chunk_exec is not None
                        and self._chunk not in self._prefill_exec):
                    new_pool, _ = self._chunk_exec(
                        self._params, self.pool.pool, spt[None, :],
                        np.zeros((1, self._chunk), np.int32),
                        np.zeros(1, np.int32))
                    self.pool.swap(new_pool)
                if self._swap_out_exec is not None:
                    ids = np.full(pmax, self.pool.scratch_page, np.int32)
                    data = self._swap_out_exec(self.pool.pool, ids)
                    new_pool = self._swap_in_exec(self.pool.pool, ids,
                                                  data)
                    self.pool.swap(new_pool)
                return
            for lb, ex in self._prefill_exec.items():
                new_pool, _ = ex(self._params, self.pool.pool,
                                 np.zeros((1, lb), np.int32), scratch,
                                 np.int32(lb))
                self.pool.swap(new_pool)
            for n, ex in self._decode_exec.items():
                lanes = np.full(n, scratch, np.int32)
                zeros = np.zeros(n, np.int32)
                new_pool, *_ = ex(self._params, self.pool.pool, lanes,
                                  zeros, zeros)
                self.pool.swap(new_pool)
            for n, ex in self._verify_exec.items():
                lanes = np.full(n, scratch, np.int32)
                zeros = np.zeros(n, np.int32)
                new_pool, _ = ex(self._params, self.pool.pool, lanes,
                                 np.zeros((n, self._spec_k + 1), np.int32),
                                 zeros)
                self.pool.swap(new_pool)

    @property
    def compiled_executables(self):
        """{"prefill": bucket sizes, "decode": lane widths} actually
        compiled — tests assert this equals the declared ladders and
        never grows. Optional features add their own (equally fixed)
        keys: "prefill_chunk" under chunked prefill, "verify" lane
        widths under speculative decoding, "swap" under the prefix
        cache, "draft_prefill"/"draft_decode" with a
        :class:`ModelDraft` attached."""
        execs = {"prefill": tuple(sorted(self._prefill_exec)),
                 "decode": tuple(sorted(self._decode_exec))}
        if self._chunk_exec is not None:
            execs["prefill_chunk"] = (self._chunk,)
        if self._verify_exec:
            execs["verify"] = tuple(sorted(self._verify_exec))
        if self._swap_in_exec is not None:
            execs["swap"] = ("in", "out")
        if self._draft is not None and hasattr(self._draft,
                                               "compiled_executables"):
            de = self._draft.compiled_executables
            execs["draft_prefill"] = de["prefill"]
            execs["draft_decode"] = de["decode"]
        return execs

    # -- live weight rollout (serving/rollout.py, DESIGN.md §18) -----------

    def swap_weights(self, params, version: int,
                     timeout: float = 60.0) -> None:
        """Hand ``params`` to the scheduler thread as ``version`` and
        block until installed. Validation runs on the caller's thread —
        a torn tree raises ValueError with engine state untouched. The
        scheduler applies the swap between iterations: requests prefilled
        before it keep decoding on their pinned version (retire before
        reclaim); requests admitted after it prefill on the new one. The
        executables are shared across versions — the compile cache cannot
        grow from a swap."""
        import jax

        from distkeras_tpu.serving.rollout import validate_tree_like

        t0 = time.perf_counter()
        try:
            validate_tree_like(params, self._params)
        except ValueError:
            telemetry.counter("rollout.torn_swaps_blocked",
                              engine="generation").inc()
            raise
        if self._device is not None:
            params = jax.device_put(params, self._device)
        jax.block_until_ready(params)
        done = threading.Event()
        errbox: list = []
        with self._cv:
            if self._closed:
                raise EngineClosed("engine is shut down; no weight swaps")
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already pending")
            self._pending_swap = (int(version), params, done, errbox)
            self._cv.notify_all()
        if not done.wait(timeout):
            raise TimeoutError(f"weight swap to version {version} not "
                               f"applied within {timeout}s")
        if errbox:
            raise errbox[0]
        dt = time.perf_counter() - t0
        telemetry.counter("rollout.swaps", engine="generation").inc()
        telemetry.histogram("rollout.swap_s", engine="generation").record(dt)
        telemetry.record_event("rollout", action="swap",
                               engine="generation", version=int(version),
                               seconds=dt)

    def _apply_pending_swap(self) -> None:
        """Scheduler-thread half of :meth:`swap_weights`: install the
        pending version as current between iterations. In-flight slots
        keep their pinned entry in ``_versions`` until they retire."""
        with self._cv:
            pending = self._pending_swap
            self._pending_swap = None
        if pending is None:
            return
        version, params, done, _errbox = pending
        self._params = params
        self._versions[version] = params
        self.model_version = version
        self.last_swap_time = time.time()
        telemetry.gauge("rollout.model_version",
                        engine="generation").set(version)
        telemetry.gauge("rollout.last_swap_time",
                        engine="generation").set(self.last_swap_time)
        from distkeras_tpu.health import recorder as flight_recorder

        flight_recorder.configure(decode_model_version=int(version))
        self._reclaim_versions()
        done.set()

    def _fail_pending_swap(self, err: Exception) -> None:
        """Unblock a swapper whose swap can no longer be applied
        (scheduler crash or shutdown) with ``err`` instead of a hang."""
        with self._cv:
            pending = self._pending_swap
            self._pending_swap = None
        if pending is not None:
            _version, _params, done, errbox = pending
            errbox.append(err)
            done.set()

    def _reclaim_versions(self) -> None:
        """Retire-before-reclaim: drop params of versions no in-flight
        slot pins and that are not current. Buffers release only after
        the last sequence that started on them finished."""
        pinned = set(self._slot_version.values())
        pinned.add(self.model_version)
        for stale in [v for v in self._versions if v not in pinned]:
            del self._versions[stale]
            telemetry.counter("rollout.versions_retired").inc()
            telemetry.record_event("rollout", action="version_retired",
                                   engine="generation", version=stale)

    # -- cross-host prefix handoff (serving/fleet.py, DESIGN.md §22) -------

    def _host_op(self, kind: str, payload, timeout: float):
        """Hand one prefix-cache operation to the scheduler thread and
        block for its result — server handler threads must never touch
        ``self._prefix`` directly (single-owner contract)."""
        done = threading.Event()
        box: list = []
        with self._cv:
            if self._closed:
                return None if kind == "export" else False
            self._host_ops.append((kind, payload, done, box))
            self._cv.notify_all()
        if not done.wait(timeout):
            return None if kind == "export" else False
        return box[0]

    def export_prefix(self, tokens, timeout: float = 10.0):
        """Host copy of the parked KV for exactly ``tokens`` — the
        prefill half of a fleet KV handoff. Returns ``(data, last_logits)``
        (``data`` is the host page pytree ``swap_out`` captured, sliced to
        the prefix's pages; ``last_logits`` may be None) or None when the
        prefix cache holds no such entry (or the engine has no cache)."""
        tokens = tuple(int(t) for t in np.asarray(tokens).reshape(-1))
        return self._host_op("export", tokens, timeout)

    def import_prefix(self, tokens, leaves, last_logits=None,
                      timeout: float = 10.0) -> bool:
        """Install a shipped prefix into this engine's cache — the decode
        half of a fleet KV handoff. ``leaves`` is the flat leaf list of an
        :meth:`export_prefix` page pytree (the engine rebuilds the tree
        against its OWN pool structure; a shape/dtype/leaf-count mismatch
        is refused, never half-installed). Returns True when the entry is
        resident; False means the caller must cold-prefill."""
        tokens = tuple(int(t) for t in np.asarray(tokens).reshape(-1))
        if last_logits is not None:
            last_logits = np.asarray(last_logits)
        return bool(self._host_op("import", (tokens, list(leaves),
                                             last_logits), timeout))

    def _apply_host_ops(self) -> None:
        """Scheduler-thread half of import/export_prefix."""
        import jax

        while True:
            with self._cv:
                if not self._host_ops:
                    return
                kind, payload, done, box = self._host_ops.popleft()
            try:
                if self._prefix is None:
                    box.append(None if kind == "export" else False)
                elif kind == "export":
                    entry = self._prefix.peek(payload)
                    if entry is None:
                        box.append(None)
                    else:
                        self._prefix_exports_c.inc()
                        box.append((entry.data, entry.last_logits))
                else:
                    tokens, leaves, last_logits = payload
                    treedef = jax.tree.structure(self.pool.pool)
                    pool_leaves = jax.tree.leaves(self.pool.pool)
                    ok = len(leaves) == len(pool_leaves) and all(
                        l.shape[1:] == p.shape[1:] and l.dtype == p.dtype
                        for l, p in zip(leaves, pool_leaves))
                    if ok:
                        data = jax.tree.unflatten(treedef, leaves)
                        self._prefix.insert(tokens, data, last_logits)
                        ok = self._prefix.has(tokens)
                        if ok:
                            self._prefix_imports_c.inc()
                    box.append(bool(ok))
            except Exception:  # a bad handoff must not kill the loop
                self._swap_fail_c.inc()
                box.append(None if kind == "export" else False)
            finally:
                done.set()

    def _fail_host_ops(self) -> None:
        """Unblock waiters whose op can no longer run (crash/shutdown)."""
        with self._cv:
            pending = list(self._host_ops)
            self._host_ops.clear()
        for kind, _payload, done, box in pending:
            box.append(None if kind == "export" else False)
            done.set()

    # -- client API --------------------------------------------------------

    def generate(self, prompt, *, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 stream=None, trace=None) -> Future:
        """Queue one prompt; returns a Future of :class:`GenerationResult`.

        Raises :class:`QueueFull` when the admission queue is at
        capacity (slot exhaustion surfaces HERE, as backpressure, never
        as a device OOM) and :class:`EngineClosed` after shutdown.

        ``trace``: a :class:`~distkeras_tpu.telemetry.TraceContext` the
        request's spans (queue-wait, prefill, its decode steps as one
        span, the request total) chain under; defaults to the submitting
        thread's current trace (DESIGN.md §15). The scheduler thread
        records the spans with this explicit context — it serves many
        requests per iteration, so no single thread-local trace can be
        "current" there.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.size > self._buckets.max_size:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest prefill "
                f"bucket {self._buckets.max_size}")
        mnt = (self.default_max_new_tokens if max_new_tokens is None
               else int(max_new_tokens))
        if mnt < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {mnt}")
        if prompt.size + mnt > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({mnt}) exceeds "
                f"max_len {self.max_len}")
        now = time.monotonic()
        deadline = None if timeout_ms is None else now + timeout_ms / 1e3
        req = _GenRequest(prompt, mnt,
                          self.eos_id if eos_id is None else eos_id,
                          stream, now, deadline,
                          trace=telemetry.current_trace()
                          if trace is None else trace)
        with self._cv:
            if self._closed:
                raise EngineClosed("engine is shut down; no new requests")
            if len(self._dq) >= self.queue_capacity:
                self._rejected_c.inc()
                telemetry.record_event("serving", outcome="rejected",
                                       depth=len(self._dq),
                                       capacity=self.queue_capacity)
                raise QueueFull(
                    f"generation queue at {len(self._dq)}/"
                    f"{self.queue_capacity}")
            if self._sampling:
                # stream id = (engine seed, submission index): two
                # engines fed the same requests in the same order draw
                # identical streams — the sampled-spec identity oracle
                req.rng = np.random.default_rng([self._seed,
                                                 self._req_seq])
                self._req_seq += 1
            self._dq.append(req)
            self._depth_g.set(len(self._dq))
            self._cv.notify()
        return req.future

    # -- scheduler ---------------------------------------------------------

    def _scheduler_loop(self) -> None:
        active = {}      # slot -> _GenRequest (decoding)
        prefilling = {}  # slot -> _GenRequest (chunked prefill cursor)
        sched = self._sched
        try:
            while True:
                with self._cv:
                    while not self._dq and not active and not prefilling \
                            and not self._closed \
                            and self._pending_swap is None \
                            and not self._host_ops:
                        self._cv.wait()
                    if self._closed and not self._drain:
                        pending = list(self._dq)
                        self._dq.clear()
                        self._depth_g.set(0)
                        break
                    if self._closed and not self._dq and not active \
                            and not prefilling:
                        self._fail_pending_swap(EngineClosed(
                            "engine is shut down; no weight swaps"))
                        self._fail_host_ops()
                        return
                    # an iteration with a lane to serve; a wake-up for a
                    # weight swap or a host op alone is not one
                    busy = bool(self._dq or active or prefilling)
                sched.start()
                with sched.phase("control"):
                    self._apply_pending_swap()
                    self._apply_host_ops()
                with sched.phase("admit"):
                    self._admit(active, prefilling)
                with sched.phase("control"):
                    self._expire(active, prefilling)
                if prefilling:
                    with sched.phase("admit"):
                        self._chunk_step(active, prefilling)
                if active:
                    self._decode_step(active)
                    self._steps_since_admit += 1
                if not active and not prefilling:
                    # no lane left, so no dispatch is sure to come:
                    # nothing stays owed over a wait or a return
                    self._deliver(dispatched=False)
                if busy:
                    sched.commit()
        except BaseException as e:  # scheduler must never die silently
            self._loop_err_c.inc()
            telemetry.record_event("serving", outcome="loop_error",
                                   error=type(e).__name__,
                                   message=str(e)[:200])
            with self._cv:
                self._closed = True
                pending = list(self._dq)
                self._dq.clear()
                self._depth_g.set(0)
            err = EngineClosed(f"generation scheduler failed: {e!r}")
            self._fail_pending_swap(err)
            self._fail_host_ops()
            self._deliver(dispatched=False)  # then fail the rest
            for req in (pending + list(active.values())
                        + list(prefilling.values())):
                req.future.set_exception(err)
            for slot in list(active) + list(prefilling):
                self.pool.free(slot)
            self._slot_version.clear()
            raise
        # non-draining shutdown: fail everything still in flight
        err = EngineClosed("engine shut down without draining")
        self._fail_pending_swap(err)
        self._fail_host_ops()
        self._deliver(dispatched=False)  # then fail the rest
        for req in (pending + list(active.values())
                    + list(prefilling.values())):
            req.future.set_exception(err)
        for slot in list(active) + list(prefilling):
            self.pool.free(slot)
        self._slot_version.clear()
        self._active_g.set(0)

    def _admit(self, active, prefilling=None) -> None:
        """Move queued requests into free slots (prefill each). Runs
        every iteration — admission interleaves with in-flight decode.
        Under chunked prefill a request parks in ``prefilling`` with a
        cursor instead of paying its whole prefill here. Under
        ``admit_spacing`` a request waits, queued, until the lanes have
        decoded the steps that the last admission bought."""
        while self.pool.num_free > 0:
            if active and self._steps_since_admit < self._admit_wait:
                return
            with self._cv:
                if not self._dq:
                    return
                req = self._dq.popleft()
                self._depth_g.set(len(self._dq))
            now = time.monotonic()
            if req.deadline is not None and now > req.deadline:
                self._expired_c.inc()
                self._owed.append(("retire", req.future.set_exception, (
                    DeadlineExceeded(
                        f"deadline passed {1e3 * (now - req.deadline):.1f} "
                        f"ms before admission"),)))
                continue
            self._owe_row(req, "trace.queue_wait", req.t_perf,
                          time.perf_counter() - req.t_perf)
            slot = self.pool.allocate()
            if self._paged and not self.pool.reserve(
                    slot, min(req.prompt.size + req.max_new_tokens,
                              self.max_len)):
                # page exhaustion: the paged pool's backpressure. Leave
                # the request at the queue head — retiring sequences
                # return pages and the next iteration retries.
                self.pool.free(slot)
                with self._cv:
                    self._dq.appendleft(req)
                    self._depth_g.set(len(self._dq))
                return
            self._steps_since_admit = 0
            self._admit_wait = self._admit_spacing * req.max_new_tokens \
                / self.pool.num_slots
            if self._chunk is not None:
                parked = self._start_chunked(req, slot, prefilling)
                self._admitted_c.inc()
                if parked:
                    self._chunk_admits_c.inc()
                    self._chunk_depth_g.set(len(prefilling))
                    continue
                # a full prefix hit needs no chunk work: it completed
                # through the normal zero-forward path above
                if self._emit(req, slot) is None:
                    active[slot] = req
                self._active_g.set(len(active))
                continue
            if self._paged:
                self._prefill_paged(req, slot)
            else:
                self._prefill(req, slot)
            self._admitted_c.inc()
            if self._emit(req, slot) is None:
                active[slot] = req
            self._active_g.set(len(active))

    def _prefill(self, req: _GenRequest, slot: int) -> None:
        n = req.prompt.size
        lb = self._buckets.bucket_for(n)
        ids = np.zeros((1, lb), np.int32)
        ids[0, :n] = req.prompt
        t0 = time.monotonic()
        tp0 = time.perf_counter()
        with self._sched.phase("prefill_wait"):
            new_pool, logits = self._prefill_exec[lb](
                self._params, self.pool.pool, ids, np.int32(slot),
                np.int32(n))
            # pin the version this sequence started on: every later decode
            # step for this slot runs on the SAME params even if a swap
            # lands mid-generation (in-flight requests provably finish on
            # it)
            self._slot_version[slot] = self.model_version
            self.pool.swap(new_pool)
            self.pool.lengths[slot] = n
            self._deliver(dispatched=True)
            logits = np.asarray(logits)
        tok = self._pick_token(req, logits)
        now = time.monotonic()
        self._prefills_c.inc()
        self._prefill_tokens_c.inc(n)
        self._prefill_positions_c.inc(lb)
        self._prefill_h.record(now - t0)
        self._ttft_h.record(now - req.t_submit)
        self._owe_row(req, "trace.prefill", tp0,
                      time.perf_counter() - tp0, bucket=lb, slot=slot,
                      model_version=self.model_version)
        req.generated.append(tok)
        req.last_token = tok
        if self._draft is not None:
            self._draft.begin(slot, req.prompt, tok)
        self._owe_token(req, tok)

    def _prefix_start(self, req: _GenRequest, slot: int):
        """Prefix-cache half of paged admission: lookup + page swap-in.
        Returns ``(start, logits_row, hit)``: cached positions
        ``[0, start)`` are resident in ``slot``; ``logits_row`` is
        non-None on a full hit with parked logits (the caller emits
        with ZERO forward calls); ``hit`` reports whether any cached
        prefix was restored (the trace span's ``prefix_hit``)."""
        n = req.prompt.size
        entry = (self._prefix.lookup(req.prompt)
                 if self._prefix is not None else None)
        start = 0
        if entry is not None and self._swap_in_entry(slot, entry):
            start = entry.length
        else:
            entry = None
        if entry is not None and start == n:
            if entry.last_logits is not None:
                # full hit: the parked logits ARE the first-token
                # distribution — no device math at all
                self._prefix_full_c.inc()
                return n, entry.last_logits, True
            # KV covers the prompt but the logits weren't parked;
            # re-derive them by re-feeding the final prompt token
            start = n - 1
        return start, None, entry is not None

    def _finish_prefill(self, req: _GenRequest, slot: int, logits_row,
                        ran_prefill: bool, t0: float, tp0: float,
                        prefix_hit: bool) -> None:
        """Shared tail of every paged prefill path (one-shot, chunked,
        full hit): version pin, first-token pick, TTFT accounting,
        prefix capture, draft begin, stream."""
        n = req.prompt.size
        # setdefault: a chunked slot pinned its version at admission
        # and must NOT re-pin to a newer one a mid-prefill swap installed
        version = self._slot_version.setdefault(slot, self.model_version)
        self.pool.lengths[slot] = n
        logits_row = np.asarray(logits_row)
        tok = self._pick_token(req, logits_row)
        now = time.monotonic()
        if ran_prefill:
            self._prefills_c.inc()
            self._prefill_h.record(now - t0)
        self._ttft_h.record(now - req.t_submit)
        self._owe_row(req, "trace.prefill", tp0,
                      time.perf_counter() - tp0, slot=slot,
                      prefix_hit=prefix_hit, model_version=version)
        req.generated.append(tok)
        req.last_token = tok
        if self._prefix is not None:
            req.last_logits = logits_row.copy()
            # _capture_prefix's has() check already skips re-parking a
            # prompt the cache holds (incl. the full-hit path)
            self._capture_prefix(slot, req.prompt, req.last_logits)
        if self._draft is not None:
            self._draft.begin(slot, req.prompt, tok)
        self._owe_token(req, tok)

    def _prefill_paged(self, req: _GenRequest, slot: int) -> None:
        """Paged admission: prefix-cache lookup, page swap-in, then a
        suffix (or full) prefill of whatever the cache didn't cover. A
        full hit with parked logits emits the first token with ZERO
        forward calls."""
        n = req.prompt.size
        t0 = time.monotonic()
        tp0 = time.perf_counter()
        start, logits_row, hit = self._prefix_start(req, slot)
        self.pool.lengths[slot] = start
        ran_prefill = logits_row is None
        if ran_prefill:
            suffix = req.prompt[start:]
            lb = self._buckets.bucket_for(suffix.size)
            ids = np.zeros((1, lb), np.int32)
            ids[0, :suffix.size] = suffix
            pts = self.pool.page_table_row(slot)[None, :]
            with self._sched.phase("prefill_wait"):
                new_pool, logits = self._prefill_exec[lb](
                    self._params, self.pool.pool, pts, ids,
                    np.full(1, start, np.int32))
                self.pool.swap(new_pool)
                self._deliver(dispatched=True)
                logits_row = np.asarray(logits)[0, n - start - 1]
            self._prefill_tokens_c.inc(int(suffix.size))
            self._prefill_positions_c.inc(lb)
        self._finish_prefill(req, slot, logits_row, ran_prefill, t0, tp0,
                             hit)

    def _start_chunked(self, req: _GenRequest, slot: int,
                       prefilling) -> bool:
        """Chunked admission (module docstring): the prefix half of
        :meth:`_prefill_paged`, but instead of one bucket-wide prefill
        the request parks in ``prefilling`` with a ``prefill_pos``
        cursor; :meth:`_chunk_step` advances it one chunk per scheduler
        iteration, riding between decode steps. Returns False when no
        chunk work is needed (a full prefix hit with parked logits
        completes here with zero forwards)."""
        t0 = time.monotonic()
        tp0 = time.perf_counter()
        start, logits_row, hit = self._prefix_start(req, slot)
        if logits_row is not None:
            self.pool.lengths[slot] = start
            self._finish_prefill(req, slot, logits_row,
                                 ran_prefill=False, t0=t0, tp0=tp0,
                                 prefix_hit=hit)
            return False
        self.pool.lengths[slot] = start
        # pin the version NOW: every chunk (and later decode step) for
        # this slot runs on the params it was admitted under, even if a
        # weight swap lands mid-prefill
        self._slot_version[slot] = self.model_version
        req.prefill_pos = start
        prefilling[slot] = req
        return True

    def _chunk_step(self, active, prefilling) -> None:
        """Advance every partially-prefilled slot by ONE chunk: a
        T=prefill_chunk mid-sequence prefill call at the slot's cursor
        (``lengths=[cursor]``, the same hook suffix prefill uses), so a
        long prompt costs each in-flight decoder one chunk of latency
        per iteration instead of the whole prefill at once. A slot
        enters the decode set only when its cursor covers the prompt —
        a partially-prefilled slot is never in a decode group. Chunk
        logits are the one-shot prefill's rows at the decode-step
        tolerance (NUMERICS.md "Decode-step equivalence"), so
        the final chunk's last-token row IS the first-token
        distribution."""
        for slot in sorted(prefilling):
            req = prefilling[slot]
            n = req.prompt.size
            pos = req.prefill_pos
            t0 = time.monotonic()
            tp0 = time.perf_counter()
            chunk = req.prompt[pos:pos + self._chunk]
            ids = np.zeros((1, self._chunk), np.int32)
            ids[0, :chunk.size] = chunk
            pts = self.pool.page_table_row(slot)[None, :]
            params = self._versions.get(
                self._slot_version.get(slot, self.model_version),
                self._params)
            with self._sched.phase("prefill_wait"):
                new_pool, logits = self._chunk_exec(
                    params, self.pool.pool, pts, ids,
                    np.full(1, pos, np.int32))
                self.pool.swap(new_pool)
                self._deliver(dispatched=True)
            self._chunk_steps_c.inc()
            self._prefill_tokens_c.inc(int(chunk.size))
            self._prefill_positions_c.inc(self._chunk)
            req.prefill_pos = pos + chunk.size
            self.pool.lengths[slot] = req.prefill_pos
            if req.prefill_pos >= n:
                with self._sched.phase("prefill_wait"):
                    logits_row = np.asarray(logits)[0, n - pos - 1]
                del prefilling[slot]
                self._finish_prefill(req, slot, logits_row,
                                     ran_prefill=True, t0=t0, tp0=tp0,
                                     prefix_hit=False)
                if self._emit(req, slot) is None:
                    active[slot] = req
                self._active_g.set(len(active))
        self._chunk_depth_g.set(len(prefilling))

    def _swap_in_entry(self, slot: int, entry) -> bool:
        """Restore a parked prefix's pages into ``slot``'s reservation.
        The ``"kv.swap_in"`` chaos site models a torn/lost host restore:
        on failure the entry is evicted (never offered again) and the
        caller cold-prefills — a degraded path, not a corrupted lane."""
        import jax

        if fault.chaos("kv.swap_in") is not None:
            self._swap_fail_c.inc()
            self._prefix.evict(entry)
            return False
        pmax = self.pool.pages_per_slot
        p0 = self.pool.pages_for(entry.length)
        page_ids = np.full(pmax, self.pool.scratch_page, np.int32)
        page_ids[:p0] = self.pool.page_table_row(slot)[:p0]
        pad = lambda a: (a if a.shape[0] == pmax else np.concatenate(
            [a, np.zeros((pmax - a.shape[0],) + a.shape[1:], a.dtype)]))
        data = jax.tree.map(pad, entry.data)
        new_pool = self._swap_in_exec(self.pool.pool, page_ids, data)
        self.pool.swap(new_pool)
        self._swapped_in_c.inc(p0)
        return True

    def _capture_prefix(self, slot: int, tokens, last_logits) -> None:
        """Park ``slot``'s first ``len(tokens)`` cells in the prefix
        cache (compiled swap_out gather; the pool is NOT donated)."""
        import jax

        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if self._prefix.has(tokens):
            return
        pmax = self.pool.pages_per_slot
        p0 = self.pool.pages_for(tokens.size)
        page_ids = np.full(pmax, self.pool.scratch_page, np.int32)
        page_ids[:p0] = self.pool.page_table_row(slot)[:p0]
        data = self._swap_out_exec(self.pool.pool, page_ids)
        data = jax.tree.map(lambda a: np.asarray(a)[:p0].copy(), data)
        self._swapped_out_c.inc(p0)
        self._prefix.insert(tokens, data, last_logits)

    def _pick_token(self, req: _GenRequest, logits_row) -> int:
        """Greedy argmax, or — under ``sampling=True`` — ONE inverse-CDF
        draw from the tempered softmax on the request's own seeded
        stream. One uniform per emitted token, consumed in emission
        order: the coupling the sampled speculative walk reproduces
        exactly (NUMERICS.md "Sampled speculative equivalence"). Host
        float64 softmax/cumsum keeps the CDF deterministic across
        engines fed the same f32 logits."""
        if not self._sampling:
            return int(np.argmax(logits_row))
        z = np.asarray(logits_row, np.float64) / self._temperature
        z -= z.max()
        p = np.exp(z)
        cdf = np.cumsum(p / p.sum())
        u = req.rng.random()
        return int(min(np.searchsorted(cdf, u, side="right"),
                       cdf.size - 1))

    def _decode_step(self, active) -> None:
        """One scheduler iteration of decode. Slots are grouped BY PINNED
        VERSION and each group runs its own ladder call: a single decode
        executable call shares one params argument across its lanes, so a
        mixed-version call is structurally impossible — grouping is what
        makes "finish on the version you started" hold mid-rollout. The
        groups reuse the SAME ladder executables (params are a runtime
        argument), so the compile cache cannot grow. Steady state is one
        group — the multi-group step exists only for the swap window."""
        groups: dict = {}
        for s in sorted(active):
            groups.setdefault(
                self._slot_version.get(s, self.model_version),
                []).append(s)
        if len(groups) > 1:
            telemetry.histogram("rollout.version_groups").record(
                len(groups))
        for version in sorted(groups):
            slots = groups[version]
            if self._spec_k and all(
                    self.pool.lengths[s] + self._spec_k < self.max_len
                    for s in slots):
                # speculative iteration: safe only when every lane's
                # verify block [len, len+spec_k] stays inside the
                # context window; the tail of a sequence falls back to
                # plain decode (exactness is unaffected either way)
                self._spec_group(active, slots, version)
            else:
                self._decode_group(active, slots, version)
        with self._sched.phase("control"):
            self._reclaim_versions()
        self._active_g.set(len(active))

    def _group_arrays(self, active, slots, lane: int, t: int):
        """Ladder-padded step inputs: scratch lanes for padding, column
        0 = each lane's pending token, columns 1..t-1 = GHOST (the
        speculative path overwrites them with draft proposals)."""
        scratch = self.pool.scratch_slot
        slot_ids = np.full(lane, scratch, np.int32)
        tokens = np.full((lane, t), GHOST_TOKEN, np.int32)
        lengths = np.zeros(lane, np.int32)
        for i, s in enumerate(slots):
            slot_ids[i] = s
            tokens[i, 0] = active[s].last_token
            lengths[i] = self.pool.lengths[s]
        return slot_ids, tokens, lengths

    def _page_tables_for(self, slot_ids) -> np.ndarray:
        return self.pool.page_tables[slot_ids]

    def _decode_group(self, active, slots, version: int) -> None:
        import jax

        params = self._versions.get(version, self._params)
        n = len(slots)
        lane = self._ladder.bucket_for(n)
        sched = self._sched
        with sched.phase("launch"):
            slot_ids, tokens, lengths = self._group_arrays(active, slots,
                                                           lane, 2)
            tp0 = time.perf_counter()
            # out: the lanes' tokens where the step chose them
            # (pick_on_device), else their logits
            if self._paged:
                new_pool, out, *routed = self._decode_exec[lane](
                    params, self.pool.pool, self._page_tables_for(slot_ids),
                    tokens, lengths)
            else:
                new_pool, out, *routed = self._decode_exec[lane](
                    params, self.pool.pool, slot_ids, tokens[:, 0], lengths)
        self._deliver(dispatched=True)  # the last step's, under this one
        with sched.phase("wait"):
            out.block_until_ready()  # the step lands
        with sched.phase("copy"):
            # device to host, nothing else, in one round trip: out and
            # the tokens per held expert, [layers, experts_held] int32
            out, *routed = jax.device_get([out, *routed])
        if self._device_pick:
            picked = out.tolist()
        else:
            logits = out[:, 0, :] if self._paged else out
        self.pool.swap(new_pool)
        dt = time.perf_counter() - tp0
        self._steps_c.inc()
        self._tokens_c.inc(n)
        self._count_kv_read(lengths)
        if self._device_pick:
            self._device_picks_c.inc(n)
        self._step_h.record(dt)
        self._padded_h.record(lane - n)
        if dt > 0:
            self._tps_g.set(n / dt)
        if routed:
            self._record_routing(routed[0], n)
        if len(routed) > 1:
            self._record_attended(int(routed[1]), lengths[:n] + 1)
        # the lane loop is bookkeeping alone: it wakes nobody, and notes
        # lane by lane (the order a client sees) what _deliver hands over
        # once the next dispatch is with the device. One annotation around
        # it, its two phases summed lap by lap
        with telemetry.annotation("serving.sched.emit"):
            sched.lap()
            for i, s in enumerate(slots):
                req = active[s]
                self.pool.lengths[s] += 1  # the fed token is now cached
                tok = (picked[i] if self._device_pick
                       else self._pick_token(req, logits[i]))
                sched.lap("pick")
                req.generated.append(tok)
                req.last_token = tok
                if self._prefix is not None:
                    req.last_logits = logits[i].copy()
                if self._draft is not None:
                    self._draft.observe(s, (tok,))
                if req.trace is not None:
                    req.rode_step(tp0, dt)
                self._owe_token(req, tok)
                if self._emit(req, s) is not None:
                    del active[s]
                sched.lap("retire")

    def _count_kv_read(self, lengths: np.ndarray) -> None:
        """A decode step's K/V read into ``serving.decode.kv_positions_*``:
        each lane's positions with the block the step wrote (padded lanes,
        at length 0, too), rounded up to the model's read block and cut at
        the row's end, beside the whole rows."""
        row = read = self.pool.max_len * len(lengths)
        block = self._kv_read_block
        if block:
            held = lengths + self._step_tokens
            read = int(np.minimum(-(-held // block) * block,
                                  self.pool.max_len).sum())
        self._kv_read_c.inc(read)
        self._kv_row_c.inc(row)

    def _record_routing(self, held: np.ndarray, lanes: int) -> None:
        """A decode step's tokens per held expert, ``[layers,
        experts_held]`` (make_decode_fn), into ``serving.moe.*``: the
        assignments its ``lanes`` real tokens made in all, those that
        fell on experts held here, and one sample a step of how many
        held experts worked (mean over layers) and of the fullest
        expert's load over the mean (mean over the layers that got a
        token)."""
        layers = held.shape[0]
        self._moe_assign_c.inc(
            lanes * self.model.experts_per_token * layers)
        self._moe_held_c.inc(int(held.sum()))
        self._moe_active_h.record(float((held > 0).sum(axis=1).mean()))
        mean = held.mean(axis=1)
        if mean.any():
            self._moe_load_h.record(
                float((held.max(axis=1)[mean > 0] / mean[mean > 0]).mean()))

    def _record_attended(self, attended: int, contexts: np.ndarray) -> None:
        """A decode step's reads into ``serving.sparse.*``: the positions
        its lanes' queries attended, summed over lanes and layers on the
        device (make_decode_fn), beside those their contexts have
        (``contexts``, a lane's length with the token the step wrote, times
        the attention layers)."""
        self._sparse_attended_c.inc(attended)
        self._sparse_cached_c.inc(
            int(contexts.sum()) * self.model.num_layers)

    def _sampled_accept_walk(self, req: _GenRequest, props_i, logits_i):
        """Host side of sampling-capable speculative verification
        (NUMERICS.md "Sampled speculative equivalence"). The standard
        target-vs-draft rule — accept draft token d with probability
        ``min(1, p_target(d) / p_draft(d))``, resample from the
        normalized residual ``max(p_target - p_draft, 0)`` on reject —
        realized for the point-mass drafts this repo ships (Ngram/
        ModelDraft propose deterministically, so p_draft is 1 on the
        proposal): ONE tempered inverse-CDF draw per position accepts
        the proposal iff the draw lands on it (probability p_target(d)
        = min(1, p_target(d)/1)), and otherwise the SAME draw is
        exactly a normalized-residual sample (p_target conditioned off
        d). One uniform per EMITTED token, in emission order — the
        stream plain sampled decode consumes, so output is seeded-
        identical to no-draft sampling. Returns ``(emit, resampled)``;
        caps (max_new_tokens, EOS) apply inside the walk so no draw is
        ever consumed for a token that isn't emitted."""
        s = self._spec_k
        emit: list = []
        resampled = False
        remaining = req.max_new_tokens - len(req.generated)
        for m in range(min(s + 1, remaining)):
            tok = self._pick_token(req, logits_i[m])
            emit.append(tok)
            if req.eos_id is not None and tok == req.eos_id:
                break
            if m < s and tok != int(props_i[m]):
                resampled = True
                break
        return emit, resampled

    def _spec_group(self, active, slots, version: int) -> None:
        """One draft-verify iteration: the draft proposes ``spec_k``
        tokens per lane, ONE verify call scores every proposal, and the
        exact greedy accept/reject rule walks each lane's logits — token
        i+1 is emitted iff proposals 1..i all matched what greedy would
        have produced, plus the one free token the verify call always
        yields. Output is token-for-token what sequential greedy decode
        emits (NUMERICS.md "Speculative accept/reject exactness").
        Under ``sampling=True`` the walk is the sampled accept/reject
        rule instead (:meth:`_sampled_accept_walk`) — stream-identical
        to plain sampled decode rather than to greedy."""
        params = self._versions.get(version, self._params)
        n = len(slots)
        s = self._spec_k
        lane = self._ladder.bucket_for(n)
        sched = self._sched
        with sched.phase("launch"):
            slot_ids, tokens, lengths = self._group_arrays(active, slots,
                                                           lane, s + 1)
            props = self._draft.propose(
                slots, tokens[:n, 0], lengths[:n], s)
            tokens[:n, 1:] = props
            tp0 = time.perf_counter()
            if self._paged:
                new_pool, logits = self._verify_exec[lane](
                    params, self.pool.pool, self._page_tables_for(slot_ids),
                    tokens, lengths)
            else:
                new_pool, logits = self._verify_exec[lane](
                    params, self.pool.pool, slot_ids, tokens, lengths)
            self.pool.swap(new_pool)
        self._deliver(dispatched=True)
        with sched.phase("wait"):
            logits.block_until_ready()
        with sched.phase("copy"):
            logits = np.asarray(logits)  # [lane, s+1, V]
        with sched.phase("pick"):
            greedy = np.argmax(logits, axis=-1)  # [lane, s+1]
        dt = time.perf_counter() - tp0
        self._steps_c.inc()
        self._step_h.record(dt)
        self._padded_h.record(lane - n)
        self._spec_iters_c.inc()
        emitted_total = 0
        with telemetry.annotation("serving.sched.emit"):
            sched.lap()
            for i, slot in enumerate(slots):
                req = active[slot]
                if self._sampling:
                    emit, resampled = self._sampled_accept_walk(
                        req, props[i], logits[i])
                else:
                    m = 0
                    while m < s and props[i, m] == greedy[i, m]:
                        m += 1
                    emit = [int(t) for t in greedy[i, :m + 1]]
                    # caps: never emit past max_new_tokens, truncate at EOS
                    emit = emit[:req.max_new_tokens - len(req.generated)]
                    if req.eos_id is not None and req.eos_id in emit:
                        emit = emit[:emit.index(req.eos_id) + 1]
                    resampled = False
                p = len(emit)
                sched.lap("pick")
                self._spec_proposed_c.inc(s)
                self._spec_accepted_c.inc(p - 1)
                if self._sampling:
                    self._spec_s_accepts_c.inc(p - 1)
                    if resampled:
                        self._spec_s_resamples_c.inc()
                self.pool.lengths[slot] += p  # cells L..L+p-1 are now true
                for tok in emit:
                    req.generated.append(tok)
                    req.last_token = tok
                    self._owe_token(req, tok)
                if self._prefix is not None:
                    req.last_logits = logits[i, p - 1].copy()
                self._draft.observe(slot, emit)
                emitted_total += p
                if req.trace is not None:
                    req.rode_step(tp0, dt)
                if self._emit(req, slot) is not None:
                    del active[slot]
                sched.lap("retire")
        self._tokens_c.inc(emitted_total)
        if dt > 0:
            self._tps_g.set(emitted_total / dt)
        prop = self._spec_proposed_c.value
        if prop:
            self._spec_rate_g.set(self._spec_accepted_c.value / prop)

    def _emit(self, req: _GenRequest, slot: int) -> Optional[str]:
        """After a token lands, decide retirement. Returns the reason
        when the sequence finished (slot already freed, the result owed
        behind its last token), else None."""
        tok = req.last_token
        if req.eos_id is not None and tok == req.eos_id:
            reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            reason = "length"
        elif self.pool.lengths[slot] >= self.max_len:
            # next feed would write at position max_len — context full
            reason = "max_len"
        else:
            return None
        if (self._prefix is not None and req.last_logits is not None
                and len(req.generated) > 1):
            # park the finished conversation: cells [0, lengths) hold
            # prompt + generated[:-1], and last_logits reproduces the
            # final token — a resumed conversation becomes a full hit
            self._capture_prefix(
                slot,
                np.concatenate([req.prompt,
                                np.asarray(req.generated[:-1], np.int32)]),
                req.last_logits)
        self.pool.free(slot)
        version = self._slot_version.pop(slot, None)  # unpin: may reclaim
        if self._draft is not None:
            self._draft.release(slot)
        self._owed.append(("retire", self._leave, (req, version, reason)))
        return reason

    def _expire(self, active, prefilling=None) -> None:
        """Fail in-flight sequences whose deadline passed mid-generation
        (or mid-chunked-prefill); their slots free immediately (the
        mid-flight retirement path)."""
        now = time.monotonic()
        expired = False
        groups = [active]
        if prefilling:
            groups.append(prefilling)
        for grp in groups:
            for slot in list(grp):
                req = grp[slot]
                if req.deadline is not None and now > req.deadline:
                    del grp[slot]
                    self.pool.free(slot)
                    version = self._slot_version.pop(slot, None)
                    if self._draft is not None:
                        self._draft.release(slot)
                    self._expired_c.inc()
                    self._owed.append(("retire", self._leave, (
                        req, version, "deadline", DeadlineExceeded(
                            f"deadline passed after {len(req.generated)} "
                            f"tokens"))))
                    expired = True
        if expired:
            # the token a request is still owed reaches it first
            self._deliver(dispatched=False)
        self._active_g.set(len(active))

    def _trace_row(self, req: _GenRequest, name: str, t0: float,
                   dur_s: float, labels: dict) -> None:
        """One ``trace.*`` row of a traced request, written from the
        scheduler thread (each mints a span id with a system call, which
        lets waiting handler threads in: never once a lane a step, and
        only from :meth:`_deliver`)."""
        telemetry.record_trace_span(req.trace, name, t0, dur_s, **labels)
        self._trace_rows_c.inc()

    def _owe_row(self, req: _GenRequest, name: str, t0: float,
                 dur_s: float, **labels) -> None:
        if req.trace is not None:
            self._owed.append(
                ("retire", self._trace_row, (req, name, t0, dur_s, labels)))

    def _owe_token(self, req: _GenRequest, tok: int) -> None:
        if req.stream is not None:
            self._owed.append(("stream", self._stream_token, (req, tok)))

    def _deliver(self, dispatched: bool) -> None:
        """Hand the clients what the scheduler owes them, oldest first: a
        request's tokens in order, then its result. ``dispatched`` says
        that an executable's call has just returned, so the handler
        threads this wakes run while the device works; False is a flush,
        where no dispatch is sure to come (no lane left) or a request is
        about to be failed (expiry, a scheduler error, a shutdown that
        does not drain). Never called under ``self._cv``: a future's
        callback may call :meth:`generate`. An item leaves the queue
        before its call, so a call that raises loses no other."""
        owed = self._owed
        if not owed and not dispatched:
            return
        sched = self._sched
        n = len(owed)
        with telemetry.annotation("serving.sched.deliver"):
            sched.lap()
            while owed:
                phase, call, args = owed.popleft()
                call(*args)
                sched.lap(phase)
        self._delivered_c.inc(n)
        if dispatched:
            self._delivered_after_c.inc(n)

    def _leave(self, req: _GenRequest, version, reason: str,
               error: Optional[Exception] = None) -> None:
        """A request's last act: its leaving rows, then its result, or
        ``error`` for one that expired in flight."""
        telemetry.counter("serving.decode.retired", reason=reason).inc()
        self._trace_leave(req, version, reason)
        if error is not None:
            req.future.set_exception(error)
        else:
            req.future.set_result(GenerationResult(
                np.asarray(req.generated, np.int32), reason))

    def _trace_leave(self, req: _GenRequest, version, reason: str) -> None:
        """The rows a traced request leaves with: its decoding as ONE
        ``trace.decode`` row, first step's start to last step's end (none
        if it never decoded), then ``trace.request``. One decode step
        serves every lane at once, so ``steps`` x ``step_ms`` is the time
        of the steps this request rode, not a per-lane cost: attributing a
        batched step to its lanes would be an invention, not a
        measurement. Every step's own interval is in
        ``serving.decode.step_s``. ``step_ms`` is their mean in whole
        milliseconds: a label feeds ``span.trace.decode.duration_s``, and
        the raw sum would mint one histogram a request."""
        if req.trace is None:
            return
        steps = req.decode_steps
        if steps:
            self._trace_row(
                req, "trace.decode", req.decode_t0,
                req.decode_end - req.decode_t0, dict(
                    steps=steps,
                    step_ms=round(1e3 * req.decode_step_s / steps),
                    model_version=version))
        self._trace_row(req, "trace.request", req.t_perf,
                        time.perf_counter() - req.t_perf,
                        dict(reason=reason, tokens=len(req.generated)))

    def _stream_token(self, req: _GenRequest, tok: int) -> None:
        if req.stream is None:
            return
        try:
            req.stream(tok)
        except Exception:
            # a broken consumer must not stall every in-flight sequence
            self._stream_err_c.inc()
            req.stream = None

    # -- health / lifecycle ------------------------------------------------

    def health_status(self) -> dict:
        with self._cv:
            depth = len(self._dq)
            oldest = (time.monotonic() - self._dq[0].t_submit
                      if self._dq else 0.0)
        self._depth_g.set(depth)
        status = {
            "num_slots": self.pool.num_slots,
            "slots_active": self.pool.num_active,
            "slots_free": self.pool.num_free,
            "queue_depth": depth,
            "oldest_request_age_s": oldest,
            "cache_bytes": self.pool.cache_bytes,
            "prefill_buckets": list(self._buckets.sizes),
            "decode_ladder": list(self._ladder.sizes),
            "admit_spacing": self._admit_spacing,
            "compiled": {k: list(v) for k, v in
                         self.compiled_executables.items()},
            "model_version": self.model_version,
            "last_swap_time": self.last_swap_time,
            "live_versions": sorted(self._versions),
        }
        if self._paged:
            status["paged"] = {
                "page_size": self.pool.page_size,
                "num_pages": self.pool.num_pages,
                "pages_in_use": self.pool.pages_in_use,
                "page_occupancy": (self.pool.pages_in_use
                                   / self.pool.num_pages),
                "page_bytes": self.pool.page_bytes,
                "kv_dtype": self.pool.kv_dtype,
            }
            if self.pool.kv_dtype == "int8":
                status["paged"]["kv_quant_bytes_saved"] = (
                    self.pool.kv_quant_bytes_saved)
        if self._chunk is not None:
            status["chunked_prefill"] = {
                "prefill_chunk": self._chunk,
                "admitted": self._chunk_admits_c.value,
                "chunk_steps": self._chunk_steps_c.value,
            }
        if self._sampling:
            status["sampling"] = {
                "temperature": self._temperature,
                "seed": self._seed,
            }
        if self._prefix is not None:
            status["prefix_cache"] = {
                "entries": len(self._prefix),
                "bytes": self._prefix.bytes,
                "budget_bytes": self._prefix.budget_bytes,
                "hits": self._prefix.hits,
                "misses": self._prefix.misses,
                "hit_rate": self._prefix.hit_rate,
                "evictions": self._prefix.evictions,
            }
        if self._spec_k:
            proposed = self._spec_proposed_c.value
            accepted = self._spec_accepted_c.value
            status["speculative"] = {
                "spec_k": self._spec_k,
                "proposed": proposed,
                "accepted": accepted,
                "accept_rate": accepted / proposed if proposed else 0.0,
                "sampling": self._sampling,
            }
        return status

    def shutdown(self, drain: bool = True, timeout: float = 60.0) -> None:
        with self._cv:
            self._closed = True
            self._drain = drain
            self._cv.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            telemetry.counter("serving.shutdown_timeouts").inc()
            with self._cv:
                pending = list(self._dq)
                self._dq.clear()
                self._depth_g.set(0)
            err = EngineClosed(
                f"scheduler still running after {timeout}s shutdown join")
            self._fail_pending_swap(err)
            self._fail_host_ops()
            for req in pending:
                req.future.set_exception(err)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
