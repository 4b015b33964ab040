"""Causal LM — the long-context model family (sequence parallelism ready).

No reference parity (dist-keras predates transformers; SURVEY.md §5 marks
long-context ABSENT) — this is the framework's first-class long-context
story: a GPT-style decoder whose attention can run either

- ``attention="full"``: single-device causal attention; with no cache, on
  a TPU, on one device as traced (a ``shard_map`` body or a single-device
  program; GSPMD over a mesh keeps XLA, which it partitions) and at a
  shape the kernel takes, the fused training kernel of
  ``ops/pallas/flash_attention.py`` (forward and backward, no ``[t, t]``
  scores in HBM), elsewhere ``dot_product_attention`` (long sequences
  too: the kernel keeps a lane block's whole sequence in VMEM, so past
  2048 positions of 64-wide bfloat16 heads they keep the O(t^2) path),
- ``attention="flash"``: the fused pallas TPU kernel (O(seq) memory,
  TPU only; measured 1.4x over the XLA path at seq 8192 on v5e on an
  earlier installation, not re-measured), or
- ``attention="ring"``: ring attention over a ``seq`` mesh axis
  (ops/ring_attention.py) — the module then operates on the LOCAL sequence
  block inside ``shard_map``, with global positions derived from
  ``jax.lax.axis_index``; peak memory per device drops from O(T^2) to
  O((T/P)^2) and k/v blocks ride the ICI ring.

Both paths share weights: a model trained sequence-parallel serves
single-device and vice versa.

Decode mode (generative serving, DESIGN.md §14): every module also
accepts ``cache``/``cache_index``. **The model owns its cache's leaves**:
the serving pool (serving/kv_cache.py) and the draft ask the model for
them and for their cost (:meth:`CausalLM.init_cache`,
:meth:`CausalLM.cache_bytes_per_row`, :meth:`CausalLM.prefill_row_len`
— the protocol every family that serves through ``GenerationEngine``
meets; models/latent_moe.py is the second), and treat what comes back as
a pytree whose leaves have rows first. This family's cache is a per-layer
``{"k", "v"}`` pytree of ``[rows, max_len, width]`` arrays (see
:meth:`CausalLM.init_cache`): one row a sequence, one ``width``-wide line a
position, every head's ``head_dim`` values side by side in it — the
form the qkv projection emits and the form the TPU stores as written
(a last dimension that is a multiple of 128 lanes; a ``head_dim`` of 64
there is kept position-minor, and every step pays to turn it round).
``cache_index[b]`` is the number of tokens already cached for lane
``b``, i.e. the position of this call's first input token. The module
writes the block's K/V lines into the cache IN PLACE, first, then
attends with positions ``> cache_index + q`` masked to exact-zero
softmax weight, and returns ``(logits, new_cache)``: every cell the
write changes is either an in-call position or masked, so writing first
changes no output. One code path covers both phases: prefill is a
T-token call at ``cache_index=0``, decode a short call at
``cache_index=lengths``. Lane ``b`` reads and writes cache row ``b``;
``cache_rows`` (``[batch]`` row ids) points the lanes at rows of a
larger cache instead — the serving slot pool, which the step then
updates in place. What the attention reads of a row depends on where it
runs. **A short block on a TPU** (``t * heads`` within one pass of the
MXU's rows: decode, verify, a short chunk) attends each lane's row IN
THE POOL, block by block up to ``cache_index + t`` and no further, in
one Pallas call with an online softmax
(``ops/pallas/decode_attention.py``): no copy of the lanes' rows exists
and the bytes read follow what the lanes hold. **Everywhere else** (the
CPU, a long block, a shape that kernel declines) the lanes' rows are
gathered whole (``ops/cache_rows.gather_rows``) and the contraction runs
over all ``max_len`` keys with an exact-zero tail. Either way decode
logits equal the standard full forward evaluated at the same
``max_len`` padded shape to the order the sums are taken in (f32:
NUMERICS.md "Decode-step equivalence"); cache mode requires
``attention="full"``.

Paged decode mode (DESIGN.md §19): passing ``page_table`` alongside
``cache`` switches the cache layout from one ``max_len`` row per batch
row to a shared **page pool** — per layer ``{"k", "v"}`` arrays of
``[num_pages + 1, page_size, heads, head_dim]`` (see
:func:`init_paged_cache`; the last page is scratch) — with
``page_table[b, j]`` naming the physical page that backs row ``b``'s
logical token positions ``[j*page_size, (j+1)*page_size)``. The forward
gathers each row's pages into a dense ``[batch, max_len, ...]`` view,
places the in-call K/V block into that view, and runs the same
fixed-length masked attention as the rectangular path, per head
(:func:`dot_product_attention`) — the view holds bitwise-the-same
values at every unmasked position, so paged decode logits equal
rectangular decode up to the order the sums are taken in (each is held
to the full forward in its own test file). The new K/V block is then
scattered to its physical page cells; positions past ``max_len`` (the
ghost slot)
and cells of unmapped table entries land in the scratch page.

Int8 KV pages (DESIGN.md §19, ISSUE 20): when the paged cache carries
``k_scale``/``v_scale`` leaves (:func:`init_paged_cache` with
``kv_dtype="int8"``), pages store int8 codes on the wire codec's
symmetric affine grid — one f32 scale per (page, layer, k/v), the SAME
``affine_qparams(-amax, amax, 254)`` rule precision.py and comms/codec
share — and the forward dequantizes at the gather, overlays the exact
in-call block, attends, then requantizes ONLY the pages the block
touched. Pages fill monotonically, so a full page's codes freeze
forever; the per-encode error is bounded by ``scale / 2`` per cell
(:func:`quantize_kv_page`). Lossy by design: ~4x capacity per HBM byte
at f32 compute (:func:`page_bytes` with ``kv_dtype="int8"``) for a
stated, tested error bound — never silently on (the pool opts in).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import precision as precision_lib
from distkeras_tpu import telemetry
from distkeras_tpu.models.remat import remat_wrap
from distkeras_tpu.models.transformer import MlpBlock
from distkeras_tpu.ops.attention import MASK_VALUE, dot_product_attention
from distkeras_tpu.ops.cache_rows import gather_rows
from distkeras_tpu.ops.pallas import decode_attention as _pool_kernel
from distkeras_tpu.ops.pallas import flash_attention as _flash_kernel
from distkeras_tpu.ops.ring_attention import ring_attention

#: the ``jax.named_scope`` names this file's forward declares, serve and
#: train: every operation it traces lies under one, and
#: ``profiling/scopes.py`` gives an executable's instruction to the
#: innermost one on its ``op_name`` path. ``attn.cache`` is the lanes' K and
#: V rows read out of the pool (or a row's pages): empty in a short block's
#: step on a TPU, which attends in the pool under ``attn.scores`` alone;
#: ``cache.write`` the block's lines written into it
SCOPES = ("embed", "norm", "attn.qkv", "attn.cache", "attn.scores",
          "attn.out", "cache.write", "mlp", "head")

#: most query rows (block positions x heads) the cache attention spreads
#: over the width: up to one pass of the MXU's rows the spread costs no
#: more than streaming K and V once, past it `heads` times the matmul
_SPREAD_QUERY_ROWS = 128


def _attend_rows(q, k_rows, v_rows, pos, num_heads):
    """Causal attention of a block's queries ``q [b, t, width]`` at
    positions ``pos [b, t]`` over whole cache rows ``[b, max_len,
    width]``, K and V read as they lie. Key ``p`` is visible to query
    ``j`` iff ``p <= pos[j]``; masked keys get exact-zero softmax weight
    (MASK_VALUE underflows), so the fixed-length contraction matches the
    max_len-padded full forward (NUMERICS.md "Decode-step equivalence").

    A short block (``t * heads <= _SPREAD_QUERY_ROWS``: decode, verify)
    never splits K or V into heads: each head's query is spread over the
    width, zeros outside its own ``head_dim`` columns, and contracted
    with the ``[max_len, width]`` rows as matrices. The zeros add
    nothing to the float32 sums, so these are
    :func:`dot_product_attention`'s numbers: bf16 K/V, float32 logits
    and softmax, weights cast back for PV. A long block (prefill)
    reshapes its rows to heads and calls it."""
    b, t, width = q.shape
    head_dim = width // num_heads
    max_len = k_rows.shape[1]
    mask = jnp.arange(max_len)[None, None, None, :] <= pos[:, None, :, None]
    if t * num_heads > _SPREAD_QUERY_ROWS:
        heads = lambda a: a.reshape(a.shape[:2] + (num_heads, head_dim))
        out = dot_product_attention(heads(q), heads(k_rows), heads(v_rows),
                                    mask=mask)
        return out.reshape(b, t, width)
    own = (jnp.arange(width)[None, :] // head_dim
           == jnp.arange(num_heads)[:, None])[None, :, None, :]  # [1,h,1,w]
    spread = jnp.where(own, q[:, None], 0).reshape(b, num_heads * t, width)
    logits = jnp.einsum("bqw,bkw->bqk", spread, k_rows).astype(jnp.float32)
    logits = logits.reshape(b, num_heads, t, max_len) * head_dim ** -0.5
    logits = jnp.where(mask, logits, MASK_VALUE)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bqk,bkw->bqw",
                     weights.reshape(b, num_heads * t, max_len), v_rows)
    out = out.reshape(b, num_heads, t, width)
    return jnp.where(own, out, 0).sum(axis=1)


class CausalSelfAttention(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "full"  # "full" | "flash" | "ring"
    axis_name: str = "seq"
    precision: Optional[str] = None

    @nn.compact
    def __call__(self, x, cache=None, cache_index=None, page_table=None,
                 cache_rows=None):
        dtype, dense_kw, _, _ = precision_lib.resolve(self.precision,
                                                      self.dtype)
        width = x.shape[-1]
        head_dim = width // self.num_heads
        with jax.named_scope("attn.qkv"):
            qkv = nn.Dense(3 * width, dtype=dtype, name="qkv", **dense_kw)(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            split = lambda t: t.reshape(
                t.shape[:2] + (self.num_heads, head_dim))
            q, k, v = split(q), split(k), split(v)

        def project(out):
            """The heads' outputs side by side through ``out``."""
            with jax.named_scope("attn.out"):
                out = out.reshape(out.shape[:2] + (width,))
                return nn.Dense(width, dtype=dtype, name="out",
                                **dense_kw)(out)

        if cache is not None:
            if self.attention != "full":
                raise ValueError(
                    f"KV-cache decode requires attention='full', got "
                    f"{self.attention!r}")
            b, t = x.shape[:2]
            with jax.named_scope("cache.write"):
                rows = jnp.arange(b)[:, None]
                pos = cache_index[:, None] + jnp.arange(t)[None, :]  # [b, t]
            if page_table is not None:
                from distkeras_tpu.ops.pallas import flash_attention as _fa

                if "k_scale" in cache:
                    # int8 KV pages (module docstring): dequantize at
                    # the gather, overlay the exact in-call block,
                    # attend, requantize only the touched page window
                    out, new_cache = _paged_int8_attention(
                        q, k, v, cache, page_table, pos, cache_index,
                        _fa)
                    return project(out), new_cache
                ps = cache["k"].shape[1]
                pmax = page_table.shape[1]
                max_len = pmax * ps
                # scatter the in-call block to its PHYSICAL page cells
                # FIRST. Ghost/overflow positions (>= max_len) and
                # positions whose table entry is unmapped route to the
                # scratch page (the pool keeps unmapped entries pointing
                # there), so no live page is ever perturbed by padding.
                # Scatter-before-attend is value-identical to the old
                # gather-then-overlay order: every view position the
                # scatter changes is either an in-call position (where
                # the overlay put the same k/v value) or masked to
                # exact-zero softmax weight, so attention output is
                # bitwise unchanged — and it lets the paged kernel read
                # pages[page_table] directly.
                scratch_page = cache["k"].shape[0] - 1
                with jax.named_scope("cache.write"):
                    page_idx = jnp.clip(pos // ps, 0, pmax - 1)
                    phys = jnp.take_along_axis(page_table, page_idx, axis=1)
                    phys = jnp.where(pos < max_len, phys, scratch_page)
                    off = jnp.where(pos < max_len, pos % ps, 0)
                    new_cache = {"k": cache["k"].at[phys, off].set(k),
                                 "v": cache["v"].at[phys, off].set(v)}
                if _fa.paged_dispatch(q.shape, cache["k"].shape,
                                      page_table.shape, q.dtype):
                    # fused paged kernel (DESIGN.md §23): the page DMAs
                    # are indexed by page_table INSIDE the kernel grid —
                    # the dense [b, max_len] HBM view below is never
                    # materialized (DESIGN.md §19's honest limit)
                    with jax.named_scope("attn.scores"):
                        out = _fa.paged_flash_attention(
                            q, new_cache["k"], new_cache["v"], page_table,
                            cache_index, interpret=_fa.PAGED_INTERPRET)
                else:
                    # XLA fallback: gather each row's pages into the
                    # SAME dense [b, max_len, heads, head_dim] view the
                    # rectangular path attends over (shape- and
                    # value-identical — bitwise parity)
                    gather = lambda pages: pages[page_table].reshape(
                        b, max_len, self.num_heads, head_dim)
                    with jax.named_scope("attn.cache"):
                        k_cache = gather(new_cache["k"])
                        v_cache = gather(new_cache["v"])
                    with jax.named_scope("attn.scores"):
                        key_pos = jnp.arange(max_len)
                        mask = (key_pos[None, None, None, :]
                                <= pos[:, None, :, None])
                        out = dot_product_attention(q, k_cache, v_cache,
                                                    mask=mask)
                return project(out), new_cache
            # rectangular cache, leaves [rows, max_len, width]: write the
            # block's lines into their rows in place FIRST (the paged
            # branch's argument: every cell this changes is an in-call
            # position or masked), then attend the lanes' rows where
            # they lie. mode="drop": a position past max_len-1 (the
            # decode step's ghost, DESIGN.md §14) must not clamp onto
            # the last real cell
            lines = lambda a: a.reshape(b, t, width)
            with jax.named_scope("cache.write"):
                if cache_rows is not None:
                    rows = cache_rows[:, None]
                new_cache = {
                    "k": cache["k"].at[rows, pos].set(lines(k), mode="drop"),
                    "v": cache["v"].at[rows, pos].set(lines(v), mode="drop")}
            if _pool_kernel.dispatch(lines(q), new_cache["k"],
                                     self.num_heads):
                # a short block on a TPU: one call attends each lane's
                # row in the pool, as far as the lane has written
                with jax.named_scope("attn.scores"):
                    out = _pool_kernel.pool_attention(
                        lines(q), new_cache["k"], new_cache["v"],
                        cache_rows, cache_index, self.num_heads)
                return project(out), new_cache
            with jax.named_scope("attn.cache"):
                k_rows = gather_rows(new_cache["k"], cache_rows)
                v_rows = gather_rows(new_cache["v"], cache_rows)
            with jax.named_scope("attn.scores"):
                out = _attend_rows(lines(q), k_rows, v_rows, pos,
                                   self.num_heads)
            return project(out), new_cache
        with jax.named_scope("attn.scores"):
            if self.attention == "ring":
                out = ring_attention(q, k, v, axis_name=self.axis_name,
                                     causal=True)
            elif self.attention == "flash":
                # resolve()-style dispatch (ops/attention.py): in-repo fused
                # kernel when enabled+fits, else the upstream pallas kernel;
                # off-TPU it raises rather than run XLA under this name
                from distkeras_tpu.ops.attention import apply_attention

                out = apply_attention(q, k, v, causal=True,
                                      attention="flash")
            elif self.attention == "full":
                # on a TPU, one device's call at a shape the training
                # kernel takes attends in one fused causal kernel, forward
                # and backward: no [t, t] scores in HBM (PERF.md §6); XLA
                # everywhere else, GSPMD over a mesh included
                telemetry.counter("model.attention.calls").inc()
                if _flash_kernel.train_dispatch(q):
                    telemetry.counter("model.attention.fused_calls").inc()
                    out = _flash_kernel.flash_attention(q, k, v, causal=True)
                else:
                    out = dot_product_attention(q, k, v, causal=True)
            else:
                raise ValueError(
                    f"Unknown attention {self.attention!r}; "
                    "expected 'full', 'flash', or 'ring'")
        return project(out)


class DecoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "full"
    axis_name: str = "seq"
    precision: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False, cache=None, cache_index=None,
                 page_table=None, cache_rows=None):
        dtype = precision_lib.resolve(self.precision, self.dtype)[0]
        with jax.named_scope("norm"):
            y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x).astype(dtype)
        attn = CausalSelfAttention(self.num_heads, self.dtype, self.attention,
                                   self.axis_name, precision=self.precision,
                                   name="attn")
        if cache is not None:
            y, new_cache = attn(y, cache, cache_index, page_table,
                                cache_rows)
        else:
            y, new_cache = attn(y), None
        # a residual sum goes with the sub-layer whose result it takes: the
        # compiler fuses it into that product
        with jax.named_scope("attn.out"):
            x = x + y
        with jax.named_scope("norm"):
            y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x).astype(dtype)
        with jax.named_scope("mlp"):
            y = MlpBlock(self.mlp_dim, 0.0, self.dtype,
                         precision=self.precision, name="mlp")(y, train=train)
            x = x + y
        return x if new_cache is None else (x, new_cache)


class CausalLM(nn.Module):
    vocab_size: int = 32000
    max_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    width: int = 768
    mlp_dim: int = 3072
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "full"
    axis_name: str = "seq"
    #: activation rematerialization policy for the decoder blocks
    #: (models/remat.py); "full" also wraps the token embedding.
    remat: str = "none"
    #: mixed-precision policy (distkeras_tpu/precision.py); f32 LM head
    #: stays f32
    precision: Optional[str] = None

    # -- the cache protocol (module docstring): the model owns its
    # cache's leaves; the serving pool asks for them and for their cost

    def init_cache(self, batch: int, dtype=None, positions=None):
        """Zeroed per-layer K/V cache for ``batch`` rows of ``max_len``
        context (``positions``, where a prefill asks for its fresh row):
        a tuple (one entry per layer) of ``{"k", "v"}`` arrays
        shaped ``[batch, max_len, width]`` (a position's heads side by
        side, as the qkv projection emits them; module docstring) in the
        model's resolved compute dtype (K/V are produced by the qkv
        projection, which runs in that dtype)."""
        if dtype is None:
            dtype = precision_lib.resolve(self.precision, self.dtype)[0]
        shape = (batch, positions or self.max_len, self.width)
        return tuple({"k": jnp.zeros(shape, dtype),
                      "v": jnp.zeros(shape, dtype)}
                     for _ in range(self.num_layers))

    def cache_bytes_per_row(self, dtype=None) -> int:
        """HBM bytes one cache row costs (k + v, every layer): ``2 *
        layers * max_len * width * itemsize``, the unit the serving slot
        pool's budget check multiplies by its rows."""
        if dtype is None:
            dtype = precision_lib.resolve(self.precision, self.dtype)[0]
        return (2 * self.num_layers * self.max_len * self.width
                * np.dtype(dtype).itemsize)

    def prefill_row_len(self, block: int) -> int:
        """Positions of the fresh row a ``block``-token prefill is given:
        all ``max_len``, because this family's attention contracts over
        the whole row at every step (NUMERICS.md "Decode-step
        equivalence")."""
        del block
        return self.max_len

    def decode_read_block(self, block: int, dtype=None) -> int:
        """Positions by which a ``block``-token step over a pool of
        ``dtype`` bounds what it reads of a lane's row: a lane that has
        written ``n`` positions costs ``n`` rounded up to this many. 0
        where the step reads the whole row whatever the lane holds (off
        the TPU, a long block, a shape the pool kernel declines)."""
        own = precision_lib.resolve(self.precision, self.dtype)[0]
        pool = jax.ShapeDtypeStruct((1, self.max_len, self.width),
                                    own if dtype is None else dtype)
        if not _pool_kernel.dispatch(
                jax.ShapeDtypeStruct((1, block, self.width), own), pool,
                self.num_heads):
            return 0
        return _pool_kernel.block_positions(
            self.width, pool.dtype.itemsize, self.max_len)

    @nn.compact
    def __call__(self, input_ids, train: bool = False, cache=None,
                 cache_index=None, page_table=None, cache_rows=None,
                 real_len=None):
        """Logits ``[b, t, vocab]`` float32; with ``cache`` ``(logits,
        new_cache)``. ``real_len [b]`` (a cache call only: a prefill of a
        whole prompt, that many of its positions real) cuts the logits to
        those of position ``real_len - 1``, ``[b, 1, vocab]``: the final
        LayerNorm and the head run on that row alone."""
        dtype = precision_lib.resolve(self.precision, self.dtype)[0]
        ids = input_ids.astype(jnp.int32)
        b, t = ids.shape  # t = LOCAL block length under sequence parallelism
        embed_cls = remat_wrap(nn.Embed, self.remat, stem=True)
        with jax.named_scope("embed"):
            x = embed_cls(self.vocab_size, self.width, dtype=dtype,
                          name="tok_embed")(ids)
        pos_table = self.param("pos_embed", nn.initializers.normal(0.02),
                               (self.max_len, self.width))

        def head(x):
            with jax.named_scope("norm"):
                x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
            with jax.named_scope("head"):
                logits = nn.Dense(self.vocab_size, dtype=jnp.float32,
                                  name="lm_head")(x)
                return logits.astype(jnp.float32)

        if cache is not None:
            # decode mode: positions come from each row's cache cursor;
            # blocks run un-rematted (inference) but with identical param
            # structure, so trained checkpoints serve as-is
            with jax.named_scope("embed"):
                pos = pos_table[
                    cache_index[:, None] + jnp.arange(t)[None, :]]
                x = x + pos.astype(dtype)
            new_cache = []
            for i in range(self.num_layers):
                x, layer_cache = DecoderBlock(
                    self.num_heads, self.mlp_dim, self.dtype,
                    self.attention, self.axis_name,
                    precision=self.precision, name=f"layer_{i}")(
                        x, train, cache=cache[i], cache_index=cache_index,
                        page_table=page_table, cache_rows=cache_rows)
                new_cache.append(layer_cache)
            if real_len is not None:    # the one row a prefill returns
                with jax.named_scope("head"):
                    x = jnp.take_along_axis(
                        x, (real_len - 1)[:, None, None], axis=1)
            return head(x), tuple(new_cache)
        if self.attention == "ring":
            # global positions of this device's block. psum(1) over the mesh
            # axis is concrete at trace time, so this bound check is static —
            # without it dynamic_slice would silently CLAMP an out-of-range
            # offset and reuse another block's position rows.
            num_blocks = jax.lax.psum(1, self.axis_name)
            if t * num_blocks > self.max_len:
                raise ValueError(
                    f"global sequence {t}*{num_blocks} exceeds max_len "
                    f"{self.max_len}")
            with jax.named_scope("embed"):
                offset = jax.lax.axis_index(self.axis_name) * t
                pos = jax.lax.dynamic_slice_in_dim(pos_table, offset, t)
        else:
            pos = pos_table[:t]     # a static slice: no operation of its own
        with jax.named_scope("embed"):
            x = x + pos.astype(dtype)
        # positional call, train static at index 2 (models/remat.py rules)
        block_cls = remat_wrap(DecoderBlock, self.remat, static_argnums=(2,))
        for i in range(self.num_layers):
            x = block_cls(self.num_heads, self.mlp_dim, self.dtype,
                          self.attention, self.axis_name,
                          precision=self.precision,
                          name=f"layer_{i}")(x, train)
        return head(x)


def init_cache(model, batch: int, dtype=None):
    """``model.init_cache(batch, dtype)``: the model says what its cache
    is (:meth:`CausalLM.init_cache`; any family that keeps the cache
    protocol answers for itself). Kept for ``perf/aot_check.py``."""
    return model.init_cache(batch, dtype)


def init_paged_cache(model: CausalLM, num_pages: int, page_size: int,
                     dtype=None, kv_dtype=None):
    """Zeroed shared page pool for paged decode (DESIGN.md §19): a tuple
    (one entry per layer) of ``{"k", "v"}`` arrays shaped
    ``[num_pages + 1, page_size, num_heads, head_dim]``. One logical
    page spans every layer (the same page id indexes each layer's
    array), so a page costs :func:`page_bytes` of HBM. The extra LAST
    page is **scratch**: unmapped page-table entries and ghost/overflow
    writes point at it, mirroring the rectangular pool's scratch row.

    ``kv_dtype="int8"`` switches the page format to symmetric int8
    codes plus per-page f32 ``k_scale``/``v_scale`` leaves shaped
    ``[num_pages + 1]`` (module docstring, "Int8 KV pages"); the
    attention path detects the format by the presence of the scale
    leaves, so every consumer that treats the pool as a pytree
    (host swap, prefix cache, fleet kv_export/kv_handoff) ships the
    quantized blobs unchanged."""
    if kv_dtype not in (None, "native", "int8"):
        raise ValueError(
            f"kv_dtype must be None, 'native', or 'int8', got {kv_dtype!r}")
    if dtype is None:
        dtype = precision_lib.resolve(model.precision, model.dtype)[0]
    head_dim = model.width // model.num_heads
    shape = (num_pages + 1, page_size, model.num_heads, head_dim)
    if kv_dtype == "int8":
        return tuple({"k": jnp.zeros(shape, jnp.int8),
                      "v": jnp.zeros(shape, jnp.int8),
                      "k_scale": jnp.zeros(num_pages + 1, jnp.float32),
                      "v_scale": jnp.zeros(num_pages + 1, jnp.float32)}
                     for _ in range(model.num_layers))
    return tuple({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                 for _ in range(model.num_layers))


#: levels of the symmetric int8 KV grid — precision.py's ``_INT8_LEVELS``
#: (codes -127..127 after centering), so KV pages, wire commits, and
#: fake-quant training share one affine arithmetic.
KV_QUANT_LEVELS = 254


def quantize_kv_page(x, valid=None):
    """Per-page symmetric int8 quantization of K/V page data.

    ``x`` is ``[..., page_size, heads, head_dim]`` (leading dims index
    pages); returns ``(codes int8, scale f32[...])`` on the wire codec's
    grid: ``scale = affine_qparams(-amax, amax, 254) = amax / 127``
    (``precision.symmetric_int8_qparams``), codes centered at zero.
    ``valid`` (``[..., page_size]`` bool) masks cells past a row's
    length so stale garbage can never inflate a page's scale; masked
    cells store code 0. A single encode's per-cell round-trip error is
    bounded by ``scale / 2`` (tests/test_decode_economics.py); pages
    fill monotonically under the serving engine, so a cell is re-encoded
    at most ``page_size`` times before its page's codes freeze."""
    from distkeras_tpu.comms import codec

    x = jnp.asarray(x, jnp.float32)
    if valid is not None:
        x = jnp.where(valid[..., None, None], x, 0.0)
    amax = jnp.max(jnp.abs(x), axis=(-3, -2, -1))
    scale = precision_lib.symmetric_int8_qparams(amax)
    sc = scale[..., None, None, None]
    codes = codec.affine_quantize(x, -amax[..., None, None, None], sc,
                                  KV_QUANT_LEVELS, xp=jnp) - 127.0
    codes = jnp.where(sc > 0, codes, 0.0)
    return codes.astype(jnp.int8), scale


def dequantize_kv_page(codes, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv_page` on the centered grid
    (precision.py's rule with ``lo = 0`` after centering):
    ``scale * codes``, broadcast per page."""
    sc = jnp.asarray(scale)[..., None, None, None]
    return (codes.astype(jnp.float32) * sc).astype(dtype)


def _paged_int8_attention(q, k, v, cache, page_table, pos, cache_index,
                          _fa):
    """One decode/prefill step over int8 KV pages (module docstring).

    Gather codes+scales through the page table into the dense
    ``[b, max_len]`` view, dequantize, overlay the EXACT in-call K/V
    block at ``pos`` (in-call positions attend at full precision — only
    history is round-tripped), attend with the same fixed-length mask
    as the native path, then requantize ONLY the statically-bounded
    window of pages this block touched (``ceil(t / page_size) + 1``
    pages from ``cache_index // page_size``); untouched pages keep
    their frozen codes bit-for-bit, which is what makes host swap and
    prefix-cache reuse of quantized pages lossless."""
    b, t = pos.shape
    heads, head_dim = k.shape[2], k.shape[3]
    ps = cache["k"].shape[1]
    pmax = page_table.shape[1]
    max_len = pmax * ps
    scratch_page = cache["k"].shape[0] - 1
    rows = jnp.arange(b)[:, None]

    def dense_view(codes, scale, block):
        deq = (codes[page_table].astype(jnp.float32)
               * scale[page_table][..., None, None, None])
        view = deq.reshape(b, max_len, heads, head_dim)
        # mode="drop": the decode ghost position (>= max_len) must not
        # clamp onto the last real cell, same rule as the native path
        return view.at[rows, pos].set(block.astype(jnp.float32),
                                      mode="drop")
    with jax.named_scope("attn.cache"):
        k_dense = dense_view(cache["k"], cache["k_scale"], k)
        v_dense = dense_view(cache["v"], cache["v_scale"], v)
    # requantize the touched window BEFORE attending so the optional
    # kernel path can read a complete pool. Positions [cache_index,
    # cache_index + t) span at most ceil(t/ps) + 1 logical pages
    # starting at cache_index // ps (the cursor may sit mid-page).
    n_touch = -(-t // ps) + 1
    with jax.named_scope("cache.write"):
        first = jnp.clip(cache_index // ps, 0, pmax - 1)
        win = first[:, None] + jnp.arange(n_touch)[None, :]  # [b, n_touch]
        last = jnp.clip((cache_index + t - 1) // ps, 0, pmax - 1)
        ok_w = (win <= last[:, None]) & (win < pmax)
        win_c = jnp.clip(win, 0, pmax - 1)
        phys_w = jnp.where(ok_w,
                           jnp.take_along_axis(page_table, win_c, axis=1),
                           scratch_page)
        cell = win_c[..., None] * ps + jnp.arange(ps)[None, None, :]
        bidx = jnp.arange(b)[:, None, None]
        # cells past the row's post-call length are zeroed before amax so a
        # page's scale only reflects real tokens (incl. this call's block
        # and its padding, which the native path also writes)
        valid = cell < (cache_index + t)[:, None, None]
        kq, ksc = quantize_kv_page(k_dense[bidx, cell], valid)
        vq, vsc = quantize_kv_page(v_dense[bidx, cell], valid)
        new_cache = {"k": cache["k"].at[phys_w].set(kq),
                     "v": cache["v"].at[phys_w].set(vq),
                     "k_scale": cache["k_scale"].at[phys_w].set(ksc),
                     "v_scale": cache["v_scale"].at[phys_w].set(vsc)}
    with jax.named_scope("attn.scores"):
        if _fa.PAGED_INT8_KERNEL and _fa.paged_dispatch(
                q.shape, (scratch_page + 1, ps, heads, head_dim),
                page_table.shape, q.dtype):
            # follow-up flag (default OFF, the groupnorm lesson): feed the
            # fused kernel a dequantized f32 pool so the page DMAs stay
            # kernel-side. The pool already holds this call's block, so
            # the kernel sees ROUND-TRIPPED in-call values where the XLA
            # path overlays them exactly — a stepping stone, not a win,
            # until the dequant moves inside the kernel grid (DESIGN.md
            # §19).
            k_pool = dequantize_kv_page(new_cache["k"],
                                        new_cache["k_scale"], q.dtype)
            v_pool = dequantize_kv_page(new_cache["v"],
                                        new_cache["v_scale"], q.dtype)
            out = _fa.paged_flash_attention(q, k_pool, v_pool, page_table,
                                            cache_index,
                                            interpret=_fa.PAGED_INTERPRET)
        else:
            key_pos = jnp.arange(max_len)
            mask = key_pos[None, None, None, :] <= pos[:, None, :, None]
            out = dot_product_attention(q, k_dense.astype(q.dtype),
                                        v_dense.astype(q.dtype), mask=mask)
    return out, new_cache


def page_bytes(model: CausalLM, page_size: int, dtype=None,
               kv_dtype=None) -> int:
    """HBM bytes one logical page costs (k + v cells across every
    layer) — the allocation unit the paged pool budgets in, replacing
    the per-slot :meth:`CausalLM.cache_bytes_per_row` rectangle. With
    ``kv_dtype="int8"`` a page is int8 codes plus one f32 scale per
    (layer, k/v): ~4x smaller than f32 pages, ~2x smaller than bf16."""
    if kv_dtype == "int8":
        return (2 * model.num_layers * page_size * model.width
                + 2 * model.num_layers * 4)
    if dtype is None:
        dtype = precision_lib.resolve(model.precision, model.dtype)[0]
    return (2 * model.num_layers * page_size * model.width
            * np.dtype(dtype).itemsize)


def gpt_small(**kw) -> CausalLM:
    """GPT-2-small shape (124M)."""
    return CausalLM(vocab_size=50304, max_len=1024, num_layers=12,
                    num_heads=12, width=768, mlp_dim=3072, **kw)


def gpt_tiny(**kw) -> CausalLM:
    """Test-sized causal LM."""
    defaults = dict(vocab_size=256, max_len=128, num_layers=2, num_heads=2,
                    width=32, mlp_dim=64, dtype=jnp.float32)
    defaults.update(kw)
    return CausalLM(**defaults)
