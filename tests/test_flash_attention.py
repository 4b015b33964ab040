"""Fused flash-attention kernel tests (DESIGN.md §23, NUMERICS.md).

Interpret mode makes the Pallas kernels executable on a CPU host, so
parity is pinned where CI actually runs:

- training kernel: forward AND backward match the masked-softmax XLA
  reference at every position within a few ulp (online softmax
  reassociates the reduction — NUMERICS.md states the carve-out);
- the dispatch chain: flag default-off, ``fits()`` honest about shapes,
  ``apply_attention("flash")`` raising (never substituting XLA) off-TPU;
- remat composition: ``jax.checkpoint`` over the custom_vjp recomputes
  to identical gradients;
- paged decode kernel: BITWISE vs the dense-gather reference (the same
  math, so equality is the claim), and through the full gpt decode path
  equal to tests/test_paged_generation.py's oracle (the full-prefix
  forward) at the decode-step tolerance (test_generation.TOL), with the
  kernel genuinely dispatched (spied) and the dense ``[max_len]`` view
  never materialized (it reads ``pages[page_table]`` inside the kernel
  grid).

The compiled (non-interpret) kernels are checked against their XLA
references on the chip by ``chip_smoke.py``'s kernels leg.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import attention as attn
from distkeras_tpu.ops.pallas import flash_attention as fa
from test_generation import TOL


def _qkv(b=2, t=256, h=2, d=32, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
            for _ in range(3)]


def _ref(q, k, v, causal):
    """Independent masked-softmax reference (same math as
    ops.attention.dot_product_attention, spelled out)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qp = jnp.arange(q.shape[1])[:, None]
        kp = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(kp <= qp, s, attn.MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------- forward

@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity_every_position(causal):
    q, k, v = _qkv()
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _ref(q, k, v, causal)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_parity_multi_block_tiles():
    """Mismatched q/k tiles exercise the online-softmax rescale across
    four k-blocks per q-block."""
    q, k, v = _qkv(b=1, t=256, h=2, d=16, seed=1)
    out = fa.flash_attention(q, k, v, causal=True, block_q=64,
                             block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(q, k, v, True)),
                               rtol=1e-5, atol=1e-5)


def test_forward_parity_bf16():
    q, k, v = _qkv(b=1, t=128, h=2, d=32, dtype=jnp.bfloat16, seed=2)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------- backward

def test_backward_parity_vs_reference_grads():
    q, k, v = _qkv(b=2, t=128, h=2, d=32, seed=3)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    flash = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               interpret=True)
    ref = lambda q, k, v: _ref(q, k, v, True)
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} diverged from the reference gradient")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,blocks", [
    (256, (64, 64)),      # 4 x 4 tiles: the diagonal, below and above it
    (256, (64, 128)),     # two q-blocks a key block
    (256, (128, 64)),     # two key blocks a q-block, one masked half-way
    (512, None),          # the shape's own tiles: 2 x 2 of 256
])
def test_backward_parity_several_blocks(causal, t, blocks):
    """dQ, dK and dV where each key block meets several q-blocks (and each
    q-block several key blocks): tiles off the diagonal, wholly visible
    or skipped, and dQ summed over key blocks, against the reference."""
    q, k, v = _qkv(b=1, t=t, h=2, d=32, seed=5)
    block_q, block_k = blocks or (None, None)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    flash = lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True)
    ref = lambda q, k, v: _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} diverged from the reference gradient")


def test_remat_composes_with_custom_vjp():
    """jax.checkpoint over the kernel recomputes the forward in the
    backward pass — gradients must be identical to the un-remat call
    (same kernel, same tiles, deterministic)."""
    q, k, v = _qkv(b=1, t=128, h=2, d=16, seed=4)
    f = lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, causal=True, interpret=True) ** 2)
    g_plain = jax.grad(f)(q, k, v)
    g_remat = jax.grad(jax.checkpoint(f))(q, k, v)
    np.testing.assert_array_equal(np.asarray(g_plain),
                                  np.asarray(g_remat))


# ------------------------------------------------------ dispatch contract

def test_flag_defaults_off():
    assert fa.USE_FLASH_ATTENTION is False
    assert fa.PAGED_INTERPRET is False


def test_kernel_enabled_requires_flag_and_tpu(monkeypatch):
    assert fa.kernel_enabled() is False
    monkeypatch.setattr(fa, "USE_FLASH_ATTENTION", True)
    assert fa.kernel_enabled() is False  # flag alone is not enough (CPU)


def test_fits_predicate():
    f32 = jnp.float32
    assert fa.fits((2, 256, 4, 32), f32)
    assert fa.fits((1, 128, 1, 128), f32)
    assert not fa.fits((2, 100, 4, 32), f32)    # seq not block-aligned
    assert not fa.fits((2, 64, 4, 32), f32)     # below one default tile
    assert not fa.fits((2, 256, 4, 4), f32)     # head_dim under sublane tile
    assert not fa.fits((2, 256, 4, 130), f32)   # head_dim over one lane tile
    assert not fa.fits((256, 4, 32), f32)       # rank
    assert fa.fits((1, 64, 2, 32), f32, block_q=64, block_k=64)  # explicit
    # a lane block's whole sequence is resident: the dtype sets how long
    assert fa.fits((8, 2048, 16, 64), jnp.bfloat16)
    assert not fa.fits((8, 2048, 16, 64), f32)
    with pytest.raises(ValueError, match="both blocks or neither"):
        fa.fits((1, 64, 2, 32), f32, block_q=64)


def test_flash_attention_raises_on_unfit_shape():
    q, k, v = _qkv(b=1, t=128, h=2, d=4)  # head_dim under sublane tile
    with pytest.raises(ValueError, match="fits"):
        fa.flash_attention(q, k, v, interpret=True)
    q, k, v = _qkv(b=1, t=100, h=2, d=32)  # seq not tile-aligned
    with pytest.raises(ValueError, match="fits"):
        fa.flash_attention(q, k, v, block_q=128, block_k=128,
                           interpret=True)
    with pytest.raises(ValueError, match="both blocks or neither"):
        fa.flash_attention(q, k, v, block_k=128, interpret=True)


def test_resolve_attention_modes():
    assert attn.resolve_attention(None) == "xla"
    assert attn.resolve_attention("xla") == "xla"
    assert attn.resolve_attention("flash") == "flash"
    with pytest.raises(ValueError, match="attention"):
        attn.resolve_attention("bogus")


def test_apply_attention_flash_raises_off_tpu():
    """attention="flash" names a TPU kernel: on CPU it must raise and
    name the platform — never run the XLA path under the kernel's name."""
    q, k, v = _qkv(b=1, t=128, h=2, d=16, seed=5)
    with pytest.raises(RuntimeError, match="'cpu'"):
        attn.apply_attention(q, k, v, causal=True, attention="flash")


def test_mha_module_threads_attention_field():
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 128, 32)),
                    jnp.float32)
    outs = {}
    for mode in (None, "xla"):
        mha = attn.MultiHeadAttention(num_heads=2, dtype=jnp.float32,
                                      causal=True, attention=mode)
        params = mha.init(jax.random.key(0), x)
        outs[mode] = np.asarray(mha.apply(params, x))
    np.testing.assert_array_equal(outs[None], outs["xla"])
    flash = attn.MultiHeadAttention(num_heads=2, dtype=jnp.float32,
                                    causal=True, attention="flash")
    with pytest.raises(RuntimeError, match="flash"):  # the field arrived
        flash.apply(params, x)


# ------------------------------------------------------------ paged decode

def test_paged_kernel_bitwise_vs_dense_gather():
    """Direct kernel call vs the dense-gather XLA fallback it replaces
    (gpt.py's own math, permuted page table): bitwise, not allclose."""
    b, t, h, d, ps, pmax = 2, 2, 2, 16, 16, 8
    num_pages = b * pmax + 1
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((num_pages, ps, h, d)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((num_pages, ps, h, d)),
                          jnp.float32)
    table = rng.permutation(num_pages - 1)[:b * pmax].reshape(b, pmax)
    page_table = jnp.asarray(table, jnp.int32)
    cache_index = jnp.asarray([5, ps * pmax - t], jnp.int32)

    max_len = pmax * ps
    gather = lambda pages: pages[page_table].reshape(b, max_len, h, d)
    pos = cache_index[:, None] + jnp.arange(t)[None, :]
    key_pos = jnp.arange(max_len)
    mask = key_pos[None, None, None, :] <= pos[:, None, :, None]
    want = attn.dot_product_attention(q, gather(k_pages), gather(v_pages),
                                      mask=mask)
    got = fa.paged_flash_attention(q, k_pages, v_pages, page_table,
                                   cache_index, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_dispatch_predicate(monkeypatch):
    q_shape, pages, table = (1, 2, 2, 16), (17, 16, 2, 16), (1, 8)
    f32 = jnp.float32
    assert fa.paged_fits(q_shape, pages, table, f32)
    assert not fa.paged_dispatch(q_shape, pages, table, f32)  # default off
    monkeypatch.setattr(fa, "PAGED_INTERPRET", True)
    assert fa.paged_dispatch(q_shape, pages, table, f32)


def test_paged_fits_counts_padded_tiles():
    """GPT-2-small's page geometry (12 heads x 64, 1024 positions): each
    position pads to a (16, 128) tile, so the bf16 view stages 8 MiB
    and fits while the f32 view needs 16 MiB and does not — the two
    verdicts Mosaic itself gave for v5e (16 MiB scoped VMEM). Unpadded
    arithmetic (h * d * 4 bytes) would have accepted both."""
    q_shape, pages, table = (4, 2, 12, 64), (257, 16, 12, 64), (4, 64)
    assert fa.paged_fits(q_shape, pages, table, jnp.bfloat16)
    assert not fa.paged_fits(q_shape, pages, table, jnp.float32)


def test_gpt_decode_through_paged_kernel_equals_full_forward(monkeypatch):
    """The acceptance oracle: the SAME harness as test_paged_generation's
    every-position test, but with the paged kernel forced into the
    dispatch (PAGED_INTERPRET) and spied on — every decode step's logits
    equal the padded full-prefix forward's at the decode-step tolerance
    while the attention contraction runs inside the kernel, pages indexed
    by page_table with no dense [max_len] gather in the traced program."""
    from distkeras_tpu.models.gpt import gpt_tiny
    from distkeras_tpu.serving import PagedKVCachePool
    from distkeras_tpu.serving.generation import make_paged_step_fn

    calls = []
    real = fa.paged_flash_attention
    monkeypatch.setattr(fa, "PAGED_INTERPRET", True)
    monkeypatch.setattr(
        fa, "paged_flash_attention",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])

    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    full = jax.jit(lambda ids: model.apply({"params": params}, ids))

    def ref(seq):
        pad = np.zeros((1, model.max_len), np.int32)
        pad[0, :len(seq)] = seq
        return np.asarray(full(pad))[0, len(seq) - 1]

    pool = PagedKVCachePool(model, num_slots=2, page_size=16)
    step = jax.jit(make_paged_step_fn(model), donate_argnums=(1,))
    a, b = pool.allocate(), pool.allocate()
    # interleave so slot a's pages are NOT contiguous (table is honest)
    assert pool.reserve(a, 16) and pool.reserve(b, 16)
    assert pool.reserve(a, model.max_len) and pool.reserve(b, model.max_len)

    seq = np.random.default_rng(8).integers(1, 256, 5).tolist()
    ids = np.zeros((1, 8), np.int32)
    ids[0, :5] = seq
    pts = pool.page_table_row(a)[None, :]
    new_pool, logits = step(params, pool.pool, pts, ids,
                            np.zeros(1, np.int32))
    pool.swap(new_pool)
    pool.lengths[a] = 5
    np.testing.assert_allclose(np.asarray(logits)[0, 4], ref(seq), **TOL)
    tok = int(np.argmax(np.asarray(logits)[0, 4]))
    for _ in range(24):
        feed = np.array([[tok, 0]], np.int32)  # token + ghost
        new_pool, logits = step(params, pool.pool, pts, feed,
                                pool.lengths[a:a + 1].copy())
        pool.swap(new_pool)
        pool.lengths[a] += 1
        seq.append(tok)
        row = np.asarray(logits)[0, 0]
        np.testing.assert_allclose(row, ref(seq), **TOL)
        tok = int(np.argmax(row))
    assert calls, "paged kernel never dispatched — oracle ran the fallback"


# ----------------------------------------------------------- cost models

def test_modeled_costs_are_consistent():
    shape = (2, 1024, 8, 64)
    f_fwd, b_fwd = fa.modeled_cost(shape)
    f_xla, b_xla = fa.xla_modeled_cost(shape)
    f_train, b_train = fa.modeled_train_cost(shape)
    assert f_fwd == f_xla  # the fusion saves traffic, not math
    assert b_xla > b_fwd   # ... by the [T, T] logits round-trips
    assert f_train > f_fwd and b_train > b_fwd  # backward is extra
    # the whole point: fused bytes stay linear in T
    _, b_fwd2 = fa.modeled_cost((2, 2048, 8, 64))
    _, b_xla2 = fa.xla_modeled_cost((2, 2048, 8, 64))
    assert b_fwd2 / b_fwd < 2.5 < (b_xla2 - b_fwd2) / (b_xla - b_fwd)
