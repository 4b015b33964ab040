"""The compiled serving step, read without a chip.

The rectangular KV pool lives on the device as ``[rows, max_len, width]``
leaves: a last dimension of a multiple of 128 lanes, which the TPU stores
as written. A leaf whose last dimension is a 64-wide ``head_dim`` it
keeps position-minor instead, and every step then copies the whole pool
between the two forms, on entry, for the gathered rows, and on exit
(59 of a 70 ms decode step at gpt2-medium's widths; PERF.md, PR 25).

These tests compile ``make_decode_fn`` / ``make_prefill_fn`` /
``make_verify_fn`` at gpt2-medium's widths (2 layers, shapes only, no
weights) for a described v5e and assert what the compiler made of the
pool: the default layout at entry, and no ``copy`` or ``transpose`` of a
leaf's size, or of a quarter of one, anywhere in the entry computation.
The TPU compiler is installed with libtpu; where it cannot describe the
topology the tests skip. Nothing here runs, and nothing here is a time.

The latent-attention family (models/latent_moe.py) keeps one line a
position of 256 + 64 values. Stored as 320 the compiler keeps the leaf
position-minor (``{1,2,0}``) and copies the whole pool every step, the
same trap; padded to 384 (three 128-lane tiles) the leaf is stored as
written. Its cases compile the steps at the published widths, 2 layers,
and hold them to the same two assertions.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from distkeras_tpu.models import gpt as gpt_lib
from distkeras_tpu.serving import generation

NUM_SLOTS = 32
MAX_LEN = 1024
WIDTH = 1024
#: a quarter of the smallest rectangle a step could copy (8 lanes)
QUARTER = 8 * MAX_LEN * WIDTH // 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shapes(one_chip):
    """gpt2-medium's widths at 2 layers: the model, and shape structs of
    its parameters and of a 32-slot pool, placed on the described chip."""
    model = gpt_lib.CausalLM(vocab_size=50304, max_len=MAX_LEN,
                             num_layers=2, num_heads=16, width=WIDTH,
                             mlp_dim=4096)
    put = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    pool = put(jax.eval_shape(
        lambda: model.init_cache(NUM_SLOTS + 1)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32,
                                              sharding=one_chip)
    return model, params, pool, i32


def _compile(shapes, step, lanes_or_bucket):
    model, params, pool, i32 = shapes
    n = lanes_or_bucket
    if step == "decode":
        fn, args = generation.make_decode_fn(model), (i32(n), i32(n), i32(n))
    elif step == "verify":
        fn, args = generation.make_verify_fn(model), (i32(n), i32(n, 4),
                                                      i32(n))
    else:
        fn, args = generation.make_prefill_fn(model), (i32(1, n), i32(),
                                                       i32())
    return jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile().as_text()


def _pool_layouts(text):
    """``(dimensions, layout)`` of the pool's leaves (the bf16 arrays of
    ``NUM_SLOTS + 1`` rows) in ``entry_computation_layout``, parameters
    and results alike."""
    header = text[text.index("entry_computation_layout"):].split("\n", 1)[0]
    return re.findall(rf"bf16\[({NUM_SLOTS + 1},[\d,]+)\]\{{([\d,]+)", header)


def _rectangle_moves(text, least=QUARTER, line=None):
    """``copy``/``transpose`` instructions of the entry computation whose
    result, in the pool's dtype, holds at least ``least`` elements: a
    quarter of an 8-lane rectangle (the float32 logits are no part of
    the pool), and, given ``line``, whose last dimension is the cache
    line's (bfloat16 weights are no part of the pool either). (The
    compiler's own ``copy-start``/``copy-done`` pairs are not counted:
    they prefetch an operand into faster memory in the layout it has.)"""
    entry = text[text.index("\nENTRY"):]
    found = []
    for m in re.finditer(
            r"= \(?bf16\[([\d,]+)\]\S* (copy|transpose)\(", entry):
        dims = [int(d) for d in m.group(1).split(",")]
        if np.prod(dims) >= least and line in (None, dims[-1]):
            found.append(m.group(0))
    return found


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``models/gpt.py`` asks what the backend is, and here that is the
    CPU whatever the compile is for: answer for the described chip, so
    that the step compiled is the one the chip runs (a short block attends
    in the pool, ``ops/pallas/decode_attention.py``)."""
    from distkeras_tpu.ops.pallas import decode_attention

    monkeypatch.setattr(decode_attention, "_on_tpu", lambda: True)


@pytest.mark.parametrize("step,size", [
    ("decode", 8), ("decode", 16), ("decode", 32),
    ("prefill", 64), ("prefill", 768), ("verify", 32)])
def test_compiled_step_keeps_the_pool_as_stored(shapes, as_on_the_chip,
                                                step, size):
    text = _compile(shapes, step, size)
    leaves = _pool_layouts(text)
    # 2 layers x (k, v), as parameters and as results
    assert len(leaves) == 8, leaves
    for dims, layout in leaves:
        rank = dims.count(",") + 1
        default = ",".join(str(d) for d in reversed(range(rank)))
        assert layout == default, f"bf16[{dims}] is kept as {{{layout}}}"
    assert _rectangle_moves(text) == []


def _lanes_rows(text, lanes):
    """Instructions of the entry computation whose result is the lanes'
    whole rows, ``[lanes, max_len, width]``, or those rows in runs,
    ``[lanes * runs, max_len / runs, width]`` (``gather_rows``): what the
    fixed-length path reads out of the pool."""
    entry = text[text.index("\nENTRY"):]
    found = []
    for m in re.finditer(rf"= \(?bf16\[(\d+),(\d+),{WIDTH}\]\S* ([\w\-]+)\(",
                         entry):
        first, second = int(m.group(1)), int(m.group(2))
        if first * second == lanes * MAX_LEN and first % lanes == 0:
            found.append(m.group(0))
    return found


@pytest.mark.parametrize("step,size", [
    ("decode", 8), ("decode", 16), ("decode", 32), ("verify", 32)])
def test_short_block_attends_in_the_pool(shapes, as_on_the_chip, step,
                                         size):
    """One Mosaic call a layer under ``attn.scores``; no gather, fusion or
    anything else whose result is the lanes' rows; each call's two leaves
    are what ``cache.write`` left in place (the scatter's result, itself
    aliased to the pool's parameter), in the default layout, with no copy
    between; and the leaves are still donated through the step."""
    text = _compile(shapes, step, size)
    calls = [line for line in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2                       # the layers
    assert _lanes_rows(text, size) == []
    assert "/attn.cache/" not in text
    pool = rf"bf16\[{NUM_SLOTS + 1},{MAX_LEN},{WIDTH}\]"
    for call in calls:
        assert re.search(r'op_name="[^"]*/attn\.scores/', call), call
        constraints = call[call.index("operand_layout_constraints"):]
        assert len(re.findall(pool + r"\{2,1,0\}", constraints)) == 2
        operands = call[call.index("custom-call(") + 12:].split(")")[0]
        for leaf in operands.split(", ")[-2:]:
            made = re.search(rf"\n\s*{re.escape(leaf)} = ({pool})\S* "
                             rf"(\w+)\((%[\w.\-]+)[^\n]*", text)
            assert made, leaf
            assert made.group(2) == "fusion", made.group(0)[:200]
            assert "/cache.write/" in made.group(0)
            assert made.group(3).startswith("%pool_"), made.group(0)[:200]
    assert _rectangle_moves(text) == []
    aliases = text[text.index("input_output_alias"):].split("\n", 1)[0]
    assert aliases.count("may-alias") + aliases.count("must-alias") == 4


def test_off_the_chip_the_step_gathers_the_lanes_rows(shapes):
    """Unsteered, the same compile takes the path the CPU runs (and the
    paged fallback): the instrument above sees the rows it looks for."""
    text = _compile(shapes, "decode", 32)
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert len(_lanes_rows(text, 32)) >= 4       # k and v, two layers
    assert "/attn.cache/" in text


# ------------------------------------------------- the latent line (PR 27)

@pytest.fixture(scope="module")
def latent_shapes(one_chip):
    """Mistral-Small-4's widths (latent 256 + rotary 64, 32 heads, 32 of
    128 experts held), 2 layers, a 32-slot pool of 4608 positions."""
    from distkeras_tpu.models.latent_moe import LatentMoELM

    model = LatentMoELM(
        vocab_size=32768, max_len=4608, num_layers=2, width=4096,
        num_heads=32, q_lora_rank=1024, kv_lora_rank=256,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        moe_width=2048, num_experts=128, experts_per_token=4,
        expert_share=(0, 4), rope_factor=128.0, position_beta=0.1)
    put = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    pool = put(jax.eval_shape(lambda: model.init_cache(NUM_SLOTS + 1)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32,
                                              sharding=one_chip)
    return model, params, pool, i32


@pytest.mark.parametrize("step,size", [("decode", 32), ("prefill", 512)])
def test_compiled_latent_step_keeps_the_pool_as_stored(latent_shapes, step,
                                                       size):
    model = latent_shapes[0]
    assert model.cache_line == 384      # 256 + 64, padded to whole tiles
    text = _compile(latent_shapes, step, size)
    leaves = _pool_layouts(text)
    assert len(leaves) == 4, leaves     # 2 layers, parameters and results
    for dims, layout in leaves:
        assert dims == f"{NUM_SLOTS + 1},4608,384"
        assert layout == "2,1,0", f"bf16[{dims}] is kept as {{{layout}}}"
    # lines only: a prefill turns its own expanded keys and values round
    # ([1, 512, 32, 128] each, the long-block form's cost) and the compiler
    # copies bfloat16 weights into fast memory; neither is the pool
    assert _rectangle_moves(text, least=8 * 4608 * 384 // 4, line=384) == []


# ------------------------------------- the greedy engine's step (PR 28)

@pytest.mark.parametrize("family,line", [
    ("gpt2_medium", None), ("mistral_small_4", 384)])
def test_greedy_step_hands_back_tokens_and_keeps_the_pool(
        shapes, latent_shapes, as_on_the_chip, family, line):
    """What a greedy ``GenerationEngine`` compiles per ladder entry
    (``pick_on_device`` around ``make_decode_fn``, the pool donated), at
    32 lanes: the executable keeps the name the trace readers look for,
    its results hold the lanes' tokens and no ``[lanes, vocabulary]``
    array, and the pool is still updated in place, as stored."""
    model, params, pool, i32 = (shapes if family == "gpt2_medium"
                                else latent_shapes)
    n = NUM_SLOTS
    fn = generation.pick_on_device(generation.make_decode_fn(model))
    text = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, i32(n), i32(n), i32(n)).compile().as_text()
    assert re.match(r"HloModule jit_decode[,\s]", text)
    header = text[text.index("entry_computation_layout"):].split("\n", 1)[0]
    results = header[header.index("->"):]
    assert f"s32[{n}]" in results
    assert "f32[" not in results    # no [lanes, vocabulary] logits, nor any
    # donated: every pool leaf among the parameters is aliased to a result
    leaves = len(jax.tree.leaves(pool))
    aliases = text[text.index("input_output_alias"):].split("\n", 1)[0]
    assert aliases.count("may-alias") + aliases.count("must-alias") == leaves
    for dims, layout in _pool_layouts(text):
        rank = dims.count(",") + 1
        assert layout == ",".join(str(d) for d in reversed(range(rank)))
    assert len(_pool_layouts(text)) == 2 * leaves
    least = QUARTER if line is None else 8 * 4608 * 384 // 4
    assert _rectangle_moves(text, least=least, line=line) == []


# ------------- two position leaves and a ring, layers of two kinds (PR 35)

@pytest.fixture(scope="module")
def two_kind_shapes(one_chip):
    """dots3-note-prev's widths (a full layer: latent 512 + rotary 64, 128
    heads, 64 index heads of 128; a window layer: latent 1024 + rotary 64,
    64 heads, window 513 in a ring of 640; 32 of 256 experts held), a
    dense full layer and a window layer with experts, a 32-slot pool of
    11 264 positions: three leaves of three widths."""
    from distkeras_tpu.models.latent_moe import LatentMoELM, WindowSizes

    model = LatentMoELM(
        vocab_size=19008, max_len=11264, num_layers=2, width=5120,
        num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        moe_width=1536, num_experts=256, experts_per_token=8,
        expert_share=(0, 8), rms_eps=1e-5, rope_theta=8e7, dense_layers=1,
        dense_width=13824, scoring="sigmoid", index_heads=64, index_dim=128,
        index_topk=2048, layer_kinds=("F", "S"),
        window_sizes=WindowSizes(
            window=513, ring=640, num_heads=64, q_lora_rank=1024,
            kv_lora_rank=1024, qk_nope_head_dim=192, qk_rope_head_dim=64,
            v_head_dim=128, rope_theta=5e4),
        head_gate=True, rank_rescale=True)
    put = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    pool = put(jax.eval_shape(lambda: model.init_cache(NUM_SLOTS + 1)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32,
                                              sharding=one_chip)
    return model, params, pool, i32


@pytest.mark.parametrize("step,size", [("decode", 32), ("prefill", 6144)])
def test_compiled_two_kind_step_keeps_all_three_leaves_as_stored(
        two_kind_shapes, step, size):
    """The latent line (576 -> 640), the index key (128, one whole tile)
    and the ring's wider line (1088 -> 1152) are each stored as written,
    and neither the score over the lanes' keys, nor the gather of the
    chosen lines, nor the read of the lanes' rings copies a leaf's
    rectangle (a narrow last dimension turned the pool round once:
    PERF.md, PR 25). The four scopes name the compiled instructions."""
    model = two_kind_shapes[0]
    assert model.cache_line == 640      # 512 + 64, padded to whole tiles
    text = _compile(two_kind_shapes, step, size)
    rows = NUM_SLOTS + 1
    assert sorted(_pool_layouts(text)) == sorted(
        [(f"{rows},11264,128", "2,1,0"), (f"{rows},11264,640", "2,1,0"),
         (f"{rows},640,1152", "2,1,0")] * 2)    # parameters and results
    for scope in ("attn.index", "attn.select", "attn.window", "attn.sparse"
                  if step == "decode" else "attn.latent"):
        assert f"/{scope}/" in text, scope
    # a leaf's positions on an axis: a prefill turns its own block's lines
    # round ([6144, 640], [6144, 1152]: the long-block forms' cost), which
    # is no leaf
    for line, positions in ((128, 11264), (640, 11264), (1152, 640)):
        moves = _rectangle_moves(text, least=8 * positions * line // 4,
                                 line=line)
        assert [m for m in moves
                if re.search(rf"[\[,]{positions},{line}\]", m)] == [], moves
