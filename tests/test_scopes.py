"""Device time by program scope (profiling/scopes.py, ISSUE 37).

- the table of a small jitted function: every instruction that runs as a
  device event of its own under the innermost declared scope of its
  ``op_name`` path, fusions whole, ``""`` for what was traced outside;
- the four families' serving executables (the rehearsal configurations of
  ``perf/configs``, built by their ``perf/builders``): at least 95 % of the
  instructions under a declared scope, and every scope a family declares
  occurring;
- the two readers of ``perf/readers`` on hand-made ``reduced`` dictionaries;
- the exact join on hand-made events of the chip's form;
- the registry: bounded, replaced by key, and read by nobody who was not
  asked; ``dump``/``load``.

All on the CPU; nothing here is a time.
"""

import copy
import importlib
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu import profiling
from distkeras_tpu.profiling import cost_model, scopes
from distkeras_tpu.serving import GenerationEngine

PERF = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perf")


@pytest.fixture(autouse=True)
def empty_registry():
    scopes.clear()
    yield
    scopes.clear()


@pytest.fixture
def perf(monkeypatch):
    """``perf/`` on the path, as ``python perf/run.py`` has it; its
    bare-named modules are taken out of the other tests' way after."""
    monkeypatch.syspath_prepend(PERF)
    before = set(sys.modules)
    yield lambda kind, name: importlib.import_module(f"{kind}.{name}")
    for key in set(sys.modules) - before:
        where = [getattr(sys.modules[key], "__file__", None) or "",
                 *getattr(sys.modules[key], "__path__", [])]
        if any(w.startswith(PERF + os.sep) for w in where):
            del sys.modules[key]


# ------------------------------------------------- (a) one small function

def _small(x, w):
    with jax.named_scope("outer"):
        y = x @ w
        with jax.named_scope("inner"):
            y = jnp.tanh(y)

    def body(c, _):
        with jax.named_scope("loop"):
            return jnp.sin(c) @ w, ()

    y, _ = jax.lax.scan(body, y, None, length=3)
    return jnp.sum(y * 2.0)             # traced under no scope


def test_table_of_a_small_function():
    ones = jnp.ones((64, 64))
    text = jax.jit(_small).lower(ones, ones).compile().as_text()
    table = scopes.table_of(text, "k", declared=("outer", "inner", "loop"))
    assert table.kind == "jit__small" and table.key == "k"
    by_name = {r.name: r for r in table.rows}
    assert len(by_name) == len(table.rows)
    # what a row says, against the text read here by other means: the
    # innermost declared name on the instruction's own op_name path
    own = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*op_name=\"([^\"]*)\"",
                     line)
        if m:
            names = [s for s in m.group(2).split("/")
                     if s in ("outer", "inner", "loop")]
            own[m.group(1)] = names[-1] if names else ""
    checked = [r for r in table.rows if r.name in own]
    assert len(checked) >= 6
    for r in checked:
        assert r.scope == own[r.name] and not r.scope_inferred, r
    seen = {r.scope for r in table.rows}
    assert seen == {"outer", "inner", "loop", ""}
    # the scan's body and condition are walked, the while itself and the
    # tuple plumbing have no row, and no row is an instruction INSIDE a
    # fusion (those are called param_*, or live in *_computation)
    assert not {r.opcode for r in table.rows} & {
        "while", "tuple", "get-tuple-element", "parameter", "constant"}
    assert any(r.scope == "loop" and r.opcode == "dot" for r in table.rows)
    assert any(r.scope == "inner" and r.opcode == "fusion"
               for r in table.rows)
    tail = [r for r in table.rows if "reduce" in r.name]
    assert tail and all(r.scope == "" for r in tail)
    for r in table.rows:
        assert r.out_type and r.out_type.split("[")[0] in (
            "f32", "s32", "pred"), r


def test_scope_of_a_wrapped_segment_and_of_none():
    declared = frozenset({"attn.qkv", "mlp"})
    path = lambda p: f'metadata={{op_name="{p}" stack_frame_id=3}}'
    assert cost_model._scope(
        path("jit(step)/transpose(jvp(attn.qkv))/dot_general"),
        declared) == "attn.qkv"
    assert cost_model._scope(path("jit(f)/mlp/attn.qkv/add"),
                             declared) == "attn.qkv"
    assert cost_model._scope(path("jit(f)/attn.qkv/mlp/mlp/add"),
                             declared) == "mlp"
    assert cost_model._scope(path("jit(f)/attn/add"), declared) == ""
    assert cost_model._scope("", declared) == ""
    assert cost_model._scope(path("jit(f)/mlp/add"), frozenset()) == ""


_TPU_TEXT = """\
HloModule jit_decode, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,128]) -> bf16[8,128] {
  %param_0.1 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %convert.3 = f32[8,128]{1,0:T(8,128)} convert(%param_0.1), metadata={op_name="jit(decode)/mlp/convert_element_type" stack_frame_id=4}
  ROOT %convert.4 = bf16[8,128]{1,0:T(8,128)(2,1)} convert(%convert.3)
}

ENTRY %main.9 (w.1: bf16[8,128], x.1: bf16[8,128]) -> bf16[8,128] {
  %w.1 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %x.1 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(1)
  %copy-start.2 = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, bf16[8,128]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%w.1)
  %copy-done.2 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.2)
  %fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.1
  %add.5 = bf16[8,128]{1,0:T(8,128)(2,1)} add(%fusion.7, %x.1), metadata={op_name="jit(decode)/add" stack_frame_id=2}
  ROOT %negate.6 = bf16[8,128]{1,0:T(8,128)(2,1)} negate(%add.5)
}
"""


def test_tiled_layouts_parse_and_the_compilers_own_copies_are_inferred():
    """A TPU executable's types carry tiles in parentheses; a fusion
    without a path of its own takes the one nearest its root; a prefetch
    the compiler made takes its user's scope and says so; an instruction
    traced outside every scope stays ``""``, and so does what only it
    feeds."""
    table = scopes.table_of(_TPU_TEXT, declared=("mlp",))
    rows = {r.name: r for r in table.rows}
    assert set(rows) == {"copy-start.2", "copy-done.2", "fusion.7",
                         "add.5", "negate.6"}
    assert (rows["fusion.7"].scope, rows["fusion.7"].scope_inferred) == \
        ("mlp", False)
    for name in ("copy-start.2", "copy-done.2"):
        assert (rows[name].scope, rows[name].scope_inferred) == ("mlp", True)
    assert rows["add.5"].scope == "" and rows["negate.6"].scope == ""
    assert rows["fusion.7"].out_type == "bf16[8,128]{1,0:T(8,128)(2,1)}"
    assert scopes.plain_type(rows["copy-start.2"].out_type) == \
        "(bf16[8,128],bf16[8,128],u32[])"


_KERNEL_TEXT = """\
HloModule jit_decode, is_scheduled=true

ENTRY %main.9 (rows.1: s32[32], len.1: s32[32], q.1: bf16[32,32,1024], k.1: bf16[33,1024,1024], v.1: bf16[33,1024,1024]) -> f32[32,2,1024] {
  %rows.1 = s32[32]{0:T(128)} parameter(0)
  %len.1 = s32[32]{0:T(128)} parameter(1)
  %q.1 = bf16[32,32,1024]{2,1,0:T(8,128)(2,1)} parameter(2)
  %k.1 = bf16[33,1024,1024]{2,1,0:T(8,128)(2,1)} parameter(3)
  %v.1 = bf16[33,1024,1024]{2,1,0:T(8,128)(2,1)} parameter(4)
  ROOT %pool_attention.2 = f32[32,2,1024]{2,1,0:T(2,128)S(1)} custom-call(%rows.1, %len.1, %q.1, %k.1, %v.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[32]{0}, s32[32]{0}, bf16[32,32,1024]{2,1,0}, bf16[33,1024,1024]{2,1,0}, bf16[33,1024,1024]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(decode)/CausalLM/layer_0/attn/attn.scores/jit(_pool_attention)/pool_attention/pallas_call" stack_frame_id=56}, backend_config={"custom_call_config":{"body":"TUzvUg"}}
}
"""


def test_a_mosaic_call_is_a_row_of_the_scope_it_was_traced_under():
    """The gpt decode step's attention on a TPU is one custom call a
    layer (``ops/pallas/decode_attention.py``), traced under
    ``attn.scores``: a row of its own there, by the line the TPU compiler
    writes for it, so ``decode_attn_device_share`` and
    ``scope_attributed_share`` hold its seconds. Nothing of the step is
    under ``attn.cache`` any more; the name stays declared for the paths
    that still read rows out of a pool."""
    table = scopes.table_of(_KERNEL_TEXT)
    assert [(r.name, r.opcode, r.scope, r.scope_inferred)
            for r in table.rows] == [
        ("pool_attention.2", "custom-call", "attn.scores", False)]
    assert scopes.plain_type(table.rows[0].out_type) == "f32[32,2,1024]"
    assert {"attn.cache", "attn.scores"} <= set(scopes.declared_scopes())


# --------------------------------------- (b) the four families' executables

#: rehearsal configuration -> the scopes its family's file declares that
#: this configuration has no mechanism for
FAMILIES = {
    "gpt2_tiny": (),
    "mistral_small_4_tiny": ("attn.index", "attn.select", "attn.sparse",
                             "attn.window", "mlp.dense"),
    "nemotron3_nano_tiny": (),
    "dots3_note_tiny": (),
}
_tables = {}


def _family_tables(name, perf):
    """The rehearsal configuration's engine, with a 320-token prefill
    bucket beside the 16-token one (a long block: the expanded and banded
    attention, the chunked scan, the experts' block loop) and one decode
    executable; its tables and the scopes its family declares."""
    if name not in _tables:
        import harness
        cfg = copy.deepcopy(harness.load_json("configs", name + ".json"))
        builder = perf("builders", cfg.get("code", cfg["name"]))
        cfg["n_positions"] = 384
        cfg["serving"].update(prefill_buckets=[16, 320], slot_ladder=[2],
                              num_slots=2)
        model = builder.build_model(cfg, "serve")
        params = builder.init_params(model, 0)
        scopes.clear()
        with GenerationEngine(model, params,
                              **builder.serving_kwargs(cfg)):
            pass
        declared = importlib.import_module(type(model).__module__).SCOPES
        _tables[name] = (scopes.scope_tables(), declared)
    return _tables[name]


@pytest.mark.parametrize("kind", ["jit_decode", "jit_prefill"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_family_executables_lie_under_declared_scopes(name, kind, perf):
    tables, declared = _family_tables(name, perf)
    mine = [t for t in tables if t.kind == kind]
    assert len(mine) == (2 if kind == "jit_prefill" else 1), \
        [(t.kind, t.key) for t in tables]
    for t in mine:
        assert len(t.rows) > 50
        under = sum(1 for r in t.rows if r.scope)
        assert under >= 0.95 * len(t.rows), (
            t.key, under, len(t.rows),
            sorted({(r.opcode, r.name) for r in t.rows if not r.scope})[:10])
        assert {r.scope for r in t.rows} <= set(scopes.declared_scopes()) \
            | {""}
    # every scope the family declares occurs in one of its executables
    seen = {r.scope for t in tables for r in t.rows}
    assert set(declared) - set(FAMILIES[name]) <= seen, \
        sorted(set(declared) - seen)
    assert not set(FAMILIES[name]) & seen
    if kind == "jit_decode":
        assert "pick" in {r.scope for r in mine[0].rows}


# ------------------------------------------------------------ (c) readers

class _Ctx:
    tracer = None

    def __init__(self):
        self.lines = []

    def log(self, message):
        self.lines.append(message)


def _row(name, out_type, scope, opcode="fusion"):
    return cost_model.OpCost(name=name, opcode=opcode, flops=1e6,
                             bytes_accessed=1e3, output_bytes=1e2,
                             out_type=out_type, scope=scope)


def _hand_tables():
    """Two prefill buckets and a decode executable. ``fusion f32[8,64]`` is
    one scope in one kind; ``fusion bf16[2,64]`` is in both kinds;
    ``copy f32[4]`` has no declared scope; within jit_prefill ``fusion
    f32[1,16]`` is two scopes."""
    return [
        scopes.ScopeTable("jit_prefill", "prefill=16", [
            _row("fusion.1", "f32[8,64]{1,0}", "attn.scores"),
            _row("fusion.2", "f32[8,64]{1,0}", "attn.scores"),
            _row("fusion.3", "bf16[2,64]{1,0}", "mlp"),
            _row("fusion.4", "f32[1,16]{1,0}", "mlp"),
            _row("fusion.5", "f32[1,16]{1,0}", "norm"),
            _row("copy.6", "f32[4]{0}", "", "copy")]),
        scopes.ScopeTable("jit_prefill", "prefill=32", [
            _row("fusion.9", "f32[8,64]{1,0}", "attn.scores"),
            _row("fusion.7", "(f32[16,64]{1,0}, f32[16]{0})", "moe.route")]),
        scopes.ScopeTable("jit_decode", "lanes=2", [
            _row("fusion.1", "bf16[2,64]{1,0}", "mlp"),
            _row("fusion.8", "f32[2,128]{1,0}", "attn.cache")]),
    ]


def _hand_reduced():
    return {
        "busy_s": 10.0,
        "modules": {"jit_prefill": {"runs": 4, "seconds": 4.0},
                    "jit_decode": {"runs": 100, "seconds": 5.0},
                    "jit_other": {"runs": 1, "seconds": 0.5}},
        "op_seconds": {"fusion f32[8,64]": 1.0, "fusion bf16[2,64]": 2.0,
                       "fusion f32[1,16]": 0.25, "copy f32[4]": 0.125,
                       "fusion f32[16,64], ..": 0.5,
                       "fusion f32[2,128]": 3.0, "fusion s32[7]": 0.5}}


@pytest.fixture
def hand_made(monkeypatch, perf):
    monkeypatch.setattr(scopes, "scope_tables", _hand_tables)
    return _Ctx(), _hand_reduced(), perf


@pytest.mark.parametrize("module,prefixes,want", [
    ("jit_prefill", ["attn."], 25.0),           # one kind and scope
    ("jit_prefill", ["moe."], 12.5),            # a tuple-typed key
    ("jit_prefill", ["attn.", "moe."], 37.5),   # two prefixes
    ("jit_decode", ["attn."], 60.0),
    ("jit_prefill", ["mlp"], None),    # shared by two kinds, and by two
    ("jit_decode", ["mlp"], None),     # scopes of one kind: given to nobody
    ("jit_prefill", ["ssm."], None),            # no such mechanism
    ("jit_verify", ["attn."], None),            # no such executable ran
])
def test_scope_share_on_hand_made_tables(hand_made, module, prefixes, want):
    ctx, reduced, perf = hand_made
    got = perf("readers", "scope_share").read(ctx, reduced, module=module,
                                      scopes=prefixes)
    assert got == (want if want is None else pytest.approx(want))


def test_scope_share_over_100_is_not_reported(hand_made):
    """A key of another executable that no table holds, taken for this
    one's: the reader says so and reports nothing; it never clamps."""
    ctx, reduced, perf = hand_made
    reduced["op_seconds"]["fusion f32[2,128]"] = 7.5
    assert perf("readers", "scope_share").read(
        ctx, reduced, module="jit_decode", scopes=["attn."]) is None
    assert any("not reported" in line for line in ctx.lines)


def test_readers_without_the_programs_scopes(monkeypatch, perf):
    """The parent's program has no ``profiling.scopes``: nothing to read,
    and nothing raised."""
    monkeypatch.delattr(profiling, "scopes")
    monkeypatch.setitem(sys.modules, "distkeras_tpu.profiling.scopes", None)
    ctx = _Ctx()
    assert perf("readers", "scope_share").read(
        ctx, _hand_reduced(), module="jit_prefill", scopes=["attn."]) is None
    assert perf("readers", "scope_attributed_share").read(
        ctx, _hand_reduced()) is None
    assert sum("no distkeras_tpu.profiling.scopes" in line
               for line in ctx.lines) == 1          # once a run


@pytest.mark.parametrize("reduced", [None, {"devices": 0},
                                     {"op_seconds": {}, "modules": {}}])
def test_readers_without_a_device_trace(hand_made, reduced):
    ctx, _, perf = hand_made
    assert perf("readers", "scope_share").read(
        ctx, reduced, module="jit_prefill", scopes=["attn."]) is None
    assert perf("readers", "scope_attributed_share").read(
        ctx, reduced) is None


def test_scope_attributed_share_and_its_logged_table(hand_made, tmp_path):
    ctx, reduced, perf = hand_made
    ctx.tracer = types.SimpleNamespace(keep=str(tmp_path))
    got = perf("readers", "scope_attributed_share").read(ctx, reduced)
    # one kind and one declared scope: 1.0 + 0.5 + 3.0 of 10 s busy; the
    # copy has no declared scope, two keys are ambiguous, one is unknown
    assert got == pytest.approx(45.0)
    text = "\n".join(ctx.lines)
    assert re.search(r"jit_decode\s+attn\.cache\s+3\.0000 s\s+60\.0 %", text)
    assert re.search(r"jit_prefill\s+attn\.scores\s+1\.0000 s\s+25\.0 %",
                     text)
    assert re.search(r"jit_prefill\s+\(no declared scope\)\s+0\.1250 s", text)
    assert "ambiguous: 2 keys, 2.2500 s = 22.5 % of busy" in text
    assert "ambiguous 'fusion bf16[2,64]' 2.0000 s " \
        "jit_decode:mlp; jit_prefill:mlp" in text
    assert "ambiguous 'fusion f32[1,16]' 0.2500 s " \
        "jit_prefill:mlp; jit_prefill:norm" in text
    assert "unknown: 1 keys, 0.5000 s = 5.0 % of busy" in text
    assert "unknown 'fusion s32[7]' 0.5000 s" in text
    # everything the trace held is in one of the four heaps
    assert "all keys 7.3750 s = 73.8 % of busy 10.0000 s" in text
    # with a kept trace the tables are left beside it
    kept = scopes.load(str(tmp_path / "scope_tables.json"))
    assert [(t.kind, t.key, len(t.rows)) for t in kept] == [
        ("jit_prefill", "prefill=16", 6), ("jit_prefill", "prefill=32", 2),
        ("jit_decode", "lanes=2", 2)]


# ---------------------------------------------------- (d) the exact join

def _chip_events():
    """Two prefill buckets whose ``%fusion.12`` differ in type and scope,
    inside runs of two fingerprints; a decode run; an event in no run, one
    no table has, and a ``while`` that spans its body."""
    def ev(name, typ, opcode, start, dur):
        return (f"%{name} = {typ} {opcode}(%p.1, %p.2), kind=kLoop", start,
                dur)

    modules = [("jit_prefill(123)", 0.0, 1.0), ("jit_decode(77)", 1.0, 0.5),
               ("jit_prefill(456)", 2.0, 1.0), ("jit_prefill(123)", 4.0, 1.0)]
    ops = [
        ev("fusion.12", "bf16[1,16,64]{2,1,0:T(8,128)(2,1)}", "fusion",
           0.0, 0.25),
        ev("while.3", "(s32[]{:T(128)}, f32[4]{0}, /*index=2*/s32[]{:T(128)})",
           "while", 0.25, 0.5),
        ev("fusion.40", "f32[16,64]{1,0:T(8,128)}", "fusion", 0.25, 0.5),
        ev("fusion.1", "bf16[2,64]{1,0:T(2,128)(2,1)}", "fusion", 1.0, 0.25),
        ev("fusion.99", "f32[3]{0:T(128)}", "fusion", 1.25, 0.125),
        ev("fusion.12", "bf16[1,32,64]{2,1,0:T(8,128)(2,1)}", "fusion",
           2.0, 0.75),
        ev("fusion.12", "bf16[1,16,64]{2,1,0:T(8,128)(2,1)}", "fusion",
           4.0, 0.25),
        ev("copy.5", "f32[4]{0:T(128)}", "copy", 9.0, 0.0625),
    ]
    tables = [
        scopes.ScopeTable("jit_prefill", "prefill=16", [
            _row("fusion.12", "bf16[1,16,64]{2,1,0}", "attn.scores"),
            _row("fusion.40", "f32[16,64]{1,0}", "mlp")]),
        scopes.ScopeTable("jit_prefill", "prefill=32", [
            _row("fusion.12", "bf16[1,32,64]{2,1,0}", "mlp"),
            _row("fusion.40", "f32[32,64]{1,0}", "mlp")]),
        scopes.ScopeTable("jit_decode", "lanes=2", [
            _row("fusion.1", "bf16[2,64]{1,0}", "attn.cache")]),
    ]
    return ops, modules, tables


def test_exact_join_on_events_of_the_chips_form():
    ops, modules, tables = _chip_events()
    joined = scopes.join_events(ops, modules, tables)
    secs = {pair: cell[0] for pair, cell in joined.cells.items()}
    assert secs == {
        ("jit_prefill", "attn.scores"): 0.5,     # two runs of bucket 16
        ("jit_prefill", "mlp"): 1.25,            # 0.5 of 16, 0.75 of 32
        ("jit_decode", "attn.cache"): 0.25,
        ("jit_decode", scopes.UNKNOWN): 0.125,
        ("", scopes.UNKNOWN): 0.0625}
    assert joined.modules == {"jit_prefill": [3, 3.0],
                              "jit_decode": [1, 0.5]}
    # modelled work once an event; seconds by instruction for the roofline
    assert joined.cells[("jit_prefill", "attn.scores")][1:] == \
        [2, 2e6, 2e3]
    assert joined.instructions[("jit_prefill", "prefill=16")] == {
        "fusion.12": 0.5, "fusion.40": 0.5}
    assert joined.instructions[("jit_prefill", "prefill=32")] == {
        "fusion.12": 0.75}
    assert joined.unknown == {("jit_decode", "fusion.99 f32[3]"): 0.125,
                              ("", "copy.5 f32[4]"): 0.0625}
    assert joined.share("jit_prefill", ["attn."]) == pytest.approx(
        100 * 0.5 / 3.0)
    assert joined.share("jit_prefill", ["attn.", "mlp"]) == pytest.approx(
        100 * 1.75 / 3.0)
    assert joined.share("jit_verify", ["attn."]) is None
    text = joined.render()
    assert re.search(r"jit_prefill\s+\(3 runs\)\s+3\.0000\s+100\.0", text)
    assert re.search(r"mlp\s+1\.2500\s+41\.7\s+2\s", text)
    assert "unknown in jit_decode: fusion.99 f32[3] 0.1250 s" in text


def test_join_feeds_the_roofline_report():
    ops, modules, tables = _chip_events()
    joined = scopes.join_events(ops, modules, tables)
    inventory = cost_model.OpInventory(rows=tables[0].rows)
    report = profiling.build_report(
        inventory, peak_flops=1e12, hbm_bandwidth=1e11,
        measured=joined.instructions[("jit_prefill", "prefill=16")])
    assert report.measured_share == pytest.approx(1.0)
    assert all(r.measured for r in report.rows)
    assert report.total_time_s == pytest.approx(1.0)


# ---------------------------------------------------- (e) the registry

class _Text:
    """Stands in for a ``Compiled``: counts the reads of its text."""
    reads = 0

    def __init__(self, kind):
        self.kind = kind

    def as_text(self):
        type(self).reads += 1
        return _TPU_TEXT.replace("jit_decode", self.kind)


def test_registry_is_bounded_and_replaced_by_key():
    _Text.reads = 0
    for engine in range(100):           # 100 engines' worth of inserts
        for lb in (16, 32, 64):
            scopes.register("jit_prefill", f"prefill={lb}",
                            _Text("jit_prefill"))
        for n in (2, 4):
            scopes.register("jit_decode", f"lanes={n}", _Text("jit_decode"))
    assert scopes.registered() == 5 and _Text.reads == 0
    for other in range(2 * scopes.MAX_EXECUTABLES):
        scopes.register("jit_step", f"lanes={other}", _Text("jit_step"))
    assert scopes.registered() == scopes.MAX_EXECUTABLES
    tables = scopes.scope_tables()
    assert len(tables) == scopes.MAX_EXECUTABLES
    assert {t.kind for t in tables} == {"jit_step"}     # the oldest went
    assert tables[-1].key == f"lanes={2 * scopes.MAX_EXECUTABLES - 1}"
    reads = _Text.reads
    assert reads == scopes.MAX_EXECUTABLES
    scopes.scope_tables()                # a table stands in the text's place
    assert _Text.reads == reads and scopes.build_seconds > 0
    scopes.register("jit_step", "lanes=0", _Text("jit_step"))
    assert len(scopes.scope_tables()[-1].rows) == 5 \
        and _Text.reads == reads + 1


def test_an_engine_registers_and_reads_nothing(monkeypatch):
    """Building an engine and running requests through it inserts its
    executables and never turns one into a table."""
    from distkeras_tpu.models.gpt import gpt_tiny

    def never(*a, **kw):
        raise AssertionError("an executable's text was read")

    monkeypatch.setattr(scopes, "table_of", never)
    monkeypatch.setattr(scopes, "scope_tables", never)
    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    with GenerationEngine(model, params, num_slots=2, slot_ladder=(2,),
                          prefill_buckets=(8, 16)) as engine:
        assert scopes.registered() == 3
        out = engine.generate([3, 4, 5], max_new_tokens=4).result(60)
    assert len(out.tokens) == 4
    assert scopes.registered() == 3
    monkeypatch.undo()
    tables = scopes.scope_tables()
    assert sorted((t.kind, t.key) for t in tables) == [
        ("jit_decode", "lanes=2"), ("jit_prefill", "prefill=16"),
        ("jit_prefill", "prefill=8")]


# ------------------------------------------------------- (f) dump / load

def test_dump_and_load_round_trip(tmp_path):
    tables = _hand_tables()
    path = str(tmp_path / "tables.json")
    scopes.dump(path, tables)
    back = scopes.load(path)
    assert [(t.kind, t.key) for t in back] == [(t.kind, t.key)
                                               for t in tables]
    for a, b in zip(tables, back):
        assert [(r.name, r.opcode, r.out_type, r.scope, r.scope_inferred,
                 r.flops, r.bytes_accessed) for r in a.rows] == \
            [(r.name, r.opcode, r.out_type, r.scope, r.scope_inferred,
              r.flops, r.bytes_accessed) for r in b.rows]
    ops, modules, _ = _chip_events()
    assert scopes.join_events(ops, modules, back).modules  # usable as read
