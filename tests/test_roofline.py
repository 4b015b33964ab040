"""Op-level attribution + roofline tests (DESIGN.md §21).

Covers the PR 16 surface: the HLO cost model (deterministic on a fixed
fixture, while-trip scaling), the roofline classifier (golden arithmetic-
intensity cases, dtype-aware peak selection, decline-don't-fabricate on
CPU), the typed fallback when a backend exposes no cost model, the
per-window MFU satellite in host_async, and the
health-plane wiring (status digest, watch OPS line, postmortem bundle).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distkeras_tpu import observability, telemetry
from distkeras_tpu import profiling
from distkeras_tpu.profiling import cost_model, roofline


# ---------------------------------------------------------------- fixture
# A hand-written post-optimization HLO module: one dot, one fusion (whose
# computation holds a multiply), and a while loop whose body holds an add.
# Small enough to audit by hand; parsing it must be exactly reproducible.
_HLO_FIXTURE = """\
HloModule fixture

%fused_mul (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%p0, %p1)
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]) parameter(0)
  %gte0 = s32[] get-tuple-element(%arg), index=0
  %gte1 = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %add.7 = f32[8,8]{1,0} add(%gte1, %gte1)
  ROOT %tuple.2 = (s32[], f32[8,8]) tuple(%gte0, %add.7)
}

%cond (arg.1: (s32[], f32[8,8])) -> pred[] {
  %arg.1 = (s32[], f32[8,8]) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[8,16], b: f32[16,8]) -> f32[8,8] {
  %a = f32[8,16]{1,0} parameter(0)
  %b = f32[16,8]{1,0} parameter(1)
  %dot.3 = f32[8,8]{1,0} dot(f32[8,16]{1,0} %a, f32[16,8]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/mlp/dense/dot_general"}
  %fusion.4 = f32[8,8]{1,0} fusion(%dot.3, %dot.3), kind=kLoop, calls=%fused_mul
  %tuple.5 = (s32[], f32[8,8]) tuple(%dot.3, %fusion.4)
  %while.6 = (s32[], f32[8,8]) while(%tuple.5), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%while.6), index=1
}
"""

_DOT_FLOPS = 2 * 8 * 8 * 16   # 2 * out_elems * contracted dim
_MUL_FLOPS = 8 * 8            # elementwise inside the fusion
_ADD_FLOPS = 8 * 8            # while-body add, per trip


def _by_opcode(rows):
    out = {}
    for r in rows:
        out.setdefault(r.opcode, []).append(r)
    return out


def test_parse_hlo_fixture_deterministic():
    rows1, floor1 = profiling.parse_hlo_ops(_HLO_FIXTURE)
    rows2, floor2 = profiling.parse_hlo_ops(_HLO_FIXTURE)
    assert [(r.name, r.flops, r.bytes_accessed) for r in rows1] == \
        [(r.name, r.flops, r.bytes_accessed) for r in rows2]
    assert floor1 and floor2  # no trip count given: floored at 1

    ops = _by_opcode(rows1)
    assert ops["dot"][0].flops == _DOT_FLOPS
    assert ops["dot"][0].source == "dense/dot_general"  # last 2 segments
    # the fusion is ONE row costing its called computation
    assert ops["fusion"][0].flops == _MUL_FLOPS
    assert "multiply" in ops["fusion"][0].fusion_ops
    # while body floored at one trip
    assert ops["add"][0].flops == _ADD_FLOPS


def test_parse_hlo_while_trips_scale():
    rows, floor = profiling.parse_hlo_ops(_HLO_FIXTURE, while_trips=5)
    assert not floor
    add = _by_opcode(rows)["add"][0]
    assert add.flops == 5 * _ADD_FLOPS


def test_classify_golden_cases():
    # peak 100 FLOP/s, bw 10 B/s -> ridge at intensity 10 FLOP/B
    kw = dict(peak=100.0, bandwidth=10.0, latency_floor_s=1e-6)
    # intensity 100 >> ridge: compute-bound
    assert roofline.classify(1000.0, 10.0, **kw) == "compute"
    # intensity 0.01 << ridge: memory-bound
    assert roofline.classify(10.0, 1000.0, **kw) == "memory"
    # exactly at the ridge counts as compute (>=)
    assert roofline.classify(100.0, 10.0, **kw) == "compute"
    # both modeled times under the floor: latency-bound
    assert roofline.classify(1e-6, 1e-7, **kw) == "latency"
    # pure data movement is memory-bound once big enough to matter
    assert roofline.classify(0.0, 1000.0, **kw) == "memory"


def test_build_report_ranks_by_headroom_and_publishes():
    inv = cost_model.OpInventory(rows=[
        # memory-bound: 1e9 bytes at 1e12 B/s = 1ms, trivial compute
        cost_model.OpCost(name="copy.1", opcode="copy", flops=0.0,
                          bytes_accessed=1e9, output_bytes=1e9,
                          dtype="f32", source="big/copy"),
        # compute-bound: 1e12 FLOPs at 1e13 FLOP/s = 100ms
        cost_model.OpCost(name="dot.2", opcode="dot", flops=1e12,
                          bytes_accessed=1e6, output_bytes=1e6,
                          dtype="f32", source="mlp/dot_general"),
        # latency-bound speck
        cost_model.OpCost(name="add.3", opcode="add", flops=8.0,
                          bytes_accessed=32.0, output_bytes=32.0,
                          dtype="f32", source="tiny/add"),
    ], available=True)
    report = profiling.build_report(inv, dtype="bf16", peak_flops=1e13,
                                    hbm_bandwidth=1e12,
                                    modeled_flops=2e12, top_k=8)
    assert report.available
    assert report.coverage == pytest.approx(0.5)
    top = report.top()
    # the compute-bound dot holds ~99% of modeled time but ZERO headroom
    # above its own compute roofline; the memory-bound copy leads
    assert top[0].op == "big/copy" and top[0].bound == "memory"
    assert top[0].fix == "memory-layout"
    by_op = {r.op: r for r in report.rows}
    assert by_op["mlp/dot_general"].bound == "compute"
    assert by_op["mlp/dot_general"].fix == "fp8-matmul"
    assert by_op["tiny/add"].bound == "latency"
    assert sum(r.share for r in report.rows) == pytest.approx(1.0)

    # digest + publish: gauges for the health plane, digest deterministic
    telemetry.reset()
    try:
        report.publish()
        snap = telemetry.get_registry().snapshot()
        gauges = snap["gauges"]
        assert gauges["profile.op.coverage"] == pytest.approx(0.5)
        assert any(k.startswith("profile.op.share{")
                   for k in gauges), gauges
        d = report.digest()
        assert d == report.digest()
        assert d["top"][0]["op"] == "big/copy"
    finally:
        telemetry.reset()


def test_build_report_declines_without_ceilings():
    """CPU hosts have no table entry: the report must decline rather than
    classify against invented ceilings (same contract as
    device_peak_flops)."""
    inv = cost_model.OpInventory(rows=[
        cost_model.OpCost(name="dot.1", opcode="dot", flops=1e9,
                          bytes_accessed=1e6, output_bytes=1e6,
                          dtype="f32", source="x")], available=True)
    report = profiling.build_report(inv)  # no peak/bw, CPU device
    assert not report.available
    assert "reference ceilings" in report.note
    assert "no cost model" in report.render() or "roofline:" in \
        report.render()


def test_fp8_sim_claims_bf16_peak():
    """PR 6 honesty rule carried into the roofline: fp8-sim runs on the
    bf16 MXU, so its roofline peak is the bf16 one."""
    from distkeras_tpu import precision

    assert precision.get_policy("fp8-sim").mfu_dtype == "bf16"
    # and the dtype-aware table rejects made-up dtypes outright
    with pytest.raises(ValueError):
        observability.device_peak_flops(None, dtype="fp7")


def test_op_inventory_typed_fallback_counts_once():
    """A backend without cost_analysis/as_text degrades to a typed empty
    inventory; the counter fires once per process, not once per call."""

    class NoCostBackend:
        pass

    telemetry.reset()
    cost_model._inventory_noted = False
    try:
        inv1 = profiling.op_inventory(NoCostBackend())
        inv2 = profiling.op_inventory(NoCostBackend())
        assert not inv1.available and not inv2.available
        assert inv1.rows == [] and inv1.total_flops == 0.0
        assert "backend" in inv1.note  # a typed, human-readable reason
        snap = telemetry.get_registry().snapshot()
        assert snap["counters"]["profile.op.inventory_unavailable"] == 1
        # an unavailable inventory yields an honest, unavailable report
        rep = profiling.build_report(inv1, peak_flops=1e12,
                                     hbm_bandwidth=1e11)
        assert not rep.available and rep.note == inv1.note
    finally:
        cost_model._inventory_noted = False
        telemetry.reset()


def test_op_inventory_real_executable_matches_analytic():
    """End to end on the local backend: inventory a compiled matmul and
    check the dot row against the analytic FLOPs count."""

    def f(a, b):
        return a @ b

    a = jnp.zeros((8, 16), jnp.float32)
    b = jnp.zeros((16, 32), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    inv = profiling.op_inventory(compiled)
    assert inv.available
    dots = [r for r in inv.rows
            if r.opcode == "dot" or "dot" in r.fusion_ops]
    assert sum(r.flops for r in dots) == observability.count_flops(f, a, b)


# A SAME-padded 3x3 conv on a 4x4 map: shape math counts 3*3 taps at
# every output position, but border positions only touch real input on
# 2x3 / 2x2 windows. Per spatial dim the tap counts are 2+3+3+2 = 10,
# so the exact model is b * f_out * c_in * 10 * 10 MACs — what the
# executable actually runs once XLA elides the padding.
_CONV_HLO = """\
HloModule conv_fixture

ENTRY %main (x: f32[1,4,4,2], w: f32[3,3,2,4]) -> f32[1,4,4,4] {
  %x = f32[1,4,4,2]{3,2,1,0} parameter(0)
  %w = f32[3,3,2,4]{3,2,1,0} parameter(1)
  ROOT %conv = f32[1,4,4,4]{3,2,1,0} convolution(f32[1,4,4,2]{3,2,1,0} %x, f32[3,3,2,4]{3,2,1,0} %w), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}
"""


def test_conv_flops_tap_exact_with_padding():
    rows, _ = profiling.parse_hlo_ops(_CONV_HLO)
    conv = _by_opcode(rows)["convolution"][0]
    assert conv.flops == 2 * 1 * 4 * 2 * 10 * 10
    # and strictly below the naive padded-shape model
    assert conv.flops < 2 * (1 * 4 * 4 * 4) * (3 * 3 * 2)


def test_source_inventory_matches_post_opt_on_conv_grad():
    """The coverage denominator must be the same currency as the
    numerator: pre-optimization HLO costed by the same tap-exact shape
    arithmetic. On a conv forward+backward (strided, padded, with the
    dilated kernel-grad convs) the two inventories must agree closely —
    this is the invariant behind the >=90% coverage gate."""

    def step(x, w):
        def loss(w):
            y = jax.lax.conv_general_dilated(
                x, w, window_strides=(2, 2), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return jnp.sum(y * y)
        return jax.grad(loss)(w)

    x = jnp.ones((2, 8, 8, 3), jnp.float32)
    w = jnp.ones((3, 3, 3, 4), jnp.float32)
    lowered = jax.jit(step).lower(x, w)
    src = profiling.source_inventory(lowered)
    inv = profiling.op_inventory(lowered.compile())
    assert src.available and inv.available
    assert src.total_flops > 0
    ratio = inv.total_flops / src.total_flops
    assert 0.9 <= ratio <= 1.1, (inv.total_flops, src.total_flops)


# ------------------------------------------- host_async MFU satellite
def _tiny_runner_bits():
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel import host_async, strategies

    model = MLP(features=(16,), num_classes=10)
    shards = host_async.stage_worker_shards(
        synthetic_mnist(n=64).repartition(1), "features", "label", 16, 2)
    init = model.init(jax.random.key(0), jnp.zeros((16, 784)),
                      train=False)["params"]
    return model, shards, init


def test_host_async_window_mfu_published_with_override():
    """Satellite 1: with a peak ceiling known, every window publishes
    observability.mfu plus the mfu_window histogram the SLO floor burns
    against. On CPU the ceiling comes from the explicit override."""
    from distkeras_tpu.parallel import host_async, strategies

    model, shards, init = _tiny_runner_bits()
    telemetry.reset()
    try:
        runner = host_async.HostAsyncRunner(
            model, "categorical_crossentropy", optax.sgd(0.05),
            strategies.get("dynsgd"), window=2)
        assert runner.mfu_dtype == "bf16"  # default policy-less dtype
        runner.mfu_peak_flops = 1e12
        runner.run(init, [shards])
        snap = telemetry.get_registry().snapshot()
        assert "observability.mfu{dtype=bf16}" in snap["gauges"]
        hist = snap["histograms"]["observability.mfu_window{dtype=bf16}"]
        assert hist["count"] >= 1
        assert 0.0 <= hist["max"] <= 1.0  # CPU MFU vs a TPU peak: ~0
    finally:
        telemetry.reset()


def test_host_async_window_mfu_silent_without_ceiling():
    """No ceiling (CPU, no override): the satellite must stay cold —
    no gauges, no per-window analytic FLOPs counting."""
    from distkeras_tpu.parallel import host_async, strategies

    model, shards, init = _tiny_runner_bits()
    telemetry.reset()
    try:
        runner = host_async.HostAsyncRunner(
            model, "categorical_crossentropy", optax.sgd(0.05),
            strategies.get("dynsgd"), window=2)
        runner.run(init, [shards])
        snap = telemetry.get_registry().snapshot()
        assert not any(k.startswith("observability.mfu")
                       for k in snap["gauges"])
        assert runner._window_flops is None  # count_flops never ran
    finally:
        telemetry.reset()


def test_host_async_fp8_sim_mfu_dtype_is_bf16():
    from distkeras_tpu.parallel import host_async, strategies

    model, _, _ = _tiny_runner_bits()
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", optax.sgd(0.05),
        strategies.get("dynsgd"), window=2, precision="fp8-sim")
    assert runner.mfu_dtype == "bf16"


# ----------------------------------------------------- health wiring
def _publish_sample_report():
    inv = cost_model.OpInventory(rows=[
        cost_model.OpCost(name="copy.1", opcode="copy", flops=0.0,
                          bytes_accessed=1e9, output_bytes=1e9,
                          dtype="f32", source="big/copy"),
        cost_model.OpCost(name="dot.2", opcode="dot", flops=1e12,
                          bytes_accessed=1e6, output_bytes=1e6,
                          dtype="f32", source="mlp/dot_general"),
    ], available=True)
    report = profiling.build_report(inv, peak_flops=1e13,
                                    hbm_bandwidth=1e12, modeled_flops=1e12)
    report.publish()
    return report


def test_status_digest_carries_top_offenders():
    from distkeras_tpu.health.endpoints import handle_health_op

    telemetry.reset()
    try:
        _publish_sample_report()
        status = handle_health_op("status", {})
        assert "roofline" in status
        # gauge consumers rank by published share: the dot holds ~99%
        # of modeled time, the memory-bound copy rides second
        assert status["roofline"][0]["op"] == "mlp/dot_general"
        by_op = {r["op"]: r for r in status["roofline"]}
        assert by_op["big/copy"]["bound"] == "memory"
        assert len(status["roofline"]) <= 3
        assert status["roofline_coverage"] == pytest.approx(1.0)
    finally:
        telemetry.reset()


def test_watch_table_ops_line():
    from distkeras_tpu.health import cli as health_cli

    telemetry.reset()
    try:
        _publish_sample_report()
        rows = telemetry.get_registry().rows()
        fleet_ops = health_cli._fleet_ops(rows)
        assert fleet_ops and fleet_ops[0][0] == "mlp/dot_general"
        table = health_cli._watch_table({}, {}, 0.0, fleet_ops=fleet_ops)
        assert "OPS:" in table and "big/copy" in table
        # absent rows -> absent line (non-profiled fleets pay nothing)
        assert "OPS:" not in health_cli._watch_table({}, {}, 0.0)
    finally:
        telemetry.reset()


def test_recorder_bundle_carries_roofline_digest():
    from distkeras_tpu.health.recorder import FlightRecorder

    telemetry.reset()
    prev = telemetry.get_recorder()
    try:
        rec = FlightRecorder(capacity=8)
        telemetry.set_recorder(rec)
        report = _publish_sample_report()
        bundle = rec.bundle("test")
        assert bundle["roofline"] == report.digest()
        rec.clear()
        assert rec.roofline is None
    finally:
        # restore, don't clear: leaving the sink at None would silently
        # no-op record_event() for every test that runs after this one
        telemetry.set_recorder(prev)
        telemetry.reset()
