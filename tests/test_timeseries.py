"""Time-series plane tests (DESIGN.md §24): the MetricStore's tiered
retention and budget, the trend-detector suite on synthetic leak/stall/
drift/clean series, the TrendMonitor's typed events and gauges, the SLO
engine's windowed-store observation path (parity with the snapshot path
on a static series), the postmortem forensic path for a caught leak, and
— slow-marked — the end-to-end chaos soak smoke.

Every detector test drives the store with an EXPLICIT clock (backdated
``collect(now=...)`` timestamps): the synthetic histories span minutes
of wall time without the test taking minutes.
"""

import json
import os
import time

import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.health import endpoints, recorder, slo, timeseries
from distkeras_tpu.health.timeseries import (
    DriftDetector,
    LeakDetector,
    MetricStore,
    StallDetector,
    TrendMonitor,
    default_detectors,
    sparkline,
    trend_specs,
)


#: comfortably above the default 1 MiB/s HBM ceiling (a 1.0 MiB/s slope
#: sits exactly ON the rail and must NOT fire — strict inequality)
LEAK_SLOPE = 4 << 20


@pytest.fixture(autouse=True)
def fresh_state():
    telemetry.reset()
    # re-INSTALL the recorder, don't just clear it: a prior test may have
    # left telemetry's sink at None, which silently no-ops record_event()
    recorder.install(recorder.get_recorder()).clear()
    timeseries.install_store(None)
    timeseries.install_monitor(None)
    slo.install_engine(None)
    yield
    timeseries.install_store(None)
    timeseries.install_monitor(None)
    slo.install_engine(None)
    recorder.install(recorder.get_recorder()).clear()
    telemetry.reset()


def _fill(store, gauge_name, values, t0, dt=5.0, **labels):
    """Backdated synthetic history: one gauge sample per collect pass."""
    g = telemetry.gauge(gauge_name, **labels)
    for i, v in enumerate(values):
        g.set(v)
        store.collect(now=t0 + i * dt)


# -- MetricStore --------------------------------------------------------------

def test_store_collects_counters_gauges_and_histogram_fields():
    store = MetricStore()
    telemetry.counter("soak.requests").inc(3)
    telemetry.gauge("serving.queue_depth").set(7.0)
    h = telemetry.histogram("health.window.duration_s")
    for v in (0.1, 0.2, 0.3):
        h.record(v)
    t0 = time.time()
    store.collect(now=t0)
    telemetry.counter("soak.requests").inc(2)
    store.collect(now=t0 + 2.0)
    assert store.latest("serving.queue_depth") == 7.0
    assert store.latest("soak.requests") == 5.0
    # counter rate from the stored history: +2 over 2s
    assert store.rate("soak.requests", window_s=60.0,
                      now=t0 + 2.0) == pytest.approx(1.0)
    # histograms expand into count/p50/p95/max series, not raw samples
    fields = {s.field for key, s in store._series.items()
              if key[0] == "health.window.duration_s"}
    assert fields == {"count", "p50", "p95", "max"}
    # single-point rate is refused (no honest interval), unseen is None
    assert store.rate("soak.requests", window_s=60.0, now=t0 + 2.0,
                      ) is not None
    assert store.latest("no.such.metric") is None
    assert store.rate("no.such.metric") is None


def test_store_tiers_downsample_and_windowed_reads_pick_a_tier():
    store = MetricStore()
    t0 = time.time() - 7200.0
    g = telemetry.gauge("observability.mfu")
    for i in range(1440):  # one sample per 5s for two hours
        g.set(0.5)
        store.collect(now=t0 + i * 5.0)
    (s,) = store.query("observability.mfu")
    raw, mid, coarse = s.rings["raw"], s.rings["10s"], s.rings["60s"]
    # ring caps: raw holds the last 512 samples (~43 min), the 10s tier
    # the last 360 thinned points (~1 h), the 60s tier the whole run
    assert len(raw) == 512 and len(mid) == 360
    assert 115 <= len(coarse) <= 121
    assert coarse[0][0] == t0
    now = t0 + 1439 * 5.0
    # each window is served by the FINEST tier that still covers it
    def spacing(pts):
        return pts[1][0] - pts[0][0]
    assert spacing(s.points(600.0, now=now)) == 5.0     # raw
    assert spacing(s.points(3000.0, now=now)) == 10.0   # 10s tier
    assert spacing(s.points(5000.0, now=now)) == 60.0   # 60s tier


def test_store_budget_caps_series_and_counts_drops():
    store = MetricStore(budget_bytes=1)  # floor: max 16 series
    assert store.max_series == 16
    for i in range(20):
        telemetry.gauge("serving.queue_depth", replica=str(i)).set(1.0)
    store.collect(now=time.time())
    assert len(store._series) == 16
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["timeseries.dropped_series"] == 4.0
    # the second pass also sees (and drops) the store's own 8
    # self-instrument series minted by the first pass; after that the
    # count is stable — dropped keys are counted once, not per pass
    store.collect(now=time.time() + 1.0)
    store.collect(now=time.time() + 2.0)
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["timeseries.dropped_series"] == 12.0


def test_store_rows_are_json_serializable_and_windowed():
    store = MetricStore()
    _fill(store, "serving.queue_depth", [1.0, 2.0, 3.0],
          t0=time.time() - 10.0)
    rows = store.rows(name="serving.queue_depth", max_points=2)
    (row,) = rows
    assert row["kind"] == "timeseries" and row["tier"] == "raw"
    assert [v for _, v in row["points"]] == [2.0, 3.0]
    json.dumps(rows)


def test_sparkline_renders_range_and_degenerate_series():
    line = sparkline([0.0, 1.0, 2.0, 3.0], width=4)
    assert len(line) == 4
    assert line[0] == "▁" and line[-1] == "█"
    assert set(sparkline([5.0, 5.0, 5.0])) <= set("▁")
    assert sparkline([]) == ""


# -- detectors on synthetic series -------------------------------------------

def test_leak_detector_fires_on_monotone_leak_only():
    store = MetricStore()
    t0 = time.time() - 120.0
    # 4 MiB/s monotone growth: a leak
    _fill(store, "observability.hbm_allocated_bytes",
          [i * LEAK_SLOPE * 5.0 for i in range(24)], t0, dt=5.0,
          stat="leaky")
    det = LeakDetector("hbm-leak", "observability.hbm_allocated_bytes",
                       window_s=120.0, slope_per_s=1 << 20)
    (ev,) = det.evaluate(store, now=t0 + 23 * 5.0)
    assert ev.trend == "hbm-leak" and ev.detector == "leak"
    assert ev.observed == pytest.approx(LEAK_SLOPE, rel=0.05)
    assert not ev.resolved


def test_leak_detector_ignores_sawtooth_and_flat_series():
    store = MetricStore()
    t0 = time.time() - 120.0
    # same mean slope, but half the steps FREE memory: load, not a leak
    saw = [(i * LEAK_SLOPE * 5.0) * (1.0 if i % 2 else 0.25)
           for i in range(24)]
    _fill(store, "observability.hbm_allocated_bytes", saw, t0, dt=5.0,
          stat="sawtooth")
    det = LeakDetector("hbm-leak", "observability.hbm_allocated_bytes",
                       window_s=120.0, slope_per_s=1 << 20)
    assert det.evaluate(store, now=t0 + 23 * 5.0) == []
    # flat series: zero slope
    store2 = MetricStore()
    _fill(store2, "observability.hbm_allocated_bytes", [1e9] * 24, t0,
          dt=5.0, stat="flat")
    assert det.evaluate(store2, now=t0 + 23 * 5.0) == []


def test_stall_detector_fires_on_flat_cursor_not_on_advancing():
    store = MetricStore()
    t0 = time.time() - 60.0
    _fill(store, "data.service.cursor", [17.0] * 12, t0, dt=5.0)
    det = StallDetector("data-watermark-stall", "data.service.cursor",
                        window_s=30.0)
    (ev,) = det.evaluate(store, now=t0 + 11 * 5.0)
    assert ev.detector == "stall" and ev.observed == 0.0
    # an advancing watermark is healthy
    store2 = MetricStore()
    _fill(store2, "data.service.cursor", list(range(12)), t0, dt=5.0)
    assert det.evaluate(store2, now=t0 + 11 * 5.0) == []
    # too little observed history must NOT be called a stall
    store3 = MetricStore()
    _fill(store3, "data.service.cursor", [17.0] * 4, t0, dt=1.0)
    assert det.evaluate(store3, now=t0 + 3.0) == []


def test_drift_detector_fires_on_drop_vs_own_baseline():
    store = MetricStore()
    t0 = time.time() - 360.0
    # 5 minutes at 0.55 MFU, then a minute at 0.40: -27% vs baseline
    _fill(store, "observability.mfu", [0.55] * 60 + [0.40] * 12, t0,
          dt=5.0)
    det = DriftDetector("mfu-drift", "observability.mfu",
                        tolerance_frac=0.10)
    (ev,) = det.evaluate(store, now=t0 + 71 * 5.0)
    assert ev.detector == "drift" and ev.observed < -0.10
    # within tolerance: no event
    store2 = MetricStore()
    _fill(store2, "observability.mfu", [0.55] * 60 + [0.52] * 12, t0,
          dt=5.0)
    assert det.evaluate(store2, now=t0 + 71 * 5.0) == []


# -- TrendMonitor -------------------------------------------------------------

def test_trend_monitor_mints_breach_then_recovery_and_flips_gauges():
    store = MetricStore()
    t0 = time.time() - 120.0
    now = t0 + 23 * 5.0
    _fill(store, "observability.hbm_allocated_bytes",
          [i * LEAK_SLOPE * 5.0 for i in range(24)], t0, dt=5.0)
    mon = TrendMonitor(store, default_detectors())
    minted = mon.evaluate_once(now=now)
    assert [e.trend for e in minted] == ["hbm-leak"]
    assert mon.active_trends()[0]["trend"] == "hbm-leak"
    snap = telemetry.get_registry().snapshot()
    assert snap["gauges"]["timeseries.trends_active{trend=hbm-leak}"] == 1.0
    # never-breached detectors still publish a 0 (require_present specs)
    assert snap["gauges"][
        "timeseries.trends_active{trend=queue-growth}"] == 0.0
    assert snap["counters"][
        "timeseries.trend_breaches{trend=hbm-leak}"] == 1.0
    # second pass with the leak still active: no duplicate event
    assert mon.evaluate_once(now=now) == []
    # the leak plateaus: recovery event, gauge back to 0
    g = telemetry.gauge("observability.hbm_allocated_bytes")
    for i in range(24, 72):
        g.set(23 * LEAK_SLOPE * 5.0)
        store.collect(now=t0 + i * 5.0)
    minted = mon.evaluate_once(now=t0 + 71 * 5.0)
    assert [e.resolved for e in minted] == [True]
    assert mon.active_trends() == []
    snap = telemetry.get_registry().snapshot()
    assert snap["gauges"]["timeseries.trends_active{trend=hbm-leak}"] == 0.0
    # both events landed on the flight-recorder ring, typed
    trends = [e for e in recorder.get_recorder().events()
              if e["kind"] == "trend"]
    assert [e["fields"]["resolved"] for e in trends] == [False, True]


def test_trend_specs_ride_the_slo_engine():
    store = timeseries.install_store(MetricStore())
    t0 = time.time() - 120.0
    now = t0 + 23 * 5.0
    _fill(store, "observability.hbm_allocated_bytes",
          [i * LEAK_SLOPE * 5.0 for i in range(24)], t0, dt=5.0)
    detectors = default_detectors()
    mon = TrendMonitor(store, detectors)
    engine = slo.SloEngine(trend_specs(detectors))
    mon.evaluate_once(now=now)
    store.collect(now=now)  # the gauge flip must reach the store
    minted = engine.evaluate_once(now=now)
    assert [a.slo for a in minted] == ["trend-hbm-leak"]
    assert minted[0].severity == "ticket"


# -- SLO engine: store path + parity with the snapshot path -------------------

def test_slo_observe_store_parity_on_static_series():
    """On a static series the windowed-store observation and the
    registry-snapshot observation must agree — installing the store
    cannot change any verdict a static world produces."""
    telemetry.gauge("observability.mfu").set(0.42)
    h = telemetry.histogram("host_async.commit_clock_lag")
    for v in (1.0, 2.0, 8.0):
        h.record(v)
    telemetry.counter("host_async.degraded_windows").inc(6)
    specs = [
        slo.SloSpec("mfu", "observability.mfu", 0.50),
        slo.SloSpec("lag", "host_async.commit_clock_lag", 8.0, op="<=",
                    field="p95"),
        slo.SloSpec("degraded", "host_async.degraded_windows", 1.0,
                    op="<=", field="rate", window_s=60.0),
    ]
    now = time.time()
    snap_engine = slo.SloEngine(specs)
    snap_engine.evaluate_once(now=now - 2.0)  # arm the counter-rate prev
    snapshot = {s.name: snap_engine._observe(s, now) for s in specs}

    store = timeseries.install_store(MetricStore())
    store.collect(now=now - 2.0)
    store.collect(now=now)
    store_engine = slo.SloEngine(specs)
    stored = {s.name: store_engine._observe(s, now) for s in specs}
    assert stored == pytest.approx(snapshot)
    assert stored["mfu"] == 0.42
    assert stored["degraded"] == pytest.approx(0.0)  # static counter


def test_slo_store_path_falls_back_when_store_is_cold():
    """A store that has never seen the metric must not mask the live
    registry (and histogram ``min`` is never store-served)."""
    store = timeseries.install_store(MetricStore())
    telemetry.gauge("observability.mfu").set(0.61)
    h = telemetry.histogram("host_async.commit_clock_lag")
    h.record(3.0)
    engine = slo.SloEngine([
        slo.SloSpec("mfu", "observability.mfu", 0.50),
        slo.SloSpec("lag-min", "host_async.commit_clock_lag", 0.1,
                    op=">=", field="min")])
    now = time.time()
    # store empty -> snapshot path serves both
    assert engine._observe(engine.specs[0], now) == 0.61
    assert engine._observe(engine.specs[1], now) == 3.0
    store.collect(now=now)
    # store warm: the gauge is store-served, min still snapshot-served
    assert engine._observe(engine.specs[0], now) == 0.61
    assert engine._observe(engine.specs[1], now) == 3.0


def test_default_specs_carry_trend_and_collector_rails():
    names = {s.name: s for s in slo.default_specs()}
    assert names["hbm-growth"].metric == "timeseries.trends_active"
    assert names["hbm-growth"].labels == {"trend": "hbm-leak"}
    assert names["data-watermark-stall"].labels == {
        "trend": "data-watermark-stall"}
    assert names["collector-drops"].metric == "collector.dropped_batches"
    assert names["collector-drops"].field == "rate"


# -- forensics: the leak lands in a postmortem bundle -------------------------

def test_caught_leak_lands_typed_in_postmortem_bundle(tmp_path):
    store = timeseries.install_store(MetricStore())
    mon = timeseries.install_monitor(
        TrendMonitor(store, default_detectors()))
    t0 = time.time() - 120.0
    _fill(store, "observability.hbm_allocated_bytes",
          [i * LEAK_SLOPE * 5.0 for i in range(24)], t0, dt=5.0)
    minted = mon.evaluate_once(now=t0 + 23 * 5.0)
    assert [e.trend for e in minted] == ["hbm-leak"]
    path = recorder.get_recorder().dump(str(tmp_path), reason="leak")
    with open(path) as f:
        bundle = json.load(f)
    # the typed event on the ring...
    (ev,) = [e for e in bundle["events"] if e["kind"] == "trend"]
    assert ev["fields"]["trend"] == "hbm-leak"
    assert ev["fields"]["threshold"] == float(1 << 20)
    # ...the still-active judgement...
    assert [t["trend"] for t in bundle["trends"]] == ["hbm-leak"]
    # ...and the series evidence itself ride the same bundle
    assert any(r["name"] == "observability.hbm_allocated_bytes"
               for r in bundle["timeseries"])


def test_series_wire_op_serves_installed_store():
    assert endpoints.handle_health_op("series", {}) == {"series": []}
    store = timeseries.install_store(MetricStore())
    _fill(store, "serving.queue_depth", [1.0, 2.0], time.time() - 5.0)
    out = endpoints.handle_health_op(
        "series", {"name": "serving.queue_depth", "max_points": 1})
    (row,) = out["series"]
    assert row["name"] == "serving.queue_depth"
    assert len(row["points"]) == 1


# -- the e2e soak smoke (slow) ------------------------------------------------

@pytest.mark.slow
def test_soak_smoke_all_authorities_and_invariants(tmp_path):
    """A minimum-budget chaos soak must kill every authority at least
    once and hold the three flywheel invariants: zero lost windows (and
    data ranges), zero failed/wrong requests, strictly monotone
    model_version — plus catch-and-bundle the injected HBM leak."""
    import soak_harness

    rows, summary = soak_harness.run_soak(budget_s=1.0, seed=0,
                                 out_dir=str(tmp_path))
    assert summary["authorities_killed"] == 4
    assert min(summary["kills"].values()) >= 1
    assert summary["windows"] > 0 and summary["windows_lost"] == 0
    assert summary["ranges"] > 0 and summary["ranges_lost"] == 0
    assert summary["duplicated"] == 0
    assert summary["requests"] > 0 and summary["failed"] == 0
    assert summary["wrong_tokens"] == 0
    assert summary["version_monotone"] == 1.0
    assert summary["versions"] == sorted(set(summary["versions"]))
    assert summary["leak_drill_caught"] == 1.0
    drill = next(r for r in rows if r["kind"] == "trend_drill")
    assert drill["caught"] and drill["landed_in_bundle"]
    assert os.path.exists(summary["postmortem_bundle"])
    json.dumps(rows)  # the report must be committable JSONL
