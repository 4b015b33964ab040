"""Flight recorder + SLO engine (DESIGN.md §16).

Unit layers: the bounded forensic ring and its atomic postmortem bundles,
the cross-process merge, the declarative SLO engine (breach/recovery/
burn-rate), the watchdog's SloBreach policy-ladder seam, and the CLI
``postmortem`` / ``--once`` surfaces.

Integration (the ISSUE acceptance): a fault-injected NaN and a
chaos-injected terminal ``PSUnavailable`` each leave a postmortem bundle
whose merged timeline carries the trailing windows' phase profiles and
the breaching alert.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.health import recorder as recorder_mod
from distkeras_tpu.health import slo
from distkeras_tpu.health import cli as health_cli
from distkeras_tpu.health.recorder import FlightRecorder
from distkeras_tpu.health.slo import AlertEvent, SloEngine, SloSpec
from distkeras_tpu.health.watchdog import SloBreach, TrainingWatchdog
from distkeras_tpu.utils import fault



@pytest.fixture(autouse=True)
def fresh_state():
    telemetry.reset()
    telemetry.set_process_index(0)
    fault.clear_injections()
    fault.clear_chaos()
    rec = recorder_mod.get_recorder()
    rec.clear()
    rec.dump_dir = None
    rec.fingerprint.clear()
    recorder_mod.install(rec)
    slo.install_engine(None)
    yield
    fault.clear_injections()
    fault.clear_chaos()
    rec = recorder_mod.get_recorder()
    rec.clear()
    rec.dump_dir = None
    rec.fingerprint.clear()
    slo.install_engine(None)
    telemetry.set_process_index(0)
    telemetry.reset()


# -- the ring ---------------------------------------------------------------

def test_record_event_rides_the_default_ring():
    telemetry.record_event("wire", outcome="retry", op="pull")
    evs = recorder_mod.get_recorder().events()
    assert evs[-1]["kind"] == "wire"
    assert evs[-1]["fields"] == {"outcome": "retry", "op": "pull"}
    # the ring append is also counted (the recorder observes itself)
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["recorder.events{kind=wire}"] == 1


def test_ring_is_bounded_and_keeps_the_newest():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("tick", i=i)
    evs = rec.events()
    assert len(evs) == 8
    assert [e["fields"]["i"] for e in evs] == list(range(12, 20))
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_span_events_forward_to_recorder_with_trace_ids():
    ctx = telemetry.TraceContext.new_root()
    with telemetry.use_trace(ctx):
        with telemetry.span("trace.window", worker=0):
            pass
    rec = recorder_mod.get_recorder()
    spans = [e for e in rec.events() if e["kind"] == "span"]
    assert spans and spans[-1]["fields"]["name"] == "trace.window"
    assert rec.last_trace_ids() == [ctx.trace_id]


def test_uninstalled_recorder_makes_record_event_a_noop():
    prev = telemetry.get_recorder()
    telemetry.set_recorder(None)
    try:
        telemetry.record_event("wire", outcome="retry")  # must not raise
    finally:
        telemetry.set_recorder(prev)
    assert all(e["kind"] != "wire" for e in prev.events())


# -- postmortem bundles ------------------------------------------------------

def test_dump_writes_suffixed_bundle_with_fingerprint_and_sha(tmp_path):
    telemetry.set_process_index(3)
    rec = recorder_mod.get_recorder()
    rec.set_fingerprint(precision="bf16", codec="topk", ignored=None)
    telemetry.counter("ps.commit.count").inc(2)
    telemetry.record_event("membership", transition="evict", worker=1,
                           reason="lease")
    path = rec.dump(str(tmp_path), reason="explicit")
    assert path is not None and path.endswith("postmortem_explicit.json.p3")
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["kind"] == "postmortem"
    assert bundle["process_index"] == 3
    assert bundle["fingerprint"] == {"precision": "bf16", "codec": "topk"}
    # SHA read straight from .git (no subprocess on the crash path)
    assert bundle["git_sha"] and len(bundle["git_sha"]) >= 12
    assert any(e["kind"] == "membership" for e in bundle["events"])
    assert any(r.get("name") == "ps.commit.count"
               for r in bundle["rows"])
    assert "workers" in bundle["status"]
    # no tmp file left behind (atomic rename)
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


def test_auto_dump_needs_dump_dir_and_fires_once_per_reason(tmp_path):
    rec = recorder_mod.get_recorder()
    assert recorder_mod.auto_dump("watchdog_nan") is None  # no dir bound
    recorder_mod.configure(dump_dir=str(tmp_path))
    first = recorder_mod.auto_dump("watchdog_nan")
    assert first is not None and os.path.exists(first)
    # retried failures of the same class must not thrash the disk
    assert recorder_mod.auto_dump("watchdog_nan") is None
    # but a DIFFERENT failure class still dumps
    assert recorder_mod.auto_dump("trainer_exception") is not None
    assert len(recorder_mod.find_bundles(str(tmp_path))) == 2
    assert rec.last_dump_path is not None


def test_merge_bundles_builds_cross_process_timeline(tmp_path):
    # process 0: a window profile then an alert
    telemetry.set_process_index(0)
    rec0 = FlightRecorder()
    telemetry.set_recorder(rec0)
    telemetry.record_event("window_profile", worker=0, window=7,
                           phases={"window": 0.5})
    telemetry.record_event("alert", slo="mfu-floor", observed=0.2,
                           message="mfu too low", resolved=False)
    rec0.dump(str(tmp_path), reason="watchdog_nan")
    # process 1: a wire outcome
    telemetry.set_process_index(1)
    rec1 = FlightRecorder()
    telemetry.set_recorder(rec1)
    telemetry.record_event("wire", outcome="unavailable", op="commit")
    rec1.dump(str(tmp_path), reason="ps_unavailable")

    paths = recorder_mod.find_bundles(str(tmp_path))
    assert len(paths) == 2
    merged = recorder_mod.merge_bundles(paths)
    assert merged["processes"] == [0, 1]
    kinds = [(e["pid"], e["kind"]) for e in merged["events"]]
    assert (0, "window_profile") in kinds and (1, "wire") in kinds
    # events are wall-clock ordered across processes
    times = [e["time"] for e in merged["events"]]
    assert times == sorted(times)
    # the breaching alert is surfaced on its bundle header
    b0 = next(b for b in merged["bundles"] if b["process_index"] == 0)
    assert b0["alerts"] and b0["alerts"][0]["fields"]["slo"] == "mfu-floor"
    text = recorder_mod.render_timeline(merged)
    assert "ALERT mfu-floor" in text and "[wire]" in text
    # a torn sibling must not kill the merge
    torn = tmp_path / "postmortem_torn.json.p9"
    torn.write_text('{"kind": "postmo')
    assert len(recorder_mod.merge_bundles(
        recorder_mod.find_bundles(str(tmp_path)))["bundles"]) == 2


def test_collector_drop_is_recovered_by_postmortem_merge(tmp_path):
    """Satellite: when the coordinator's bounded collector drops worker
    A's oldest batch, A's rows are NOT gone — its local flight-recorder
    bundle still carries them and the postmortem merge recovers them."""
    from distkeras_tpu.health.collector import TelemetryCollector

    col = TelemetryCollector(max_batches=1)
    rows_a = [{"kind": "counter", "name": "ps.commit.count", "value": 5}]
    rows_b = [{"kind": "counter", "name": "ps.pull.count", "value": 9}]
    col.add_batch(1, rows_a)
    col.add_batch(2, rows_b)  # bound hit: A's batch is dropped
    merged_live = col.merged_rows()
    assert all(r["pid"] != 1 for r in merged_live)
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["collector.dropped_batches"] == 1

    # worker A's OWN process: its registry still holds the rows, and its
    # crash bundle preserves them
    telemetry.reset()
    telemetry.set_process_index(1)
    telemetry.counter("ps.commit.count").inc(5)
    rec = FlightRecorder()
    telemetry.set_recorder(rec)
    rec.dump(str(tmp_path), reason="worker_exception")

    merged = recorder_mod.merge_bundles(
        recorder_mod.find_bundles(str(tmp_path)))
    recovered = [r for r in merged["rows"]
                 if r.get("name") == "ps.commit.count" and r["pid"] == 1]
    assert recovered and recovered[0]["value"] == 5


def test_load_jsonl_truncated_tail_bumps_recovery_counter(tmp_path):
    telemetry.counter("ps.commit.count").inc()
    path = str(tmp_path / "run.telemetry.jsonl")
    telemetry.get_registry().dump_jsonl(path)
    with open(path, "a") as f:
        f.write('{"kind": "gauge", "name": "cut-off-mid')
    with pytest.warns(RuntimeWarning, match="truncated trailing line"):
        telemetry.load_jsonl(path)
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["telemetry.load.truncated_tail"] == 1


def test_per_process_path_suffix_roundtrip():
    assert telemetry.per_process_path("/x/run.jsonl") == "/x/run.jsonl.p0"
    telemetry.set_process_index(7)
    assert telemetry.process_index() == 7
    assert telemetry.per_process_path("a.json") == "a.json.p7"
    with pytest.raises(ValueError):
        telemetry.set_process_index(-1)


# -- SLO engine --------------------------------------------------------------

def test_slo_spec_validation():
    with pytest.raises(ValueError, match="op"):
        SloSpec("x", "observability.mfu", 0.5, op="==")
    with pytest.raises(ValueError, match="field"):
        SloSpec("x", "observability.mfu", 0.5, field="p99")
    with pytest.raises(ValueError, match="budget_frac"):
        SloSpec("x", "observability.mfu", 0.5, budget_frac=1.0)
    with pytest.raises(ValueError, match="duplicate"):
        SloEngine([SloSpec("x", "observability.mfu", 0.5),
                   SloSpec("x", "observability.mfu", 0.6)])


def test_breach_mints_alert_and_recovery_resolves_it():
    eng = SloEngine([SloSpec("mfu-floor", "observability.mfu", 0.5,
                             op=">=")])
    telemetry.gauge("observability.mfu").set(0.31)
    minted = eng.evaluate_once()
    assert len(minted) == 1 and not minted[0].resolved
    assert minted[0].observed == pytest.approx(0.31)
    assert eng.active_alerts() and isinstance(minted[0], AlertEvent)
    # still breached: no duplicate mint
    assert eng.evaluate_once() == []
    telemetry.gauge("observability.mfu").set(0.62)
    resolved = eng.evaluate_once()
    assert len(resolved) == 1 and resolved[0].resolved
    assert not eng.active_alerts()
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["health.alerts.breaches{slo=mfu-floor}"] == 1
    assert snap["gauges"]["health.alerts.active{slo=mfu-floor}"] == 0.0
    assert snap["counters"]["health.alerts.evals"] == 3
    # both transitions rode the recorder ring
    alerts = [e for e in recorder_mod.get_recorder().events()
              if e["kind"] == "alert"]
    assert [a["fields"]["resolved"] for a in alerts] == [False, True]


def test_burn_rate_budget_tolerates_blips():
    """budget_frac=0.5 over a 10 s window: a single bad sample among good
    ones must NOT page; a majority of bad samples must."""
    clock = {"t": 1000.0}
    eng = SloEngine([SloSpec("mfu-floor", "observability.mfu", 0.5,
                             op=">=", window_s=10.0, budget_frac=0.5)],
                    clock=lambda: clock["t"])
    telemetry.gauge("observability.mfu").set(0.9)
    for _ in range(3):
        clock["t"] += 1.0
        assert eng.evaluate_once() == []
    telemetry.gauge("observability.mfu").set(0.1)  # one blip
    clock["t"] += 1.0
    assert eng.evaluate_once() == []  # burn 1/4 <= 0.5: no page
    for _ in range(4):                # sustained: burn crosses the budget
        clock["t"] += 1.0
        minted = eng.evaluate_once()
        if minted:
            break
    assert minted and minted[0].slo == "mfu-floor"


def test_histogram_tail_judged_on_worst_label_set():
    eng = SloEngine([SloSpec("staleness-tail", "ps.commit.staleness",
                             4.0, op="<=", field="p95")])
    for v in (1.0, 1.0, 1.0):
        telemetry.histogram("ps.commit.staleness", worker=0).record(v)
    minted = eng.evaluate_once()
    assert minted == []
    for v in (9.0, 9.0, 9.0):  # one straggling worker breaks the SLO
        telemetry.histogram("ps.commit.staleness", worker=1).record(v)
    minted = eng.evaluate_once()
    assert minted and minted[0].observed >= 9.0


def test_counter_rate_field_needs_two_samples():
    clock = {"t": 50.0}
    eng = SloEngine([SloSpec("degraded-windows",
                             "host_async.degraded_windows", 0.5,
                             op="<=", field="rate",
                             require_present=False)],
                    clock=lambda: clock["t"])
    telemetry.counter("host_async.degraded_windows").inc(0)
    assert eng.evaluate_once() == []  # first sample: no interval yet
    telemetry.counter("host_async.degraded_windows").inc(10)
    clock["t"] += 2.0  # 10 degraded windows / 2 s = 5/s > 0.5/s
    minted = eng.evaluate_once()
    assert minted and minted[0].observed == pytest.approx(5.0)


def test_require_present_skips_absent_metric():
    eng = SloEngine([SloSpec("serving-ttft", "serving.decode.ttft_s",
                             2.0, op="<=", field="p95")])
    assert eng.evaluate_once() == []  # nothing measured: no judgement
    assert eng.active_alerts() == []


def test_default_specs_install_and_surface_in_status():
    specs = slo.default_specs(mfu_floor=0.5)
    assert {s.name for s in specs} >= {"mfu-floor", "staleness-tail",
                                       "serving-ttft", "degraded-windows",
                                       "serving-queue"}
    eng = SloEngine(specs)
    slo.install_engine(eng)
    telemetry.gauge("serving.queue_depth").set(10_000.0)
    eng.evaluate_once()
    from distkeras_tpu.health.endpoints import handle_health_op

    status = handle_health_op("status", {})
    assert [a["slo"] for a in status["alerts"]] == ["serving-queue"]
    assert "recorder" in status


def test_engine_daemon_evaluates_and_stops():
    eng = SloEngine([SloSpec("mfu-floor", "observability.mfu", 0.5,
                             op=">=")])
    telemetry.gauge("observability.mfu").set(0.1)
    eng.start(interval=0.01)
    deadline = time.time() + 5.0
    while not eng.active_alerts() and time.time() < deadline:
        time.sleep(0.01)
    eng.stop()
    assert eng.active_alerts()


# -- watchdog seam -----------------------------------------------------------

def test_slo_breach_enters_watchdog_policy_ladder():
    wd = TrainingWatchdog(policy="raise")
    eng = SloEngine([SloSpec("mfu-floor", "observability.mfu", 0.5,
                             op=">=", severity="page")],
                    on_breach=slo.watchdog_on_breach(wd))
    telemetry.gauge("observability.mfu").set(0.2)
    with pytest.raises(SloBreach, match="mfu-floor"):
        eng.evaluate_once()
    assert wd.tripped is not None and wd.tripped.kind == "slo"
    # warn policy: the breach is recorded, training continues
    wd2 = TrainingWatchdog(policy="warn")
    eng2 = SloEngine([SloSpec("mfu-floor", "observability.mfu", 0.5,
                              op=">=")],
                     on_breach=slo.watchdog_on_breach(wd2))
    minted = eng2.evaluate_once()
    assert minted and wd2.tripped is not None


def test_watchdog_trip_dumps_postmortem_bundle(tmp_path):
    recorder_mod.configure(dump_dir=str(tmp_path), precision="f32")
    wd = TrainingWatchdog(policy="warn")
    wd.observe_loss(float("nan"))
    paths = recorder_mod.find_bundles(str(tmp_path))
    assert len(paths) == 1 and "watchdog_nan" in paths[0]
    with open(paths[0]) as f:
        bundle = json.load(f)
    assert bundle["fingerprint"]["precision"] == "f32"
    trips = [e for e in bundle["events"] if e["kind"] == "watchdog_trip"]
    assert trips and trips[0]["fields"]["kind"] == "nan"


# -- CLI ---------------------------------------------------------------------

def test_cli_rejects_non_positive_interval(capsys):
    with pytest.raises(SystemExit):
        health_cli.main(["127.0.0.1:1", "watch", "--interval", "0"])
    assert "--interval must be > 0" in capsys.readouterr().err


def test_cli_watch_once_polls_exactly_once(capsys):
    import jax

    from distkeras_tpu.parameter_servers import DeltaParameterServer
    from distkeras_tpu.parallel.remote_ps import ParameterServerService

    params = {"w": np.ones((4, 3), np.float32)}
    svc = ParameterServerService(DeltaParameterServer(
        jax.device_put(params)), params)
    svc.start()
    try:
        rc = health_cli.main([f"127.0.0.1:{svc.port}", "watch", "--once"])
    finally:
        svc.stop()
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("watchdog=ok") == 1
    assert "alerts=0" in out


def test_cli_postmortem_merges_and_writes_json(tmp_path, capsys):
    telemetry.record_event("window_profile", worker=0, window=1,
                           phases={"window": 0.4})
    recorder_mod.get_recorder().dump(str(tmp_path), reason="explicit")
    out_json = str(tmp_path / "merged.json")
    rc = health_cli.main(["postmortem", str(tmp_path), "--json", out_json])
    assert rc == 0
    assert "[window_profile]" in capsys.readouterr().out
    with open(out_json) as f:
        assert json.load(f)["processes"] == [0]
    # empty directory: exit 1 with a message, not a traceback
    rc = health_cli.main(["postmortem", str(tmp_path / "nothing_here")])
    assert rc == 1


def test_watch_table_renders_alerts_column():
    from distkeras_tpu.health.collector import worker_table

    now = time.time()
    rows = [
        {"kind": "gauge", "name": "health.worker.heartbeat_time",
         "labels": {"worker": "0"}, "value": now},
        {"kind": "gauge", "name": "health.alerts.active",
         "labels": {"slo": "mfu-floor", "worker": "0"}, "value": 1.0},
        {"kind": "gauge", "name": "health.alerts.active",
         "labels": {"slo": "serving-queue"}, "value": 1.0},
    ]
    workers = worker_table(rows, now)
    assert workers["0"]["alerts"] == 1
    fleet = health_cli._fleet_alerts(rows)
    assert fleet == ["serving-queue"]
    table = health_cli._watch_table(workers, {}, 0.0, fleet_alerts=fleet)
    assert "alerts" in table and "ALERTS: serving-queue" in table


# -- integration: crashes leave evidence -------------------------------------

def _mlp_fixture(workers=1, window=2, batch=16, n=512):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu import DOWNPOUR, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel import host_async

    model = MLP(features=(32,), num_classes=10)
    t = DOWNPOUR(model, mode="host_async", num_workers=workers,
                 worker_optimizer="sgd", learning_rate=0.05, metrics=(),
                 batch_size=batch, communication_window=window)
    shards = host_async.stage_worker_shards(
        synthetic_mnist(n=n).repartition(workers), "features", "label",
        batch, window)
    params = model.init(jax.random.key(0), jnp.zeros((batch, 784)),
                        train=False)["params"]
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", t.tx, t.strategy, window=window)
    return model, params, shards, runner, t


@pytest.mark.slow
def test_nan_crash_leaves_postmortem_with_profiles_and_alert(tmp_path):
    """ISSUE acceptance (NaN leg): an injected NaN under
    checkpoint_and_raise leaves a bundle next to the crash checkpoint
    whose merged timeline carries the trailing windows' phase profiles
    and the breaching alert."""
    from distkeras_tpu import DOWNPOUR, synthetic_mnist
    from distkeras_tpu.health import HealthConfig
    from distkeras_tpu.health.watchdog import NaNLoss
    from distkeras_tpu.models.mlp import MLP

    # the SLO engine pages on low MFU before the NaN kills the run: the
    # alert is on the ring when the crash bundle is written
    eng = SloEngine([SloSpec("mfu-floor", "observability.mfu", 0.5,
                             op=">=")])
    slo.install_engine(eng)
    telemetry.gauge("observability.mfu").set(0.12)
    eng.evaluate_once()

    fault.inject("host_async.window_loss", after=3)
    ckdir = str(tmp_path / "crash")
    model = MLP(features=(32,), num_classes=10)
    t = DOWNPOUR(model, mode="host_async", num_workers=2,
                 worker_optimizer="sgd", learning_rate=0.05, metrics=(),
                 batch_size=16, communication_window=2, num_epoch=4,
                 checkpoint_dir=ckdir,
                 health=HealthConfig(policy="checkpoint_and_raise"))
    with pytest.raises(NaNLoss):
        t.train(synthetic_mnist(n=1024), "features", "label")

    paths = recorder_mod.find_bundles(ckdir)
    assert paths, "the crash left no postmortem bundle"
    merged = recorder_mod.merge_bundles(paths)
    kinds = {e["kind"] for e in merged["events"]}
    assert "window_profile" in kinds, kinds
    assert "watchdog_trip" in kinds, kinds
    profiles = [e for e in merged["events"]
                if e["kind"] == "window_profile"]
    assert all("window" in p["fields"]["phases"] for p in profiles)
    alerts = [a for b in merged["bundles"] for a in b["alerts"]]
    assert any(a["fields"]["slo"] == "mfu-floor" for a in alerts)
    reasons = {b["reason"] for b in merged["bundles"]}
    assert "watchdog_nan" in reasons
    # the fingerprint stamped by the trainer rode along
    assert any(b["fingerprint"].get("trainer") == "DOWNPOUR"
               for b in merged["bundles"])


@pytest.mark.slow
def test_ps_outage_leaves_postmortem_with_profiles(tmp_path):
    """ISSUE acceptance (PSUnavailable leg): a chaos-injected permanent
    transport outage exhausts the degraded-window ladder; the dying
    worker leaves a bundle carrying the trailing window profiles and the
    terminal wire outcome."""
    import jax

    from distkeras_tpu.parallel import host_async
    from distkeras_tpu.comms import RetryPolicy
    from distkeras_tpu.parallel.remote_ps import (ParameterServerService,
                                                  PSUnavailable,
                                                  RemoteParameterServer)

    model, params, shards, runner, t = _mlp_fixture(workers=1)
    runner.max_degraded_windows = 1
    recorder_mod.configure(dump_dir=str(tmp_path))
    ps_dev = host_async.server_for(
        t.strategy, jax.device_put(params, runner.devices[0]))
    svc = ParameterServerService(ps_dev, params)
    svc.start()
    try:
        cli = RemoteParameterServer(
            f"127.0.0.1:{svc.port}", params,
            retry=RetryPolicy(max_retries=0, base_s=0.01, max_s=0.02),
            op_timeout=2.0)
        # the first data-channel rpc (the pull) lands; then the fleet
        # goes dark for good
        fault.inject_chaos("remote_ps.send", "reset", after=1, count=None)
        with pytest.raises(PSUnavailable):
            runner.run(params, [shards], ps=cli)
        cli.close()
    finally:
        fault.clear_chaos()
        svc.stop()

    paths = recorder_mod.find_bundles(str(tmp_path))
    assert paths, "the outage left no postmortem bundle"
    merged = recorder_mod.merge_bundles(paths)
    assert any(b["reason"] == "ps_unavailable" for b in merged["bundles"])
    kinds = {e["kind"] for e in merged["events"]}
    assert "window_profile" in kinds, kinds
    wires = [e for e in merged["events"] if e["kind"] == "wire"]
    assert any(e["fields"]["outcome"] == "unavailable" for e in wires)
    assert any(e["kind"] == "degraded_window" for e in merged["events"])
