"""Telemetry layer: registry semantics, edge cases, overhead guarantees,
and the end-to-end artifact an async run must leave behind."""

import inspect
import json
import threading
import time

import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu import observability as obs


@pytest.fixture(autouse=True)
def fresh_registry():
    """Isolate every test in its own registry; restore the default after."""
    reg = telemetry.reset()
    yield reg
    telemetry.reset()


# -- metric semantics -------------------------------------------------------

def test_counter_and_labels():
    c = telemetry.counter("c", op="pull")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert c.full_name == "c{op=pull}"
    # same name+labels -> same metric; different labels -> different metric
    assert telemetry.counter("c", op="pull") is c
    assert telemetry.counter("c", op="commit") is not c


def test_counter_rejects_negative():
    with pytest.raises(ValueError):
        telemetry.counter("c").inc(-1)


def test_gauge_set_plus_add():
    g = telemetry.gauge("g")
    g.set(10.0)
    g.add(1)
    g.add(-3)
    assert g.value == 8.0


def test_kind_conflict_raises():
    telemetry.counter("x")
    with pytest.raises(TypeError):
        telemetry.histogram("x")


def test_histogram_empty_stats():
    h = telemetry.histogram("h")
    assert h.stats()["count"] == 0
    assert h.stats()["p50"] is None


def test_histogram_bounds():
    """count/sum/min/max stay exact past the ring bound; the kept-sample
    set is capped at max_samples (recency-weighted percentiles)."""
    reg = telemetry.get_registry()
    h = reg.histogram("bounded", max_samples=8)
    for i in range(100):
        h.record(float(i))
    s = h.stats()
    assert s["count"] == 100
    assert s["sum"] == sum(range(100))
    assert s["min"] == 0.0 and s["max"] == 99.0
    assert s["samples_kept"] == 8
    # ring holds the most recent 8 values -> percentiles from [92..99]
    assert s["p50"] >= 92.0


def test_concurrent_counter_bumps():
    """host_async worker threads bump shared counters concurrently; the
    thread-sharded design must lose no increments without a lock."""
    c = telemetry.counter("racy")
    h = telemetry.histogram("racy_h")
    N, T = 10_000, 8

    def bump():
        for _ in range(N):
            c.inc()
            h.record(1.0)

    threads = [threading.Thread(target=bump) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    assert h.stats()["count"] == N * T


def test_span_records_event_and_histogram():
    with telemetry.span("unit.work", phase="a"):
        time.sleep(0.001)
    reg = telemetry.get_registry()
    assert len(reg.spans) == 1
    name, t0, dur, labels = reg.spans[0]
    assert name == "unit.work" and labels == {"phase": "a"} and dur > 0
    snap = reg.snapshot()
    assert "span.unit.work.duration_s{phase=a}" in snap["histograms"]


def test_jsonl_round_trip(tmp_path):
    reg = telemetry.get_registry()
    telemetry.counter("n").inc(7)
    telemetry.gauge("q").set(3.5)
    h = telemetry.histogram("lat_s")
    for v in (0.1, 0.2, 0.3):
        h.record(v)
    with telemetry.span("rt"):
        pass
    path = str(tmp_path / "t.jsonl")
    assert reg.dump_jsonl(path) == path
    rows = telemetry.load_jsonl(path)
    assert rows[0]["kind"] == "meta" and rows[0]["schema"] == 1
    by = {(r["kind"], r["name"]): r for r in rows[1:]}
    assert by[("counter", "n")]["value"] == 7
    assert by[("gauge", "q")]["value"] == 3.5
    hist = by[("histogram", "lat_s")]
    assert hist["count"] == 3 and abs(hist["sum"] - 0.6) < 1e-9
    assert ("span", "rt") in by
    # every line is valid standalone JSON (the artifact contract)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_uninstalled_is_noop():
    telemetry.uninstall()
    try:
        c = telemetry.counter("ghost")
        c.inc()
        telemetry.gauge("ghost").set(1)
        telemetry.histogram("ghost").record(1.0)
        with telemetry.span("ghost"):
            pass
        assert c.value == 0
        assert telemetry.get_registry() is None
    finally:
        telemetry.reset()
    assert telemetry.get_registry().snapshot()["counters"] == {}


# -- overhead guard (acceptance criterion) ----------------------------------

def test_record_path_is_lock_free_and_device_free():
    """The step-path record calls must take no lock and cannot possibly
    device-sync: telemetry.py never imports jax, and inc/record/set/add
    reference no lock acquisition (only shard creation, off the hot path,
    does)."""
    src = inspect.getsource(telemetry)
    assert "import jax" not in src  # no jax -> no device syncs, ever
    for fn in (telemetry.Counter.inc, telemetry.Histogram.record,
               telemetry.Gauge.set, telemetry.Gauge.add):
        names = fn.__code__.co_names
        assert "acquire" not in names and "Lock" not in names, \
            f"{fn.__qualname__} touches a lock on the record path: {names}"


def test_record_overhead_microbench():
    """Generous absolute bound: a record call is a dict-free few attribute
    ops; even a loaded CI box does it in well under 20 µs amortized."""
    h = telemetry.histogram("bench_s")
    c = telemetry.counter("bench")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
        h.record(0.5)
    per_pair = (time.perf_counter() - t0) / n
    assert per_pair < 20e-6, f"{per_pair * 1e6:.2f} µs per inc+record"


# -- observability satellites ----------------------------------------------

def test_step_timer_zero_steps():
    t = obs.StepTimer()
    with t.measure(0):
        pass
    assert t.steps == 0
    assert t.mean_step_s is None  # no steps measured -> no per-step claim
    assert t.total_s >= 0


def test_time_threaded_steps_zero_steps():
    import jax.numpy as jnp

    def step(state, batch):
        return state + 1, jnp.float32(state)

    state, timer = obs.time_threaded_steps(step, jnp.int32(0), None,
                                           warmup=1, steps=0)
    assert timer.steps == 0 and timer.mean_step_s is None


def test_while_flops_floor_counter():
    """count_flops on a while-loop body: counted once (a floor), and the
    telemetry counter flags the floor for MFU consumers."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def cond(c):
            return c[1] < 5

        def body(c):
            y, i = c
            return (y @ y, i + 1)

        out, _ = jax.lax.while_loop(cond, body, (x, 0))
        return out

    x = jnp.ones((4, 4))
    before = telemetry.counter("observability.flops.while_floor").value
    flops = obs.count_flops(f, x)
    assert flops == 2 * 4 * 4 * 4  # ONE body execution — the floor
    after = telemetry.counter("observability.flops.while_floor").value
    assert after == before + 1


def test_compiled_flops_unavailable_records_once(monkeypatch):
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("not supported on this backend")

    monkeypatch.setattr(obs, "_cost_analysis_noted", False)
    assert obs.compiled_flops(Broken()) is None
    assert obs.compiled_flops(Broken()) is None  # second failure: no re-count
    c = telemetry.counter("observability.cost_analysis_unavailable")
    assert c.value == 1


# -- the artifact an async run must leave (acceptance criterion) ------------

def test_adag_host_async_leaves_artifact(tmp_path):
    from distkeras_tpu import ADAG, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    path = str(tmp_path / "run.telemetry.jsonl")
    t = ADAG(MLP(features=(16,), num_classes=10), num_workers=2,
             batch_size=16, communication_window=2, num_epoch=1,
             mode="host_async", telemetry_path=path)
    t.train(synthetic_mnist(n=256))
    rows = telemetry.load_jsonl(path)
    have = {(r.get("kind"), r.get("name")) for r in rows}
    for needed in [("histogram", "ps.commit.staleness"),
                   ("counter", "ps.commit.count"),
                   ("counter", "ps.pull.count"),
                   ("histogram", "host_async.window_s"),
                   ("histogram", "data.prefetch.queue_depth_samples")]:
        assert needed in have, f"artifact missing {needed}"
    by = {(r["kind"], r["name"], tuple(sorted((r.get("labels") or {})
                                              .items()))): r for r in rows
          if r.get("kind") != "meta"}
    # 2 workers x 4 rounds each: every commit recorded at the PS
    commits = by[("counter", "ps.commit.count", ())]["value"]
    assert commits == 8
    stal = by[("histogram", "ps.commit.staleness", ())]
    assert stal["count"] == commits and stal["min"] >= 0
    # per-WORKER window durations (labelled), 4 windows each
    for w in (0, 1):
        win = by[("histogram", "host_async.window_s", (("worker", w),))]
        assert win["count"] == 4 and win["min"] > 0
    # lifecycle spans surfaced through the accessor
    span_names = {s["name"] for s in t.get_telemetry()["spans"]}
    assert {"trainer.init", "trainer.compile", "trainer.epoch",
            "trainer.stage", "trainer.finalize"} <= span_names
    # and the CLI renders it without error
    from distkeras_tpu.health import summary

    report = summary.summarize(rows)
    assert "ps.commit.staleness" in report
    assert "staleness (commits folded between pull and fold)" in report


def test_sync_adag_records_lifecycle_spans(tmp_path):
    """The default (sync substrate) path records trainer spans + prefetch
    occupancy when chunked staging streams through the background thread."""
    from distkeras_tpu import ADAG, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    t = ADAG(MLP(features=(16,), num_classes=10), num_workers=2,
             batch_size=16, communication_window=2, num_epoch=1,
             staging_rounds=1)
    t.train(synthetic_mnist(n=256))
    snap = t.get_telemetry()
    names = {s["name"] for s in snap["spans"]}
    assert {"trainer.init", "trainer.compile", "trainer.stage",
            "trainer.epoch", "trainer.finalize"} <= names
    assert any(k.startswith("data.prefetch.queue_depth_samples")
               for k in snap["histograms"])
