"""The generation scheduler's iteration partitioned into phases (ISSUE 24):
the bridge from the program's spans to the profiler's clock, the phase
timer, the ``serving.sched.*`` histograms on a tiny engine, the shared
clock under a real profiler session, and the benchmark's registry readers.

The load-bearing guarantees:

- ``telemetry.py`` reaches the profiler through one slot and imports no
  device runtime; an empty slot and ``uninstall()`` raise nothing, and what
  ``record_span`` stores is the interval it always was;
- every phase histogram holds one sample per scheduler iteration that held
  a lane, the phases account for the iteration, and decode steps grow
  neither the span ring nor the flight recorder;
- the walk that hands the clients what they are owed (ISSUE 33) is an
  annotation of its own, entered once a dispatch, right behind it;
- the phases are events of the profiler's own trace beside the trainer's
  spans (the clock the device trace is on);
- splitting the logits fetch into a wait and a copy serves the same tokens.
"""

import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import observability, telemetry  # noqa: F401 (installs)
from distkeras_tpu.models.gpt import gpt_tiny
from distkeras_tpu.serving import GenerationEngine
from distkeras_tpu.serving.generation import NgramDraft

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("control", "admit", "prefill_wait", "launch", "wait", "copy",
          "pick", "stream", "retire")


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def lm():
    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


class FakeAnnotator:
    """Stands in for ``jax.profiler.TraceAnnotation``: a callable that,
    given a name, returns a context manager; it keeps what it saw."""

    def __init__(self):
        self.events = []  # (what, name, perf_counter)

    def __call__(self, name):
        return _FakeAnnotation(self, name)

    def names(self, what):
        return [n for w, n, _ in self.events if w == what]


class _FakeAnnotation:
    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        self.owner.events.append(("enter", self.name, time.perf_counter()))

    def __exit__(self, *exc):
        self.owner.events.append(("exit", self.name, time.perf_counter()))
        return False


@pytest.fixture
def fake():
    prev = telemetry.get_annotator()
    ann = telemetry.set_annotator(FakeAnnotator())
    yield ann
    telemetry.set_annotator(prev)


# -- the bridge slot ---------------------------------------------------------

def test_observability_installs_the_profilers_annotation():
    assert telemetry.get_annotator() is jax.profiler.TraceAnnotation


@pytest.mark.parametrize("traced", [False, True])
def test_span_enters_the_annotation_and_its_row_is_unchanged(fake, traced):
    ctx = telemetry.TraceContext.new_root() if traced else None
    with telemetry.use_trace(ctx):
        with telemetry.span("unit.work", phase="a") as got:
            time.sleep(0.001)
    assert (got is not None) == traced
    assert [(w, n) for w, n, _ in fake.events] == [
        ("enter", "unit.work"), ("exit", "unit.work")]
    (name, t0, dur, labels), = telemetry.get_registry().spans
    assert name == "unit.work" and labels["phase"] == "a" and dur >= 0.001
    assert ("trace_id" in labels) == traced
    # the annotation lies around the recorded interval, not inside it
    assert fake.events[0][2] <= t0 and t0 + dur <= fake.events[1][2]
    snap = telemetry.get_registry().snapshot()
    assert snap["histograms"]["span.unit.work.duration_s{phase=a}"][
        "count"] == 1


def test_empty_slot_and_uninstall_raise_nothing(fake):
    telemetry.set_annotator(None)
    assert telemetry.annotation("a") is telemetry.annotation("b")  # shared
    with telemetry.span("unit.empty"):
        pass
    timer = telemetry.PhaseTimer("unit.", ("a",), whole="all")
    timer.start()
    with timer.phase("a"):
        pass
    timer.commit()
    assert len(telemetry.get_registry().spans) == 1 and not fake.events
    telemetry.set_annotator(fake)
    telemetry.uninstall()
    with telemetry.span("unit.off"):    # no registry: nothing at all
        pass
    timer = telemetry.PhaseTimer("unit.", ("a",), whole="all")
    timer.start()
    with timer.phase("a"):
        timer.lap("a")
    timer.commit()                      # records into the shared null
    assert fake.names("enter") == ["unit.a"]


def test_phase_timer_partitions_an_iteration(fake):
    reg = telemetry.get_registry()
    rec = telemetry.get_recorder()
    ring = len(rec._ring) if rec is not None else 0
    timer = telemetry.PhaseTimer("unit.", ("outer", "inner", "x", "y",
                                           "never"), whole="all")
    for _ in range(3):
        timer.start()
        with timer.phase("outer"):
            time.sleep(0.001)
            with timer.phase("inner"):      # pauses outer: self times
                time.sleep(0.01)
        timer.lap()
        for _ in range(4):
            time.sleep(0.0005)
            timer.lap("x")
            timer.lap("y")
        timer.commit()
    timer.start()                           # an iteration never committed
    with timer.phase("outer"):
        pass
    hist = reg.snapshot()["histograms"]
    stats = {p: hist[f"unit.{p}_s"] for p in ("outer", "inner", "x", "y",
                                              "never", "all")}
    assert all(s["count"] == 3 for s in stats.values())
    assert stats["never"]["sum"] == 0.0     # zeros are samples too
    assert stats["outer"]["min"] >= 0.001 and stats["inner"]["min"] >= 0.01
    assert stats["outer"]["sum"] < stats["inner"]["sum"]  # inner not in outer
    assert stats["x"]["min"] >= 4 * 0.0005
    assert stats["y"]["sum"] < stats["x"]["sum"]
    parts = sum(stats[p]["sum"] for p in ("outer", "inner", "x", "y"))
    assert 0.9 * stats["all"]["sum"] <= parts <= stats["all"]["sum"]
    # annotations are named like the histograms without "_s"; laps and the
    # whole have none; nothing reaches the span ring or the recorder
    assert set(fake.names("enter")) == {"unit.outer", "unit.inner"}
    assert fake.names("enter").count("unit.inner") == 3
    assert len(reg.spans) == 0
    assert rec is None or len(rec._ring) == ring


# -- the phases on a tiny engine ---------------------------------------------

ENGINES = {
    "rectangular": dict,
    "paged": lambda: dict(page_size=16),
    "chunked": lambda: dict(page_size=16, prefill_chunk=8),
    "speculative": lambda: dict(draft=NgramDraft(ngram=2), spec_k=3),
}


@pytest.mark.parametrize("flavour", sorted(ENGINES))
def test_every_phase_has_one_sample_per_busy_iteration(lm, fake, flavour):
    model, params = lm
    with GenerationEngine(model, params, num_slots=4,
                          prefill_buckets=(8, 32), queue_capacity=16,
                          **ENGINES[flavour]()) as eng:
        reg = telemetry.get_registry()
        rec = telemetry.get_recorder()
        spans = len(reg.spans)
        ring = len(rec._ring) if rec is not None else 0
        futs = [eng.generate(_prompt(n, n), max_new_tokens=12)
                for n in (3, 8, 20, 5, 30)]
        for f in futs:
            assert len(f.result(timeout=120).tokens) == 12
        # decode steps reach neither the span ring nor the flight recorder
        assert len(reg.spans) == spans
        assert rec is None or len(rec._ring) == ring
    # read once the scheduler has stopped: a request's future is set inside
    # the lane loop, before its iteration commits
    snap = reg.snapshot()
    hist, counters = snap["histograms"], snap["counters"]
    iters = hist["serving.sched.iter_s"]["count"]
    assert iters >= 12
    for p in PHASES:
        assert telemetry.declared_kind(f"serving.sched.{p}_s") == "histogram"
        assert hist[f"serving.sched.{p}_s"]["count"] == iters, p
    if flavour != "chunked":    # a chunk-only iteration takes no decode step
        assert iters == counters["serving.decode.steps"]
    assert hist["serving.decode.step_s"]["count"] == \
        counters["serving.decode.steps"]
    parts = sum(hist[f"serving.sched.{p}_s"]["sum"] for p in PHASES)
    whole = hist["serving.sched.iter_s"]["sum"]
    assert 0.9 * whole <= parts <= whole, (parts, whole)
    # the step's own histogram is the launch-to-host interval it always
    # was; the walk that delivers the step before now lies inside it
    assert hist["serving.decode.step_s"]["sum"] <= sum(
        hist[f"serving.sched.{p}_s"]["sum"]
        for p in ("launch", "wait", "copy", "pick", "stream", "retire")
    ) + 1e-3 * iters
    for p in ("launch", "wait", "copy", "pick", "retire", "prefill_wait"):
        assert hist[f"serving.sched.{p}_s"]["sum"] > 0, p
    # annotations: leaves named like the histograms, one `emit` around the
    # lane loop's bookkeeping, one `deliver` around the walk, none around
    # the whole iteration
    entered = set(fake.names("enter"))
    assert {"serving.sched." + p for p in
            ("control", "admit", "prefill_wait", "launch", "wait", "copy",
             "emit", "deliver")} <= entered
    assert not {"serving.sched.iter", "serving.sched.stream",
                "serving.sched.retire"} & entered
    for name in ("emit", "deliver"):
        assert telemetry.declared_kind(
            "serving.sched." + name) == "annotation"
    steps = counters["serving.decode.steps"]
    assert fake.names("enter").count("serving.sched.wait") == steps
    # one walk a dispatch, right behind it: between a launch's exit and
    # the wait's entry, and inside a prefill's call-to-logits interval;
    # besides those, only flushes (at most one an iteration)
    events = [(w, n.removeprefix("serving.sched."))
              for w, n, _ in fake.events]
    for i, event in enumerate(events):
        if event == ("exit", "launch"):
            assert events[i + 1:i + 4] == [
                ("enter", "deliver"), ("exit", "deliver"),
                ("enter", "wait")], events[i:i + 4]
    calls = counters.get("serving.decode.chunk.steps",
                         counters["serving.decode.prefills"])
    inside = sum(1 for i, event in enumerate(events)
                 if event == ("enter", "deliver")
                 and events[i - 1] == ("enter", "prefill_wait"))
    assert inside == calls
    walks = fake.names("enter").count("serving.sched.deliver")
    assert steps + calls <= walks <= steps + calls + iters


def test_trace_rows_are_counted_by_the_request_not_by_the_token(monkeypatch):
    """A traced request costs the scheduler thread a constant number of
    ``trace.*`` rows (each mints its span id with ``os.urandom``, a system
    call that lets waiting handler threads in), whatever it generates: none
    in the lane loop, so the span ring keeps a long answer's other rows."""
    model = gpt_tiny(max_len=256)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    real, calls = os.urandom, []

    def urandom(n):
        calls.append(threading.current_thread().name)
        return real(n)

    monkeypatch.setattr(os, "urandom", urandom)
    answers = (40, 90, 200)     # the last one outlives the others
    with GenerationEngine(model, params, num_slots=4, queue_capacity=8,
                          prefill_buckets=(8,)) as eng:
        roots = [telemetry.TraceContext.new_root() for _ in answers]
        futs = [eng.generate(_prompt(5, n), max_new_tokens=n, trace=root)
                for n, root in zip(answers, roots)]
        for n, f in zip(answers, futs):
            assert len(f.result(timeout=120).tokens) == n
    on_scheduler = calls.count("generation-scheduler")
    counters = telemetry.get_registry().snapshot()["counters"]
    rows, tokens = (counters["serving.decode.trace_rows"],
                    counters["serving.decode.tokens"])
    # queue_wait, prefill, decode, request: four a request, none a token
    assert on_scheduler == rows == 4 * len(answers)
    assert tokens == sum(answers) - len(answers)
    assert rows / tokens < 0.04
    # the newest 100 spans still hold the 200-token answer's first rows
    mine = {r["name"]: r for r in telemetry.get_registry().recent_spans()
            if r.get("trace_id") == roots[-1].trace_id}
    assert set(mine) == {"trace.queue_wait", "trace.prefill", "trace.decode",
                         "trace.request"}
    assert mine["trace.decode"]["labels"]["steps"] == answers[-1] - 1
    # and no histogram per step number
    assert not [k for k in telemetry.get_registry().snapshot()["histograms"]
                if k.startswith("span.trace.decode.") and "step=" in k]


class _UnawaitedLogits:
    """The logits as the scheduler fetched them before the split: nothing
    waits for the step, ``np.asarray`` on the running result does it all."""

    def __init__(self, array):
        self.array = array

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.array)


@pytest.mark.parametrize("kwargs", [dict(), dict(page_size=16)],
                         ids=["rectangular", "paged"])
def test_seeded_tokens_identical_with_and_without_the_wait_split(lm, kwargs):
    model, params = lm

    def serve(old_fetch):
        telemetry.reset()
        with GenerationEngine(model, params, num_slots=2,
                              prefill_buckets=(8, 32), sampling=True,
                              temperature=0.8, seed=24, **kwargs) as eng:
            if old_fetch:
                for lane, ex in list(eng._decode_exec.items()):
                    def wrapped(*a, _ex=ex):
                        pool, logits = _ex(*a)
                        return pool, _UnawaitedLogits(logits)
                    eng._decode_exec[lane] = wrapped
            return [eng.generate(_prompt(n, n), max_new_tokens=16)
                    .result(timeout=120).tokens.tolist()
                    for n in (4, 11, 27)]

    assert serve(old_fetch=True) == serve(old_fetch=False)


# -- the shared clock ---------------------------------------------------------

def test_phases_and_trainer_spans_are_events_of_the_profilers_trace(
        lm, tmp_path):
    from distkeras_tpu import ADAG, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    model, params = lm
    trainer = ADAG(MLP(features=(16,), num_classes=10), num_workers=2,
                   batch_size=16, communication_window=2, num_epoch=1,
                   staging_rounds=1)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # as perf/harness.py sets them
    options.host_tracer_level = 2
    with GenerationEngine(model, params, num_slots=2,
                          prefill_buckets=(8,)) as eng:
        eng.generate(_prompt(3), max_new_tokens=2).result(timeout=120)
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            eng.generate(_prompt(5), max_new_tokens=6).result(timeout=120)
            trainer.train(synthetic_mnist(n=256))
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    seen = {}
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    seen[e.name] = seen.get(e.name, 0) + 1
    hist = telemetry.get_registry().snapshot()["histograms"]
    assert seen.get("trainer.epoch") == 1 and seen.get("trainer.stage")
    # five decode steps were traced, each a wait, a copy and a lane loop
    for name in ("serving.sched.wait", "serving.sched.copy",
                 "serving.sched.emit", "serving.sched.launch"):
        assert seen.get(name) == 5, (name, seen.get(name))
    # a walk behind each of the five launches and the prefill's call, and
    # the flush that hands over the last token and the result
    assert seen.get("serving.sched.deliver") == 5 + 1 + 1
    assert hist["serving.sched.wait_s"]["count"] == 5 + 1
    assert "serving.sched.iter" not in seen


# -- the benchmark's readers --------------------------------------------------

@pytest.fixture
def perf_path():
    perf = os.path.join(REPO, "perf")
    sys.path.append(perf)
    try:
        yield perf
    finally:
        sys.path.remove(perf)


@pytest.fixture
def readers(perf_path):
    from readers import registry_hist, registry_hist_share
    return registry_hist, registry_hist_share


def test_registry_readers_on_a_hand_filled_registry(readers):
    hist, share = readers
    # the parent commit has no such histogram, a run that served nothing
    # an empty one: both leave the metric out, and looking creates nothing
    telemetry.histogram("unit.sched.empty_s")
    for name in ("unit.sched.iter_s", "unit.sched.empty_s"):
        assert hist.read(None, None, name=name, field="p50") is None
        assert share.read(None, None, over=["unit.sched.wait_s"],
                          under=[name]) is None
    assert "unit.sched.iter_s" not in \
        telemetry.get_registry().snapshot()["histograms"]
    for v in (0.080, 0.090, 0.085, 0.100):
        telemetry.histogram("unit.sched.iter_s").record(v)
    for v in (0.070, 0.071, 0.069, 0.072):
        telemetry.histogram("unit.sched.wait_s").record(v)
    telemetry.histogram("unit.sched.prefill_wait_s").record(0.011)
    read = lambda field: hist.read(None, None, name="unit.sched.iter_s",
                                   field=field)
    assert read("p50") == 0.090 and read("p95") == 0.100
    assert read("count") == 4 and read("sum") == pytest.approx(0.355)
    args = dict(over=["unit.sched.wait_s", "unit.sched.prefill_wait_s"],
                under=["unit.sched.iter_s"])
    want = 100.0 * (0.282 + 0.011) / 0.355
    assert share.read(None, None, **args) == pytest.approx(want)
    assert share.read(None, None, complement=True, **args) == \
        pytest.approx(100.0 - want)
    assert share.read(None, None, over=["unit.sched.wait_s",
                                        "unit.sched.empty_s"],
                      under=["unit.sched.iter_s"]) is None
    telemetry.uninstall()
    assert read("p50") is None and share.read(None, None, **args) is None


def test_the_delivery_metrics_read_through_the_harness(perf_path):
    """The two metric files of ISSUE 33, loaded the way ``perf/run.py``
    loads them (``perf/selftest.py`` holds them to ``BENCHMARK.json``):
    listed in the four serving cells, and on a program without the two
    counters (the parent) the share's reader returns None, so the metric
    is left out of the line."""
    import harness

    names = ("sched_launch_p50_s", "sched_deliver_after_dispatch_share")
    listed = harness.listed_metrics
    assert not set(names) & set(listed("gpt2m_train_s1024")["per_layer"])
    for cell in ("gpt2m_serve_batch_closed", "gpt2m_serve_label_closed",
                 "mistral4_serve_doc_closed", "nemotron3_serve_rag_closed"):
        assert set(names) <= set(listed(cell)["per_layer"]), cell
    specs = {n: harness.load_json("metrics", n + ".json") for n in names}
    read = lambda name: harness.load_module(
        "readers", specs[name]["reader"]).read(
            None, None, **specs[name]["args"])
    assert read(names[0]) is None and read(names[1]) is None
    for v in (0.0016, 0.0014, 0.0015):
        telemetry.histogram("serving.sched.launch_s").record(v)
    assert read(names[0]) == 0.0015
    # the parent counts neither; a run that delivered nothing has no
    # share either
    telemetry.counter("serving.sched.delivered")
    assert read(names[1]) is None
    telemetry.counter("serving.sched.delivered").inc(200)
    telemetry.counter("serving.sched.delivered_after_dispatch").inc(197)
    assert read(names[1]) == pytest.approx(98.5)
