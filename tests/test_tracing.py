"""Distributed tracing plane (DESIGN.md §15): context propagation, spans
under transport faults, the fleet collector, and the attribution evidence.

The load-bearing guarantees:

- a TraceContext survives the W3C traceparent round-trip and malformed
  headers degrade to untraced, never to an error;
- nested spans chain parent -> child, and the reserved identity keys are
  hoisted out of labels (no per-trace histogram cardinality);
- one trace_id stitches worker -> transport -> server -> fold across the
  loopback wire, including through chaos-injected drops/resets: a retried
  commit stays ONE logical trace.rpc + ONE trace.fold with trace.retry
  children, and no span is ever orphaned or duplicated;
- a sharded-fleet commit fans the same trace across every shard;
- the collector is bounded (drop-oldest with counters) and merges
  pid-tagged rows;
- tracing is observability only: the training trajectory is bitwise
  identical with tracing on vs off (NUMERICS.md);
- the committed PR-10 evidence artifact meets the acceptance numbers
  (phase coverage >= 95%, tracing overhead <= 2%).
"""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.comms import RetryPolicy
from distkeras_tpu.health.collector import TelemetryCollector, worker_table
from distkeras_tpu.health.export import chrome_trace
from distkeras_tpu.parallel.elastic import (
    ShardedRemoteParameterServer,
    make_ps_fleet,
)
from distkeras_tpu.parallel.remote_ps import (
    ParameterServerService,
    RemoteParameterServer,
)
from distkeras_tpu.parameter_servers import (
    DeltaParameterServer,
    DynSGDParameterServer,
)
from distkeras_tpu.utils import fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = {"w": jnp.ones((4, 3), jnp.float32),
          "b": jnp.zeros((3,), jnp.float32)}

FAST = dict(retry=RetryPolicy(max_retries=3, base_s=0.01, max_s=0.05),
            op_timeout=5.0)


@pytest.fixture(autouse=True)
def fresh_state():
    telemetry.reset()
    fault.clear_chaos()
    yield
    fault.clear_chaos()
    telemetry.reset()


def _span_rows(name=None):
    rows = [r for r in telemetry.get_registry().rows()
            if r.get("kind") == "span"]
    if name is not None:
        rows = [r for r in rows if r["name"] == name]
    return rows


def _wait_spans(name, n, timeout_s=5.0):
    """The server records trace.server when its handler block exits — a
    hair AFTER the reply is already on the wire — so a client that just
    got its answer can observe the registry before the handler thread's
    last instructions land. Poll until ``n`` spans exist (or time out and
    return whatever is there for the assertion to report)."""
    deadline = time.monotonic() + timeout_s
    rows = _span_rows(name)
    while len(rows) < n and time.monotonic() < deadline:
        time.sleep(0.01)
        rows = _span_rows(name)
    return rows


def _assert_no_orphans(rows, roots):
    """Every traced span's parent must be another recorded span or a known
    root context, and span ids must be unique (no duplicated spans)."""
    traced = [r for r in rows if "trace_id" in r]
    ids = [r["span_id"] for r in traced]
    assert len(ids) == len(set(ids)), "duplicated span ids"
    known = set(ids) | {c.span_id for c in roots}
    for r in traced:
        assert r["parent_id"] in known, (
            f"orphaned span {r['name']} (parent {r['parent_id']})")


# ------------------------------------------------------------ context basics

def test_traceparent_roundtrip_and_malformed():
    ctx = telemetry.TraceContext.new_root(worker="3")
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = telemetry.TraceContext.from_traceparent(ctx.to_traceparent())
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    for bad in ("", "00-short-abc-01", "01-" + "a" * 32 + "-" + "b" * 16
                + "-01", "00-" + "z" * 32 + "-" + "b" * 16 + "-01", None,
                42):
        assert telemetry.TraceContext.from_traceparent(bad) is None

    header = telemetry.inject({"op": "pull"}, ctx)
    assert header[telemetry.TRACEPARENT_KEY] == ctx.to_traceparent()
    assert header[telemetry.TRACE_BAGGAGE_KEY] == {"worker": "3"}
    got = telemetry.extract(header)
    assert got.trace_id == ctx.trace_id and got.baggage == {"worker": "3"}
    assert telemetry.extract({"op": "pull"}) is None
    assert telemetry.extract({telemetry.TRACEPARENT_KEY: "garbage"}) is None
    # untraced thread + no explicit ctx: inject is a no-op
    assert telemetry.TRACEPARENT_KEY not in telemetry.inject({"op": "x"})


def test_span_nesting_chains_parent_child_and_strips_identity():
    root = telemetry.TraceContext.new_root()
    with telemetry.use_trace(root):
        with telemetry.span("trace.window", worker=0) as outer:
            with telemetry.span("trace.commit") as inner:
                pass
    assert outer.trace_id == root.trace_id != None  # noqa: E711
    rows = {r["name"]: r for r in _span_rows()}
    w, c = rows["trace.window"], rows["trace.commit"]
    assert w["trace_id"] == c["trace_id"] == root.trace_id
    assert w["parent_id"] == root.span_id
    assert c["parent_id"] == w["span_id"] == outer.span_id
    assert inner.span_id == c["span_id"]
    # identity keys hoisted out of labels; functional labels stay
    assert w["labels"] == {"worker": 0}
    # and the minted duration histogram carries no per-trace identity
    hists = [r for r in telemetry.get_registry().rows()
             if r["kind"] == "histogram"
             and r["name"] == "span.trace.window.duration_s"]
    assert len(hists) == 1 and "trace_id" not in hists[0]["labels"]
    # outside any trace, span() yields None and records a plain event
    with telemetry.span("trace.window") as ctx:
        assert ctx is None


def test_record_trace_span_explicit_context():
    root = telemetry.TraceContext.new_root()
    telemetry.record_trace_span(root, "trace.queue_wait", 1.0, 0.25,
                                tokens=4)
    telemetry.record_trace_span(None, "trace.queue_wait", 2.0, 0.5)
    traced, plain = _span_rows("trace.queue_wait")
    assert traced["trace_id"] == root.trace_id
    assert traced["parent_id"] == root.span_id
    assert traced["labels"] == {"tokens": 4}
    assert traced["dur_s"] == 0.25
    assert "trace_id" not in plain


# ------------------------------------------------------- wire propagation

def test_one_trace_id_spans_client_rpc_server_and_fold():
    ps = DynSGDParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **FAST)
        root = telemetry.TraceContext.new_root()
        with telemetry.use_trace(root):
            cli.commit(one, last_update=0)
        cli.close()
    finally:
        svc.stop()
    server = _wait_spans("trace.server", 1)
    rpc = _span_rows("trace.rpc")
    folds = _span_rows("trace.fold")
    assert len(rpc) == len(server) == len(folds) == 1
    assert (rpc[0]["trace_id"] == server[0]["trace_id"]
            == folds[0]["trace_id"] == root.trace_id)
    # parentage crosses the socket: the server span's parent IS the rpc
    # span whose context rode the traceparent header
    assert server[0]["parent_id"] == rpc[0]["span_id"]
    assert folds[0]["parent_id"] == server[0]["span_id"]
    _assert_no_orphans(_span_rows(), [root])


@pytest.mark.parametrize("action", ["reset", "reset_after_send", "drop"])
def test_traced_commit_under_chaos_one_rpc_one_fold(action):
    """Transport faults during a traced commit: retries surface as tagged
    trace.retry children under the SAME trace, while the logical commit
    stays exactly one trace.rpc and exactly one trace.fold (dedup), with
    no orphaned or duplicated spans."""
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        kw = dict(retry=RetryPolicy(max_retries=3, base_s=0.3, max_s=0.6),
                  op_timeout=5.0)
        if action == "drop":  # reply never comes: wait out the op timeout
            kw["op_timeout"] = 0.2
        cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **kw)
        cli.commit(one, last_update=0)  # warmup: compile the fold path
        fault.inject_chaos("remote_ps.send", action, count=1)
        root = telemetry.TraceContext.new_root()
        with telemetry.use_trace(root):
            assert cli.commit(one, last_update=1) == 1
        assert cli.num_updates == 2  # the retry folded exactly once
        cli.close()
    finally:
        svc.stop()
    # reset_after_send delivers twice (fold + dedup hit); the other
    # actions lose the request itself, so the retry is the only delivery
    _wait_spans("trace.server", 2 if action == "reset_after_send" else 1)
    rpc = _span_rows("trace.rpc")
    folds = [r for r in _span_rows("trace.fold") if "trace_id" in r]
    retries = _span_rows("trace.retry")
    assert len(rpc) == 1, "a retry must never mint a second trace.rpc"
    assert len(folds) == 1, "dedup: one logical commit, one fold"
    assert len(retries) >= 1
    for r in retries:
        assert r["trace_id"] == root.trace_id
        assert r["parent_id"] == rpc[0]["span_id"]
    for r in _span_rows("trace.reconnect"):
        assert r["trace_id"] == root.trace_id
    _assert_no_orphans(_span_rows(), [root])


def test_sharded_fleet_commit_fans_one_trace_across_shards():
    """ISSUE 10 acceptance shape (in-process): a single traced commit
    against an N=2 fleet lands one trace_id on the coordinator leg, the
    follower leg, both servers, and both folds — and survives a chaos
    reset on the way — and the Chrome export keys every event on it."""
    services = make_ps_fleet(
        lambda part: DynSGDParameterServer(jax.device_put(part)),
        PARAMS, 2)
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        # retries slower than a warmed fold, so the dedup cache is
        # populated before the replay arrives (the retry must be answered
        # from cache, not folded again)
        fleet = ShardedRemoteParameterServer(
            [f"127.0.0.1:{svc.port}" for svc in services], PARAMS,
            retry=RetryPolicy(max_retries=3, base_s=0.3, max_s=0.6),
            op_timeout=5.0)
        fleet.commit(one, last_update=0)  # warmup: compile both folds
        fault.inject_chaos("remote_ps.send", "reset_after_send", count=1)
        root = telemetry.TraceContext.new_root()
        with telemetry.use_trace(root):
            with telemetry.span("trace.window", worker=0):
                fleet.commit(one, last_update=1)
        fleet.close()
    finally:
        for svc in services:
            svc.stop()

    # 3 deliveries: the reset_after_send leg twice (fold + dedup hit),
    # the clean leg once — the last records just after its reply
    _wait_spans("trace.server", 3)

    def traced(name):  # the warmup's spans carry no trace ids
        return [r for r in _span_rows(name) if "trace_id" in r]

    shards = traced("trace.shard")
    folds = traced("trace.fold")
    servers = [r for r in traced("trace.server")
               if r["labels"].get("op") == "commit"]
    assert {r["labels"]["shard"] for r in shards} == {0, 1}
    assert len(folds) == 2, "one fold per shard, dedup under chaos"
    assert {r["labels"]["shard"] for r in servers} == {0, 1}
    assert len(traced("trace.retry")) >= 1
    ids = {r["trace_id"]
           for r in shards + folds + servers + traced("trace.retry")}
    assert ids == {root.trace_id}
    _assert_no_orphans(_span_rows(), [root])
    # the merged Chrome view carries the trace ids in args
    events = chrome_trace(_span_rows())["traceEvents"]
    traced = [e for e in events if e["args"].get("trace_id")]
    assert {e["args"]["trace_id"] for e in traced} == {root.trace_id}


# ------------------------------------------------------------- collector

def test_collector_bounds_truncates_and_merges():
    col = TelemetryCollector(max_batches=2, max_rows_per_batch=3)
    rows = [{"kind": "counter", "name": f"c{i}", "labels": {}, "value": i}
            for i in range(5)]
    got = col.add_batch(1, rows)  # oversize: truncated to 3
    assert got == {"accepted": 3, "dropped": 2}
    col.add_batch(2, rows[:1])
    col.add_batch(3, rows[:1])  # over max_batches: pid 1's batch dropped
    merged = col.merged_rows()
    assert {r["pid"] for r in merged} == {2, 3}
    assert col.processes == [1, 2, 3]
    snap = telemetry.get_registry().snapshot()["counters"]
    assert snap["collector.dropped_rows"] == 2
    assert snap["collector.dropped_batches"] == 1
    # local_pid appends this process's own live registry under that pid
    telemetry.counter("ps.commit.count").inc()
    merged = col.merged_rows(local_pid=0)
    assert any(r["pid"] == 0 and r["name"] == "ps.commit.count"
               for r in merged)


def test_worker_table_folds_merged_rows():
    now = 100.0
    rows = [
        {"kind": "gauge", "name": "health.worker.heartbeat_time",
         "labels": {"worker": "0"}, "value": 97.0, "pid": 0},
        {"kind": "gauge", "name": "health.worker.heartbeat_time",
         "labels": {"worker": "0"}, "value": 99.0, "pid": 1},
        {"kind": "gauge", "name": "health.worker.straggler",
         "labels": {"worker": "0"}, "value": 1.0, "pid": 0},
        {"kind": "gauge", "name": "health.worker.staleness",
         "labels": {"worker": "1"}, "value": 2.0, "pid": 1},
        {"kind": "counter", "name": "health.worker.windows",
         "labels": {"worker": "1"}, "value": 7, "pid": 0},
        {"kind": "counter", "name": "health.worker.windows",
         "labels": {"worker": "1"}, "value": 4, "pid": 1},
        {"kind": "counter", "name": "host_async.degraded_windows",
         "labels": {"worker": "1"}, "value": 2, "pid": 1},
    ]
    table = worker_table(rows, now)
    assert table["0"]["age_s"] == 1.0  # newest heartbeat wins
    assert table["0"]["straggler"] is True
    assert table["0"]["degraded"] == 0
    assert table["1"]["windows"] == 11  # summed across processes
    assert table["1"]["staleness"] == 2.0
    assert table["1"]["degraded"] == 2


def test_watch_table_renders_rates_and_fallback_rows():
    from distkeras_tpu.health import cli

    workers = {"0": {"age_s": 1.5, "windows": 12, "staleness": 1,
                     "degraded": 0, "straggler": False},
               "1": {"windows": 4, "degraded": 3, "straggler": True}}
    text = cli._watch_table(workers, {"0": 8, "1": 4}, interval=2.0)
    assert "STRAGGLER" in text and "2.00" in text  # (12-8)/2 windows/s
    assert "1.5s" in text
    # the metrics-snapshot fallback feeds worker_table the same shape
    rows = cli._snapshot_rows({
        "gauges": {"health.worker.heartbeat_time{worker=0}": 99.0},
        "counters": {"health.worker.windows{worker=0}": 3}})
    table = worker_table(rows, 100.0)
    assert table["0"]["windows"] == 3 and table["0"]["age_s"] == 1.0


def test_merge_view_groups_rows_by_trace():
    from distkeras_tpu.health import summary as mod

    rows = [
        {"kind": "span", "name": "trace.window", "labels": {}, "t0": 1.0,
         "dur_s": 0.5, "trace_id": "t1", "span_id": "a", "parent_id": "r",
         "pid": 0},
        {"kind": "span", "name": "trace.server", "labels": {}, "t0": 5.0,
         "dur_s": 0.1, "trace_id": "t1", "span_id": "b", "parent_id": "a",
         "pid": 1},
        {"kind": "span", "name": "trace.request", "labels": {}, "t0": 2.0,
         "dur_s": 0.05, "trace_id": "t2", "span_id": "c",
         "parent_id": "r2", "pid": 0},
    ]
    text = mod.merge_view(rows)
    assert "t1" in text and "t2" in text
    assert text.index("t1") < text.index("t2")  # longest trace first
    assert "trace.server" in text and "a -> b" in text


# ---------------------------------------------------- numerics + lifecycle

def test_trajectory_bitwise_identical_tracing_on_vs_off():
    """NUMERICS.md: tracing is observability only. A single-worker async
    run (deterministic schedule) must land bitwise-identical parameters
    with tracing on and off."""
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel import host_async, strategies

    ds = synthetic_mnist(n=128)
    model = MLP(features=(16,), num_classes=10)
    shards = host_async.stage_worker_shards(
        ds.repartition(1), "features", "label", 16, 2)
    init = model.init(jax.random.key(0), jnp.zeros((16, 784)),
                      train=False)["params"]

    def final_params(trace):
        telemetry.reset()
        runner = host_async.HostAsyncRunner(
            model, "categorical_crossentropy", optax.sgd(0.05),
            strategies.get("dynsgd"), window=2, trace=trace)
        center, _, _, _ = runner.run(init, [shards])
        return center

    on, off = final_params(True), final_params(False)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        on, off)
    # and the traced run actually traced
    telemetry.reset()
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", optax.sgd(0.05),
        strategies.get("dynsgd"), window=2, trace=True)
    runner.run(init, [shards])
    windows = _span_rows("trace.window")
    assert windows and all("trace_id" in r for r in windows)
    assert len({r["trace_id"] for r in windows}) == len(windows)
    # every other traced span resolves to a recorded parent (the window
    # spans' own parents are the per-window root contexts, not recorded)
    ids = {r["span_id"] for r in _span_rows() if "span_id" in r}
    for r in _span_rows():
        if "trace_id" in r and r["name"] != "trace.window":
            assert r["parent_id"] in ids, r["name"]


class _FixedDraft:
    """A draft that proposes one token whatever the context: with a token
    the model never emits, the verify step accepts nothing and every
    speculative iteration yields its one free token."""

    token = 0

    def bind(self, engine):
        pass

    def begin(self, slot, prompt, first_token):
        pass

    def propose(self, slots, last_tokens, lengths, k):
        return np.full((len(slots), k), self.token, np.int32)

    def observe(self, slot, emitted):
        pass

    def release(self, slot):
        pass


def _trace_rows():
    return [r for r in _span_rows() if r["name"].startswith("trace.")]


@pytest.mark.parametrize("loop", ["plain", "speculative"])
@pytest.mark.parametrize("end", ["length", "eos", "deadline",
                                 "eos_at_prefill"])
def test_generation_request_trace_covers_lifecycle(end, loop):
    """A traced request leaves trace.queue_wait, trace.prefill, ONE
    trace.decode spanning its decode steps and trace.request, however it
    ends and whichever lane loop decoded it; one that finished at prefill
    leaves no trace.decode."""
    from distkeras_tpu.models.gpt import gpt_tiny
    from distkeras_tpu.serving import DeadlineExceeded, GenerationEngine

    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = [9, 10, 11]
    draft = _FixedDraft()
    kwargs = dict(draft=draft, spec_k=2) if loop == "speculative" else {}
    with GenerationEngine(model, params, num_slots=2, queue_capacity=8,
                          prefill_buckets=(8,), **kwargs) as eng:
        # the same engine, untraced, says what greedy emits (no rows)
        ref = eng.generate(prompt, max_new_tokens=8).result(
            timeout=60).tokens.tolist()
        assert not _trace_rows() and ref[3] not in ref[:3]
        draft.token = next(t for t in range(256) if t not in ref)
        steps0 = telemetry.counter("serving.decode.steps").value
        ask = {"length": dict(max_new_tokens=4),
               "eos": dict(max_new_tokens=8, eos_id=ref[3]),
               "eos_at_prefill": dict(max_new_tokens=8, eos_id=ref[0]),
               "deadline": dict(max_new_tokens=8, timeout_ms=1000.0)}[end]
        seen = []
        t_late = time.monotonic() + 1.05

        def stream(tok):
            seen.append(tok)
            if end == "deadline" and len(seen) == 3:
                # on the scheduler thread, which hands token 3 over with
                # step 3 already launched: the deadline passes here, so
                # the next iteration expires the request after 3 steps,
                # and its 4th token, still owed, reaches it first
                time.sleep(max(0.0, t_late - time.monotonic()))

        root = telemetry.TraceContext.new_root()
        with telemetry.use_trace(root):
            fut = eng.generate(prompt, stream=stream, **ask)
        reason = "eos" if end == "eos_at_prefill" else end
        if end == "deadline":
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
        else:
            assert fut.result(timeout=60).reason == reason
        steps = telemetry.counter("serving.decode.steps").value - steps0
    decoded = end != "eos_at_prefill"
    assert seen == ref[:4 if decoded else 1]
    assert steps == (3 if decoded else 0)
    names = ["trace.queue_wait", "trace.prefill", "trace.request"]
    if decoded:
        names.insert(2, "trace.decode")
    assert [r["name"] for r in _trace_rows()] == names
    assert all(r["trace_id"] == root.trace_id for r in _trace_rows())
    assert telemetry.counter("serving.decode.trace_rows").value == len(names)
    _assert_no_orphans(_span_rows(), [root])
    (request,), (prefill,) = (_span_rows("trace.request"),
                              _span_rows("trace.prefill"))
    assert request["labels"]["reason"] == reason
    assert request["labels"]["tokens"] == len(seen)
    if decoded:
        (decode,) = _span_rows("trace.decode")
        assert decode["parent_id"] == root.span_id
        # prefill emits token 1; each remaining token is one decode step
        assert decode["labels"]["steps"] == 3
        assert set(decode["labels"]) == {"steps", "step_ms", "model_version"}
        # the steps it rode lie inside its decode interval (whole ms each)
        assert 0 <= 3 * decode["labels"]["step_ms"] <= (
            1e3 * decode["dur_s"] + 1.5)
        eps = 1e-6
        assert decode["t0"] >= prefill["t0"] + prefill["dur_s"] - eps
        assert decode["t0"] + decode["dur_s"] <= (
            request["t0"] + request["dur_s"] + eps)
        assert request["t0"] <= decode["t0"]


def test_serving_server_extracts_or_mints_request_trace():
    from distkeras_tpu.serving.server import ServingServer

    ctx = telemetry.TraceContext.new_root()
    got = ServingServer._request_trace(telemetry.inject({"op": "infer"},
                                                        ctx))
    assert (got.trace_id, got.span_id) == (ctx.trace_id, ctx.span_id)
    minted = ServingServer._request_trace({"op": "infer"})
    assert minted is not None and minted.trace_id != ctx.trace_id


def test_flush_at_exit_writes_artifact(tmp_path):
    """The atexit flush must persist the span/metric artifact through a
    normal interpreter exit without an explicit dump call."""
    out = tmp_path / "exit_telemetry.jsonl"
    code = (
        "from distkeras_tpu import telemetry\n"
        "telemetry.reset()\n"
        f"telemetry.flush_at_exit({str(out)!r})\n"
        "telemetry.counter('ps.commit.count').inc(3)\n"
        "with telemetry.span('trace.window'):\n"
        "    pass\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120, cwd=REPO)
    # the flush suffixes the path with the process index (multi-host runs
    # must not clobber one another's artifact): .p0 in a single process
    rows = telemetry.load_jsonl(str(out) + ".p0")
    assert any(r.get("name") == "ps.commit.count" and r.get("value") == 3
               for r in rows)
    assert any(r.get("kind") == "span" and r.get("name") == "trace.window"
               for r in rows)
