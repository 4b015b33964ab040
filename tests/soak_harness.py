"""Chaos soak: the whole loop, under fire, for as long as you give it.

The long-horizon acceptance harness of ROADMAP item 4(b) and DESIGN.md
§24: compose ADAG host-async training (standby-backed PS fleet), the
streaming data service, the rollout publish plane and a routed serving
fleet into one process, then run repeated CYCLES under a seeded kill
schedule until the wall-clock budget is spent AND every authority has
been killed at least once:

==================  =======================================================
authority           drill (all via utils/fault.py chaos sites)
==================  =======================================================
trainer-worker      ``remote_ps.send`` ``reset`` — a worker's PS
                    connection dies mid-window (its egress socket is
                    reset); retry/reconnect must recover the window.
                    Honest limit: the repo has no worker-death-with-
                    range-reassignment in the elastic plane, so this
                    drills the worker's TRANSPORT death, not its host.
ps-coordinator      ``remote_ps.server.handle`` ``kill`` on shard 0 —
                    listener and live connections die; the §17 standby
                    must promote via lease handoff, workers re-resolve.
data-coordinator    ``data.lease`` ``kill`` — the coordinator process
                    dies mid-epoch; a FRESH coordinator restored from
                    the ``[epoch, watermark]`` cursor must resume the
                    stream bitwise (the §20 drill), zero ranges lost.
serving-replica     a hard replica kill mid-storm (listener down, engine
                    dead); every in-flight request must re-queue onto a
                    survivor token-exact, and the pool is replenished.
==================  =======================================================

Every cycle also: drains one data-service epoch, serves a prompt burst
checked token-exact against a local greedy reference, publishes the next
weight version through :class:`WeightPublisher` → fleet-wide
``push_weights``, and snapshots the invariants. Throughout, the §24
:class:`MetricStore` collects registry history on its daemon thread and
a :class:`TrendMonitor` + :class:`SloEngine` judge it continuously —
leaks, stalls and drift are failures even when every request succeeded.

The three flywheel invariants (summary row, asserted by
``test_timeseries.py::test_soak_smoke_all_authorities_and_invariants``
and by ``main()``'s exit code): **zero lost windows**, **zero
failed requests** (token-exactness counts as success), **strictly
monotone model_version** across every published cycle. After the soak, a
deliberate HBM-leak drill injects a synthetic monotone series, requires
the LeakDetector to catch it, and dumps the resulting typed trend event
into a flight-recorder postmortem bundle — proving the forensic path,
not just the happy path.

Usage:
  python tests/soak_harness.py [--budget-s 120] [--seed 0]
      [--out soak.jsonl]
      [--workers 2] [--shards 2] [--replicas 3]

CPU-safe (MNIST MLP trainer + gpt_tiny serving over loopback TCP).
Honest limit: minutes on CI stand in for hours on hardware — the
schedule, invariants and forensic record are identical, only the budget
scales; and all clocks are one host's wall clock.
JSONL schema: ``{"kind": "cycle"}`` per cycle, ``{"kind": "kill"}`` per
drill, ``{"kind": "trend_drill"}``, then one ``{"kind": "summary"}``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from concurrent.futures import ThreadPoolExecutor

try:
    import distkeras_tpu  # noqa: F401  (pip-installed)
except ImportError:  # running from a source checkout: use the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

AUTHORITIES = ("trainer-worker", "ps-coordinator", "data-coordinator",
               "serving-replica")

DATA_ROWS = 112
DATA_RANGE = 16


# -- shared model stack --------------------------------------------------------

def _setup():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.models.gpt import gpt_tiny
    from distkeras_tpu.models.mlp import MLP

    model = gpt_tiny()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    mlp = MLP(features=(8,), num_classes=2)
    mlp_params = mlp.init(jax.random.key(0), jnp.zeros((1, 4)),
                          train=False)["params"]
    full = jax.jit(lambda p, ids: model.apply({"params": p}, ids))

    def greedy_ref(prompt, steps):
        seq, out = list(prompt), []
        for _ in range(steps):
            pad = np.zeros((1, model.max_len), np.int32)
            pad[0, :len(seq)] = seq
            tok = int(np.argmax(
                np.asarray(full(params, pad))[0, len(seq) - 1]))
            out.append(tok)
            seq.append(tok)
        return out

    return (model, params, mlp, mlp_params), greedy_ref


class _Fleet:
    """N loopback replicas behind one FleetRouter, replenishable after
    kills (the soak keeps the pool at its configured size)."""

    def __init__(self, stack, n, **router_kw):
        from distkeras_tpu.serving import FleetRouter

        self.stack = stack
        self.router = FleetRouter(**router_kw)
        self.replicas = []
        for _ in range(n):
            self.add()

    def add(self):
        from distkeras_tpu.serving import (GenerationEngine, ServingEngine,
                                           ServingServer)

        model, params, mlp, mlp_params = self.stack
        gen = GenerationEngine(model, params, num_slots=2,
                               prefill_buckets=(8, 32), page_size=16,
                               prefix_cache_bytes=4 << 20)
        eng = ServingEngine(mlp, mlp_params, input_shape=(4,),
                            buckets=(1, 8), max_wait_ms=1.0)
        srv = ServingServer(eng, host="127.0.0.1", generator=gen,
                            router=self.router)
        srv.start()
        rid = self.router.add_replica(f"127.0.0.1:{srv.port}", role="both")
        rep = {"rid": rid, "gen": gen, "eng": eng, "srv": srv,
               "dead": False}
        self.replicas.append(rep)
        return rep

    def live(self):
        return [r for r in self.replicas if not r["dead"]]

    def kill_one(self, rng):
        victim = rng.choice(self.live())
        victim["srv"].stop()
        victim["gen"].shutdown(drain=False, timeout=10.0)
        victim["dead"] = True
        return victim["rid"]

    def close(self):
        self.router.close()
        for rep in self.replicas:
            rep["srv"].stop()
            if not rep["dead"]:
                rep["gen"].shutdown(drain=False, timeout=10.0)
            rep["eng"].shutdown(drain=False)


# -- per-cycle legs -----------------------------------------------------------

def _train_leg(stack_seed, workers, shards, window, batch, n, lease_s,
               kill):
    """One host-async epoch against a fresh standby-backed PS fleet
    (failover_probe's recipe). ``kill``: None | "trainer-worker" |
    "ps-coordinator". Returns windows/lost/promoted."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu import DynSGD, synthetic_mnist
    from distkeras_tpu.comms import RetryPolicy
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel import elastic, host_async
    from distkeras_tpu.utils import fault

    model = MLP(features=(32,), num_classes=10)
    t = DynSGD(model, mode="host_async", num_workers=workers,
               worker_optimizer="sgd", learning_rate=0.05, metrics=(),
               batch_size=batch, communication_window=window)
    ds = synthetic_mnist(n=n)
    staged = host_async.stage_worker_shards(
        ds.repartition(workers), "features", "label", batch, window)
    params = model.init(jax.random.key(stack_seed),
                        jnp.zeros((batch, 784)), train=False)["params"]
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", t.tx, t.strategy,
        window=window, max_degraded_windows=32)

    def make_ps(part):
        return host_async.server_for(
            t.strategy, jax.device_put(part, runner.devices[0]))

    services = elastic.make_ps_fleet(make_ps, params, shards,
                                     standby=True, coord_lease_s=lease_s)
    client = elastic.ShardedRemoteParameterServer(
        [svc.advertised for svc in services if not svc.is_standby],
        params, standby=services[-1].advertised,
        retry=RetryPolicy(max_retries=4, base_s=0.02, max_s=0.25),
        op_timeout=5.0)
    # past the registration/initial-pull handshake, like failover_probe
    if kill == "ps-coordinator":
        fault.inject_chaos("remote_ps.server.handle", "kill",
                           after=2 * workers + 2, count=1, shard=0)
    elif kill == "trainer-worker":
        fault.inject_chaos("remote_ps.send", "reset",
                           after=2 * workers + 2, count=1)
    t0 = time.perf_counter()
    try:
        runner.run(params, [staged], ps=client)
        dt = time.perf_counter() - t0
        promoted = bool(services[-1].standby.promoted)
    finally:
        fault.clear_chaos()
        client.close()
        for svc in services:
            if svc.replicator is not None:
                svc.replicator.close(timeout=1.0)
            svc.stop()
    windows = sum(len(rounds) for rounds in staged)
    return {"windows": windows, "seconds": dt,
            "windows_lost": windows - len(runner.merged_windows),
            "promoted": promoted}


def _data_leg(seed, kill):
    """One full data-service epoch. Clean: drain and require exactly-once
    coverage. Kill: chaos-kill the coordinator mid-epoch, restore a FRESH
    one from the checkpointed cursor (the §20 drill) and require combined
    coverage with zero lost/duplicated ranges."""
    import numpy as np

    from distkeras_tpu.comms import RetryPolicy
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.data.service import (DataCoordinator,
                                            DataServiceClient,
                                            DataServiceUnavailable,
                                            stream_ranges)
    from distkeras_tpu.utils import fault

    retry = RetryPolicy(max_retries=2, base_s=0.01, max_s=0.02)
    ds = Dataset({
        "features": np.arange(2 * DATA_ROWS,
                              dtype=np.float32).reshape(DATA_ROWS, 2),
        "label": np.arange(DATA_ROWS, dtype=np.int64)})

    def mk():
        return DataCoordinator(dataset=ds, range_size=DATA_RANGE,
                               seed=seed)

    coord = mk()
    coord.start()
    consumed, carry = [], coord.cursor_carry()
    t0 = time.perf_counter()
    try:
        if kill:
            # register + 3x(lease, ack) land clean; the 8th dispatch dies
            fault.inject_chaos("data.lease", "kill", after=7)
        try:
            with DataServiceClient(coord.address, worker=0,
                                   retry=retry) as c:
                for item in stream_ranges(c):
                    consumed.append(item[:4])
                    carry = coord.cursor_carry()
        except DataServiceUnavailable:
            if not kill:
                raise
        fault.clear_chaos()
        covered = [pos for _, pos, _, _ in consumed]
        if kill:
            # resume on a fresh coordinator from the checkpointed cursor;
            # post-snapshot pre-crash ranges replay deterministically, so
            # coverage counts the checkpoint prefix + the resumed suffix
            covered = covered[:int(carry[1])]
            fresh = mk()
            fresh.restore_cursor(carry)
            fresh.start()
            try:
                with DataServiceClient(fresh.address, worker=0,
                                       retry=retry) as c:
                    for item in stream_ranges(c):
                        covered.append(item[1])
            finally:
                fresh.stop()
        dt = time.perf_counter() - t0
    finally:
        fault.clear_chaos()
        coord.stop()
    lost = coord.num_ranges - len(set(covered))
    return {"ranges": coord.num_ranges, "covered": len(set(covered)),
            "duplicated": len(covered) - len(set(covered)),
            "ranges_lost": lost, "killed": bool(kill), "seconds": dt}


def _serve_leg(fleet, prompts, want, new_tokens, kill, rng):
    """One prompt burst through the router, token-exact against the local
    greedy reference. ``kill=True``: concurrent storm with a mid-storm
    replica kill, then replenish the pool."""
    total = failed = wrong = 0

    def score(p, res):
        nonlocal wrong
        if res.tokens.tolist() != want[tuple(p)]:
            wrong += 1

    t0 = time.perf_counter()
    killed_rid = None
    if kill:
        with ThreadPoolExecutor(max_workers=4) as pool:
            futs = [(p, pool.submit(fleet.router.generate, p,
                                    max_new_tokens=new_tokens))
                    for p in prompts for _ in range(2)]
            time.sleep(0.05)
            killed_rid = fleet.kill_one(rng)
            for p, fut in futs:
                total += 1
                try:
                    score(p, fut.result(timeout=120))
                except Exception:
                    failed += 1
        fleet.add()  # replenish: the soak pool never shrinks for good
    for p in prompts:
        total += 1
        try:
            score(p, fleet.router.generate(p, max_new_tokens=new_tokens))
        except Exception:
            failed += 1
    return {"requests": total, "failed": failed, "wrong_tokens": wrong,
            "killed_rid": killed_rid, "seconds": time.perf_counter() - t0}


def _publish_leg(publisher, fleet, params):
    """Mint the next model_version and push it fleet-wide; returns the
    version and the per-replica versions the router now observes."""
    version = publisher.publish(params=params)
    fleet.router.push_weights(params, version, target="generation")
    digest = fleet.router.status_digest()
    observed = sorted(r["model_version"]
                      for r in digest["replicas"].values())
    return version, observed


# -- the leak drill -----------------------------------------------------------

def _leak_drill(out_dir):
    """Inject a synthetic monotone HBM series into a fresh MetricStore,
    require the LeakDetector to mint a typed TrendEvent, and dump it into
    a postmortem bundle (read back to prove it landed). Runs AFTER the
    soak so the drill never pollutes the invariants."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.health import recorder, timeseries

    store = timeseries.MetricStore()
    mon = timeseries.TrendMonitor(store, timeseries.default_detectors())
    prev_store = timeseries.get_store()
    prev_mon = timeseries.get_monitor()
    # a fresh registry: the soak just minted hundreds of series, and the
    # drill store's budget would (correctly) shed late arrivals — the
    # drill tests the detector, not the shedding policy
    prev_reg = telemetry.get_registry()
    telemetry.install(telemetry.MetricsRegistry())
    timeseries.install_store(store)
    timeseries.install_monitor(mon)
    try:
        gauge = telemetry.gauge("observability.hbm_allocated_bytes",
                                stat="soak_leak_drill")
        t0 = time.time() - 240.0  # a backdated 4-minute leak history
        for i in range(48):
            gauge.set(1e6 + i * 16e6)  # ~3.2 MiB/s, over the 1 MiB/s rail
            store.collect(now=t0 + i * 5.0)
        minted = mon.evaluate_once()
        caught = any(e.trend == "hbm-leak" and not e.resolved
                     for e in minted)
        path = recorder.get_recorder().dump(out_dir, reason="leak-drill")
        landed = False
        if path:
            with open(path) as f:
                bundle = json.load(f)
            landed = any(
                ev.get("kind") == "trend"
                and ev.get("fields", {}).get("trend") == "hbm-leak"
                for ev in bundle.get("events", [])) and any(
                tr.get("trend") == "hbm-leak"
                for tr in bundle.get("trends", []))
        gauge.set(0.0)
    finally:
        if prev_reg is not None:
            telemetry.install(prev_reg)
        timeseries.install_store(prev_store)
        timeseries.install_monitor(prev_mon)
    return {"caught": caught, "landed_in_bundle": landed, "bundle": path}


# -- the soak loop ------------------------------------------------------------

def run_soak(budget_s=120.0, seed=0, workers=2, shards=2, replicas=3,
             window=4, batch=16, train_rows=1024, lease_s=0.3,
             num_prompts=4, new_tokens=4, out_dir="."):
    from distkeras_tpu import telemetry
    from distkeras_tpu.health import recorder, slo, timeseries
    from distkeras_tpu.serving.rollout import WeightPublisher
    from distkeras_tpu.utils import fault

    rng = random.Random(seed)
    fault.clear_chaos()
    telemetry.reset()
    os.makedirs(out_dir, exist_ok=True)
    recorder.configure(dump_dir=out_dir, run="soak", seed=seed)

    # the §24 observatory: store collecting on its daemon thread, trend
    # monitor + SLO engine (stock specs + one per detector) judged per
    # cycle
    store = timeseries.install_store(timeseries.MetricStore())
    detectors = timeseries.default_detectors()
    monitor = timeseries.install_monitor(
        timeseries.TrendMonitor(store, detectors))
    engine = slo.install_engine(slo.SloEngine(
        slo.default_specs() + timeseries.trend_specs(detectors)))
    store.start(interval=0.5)

    stack, greedy_ref = _setup()
    import numpy as np

    prompt_rng = np.random.default_rng(seed + 100)
    prompts = [prompt_rng.integers(1, 256, size=8,
                                   dtype=np.int64).tolist()
               for _ in range(num_prompts)]
    want = {tuple(p): greedy_ref(p, new_tokens) for p in prompts}

    fleet = _Fleet(stack, replicas)
    publisher = WeightPublisher()
    rows, versions = [], []
    kills = {a: 0 for a in AUTHORITIES}
    totals = {"windows": 0, "windows_lost": 0, "requests": 0,
              "failed": 0, "wrong_tokens": 0, "ranges": 0,
              "ranges_lost": 0, "duplicated": 0}
    # seeded schedule: a shuffled pass over all four authorities, then
    # seeded draws — every authority dies in the first four cycles, and
    # a longer budget keeps killing forever
    schedule = rng.sample(AUTHORITIES, len(AUTHORITIES))
    breaches = []
    t_start = time.perf_counter()
    cycle = 0
    try:
        while (time.perf_counter() - t_start < budget_s
               or min(kills.values()) < 1):
            authority = (schedule[cycle] if cycle < len(schedule)
                         else rng.choice(AUTHORITIES))
            c0 = time.perf_counter()
            train = _train_leg(
                seed + cycle, workers, shards, window, batch, train_rows,
                lease_s,
                kill=authority if authority in ("trainer-worker",
                                                "ps-coordinator")
                else None)
            data = _data_leg(seed + cycle,
                             kill=authority == "data-coordinator")
            serve = _serve_leg(fleet, prompts, want, new_tokens,
                               kill=authority == "serving-replica",
                               rng=rng)
            version, observed = _publish_leg(publisher, fleet, stack[1])
            monotone = not versions or version > versions[-1]
            versions.append(version)
            kills[authority] += 1
            totals["windows"] += train["windows"]
            totals["windows_lost"] += train["windows_lost"]
            totals["requests"] += serve["requests"]
            totals["failed"] += serve["failed"]
            totals["wrong_tokens"] += serve["wrong_tokens"]
            totals["ranges"] += data["ranges"]
            totals["ranges_lost"] += data["ranges_lost"]
            totals["duplicated"] += data["duplicated"]
            telemetry.counter("soak.cycles").inc()
            telemetry.counter("soak.kills", authority=authority).inc()
            telemetry.counter("soak.windows").inc(train["windows"])
            telemetry.counter("soak.lost_windows").inc(
                train["windows_lost"])
            telemetry.counter("soak.requests").inc(serve["requests"])
            telemetry.counter("soak.failed_requests").inc(
                serve["failed"] + serve["wrong_tokens"])
            if not monotone:
                telemetry.counter("soak.version_regressions").inc()
            telemetry.gauge("soak.model_version").set(version)
            telemetry.gauge("soak.elapsed_s").set(
                time.perf_counter() - t_start)
            # judge the cycle: trends first (they feed the SLO gauges)
            for ev in monitor.evaluate_once():
                if not ev.resolved:
                    breaches.append({"trend": ev.trend,
                                     "cycle": cycle,
                                     "message": ev.message})
            engine.evaluate_once()
            elapsed = time.perf_counter() - t_start
            row = {"kind": "cycle", "cycle": cycle,
                   "authority": authority, "elapsed_s": elapsed,
                   "seconds": time.perf_counter() - c0,
                   "version": version,
                   "version_monotone": monotone,
                   "replica_versions": observed,
                   "train": train, "data": data, "serve": serve,
                   "active_trends": [t["trend"] for t in
                                     monitor.active_trends()],
                   "active_alerts": [a["slo"] for a in
                                     engine.active_alerts()]}
            rows.append(row)
            rows.append({"kind": "kill", "cycle": cycle,
                         "authority": authority,
                         "detail": {
                             "trainer-worker": "remote_ps.send reset",
                             "ps-coordinator":
                                 "remote_ps.server.handle kill shard=0",
                             "data-coordinator": "data.lease kill",
                             "serving-replica":
                                 f"replica rid="
                                 f"{serve.get('killed_rid')} killed",
                         }[authority]})
            print(f"cycle {cycle:2d} [{authority:16s}] "
                  f"{row['seconds']:6.1f}s  windows={train['windows']} "
                  f"lost={train['windows_lost']} "
                  f"ranges_lost={data['ranges_lost']} "
                  f"req={serve['requests']} failed={serve['failed']} "
                  f"wrong={serve['wrong_tokens']} v{version} "
                  f"elapsed={elapsed:.0f}/{budget_s:.0f}s", flush=True)
            cycle += 1
    finally:
        fault.clear_chaos()
        store.stop()
        fleet.close()
    seconds = time.perf_counter() - t_start

    drill = _leak_drill(out_dir)
    rows.append({"kind": "trend_drill", **drill})
    # the final forensic record: bundle with fleet digest + series + any
    # still-active trends (merged by `health.cli postmortem <out_dir>`)
    bundle_path = recorder.get_recorder().dump(out_dir, reason="soak")

    monotone_all = all(b > a for a, b in zip(versions, versions[1:]))
    summary = {
        "kind": "summary", "seconds": seconds, "cycles": cycle,
        "budget_s": budget_s, "seed": seed,
        "kills": dict(kills), "total_kills": sum(kills.values()),
        "authorities_killed": sum(1 for v in kills.values() if v > 0),
        **totals,
        "versions": versions,
        "trend_breaches": breaches,
        "zero_lost_windows": float(totals["windows_lost"] == 0
                                   and totals["ranges_lost"] == 0),
        "request_success_rate": ((totals["requests"] - totals["failed"]
                                  - totals["wrong_tokens"])
                                 / max(1, totals["requests"])),
        "version_monotone": float(monotone_all and len(versions) >= 1),
        "leak_drill_caught": float(drill["caught"]
                                   and drill["landed_in_bundle"]),
        "postmortem_bundle": bundle_path,
    }
    rows.append(summary)
    slo.install_engine(None)
    from distkeras_tpu.health import timeseries as ts

    ts.install_store(None)
    ts.install_monitor(None)
    return rows, summary


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="wall-clock-budgeted chaos soak of the whole loop: "
                    "train + data service + serve + publish under a "
                    "seeded kill schedule (ROADMAP 4b, DESIGN.md §24)")
    ap.add_argument("--budget-s", type=float, default=120.0,
                    help="minimum wall-clock budget; the soak also runs "
                         "until every authority died at least once")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--train-rows", type=int, default=1024)
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--out", default="soak.jsonl", help="report JSONL")
    args = ap.parse_args(argv)

    rows, summary = run_soak(
        budget_s=args.budget_s, seed=args.seed, workers=args.workers,
        shards=args.shards, replicas=args.replicas,
        train_rows=args.train_rows, num_prompts=args.prompts,
        new_tokens=args.new_tokens,
        out_dir=os.path.dirname(args.out) or ".")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows to {args.out}")
    print(f"summary : {summary['cycles']} cycles / {summary['seconds']:.0f}s"
          f"  kills={summary['kills']}"
          f"  windows={summary['windows']} lost={summary['windows_lost']}"
          f"  requests={summary['requests']} failed={summary['failed']}"
          f" wrong={summary['wrong_tokens']}"
          f"  versions={summary['versions'][:3]}.."
          f"  zero_lost={summary['zero_lost_windows']:.0f}"
          f" success={summary['request_success_rate']:.3f}"
          f" monotone={summary['version_monotone']:.0f}"
          f" leak_drill={summary['leak_drill_caught']:.0f}")

    # the soak asserts the contracts it measures
    ok = True
    if summary["zero_lost_windows"] < 1.0:
        print(f"FAIL: lost {summary['windows_lost']} window(s) / "
              f"{summary['ranges_lost']} range(s)")
        ok = False
    if summary["request_success_rate"] < 1.0:
        print(f"FAIL: {summary['failed']} failed + "
              f"{summary['wrong_tokens']} wrong-token request(s)")
        ok = False
    if summary["version_monotone"] < 1.0:
        print(f"FAIL: model_version not strictly monotone: "
              f"{summary['versions']}")
        ok = False
    if summary["authorities_killed"] < len(AUTHORITIES):
        print(f"FAIL: only {summary['authorities_killed']} of "
              f"{len(AUTHORITIES)} authorities were killed")
        ok = False
    if summary["leak_drill_caught"] < 1.0:
        print("FAIL: the injected HBM leak was not caught and bundled")
        ok = False
    if summary["trend_breaches"]:
        # surfaced, not fatal: a trend breach during chaos is signal the
        # observatory works
        print(f"note: {len(summary['trend_breaches'])} trend breach(es) "
              f"during the soak: "
              f"{[b['trend'] for b in summary['trend_breaches']]}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
