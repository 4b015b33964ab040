"""Elastic fleet tests (DESIGN.md §13): sharded PS, churn, chaos.

Three planes, bottom-up: the deterministic shard map and its
split/join algebra; the membership table under a scripted clock (lease
lapse, eviction, late-fold decision, re-admission); and the live wire —
a loopback N=2 shard fleet driven through injected transport chaos
(connection resets before/after the bytes leave, dropped requests,
full outages) asserting the reconnect/dedup/degrade counters, not
timing luck.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.comms import RetryPolicy
from distkeras_tpu.health.heartbeat import StragglerDetector
from distkeras_tpu.health.membership import Membership
from distkeras_tpu.parallel import elastic
from distkeras_tpu.parallel.elastic import (
    ShardedRemoteParameterServer,
    join_tree,
    make_ps_fleet,
    shard_assignment,
    split_tree,
)
from distkeras_tpu.parallel.remote_ps import (
    HistoryBarrierTimeout,
    ParameterServerService,
    PSUnavailable,
    RemoteParameterServer,
)
from distkeras_tpu.parameter_servers import (
    DeltaParameterServer,
    DynSGDParameterServer,
)
from distkeras_tpu.utils import fault

PARAMS = {"w": jnp.ones((4, 3), jnp.float32),
          "b": jnp.zeros((3,), jnp.float32),
          "s": jnp.full((2,), 2.0, jnp.float32)}

#: fast schedule so retry exhaustion is milliseconds, not seconds
FAST = dict(retry=RetryPolicy(max_retries=3, base_s=0.01, max_s=0.05),
            op_timeout=5.0)


@pytest.fixture(autouse=True)
def fresh_state():
    telemetry.reset()
    fault.clear_chaos()
    yield
    fault.clear_chaos()
    telemetry.reset()


def _counter(name: str) -> int:
    snap = telemetry.get_registry().snapshot()
    return sum(v for k, v in snap["counters"].items()
               if k.split("{", 1)[0] == name)


def _fleet(num_shards=2, ps_cls=DynSGDParameterServer, **kw):
    return make_ps_fleet(lambda part: ps_cls(jax.device_put(part)),
                         PARAMS, num_shards, **kw)


def _stop(services):
    for svc in services:
        svc.stop()


# -- shard map algebra -------------------------------------------------------

def test_shard_assignment_is_deterministic_lpt():
    # crafted sizes: 16B, 8B, 8B -> LPT puts the big leaf alone
    like = {"a": np.zeros((4,), np.float32),
            "b": np.zeros((2,), np.float32),
            "c": np.zeros((2,), np.float32)}
    assignment = shard_assignment(like, 2)
    assert assignment == [[0], [1, 2]]
    assert assignment == shard_assignment(like, 2)  # pure function
    # every leaf lands on exactly one shard
    flat = sorted(i for idxs in shard_assignment(PARAMS, 3) for i in idxs)
    assert flat == list(range(len(jax.tree.leaves(PARAMS))))
    with pytest.raises(ValueError, match="num_shards"):
        shard_assignment(like, 0)
    with pytest.raises(ValueError, match="no parameters"):
        shard_assignment(like, 4)


def test_split_join_roundtrip():
    tree = {"x": np.arange(6, dtype=np.float32).reshape(2, 3),
            "y": {"z": np.full((5,), 7.0, np.float32),
                  "q": np.zeros((1,), np.float32)}}
    treedef = jax.tree_util.tree_structure(tree)
    assignment = shard_assignment(tree, 3)
    back = join_tree(split_tree(tree, assignment), assignment, treedef)
    jax.tree.map(np.testing.assert_array_equal, back, tree)


# -- sharded fleet vs single server -----------------------------------------

def test_sharded_fleet_matches_single_server_dynsgd():
    """The same commit schedule must land the same center whether the PS
    is one service or an N=2 fleet — including a STALE DynSGD commit,
    whose coordinator-fixed weight the followers must reuse exactly."""
    ps1, svc1 = (DynSGDParameterServer(jax.device_put(PARAMS)), None)
    svc1 = ParameterServerService(ps1, PARAMS)
    svc1.start()
    services = _fleet(2)
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        single = RemoteParameterServer(f"127.0.0.1:{svc1.port}", PARAMS,
                                       **FAST)
        fleet = ShardedRemoteParameterServer(
            [f"127.0.0.1:{svc.port}" for svc in services], PARAMS, **FAST)
        for cli in (single, fleet):
            _, clock0 = cli.pull()
            assert clock0 == 0
            cli.commit(one, last_update=0)   # staleness 0: full fold
            at, w = cli.commit_ex(one, last_update=0)  # staleness 1: half
            assert (at, w) == (1, 0.5)
            assert cli.num_updates == 2
        c_single, _ = single.pull()
        c_fleet, clock = fleet.pull()
        assert clock == 2
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
            c_fleet, c_single)
        # and the fold really happened: 1 + 1 + 0.5 on the ones leaf
        np.testing.assert_allclose(c_fleet["w"][0, 0], 2.5)
        single.close()
        fleet.close()
    finally:
        svc1.stop()
        _stop(services)


# -- membership under a scripted clock --------------------------------------

def test_membership_lease_lifecycle_scripted_clock():
    clock = [0.0]
    m = Membership(lease_s=10.0, time_fn=lambda: clock[0])
    assert m.register(1) == 10.0
    assert m.register(2, lease_s=100.0) == 100.0
    assert m.renew(1) is False
    assert m.sweep() == []
    clock[0] = 11.0  # worker 1's lease lapsed; worker 2's has not
    assert m.sweep() == [1]
    assert m.is_evicted(1) and not m.is_evicted(2)
    assert m.should_late_fold(1) and not m.should_late_fold(2)
    # renewing while evicted extends the lease but does NOT readmit
    assert m.renew(1) is True
    assert m.is_evicted(1)
    # a landed commit IS the readmission
    m.observe_commit(1)
    assert not m.is_evicted(1)
    assert _counter("elastic.evictions") == 1
    assert _counter("elastic.readmissions") == 1
    # clean leave forgets the worker entirely — no eviction recorded
    m.deregister(2)
    assert m.workers == [1]
    # a worker the table never saw is a non-member: folds normally
    assert not m.should_late_fold(99)
    status = m.status()
    assert status["workers"]["1"]["commits"] == 1
    assert status["evicted"] == []


def test_membership_straggler_graduates_to_eviction():
    """The StragglerDetector's verdict must evict (reason=straggler) and
    a recovered worker's sub-threshold window must readmit."""
    m = Membership(lease_s=1e6, straggler=StragglerDetector(
        k=3.0, min_samples=4), time_fn=lambda: 0.0)
    m.register(7)
    for _ in range(5):
        m.observe_commit(7, window_s=1.0)  # builds the median pool
    m.observe_commit(7, window_s=10.0)     # 10x the median: flagged
    assert m.is_evicted(7)
    assert m.status()["workers"]["7"]["reason"] == "straggler"
    assert m.should_late_fold(7)
    m.observe_commit(7, window_s=1.0)      # recovered: unflagged
    assert not m.is_evicted(7)


def test_evicted_worker_late_fold_is_dynsgd_weighted_on_any_flavor():
    """Over the wire: a commit from a lease-lapsed worker folds at
    1/(staleness+1) even on a Delta (weight-1) server, identically on
    every shard; the commit itself readmits the worker."""
    clock = [0.0]
    services = _fleet(2, ps_cls=DeltaParameterServer, lease_s=5.0,
                      time_fn=lambda: clock[0])
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        fleet = ShardedRemoteParameterServer(
            [f"127.0.0.1:{svc.port}" for svc in services], PARAMS, **FAST)
        assert fleet.register(3) == 5.0
        fleet.commit_ex(one, last_update=0, worker=3)  # clock -> 1
        clock[0] = 6.0  # lease lapses
        # stale (pulled at 0, folding at 1) AND evicted: DynSGD rule
        at, w = fleet.commit_ex(one, last_update=0, worker=3)
        assert (at, w) == (1, 0.5)
        assert _counter("elastic.late_folds") == 1
        assert _counter("elastic.evictions") == 1
        assert _counter("elastic.readmissions") == 1  # the commit landed
        # the 0.5 fold reached BOTH shards: w leaf 1+1+0.5, s leaf 2+1+0.5
        center, _ = fleet.pull()
        np.testing.assert_allclose(center["w"][0, 0], 2.5)
        np.testing.assert_allclose(center["s"][0], 3.5)
        # readmitted: the next commit folds at the server's own weight
        _, w3 = fleet.commit_ex(one, last_update=2, worker=3)
        assert w3 == 1.0
        fleet.deregister(3)
        fleet.close()
    finally:
        _stop(services)


# -- transport chaos ---------------------------------------------------------

def test_reply_loss_retries_and_dedups_to_one_fold():
    """reset_after_send: the server applies the commit but the reply dies
    with the connection. The retried commit must be answered from the
    dedup cache — ONE fold, not two."""
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **FAST)
        fault.inject_chaos("remote_ps.send", "reset_after_send", count=1)
        assert cli.commit(one, last_update=0) == 0  # transparent retry
        assert cli.num_updates == 1                 # folded exactly once
        center, _ = cli.pull()
        np.testing.assert_allclose(center["w"][0, 0], 2.0)
        assert _counter("remote_ps.server.dedup_hits") == 1
        assert _counter("remote_ps.client.retries") >= 1
        assert _counter("remote_ps.client.reconnects") >= 1
        cli.close()
    finally:
        svc.stop()


def test_reset_before_send_reconnects_and_folds_once():
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **FAST)
        fault.inject_chaos("remote_ps.send", "reset", count=1)
        assert cli.commit(one, last_update=0) == 0
        assert cli.num_updates == 1
        # the request never reached the wire: no replay for dedup to eat
        assert _counter("remote_ps.server.dedup_hits") == 0
        assert _counter("remote_ps.client.reconnects") >= 1
        cli.close()
    finally:
        svc.stop()


def test_dropped_request_times_out_then_recovers():
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    try:
        cli = RemoteParameterServer(
            f"127.0.0.1:{svc.port}", PARAMS,
            retry=RetryPolicy(max_retries=2, base_s=0.01, max_s=0.02),
            op_timeout=0.3)
        fault.inject_chaos("remote_ps.send", "drop", count=1)
        _, clock = cli.pull()  # first attempt swallowed, retry lands
        assert clock == 0
        assert _counter("remote_ps.client.retries") >= 1
        cli.close()
    finally:
        svc.stop()


def test_retry_exhaustion_raises_typed_psunavailable_then_recovers():
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    try:
        cli = RemoteParameterServer(
            f"127.0.0.1:{svc.port}", PARAMS,
            retry=RetryPolicy(max_retries=1, base_s=0.01, max_s=0.02),
            op_timeout=2.0)
        fault.inject_chaos("remote_ps.send", "reset", count=None)
        with pytest.raises(PSUnavailable):
            cli.pull()
        assert isinstance(PSUnavailable("x"), RuntimeError)
        assert _counter("remote_ps.client.unavailable") >= 1
        fault.clear_chaos()  # the outage ends: same client recovers
        _, clock = cli.pull()
        assert clock == 0
        cli.close()
    finally:
        svc.stop()


def test_server_side_reset_is_survived():
    """Chaos on the SERVER site: the handler kills the connection without
    replying; the client's retry (and commit dedup) absorb it."""
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32), PARAMS)
    try:
        cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **FAST)
        fault.inject_chaos("remote_ps.server.handle", "reset", count=1)
        assert cli.commit(one, last_update=0) == 0
        assert cli.num_updates == 1
        cli.close()
    finally:
        svc.stop()


def test_close_is_idempotent_and_bounded_after_server_death():
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS)
    svc.start()
    cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **FAST)
    svc.stop()  # server gone first — close must still return promptly
    t0 = time.perf_counter()
    cli.close()
    cli.close()  # idempotent
    assert time.perf_counter() - t0 < 5.0
    with pytest.raises(PSUnavailable, match="closed"):
        cli.pull()


def test_history_barrier_timeout_is_typed():
    ps = DeltaParameterServer(jax.device_put(PARAMS))
    svc = ParameterServerService(ps, PARAMS, expected_processes=2)
    svc.start()
    try:
        cli = RemoteParameterServer(f"127.0.0.1:{svc.port}", PARAMS, **FAST)
        with pytest.raises(HistoryBarrierTimeout, match="barrier"):
            cli.get_history(timeout=0.2)
        # typed both ways: new TimeoutError surface, old RuntimeError one
        assert issubclass(HistoryBarrierTimeout, TimeoutError)
        assert issubclass(HistoryBarrierTimeout, RuntimeError)
        with pytest.raises(HistoryBarrierTimeout):
            svc.get_history_blocking(timeout=0.1)
        cli.close()
    finally:
        svc.stop()


# -- end-to-end churn: a real training run over an N=2 fleet -----------------

def _training_pieces(workers=2, window=2, batch=8, n=256):
    from distkeras_tpu import DynSGD as DynSGDTrainer
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel import host_async

    model = MLP(features=(8,), dropout_rate=0.0)
    t = DynSGDTrainer(model, mode="host_async", num_workers=workers,
                      worker_optimizer="sgd", learning_rate=0.05,
                      metrics=(), batch_size=batch,
                      communication_window=window)
    params = model.init(jax.random.key(0), jnp.zeros((batch, 784)),
                        train=False)["params"]
    staged = host_async.stage_worker_shards(
        synthetic_mnist(n=n).repartition(workers), "features", "label",
        batch, window)
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", t.tx, t.strategy, window=window,
        max_degraded_windows=8)
    return t, params, staged, runner


def test_churn_run_survives_resets_eviction_and_outage():
    """The acceptance run: a 2-worker DynSGD training loop over a live
    N=2 shard fleet survives (a) a connection reset with reply loss —
    reconnect + dedup, no double fold; (b) worker eviction via a lapsed
    lease and re-admission with a DynSGD-weighted late fold; (c) a full
    fleet outage — degraded compute-only windows, backlog folded on
    recovery. Every window is accounted for in the merged history."""
    from distkeras_tpu.parallel import host_async  # noqa: F401

    t, params, staged, runner = _training_pieces()
    # a lease far shorter than the first window's JIT compile: worker
    # leases lapse before their first commit, so eviction, late fold,
    # and re-admission all happen organically on the live wire
    services = make_ps_fleet(
        lambda part: DynSGDParameterServer(jax.device_put(part)),
        params, 2, lease_s=0.05)
    retry = RetryPolicy(max_retries=2, base_s=0.01, max_s=0.05)
    fleet = ShardedRemoteParameterServer(
        [f"127.0.0.1:{svc.port}" for svc in services], params,
        retry=retry, op_timeout=2.0)
    try:
        # (a) reply-loss resets while the run is in flight
        fault.inject_chaos("remote_ps.send", "reset_after_send",
                           after=6, count=1)
        center, history, stal, clock = runner.run(
            params, [staged] * 2, ps=fleet)
        windows_total = 2 * sum(len(r) for r in staged)
        assert len(runner.merged_windows) == windows_total
        assert clock >= 1
        assert _counter("elastic.evictions") >= 1
        assert _counter("elastic.late_folds") >= 1
        assert _counter("elastic.readmissions") >= 1
        assert _counter("remote_ps.client.reconnects") >= 1

        # (b) deterministic dedup proof on the SAME fleet: reply loss on
        # a direct commit must not double-fold
        before = fleet.num_updates
        one = jax.tree.map(lambda l: np.ones(np.shape(l), np.float32),
                           center)
        fault.inject_chaos("remote_ps.send", "reset_after_send", count=1)
        fleet.commit_ex(one, last_update=before)
        assert fleet.num_updates == before + 1
        assert _counter("remote_ps.server.dedup_hits") >= 1

        # (c) full outage mid-run: once every worker holds a center,
        # every send resets for a counted stretch; workers degrade to
        # compute-only windows, then fold the backlog and finish the
        # epoch. The stretch is counted in sends and not in seconds, so
        # what the run survives does not depend on the clock: an
        # operation dies after `attempts` resets, a worker must attempt a
        # commit after each pull (2 shards x attempts resets), so more
        # resets than every worker's pull can absorb kill at least one
        # commit, and at most outage / attempts operations die in all:
        # fewer than the ladder's max_degraded_windows=8
        attempts = retry.max_retries + 1
        outage = len(staged) * len(services) * attempts + attempts
        assert outage // attempts < 8
        start_clock = fleet.num_updates
        pulled, lock, real_pull = set(), threading.Lock(), fleet.pull

        def pull_then_outage():
            out = real_pull()
            with lock:
                pulled.add(threading.get_ident())
                if len(pulled) == len(staged):  # the last first pull
                    pulled.add(None)            # arm once
                    fault.inject_chaos("remote_ps.send", "reset",
                                       count=outage)
            return out

        fleet.pull = pull_then_outage
        runner.run(params, [staged], ps=fleet, start_clock=start_clock)
        fleet.pull = real_pull
        assert _counter("host_async.degraded_windows") >= 1
        # the fleet recovered: it answers, and the run's windows all
        # reached the merged history despite the outage
        assert len(runner.merged_windows) == sum(len(r) for r in staged)
        assert fleet.num_updates > before
    finally:
        fault.clear_chaos()
        fleet.close()
        _stop(services)


def test_trainer_ps_shards_validation():
    from distkeras_tpu import DOWNPOUR
    from distkeras_tpu.models.mlp import MLP

    model = MLP(features=(8,))
    with pytest.raises(ValueError, match="ps_shards"):
        DOWNPOUR(model, mode="host_async", num_workers=2, ps_shards=0)
    with pytest.raises(ValueError, match="sync mode"):
        DOWNPOUR(model, mode="sync", num_workers=2, ps_shards=2)
    t = DOWNPOUR(model, mode="host_async", num_workers=2, ps_shards=2)
    assert t.ps_shards == 2
