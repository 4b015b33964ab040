"""Host-driven TRUE-async mode: wall-clock asynchrony against a live center.

The substrate (parallel/substrate.py) *emulates* asynchrony deterministically
inside one compiled program — the fast path. This module is the other half of
the reference's story: like dist-keras's socket parameter server
(``parameter_servers.py``/``workers.py`` — unverified, mount empty), workers
here run CONCURRENTLY (host threads standing in for Spark executors), each
looping pull → local window → commit against a ParameterServer whose center
updates live between any two of a worker's steps. Staleness is real thread
scheduling, not a rotation schedule.

TPU mapping: each worker's window is ONE jitted scan (compiled once, shared
by all workers), and each worker thread is PINNED to a device
(``devices[k % D]``) — its carry and staged batches live there, it pulls
the center across the interconnect, computes its window locally, and
commits back to the center's device (the PS folds on device 0). With one
device, threads serialize at window granularity — the interleaving the
reference's executors had against the driver's lock; with D devices,
windows overlap in real wall-clock, which is the multi-chip extension of
the same semantics. Either way the center lives in HBM instead of driver
RAM and the pull/commit hops are explicit device-to-device copies instead
of pickled TCP.
"""

from __future__ import annotations

import contextlib
import queue as queue_lib
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import comms, engine, observability, telemetry
from distkeras_tpu import precision as precision_lib
from distkeras_tpu.data.prefetch import prefetch
from distkeras_tpu.health import recorder as flight_recorder
from distkeras_tpu.health.heartbeat import (HeartbeatPublisher,
                                            StragglerDetector)
from distkeras_tpu.utils import fault
from distkeras_tpu.utils.fetch import device_get_batched
from distkeras_tpu.parameter_servers import (
    DeltaParameterServer,
    DynSGDParameterServer,
    ParameterServer,
)
from distkeras_tpu.parallel.remote_ps import PSUnavailable
from distkeras_tpu.parallel.strategies import Strategy


def _tree_add(a, b):
    """Leafwise sum — the degradation ladder's backlog accumulator."""
    return jax.tree.map(lambda x, y: x + y, a, b)


@contextlib.contextmanager
def _window_trace(enabled: bool, wid: int, fold: int):
    """Root one trace per worker window (DESIGN.md §15): the trace.window
    span parents the pull/compute/commit spans below it, and the commit's
    traceparent rides the wire so transport retries and shard folds in
    OTHER processes chain under this same trace_id."""
    if not enabled or telemetry.get_registry() is None:
        yield None
        return
    ctx = telemetry.TraceContext.new_root(worker=str(wid), window=str(fold))
    with telemetry.use_trace(ctx):
        with telemetry.span("trace.window", worker=wid) as child:
            yield child


def server_for(strategy: Strategy, params) -> ParameterServer:
    """The reference's trainer→server pairing (SURVEY.md §2)."""
    if strategy.name == "dynsgd":
        return DynSGDParameterServer(params)
    return DeltaParameterServer(params)


class CadenceTrigger:
    """Checkpoint cadence on a GLOBALLY counted clock (ADVICE r5 fix).

    ``clock_at_fold`` counts commits from EVERY process, but each process
    observes it only at its own commits — with P processes a local commit
    lands on an exact multiple of ``checkpoint_folds`` only ~1/P of the
    time, so the old ``(clock+1) % folds == 0`` trigger diluted the cadence
    by ~P. Firing on cadence-interval CROSSING instead — did the observed
    clock enter a later ``folds``-sized bucket than the last trigger —
    preserves the knob's meaning (≈ one snapshot per ``folds`` commits) for
    any observation stride. Thread-safe: concurrent workers observing the
    same crossing fire exactly once.
    """

    def __init__(self, folds: int, start_clock: int = 0):
        if folds < 1:
            raise ValueError(f"checkpoint_folds must be >= 1, got {folds}")
        self.folds = int(folds)
        # commits [0, start_clock) predate this run (resume): their
        # intervals must not retrigger
        self._bucket = int(start_clock) // self.folds
        self._lock = threading.Lock()

    def crossed(self, clock_at_fold: int) -> bool:
        bucket = (int(clock_at_fold) + 1) // self.folds
        if bucket <= self._bucket:  # unlocked fast path: no crossing
            return False
        with self._lock:
            if bucket <= self._bucket:
                return False  # a sibling claimed this crossing first
            self._bucket = bucket
            return True


def make_window_fn(model, loss, tx, strategy: Strategy, window: int,
                   metric_names: Sequence[str], seed: int,
                   accum_steps: int = 1, precision: Optional[str] = None):
    """One worker's compiled round: λ local steps + commit computation.

    (carry, center, batches, fold_key) -> (carry, commit, metrics dict)
    where batches leaves are [window, batch, ...]. Compiled once; every
    worker thread calls the same executable.

    ``accum_steps > 1`` microbatches each of the λ local steps
    (engine.make_accum_grad_fn). Accumulation lives entirely inside the
    local step's grad fn, so a window is still λ optimizer steps and ONE
    commit — server clock, commit counts, and staleness histograms are
    unchanged by construction.

    ``precision`` threads a PrecisionPolicy into the grad fns. Strategies
    call the grad fn without a live ``loss_scale``, so the STATIC policy
    scale applies on this path (NUMERICS.md "Low-precision step
    equivalence") — the dynamic-scale plumbing is a sync-path feature.
    """
    accum_steps = int(accum_steps)
    if accum_steps > 1:
        grad_fn = engine.make_accum_grad_fn(model, loss, accum_steps,
                                            metric_names, precision=precision)
    else:
        grad_fn = engine.make_grad_fn(model, loss, precision=precision)
    base_key = jax.random.key(seed)

    def window_fn(carry, center, batches, fold_key):
        carry = strategy.round_start(carry, center)

        def one_step(c, xs):
            batch, i = xs
            rng = jax.random.fold_in(jax.random.fold_in(base_key, fold_key), i)
            c, m = strategy.local_step(grad_fn, tx, c, batch,
                                       rngs={"dropout": rng})
            out = {"loss": m["loss"]}
            for name in metric_names:
                if accum_steps > 1:
                    out[name] = engine.finalize_metric(m["logits"][name])
                else:
                    out[name] = engine.compute_metric(name, m["logits"],
                                                      batch["labels"])
            return c, out

        idx = jnp.arange(window, dtype=jnp.int32)
        carry, ms = jax.lax.scan(one_step, carry, (batches, idx))
        commit = strategy.commit(carry, center, window)
        if not strategy.resets_to_center:
            # local side of the elastic update (EASGD family); the DOWNPOUR
            # family re-pulls the live center at its next round_start instead
            carry = strategy.post_commit(carry, commit, None)
        return carry, commit, ms

    return jax.jit(window_fn)


class HostAsyncRunner:
    """Run N concurrent workers against a live parameter server.

    ``shards``: per-worker lists of staged batch dicts (features/labels),
    each leaf [window, batch, ...]. Each window's metrics are tagged with
    the server clock at its commit; the returned history/staleness are the
    windows sorted by that clock — true commit order, not worker-major
    concatenation.
    """

    def __init__(self, model, loss, tx, strategy: Strategy, window: int,
                 metrics: Sequence[str] = (), seed: int = 0,
                 devices: Optional[Sequence[jax.Device]] = None,
                 codec: Optional[str] = None, overlap: bool = False,
                 accum_steps: int = 1, precision: Optional[str] = None,
                 max_degraded_windows: int = 16, trace: bool = True):
        self.strategy = strategy
        self.window = int(window)
        # distributed tracing (DESIGN.md §15): each worker window becomes
        # one trace whose spans follow the commit through the transport
        # (retries, reconnects) and across shard folds. trace=False keeps
        # the plain (context-free) span events — the tracing-off baseline
        # (test_tracing.py's trajectory test runs both).
        self.trace = bool(trace)
        # merged multi-process rows from the last run_cross_process (set
        # on process 0 when the coordinator mounts a collector)
        self.fleet_telemetry: Optional[list] = None
        # degradation ladder budget (DESIGN.md §13): how many consecutive
        # compute-only windows a worker rides out against an unreachable
        # fleet (stale center, commits accumulated locally) before the
        # outage is surfaced as the underlying PSUnavailable
        self.max_degraded_windows = int(max_degraded_windows)
        self.accum_steps = int(accum_steps)
        self.window_fn = make_window_fn(model, loss, tx, strategy, window,
                                        tuple(metrics), seed,
                                        accum_steps=self.accum_steps,
                                        precision=precision)
        self.tx = tx
        # worker k runs on devices[k % D]; default = every chip this
        # process sees (never "everything on the first chip")
        self.devices = list(devices) if devices else jax.local_devices()
        # wire codec for the PS exchange. With a runner-created (local) PS
        # a non-raw codec wraps it in EncodedParameterServer so commits and
        # pulls see exactly the wire numerics; with an injected ps= the
        # caller owns the codec (run_cross_process negotiates it per
        # connection).
        self.codec = None if codec is None \
            else comms.get_codec(codec)
        # overlap=True double-buffers each worker: the previous window's
        # commit and the next window's pull run on a per-worker comms
        # thread while the current window computes (see _overlapped_rounds)
        self.overlap = bool(overlap)
        # health plane (DESIGN.md §9), default-on like the rest of the
        # telemetry: every worker window publishes a heartbeat and feeds
        # the straggler detector; the watchdog stays opt-in (run(...,
        # watchdog=...)) because its policies can abort training
        self.heartbeat = HeartbeatPublisher()
        self.straggler = StragglerDetector()
        # live per-window MFU series (DESIGN.md §21 satellite): bookkeep
        # publishes observability.mfu every window so the mfu-floor SLO
        # burns on current data, not a stale end-of-run gauge. The window
        # FLOPs count is one make_jaxpr trace, taken lazily on the first
        # window and ONLY once a peak ceiling is known — on CPU hosts
        # device_peak_flops is None and the whole path stays cold.
        policy = precision_lib.get_policy(precision)
        self.mfu_dtype = policy.mfu_dtype if policy is not None else "bf16"
        self.mfu_peak_flops: Optional[float] = None  # bench/test override
        self._mfu_peak: Optional[float] = None
        self._mfu_peak_resolved = False
        self._window_flops: Optional[float] = None
        self._mfu_lock = threading.Lock()
        self.worker_devices: list = []  # actual placement, for tests/logs
        self.window_clocks: list = []   # merged commit clocks, last run
        self.merged_windows: list = []  # (clock, staleness, steps) tuples

    def run(self, init_params, epoch_shards: Sequence[Sequence[Sequence[dict]]],
            checkpointer=None, checkpoint_folds: int = 0,
            start_clock: int = 0, ps=None, worker_offset: int = 0,
            fetch_final: bool = True, watchdog=None,
            snapshot_extra=None) -> tuple:
        """``epoch_shards[epoch][worker]`` is that worker's list of staged
        rounds for that epoch (per-epoch staging preserves the sync path's
        reshuffle-every-epoch semantics; pass the same object per epoch when
        not shuffling). Workers progress through epochs without barriers —
        true asynchrony extends across epoch boundaries too. A worker entry
        may also be a ZERO-ARG CALLABLE returning its round iterable — the
        streaming data service (data/service.py) passes lease-driven
        generators this way, so rounds materialize lazily on the worker's
        own prefetch thread instead of being staged up front.

        ``checkpointer``/``checkpoint_folds``: snapshot the live center +
        server clock every ``checkpoint_folds`` commits (the async-mode
        fault-tolerance story — there is no epoch barrier to snapshot at).
        A dedicated saver thread does the pull + device→host fetch + (async
        Orbax) save; committing workers only set an event, so they never
        stall on checkpoint IO (an in-commit-path save would skew the real
        scheduling this mode exists to measure). The PS lock makes each
        pulled snapshot internally consistent. ``start_clock`` seeds the
        server clock when resuming from such a snapshot.

        ``ps``: inject a live parameter server instead of creating one —
        the cross-process mode (parallel/remote_ps.py) passes process 0's
        service-fronted PS here on process 0 and a RemoteParameterServer
        client elsewhere; the worker loop cannot tell the difference.
        ``worker_offset``: this process's first GLOBAL worker id (keeps
        dropout fold keys distinct across processes).

        ``watchdog``: optional :class:`~distkeras_tpu.health.watchdog.
        TrainingWatchdog`. Every worker window feeds it its (fault-hook
        filtered) mean loss and a progress tick; a trip under an aborting
        policy stops every worker at its next round. The runner binds the
        watchdog's crash-time ``checkpoint_fn`` (live-center snapshot via
        ``checkpointer``) and its ``on_trip`` abort hook when unset.

        ``snapshot_extra``: optional zero-arg callable returning a dict of
        extra leaves merged into every checkpoint snapshot (periodic saver
        AND crash-time). The streaming data plane passes
        ``lambda: {"data_cursor": coordinator.cursor_carry()}`` so the
        shuffle cursor rides the same save the center does (DESIGN.md
        §20); keys must not collide with ``center``/``clock``."""
        num_workers = len(epoch_shards[0])
        if ps is None:
            # center (and its folds) live on device 0; workers pull across
            ps = server_for(self.strategy,
                            jax.device_put(init_params, self.devices[0]))
            ps.num_updates = int(start_clock)
            if self.codec is not None and self.codec.name != "raw":
                # single-process codec run: every pull/commit crosses the
                # codec exactly as it would on the wire
                ps = comms.EncodedParameterServer(ps, self.codec)
        # snapshots and the final fetch read the center EXACTLY — a lossy
        # wire codec must not round the saved/returned params, only the
        # worker exchange
        base_ps = getattr(ps, "ps", ps) \
            if isinstance(ps, comms.EncodedParameterServer) else ps
        # per-window records: (commit_clock, staleness, [per-step metrics])
        windows: list[list[tuple]] = [[] for _ in range(num_workers)]
        errors: list = []
        self.worker_devices = [self.devices[k % len(self.devices)]
                               for k in range(num_workers)]
        save_trigger = threading.Event()
        stop_saving = threading.Event()

        def saver():
            """Best-effort periodic snapshots, serialized in one thread.
            Cadence crossings that arrive while a save is in flight coalesce
            into the next snapshot (which sees a newer clock anyway)."""
            last_saved = int(start_clock)
            try:
                while True:
                    fired = save_trigger.wait(timeout=0.05)
                    if fired:
                        save_trigger.clear()
                    elif stop_saving.is_set():
                        return
                    else:
                        continue
                    # consistent under the PS lock
                    center, clock = base_ps.pull()
                    if clock > last_saved:
                        t0 = time.perf_counter()
                        snap = {"center": device_get_batched(center),
                                "clock": np.array([clock], np.int64)}
                        if snapshot_extra is not None:
                            snap.update(snapshot_extra())
                        checkpointer.save(clock, snap)
                        # the stall an in-commit-path save WOULD have cost
                        # a worker (pull + fetch + save dispatch) — the
                        # number that justifies the dedicated saver thread
                        telemetry.histogram("host_async.save_s").record(
                            time.perf_counter() - t0)
                        telemetry.counter("host_async.save.count").inc()
                        last_saved = clock
            except Exception as e:  # surface save failures to the caller
                errors.append(e)

        abort = threading.Event()

        def worker(k: int):
            try:
                dev = self.worker_devices[k]
                wid = worker_offset + k  # GLOBAL worker id (telemetry label)
                pull_h = telemetry.histogram("host_async.pull_s", worker=wid)
                win_h = telemetry.histogram("host_async.window_s", worker=wid)
                commit_h = telemetry.histogram("host_async.commit_s",
                                               worker=wid)
                lag_h = telemetry.histogram("host_async.commit_clock_lag",
                                            worker=wid)
                carry = jax.device_put(
                    self.strategy.init_carry(init_params, self.tx), dev)

                def staged_rounds():
                    # device placement runs on the prefetch thread one
                    # round ahead, so H2D staging overlaps the previous
                    # window's compute
                    for shards in epoch_shards:
                        rounds = shards[k]
                        if callable(rounds):  # lease-driven stream source
                            rounds = rounds()
                        for batches in rounds:
                            yield jax.device_put(batches, dev)

                def bookkeep(clock_at_fold: int, pull_clock: int, ms,
                             win_s: float):
                    # commits the center absorbed between this worker's
                    # pull and its own fold — real scheduling staleness
                    staleness = clock_at_fold - pull_clock
                    lag_h.record(staleness)
                    ms = device_get_batched(ms)
                    n = len(ms["loss"])
                    windows[k].append((
                        clock_at_fold, staleness,
                        [{key: float(v[i]) for key, v in ms.items()}
                         for i in range(n)]))
                    # live health plane: heartbeat + straggler verdict are
                    # published BEFORE the watchdog gets to raise, so the
                    # introspection endpoints see the window that tripped
                    self.heartbeat.publish(wid, clock_at_fold, staleness,
                                           win_s)
                    self.straggler.observe(wid, win_s)
                    self._publish_window_mfu(win_s)
                    if checkpointing and cadence.crossed(clock_at_fold):
                        save_trigger.set()  # non-blocking hand-off
                    if watchdog is not None:
                        watchdog.observe_loss(fault.apply(
                            "host_async.window_loss",
                            float(np.mean(ms["loss"]))))
                        watchdog.notify_progress()

                elastic = getattr(ps, "elastic", False)
                if elastic:
                    try:
                        # join the fleet (lease on the coordinator shard);
                        # best-effort — a commit is also an implicit join
                        ps.register(wid)
                    except Exception:
                        pass
                if self.overlap:
                    self._overlapped_rounds(
                        k, wid, dev, carry, ps, staged_rounds(), abort,
                        bookkeep, pull_h, win_h, commit_h)
                else:
                    self._serial_rounds(
                        k, wid, dev, carry, ps, elastic, staged_rounds(),
                        abort, bookkeep, pull_h, win_h, commit_h)
                if elastic:
                    try:
                        # clean leave — a crashed worker never gets here,
                        # and the lease sweep evicts it instead
                        ps.deregister(wid)
                    except Exception:
                        pass
            except Exception as e:  # surface thread failures to the caller
                if e not in errors:  # a watchdog on_trip may have filed it
                    errors.append(e)
                # forensics: the failing worker's last windows are on the
                # flight-recorder ring; preserve them before the run dies
                telemetry.record_event(
                    "worker_error", worker=worker_offset + k,
                    error=type(e).__name__, message=str(e)[:200])
                flight_recorder.auto_dump(
                    "ps_unavailable" if isinstance(e, PSUnavailable)
                    else "worker_exception")
                abort.set()  # fail fast: siblings stop at their next round
                             # (the reference analogue: Spark killing the
                             # job when a task fails terminally)

        checkpointing = checkpointer is not None and checkpoint_folds > 0
        cadence = (CadenceTrigger(checkpoint_folds, start_clock)
                   if checkpointing else None)
        if watchdog is not None:
            if watchdog.checkpoint_fn is None and checkpointer is not None:
                def crash_checkpoint():
                    # live-center snapshot at trip time (the consistent
                    # read the saver thread also relies on); wait() so the
                    # files exist before the trip aborts the process
                    center, clock = base_ps.pull()
                    snap = {"center": device_get_batched(center),
                            "clock": np.array([clock], np.int64)}
                    if snapshot_extra is not None:
                        snap.update(snapshot_extra())
                    checkpointer.save(clock, snap)
                    checkpointer.wait()
                watchdog.checkpoint_fn = crash_checkpoint
            if watchdog.on_trip is None:
                def on_trip(err):
                    # files the error itself (the stall monitor thread has
                    # no caller to raise into) and stops every worker
                    if err not in errors:
                        errors.append(err)
                    abort.set()
                watchdog.on_trip = on_trip
            watchdog.start_stall_monitor()
        saver_thread = None
        if checkpointing:
            saver_thread = threading.Thread(target=saver, daemon=True)
            saver_thread.start()
        threads = [threading.Thread(target=worker, args=(k,), daemon=True)
                   for k in range(num_workers)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            if watchdog is not None:
                watchdog.stop_stall_monitor()
            if saver_thread is not None:
                stop_saving.set()
                saver_thread.join()
        if errors:
            raise errors[0]
        # merge worker windows by the server clock at their commit — the
        # wall-clock order the center actually absorbed them in
        merged = sorted((w for ws in windows for w in ws), key=lambda w: w[0])
        self.window_clocks = [w[0] for w in merged]  # for tests/diagnostics
        self.merged_windows = merged  # cross-process history upload source
        history = [step for _, _, steps in merged for step in steps]
        stal = [float(s) for _, s, _ in merged]
        if not fetch_final:
            # cross-process caller takes center/clock from the history
            # barrier instead; skipping here saves a redundant full-params
            # transfer (+ a clock roundtrip) per remote process
            return None, history, stal, -1
        center, _ = base_ps.pull()
        return device_get_batched(center), history, stal, ps.num_updates

    def _mfu_ceiling(self) -> Optional[float]:
        """Peak FLOP/s the per-window MFU series measures against: the
        explicit ``mfu_peak_flops`` override (bench/test seam) or the
        device's dtype-aware table entry; None (CPU) disables the series
        — declining beats fabricating, same rule as ``calibrate_peak``."""
        if self.mfu_peak_flops is not None:
            return self.mfu_peak_flops
        if not self._mfu_peak_resolved:
            self._mfu_peak_resolved = True
            try:
                self._mfu_peak = observability.device_peak_flops(
                    self.devices[0], dtype=self.mfu_dtype)
            except Exception:
                self._mfu_peak = None
        return self._mfu_peak

    def _note_window_flops(self, *args) -> None:
        """Count one window's model FLOPs (a single make_jaxpr trace) the
        first time a worker reaches its window; skipped entirely while no
        peak ceiling is known, so the default CPU path never pays it."""
        if self._window_flops is not None or self._mfu_ceiling() is None:
            return
        with self._mfu_lock:
            if self._window_flops is None:
                try:
                    self._window_flops = observability.count_flops(
                        self.window_fn, *args)
                except Exception:
                    self._window_flops = 0.0  # can't count: stay silent

    def _publish_window_mfu(self, win_s: float) -> None:
        if not self._window_flops or win_s <= 0:
            return
        peak = self._mfu_ceiling()
        if peak is None:
            return
        value = observability.mfu(self._window_flops, win_s,
                                  peak_per_chip=peak,
                                  dtype=self.mfu_dtype)
        if value is not None:
            # the gauge (inside mfu()) carries "now"; the histogram keeps
            # the whole window series for burn-rate math and summaries
            telemetry.histogram("observability.mfu_window",
                                dtype=self.mfu_dtype).record(value)

    def _serial_rounds(self, k, wid, dev, carry, ps, elastic, rounds,
                       abort, bookkeep, pull_h, win_h, commit_h):
        """The serialized pull → window → commit loop, with the elastic
        degradation ladder (DESIGN.md §13): when the fleet is unreachable
        (typed PSUnavailable after the transport's own retries), the
        worker degrades to compute-only windows — it keeps training
        against its last good center and accumulates the unfolded commits
        locally — then folds the combined backlog in one commit when the
        fleet returns. ``last_update`` of that fold is the OLDEST backlog
        window's pull clock, so the server charges the honest staleness
        (and DynSGD down-weights accordingly). Bookkeeping for backlog
        windows is deferred until their fold clock exists. Bounded by
        ``max_degraded_windows``; the final backlog (if the run ends
        degraded) gets one last flush attempt before the error surfaces.
        """
        fold = 0
        degraded = 0        # consecutive windows without a landed commit
        backlog = None      # accumulated unfolded commit deltas
        backlog_clock = 0   # pull clock of the OLDEST unfolded window
        deferred: list = []  # (pull_clock, ms, win_s) awaiting a fold clock
        last_center = None  # last successfully pulled (center, clock)
        # step-time decomposition (DESIGN.md §15): the top-level phases
        # data_wait/pull/h2d/compute/commit/bookkeep PARTITION each window
        # (the flight recorder's window_profile events carry the same six);
        # encode/decode/fold land as nested sub-phases from the codec/PS
        prof = {name: telemetry.histogram(f"profile.phase.{name}_s",
                                          worker=wid)
                for name in ("data_wait", "pull", "h2d", "compute",
                             "commit", "bookkeep", "window")}
        it = iter(prefetch(rounds, depth=1))
        while True:
            t_start = time.perf_counter()
            try:
                batches = next(it)
            except StopIteration:
                break
            if abort.is_set():
                return  # a sibling died: stop wasting windows
            # per-window phase breakdown, mirrored onto the flight-recorder
            # ring as ONE structured event per window — the postmortem
            # bundle's "trailing windows" evidence (histograms only keep
            # aggregates; the ring keeps the last windows individually)
            phases = {"data_wait": time.perf_counter() - t_start}
            prof["data_wait"].record(phases["data_wait"])
            with _window_trace(self.trace, wid, fold):
                t0 = time.perf_counter()
                try:
                    with telemetry.span("trace.pull", worker=wid):
                        center, clock = ps.pull()
                    last_center = (center, clock)
                except PSUnavailable:
                    if last_center is None:
                        raise  # never reached the fleet at all: real error
                    center, clock = last_center  # compute-only: stale
                t1 = time.perf_counter()
                pull_h.record(t1 - t0)
                prof["pull"].record(t1 - t0)
                phases["pull"] = t1 - t0
                center_dev = jax.device_put(center, dev)
                t_h2d = time.perf_counter()
                prof["h2d"].record(t_h2d - t1)
                phases["h2d"] = t_h2d - t1
                self._note_window_flops(carry, center_dev, batches,
                                        np.int32(wid * 1_000_003 + fold))
                with telemetry.span("trace.compute", worker=wid):
                    carry, commit, ms = self.window_fn(
                        carry, center_dev, batches,
                        np.int32(wid * 1_000_003 + fold))
                    jax.block_until_ready(commit)
                t2 = time.perf_counter()
                win_s = t2 - t1  # h2d + compute, as before the split
                win_h.record(win_s)
                prof["compute"].record(t2 - t_h2d)
                phases["compute"] = t2 - t_h2d
                to_send, last_up = commit, clock
                if backlog is not None:
                    to_send = _tree_add(backlog, commit)
                    last_up = backlog_clock
                landed = True
                try:
                    with telemetry.span("trace.commit", worker=wid):
                        if elastic:
                            clock_at_fold = ps.commit(
                                to_send, last_update=last_up,
                                worker=wid, window_s=win_s)
                        else:
                            clock_at_fold = ps.commit(to_send,
                                                      last_update=last_up)
                except PSUnavailable as e:
                    degraded += 1
                    telemetry.counter("host_async.degraded_windows",
                                      worker=wid).inc()
                    telemetry.record_event("degraded_window", worker=wid,
                                           window=fold, degraded=degraded)
                    if degraded > self.max_degraded_windows:
                        # ladder exhausted: this outage is terminal — put
                        # the judgement next to the evidence before the
                        # raise unwinds the worker
                        telemetry.record_event(
                            "ps_unavailable", worker=wid,
                            degraded=degraded, message=str(e)[:200])
                        flight_recorder.auto_dump("ps_unavailable")
                        raise
                    backlog, backlog_clock = to_send, last_up
                    deferred.append((clock, ms, win_s))
                    landed = False
                if landed:
                    t3 = time.perf_counter()
                    commit_h.record(t3 - t2)
                    prof["commit"].record(t3 - t2)
                    phases["commit"] = t3 - t2
                    degraded = 0
                    backlog = None
                    for d_clock, d_ms, d_win_s in deferred:
                        bookkeep(clock_at_fold, d_clock, d_ms, d_win_s)
                    deferred.clear()
                    bookkeep(clock_at_fold, clock, ms, win_s)
                    phases["bookkeep"] = time.perf_counter() - t3
                    prof["bookkeep"].record(phases["bookkeep"])
            phases["window"] = time.perf_counter() - t_start
            prof["window"].record(phases["window"])
            telemetry.record_event(
                "window_profile", worker=wid, window=fold,
                degraded=degraded > 0,
                phases={k: round(v, 6) for k, v in phases.items()})
            fold += 1
        if backlog is not None:
            # the run ended degraded: one last flush so the backlogged
            # windows are not silently dropped from the center/history
            clock_at_fold = ps.commit(backlog, last_update=backlog_clock)
            for d_clock, d_ms, d_win_s in deferred:
                bookkeep(clock_at_fold, d_clock, d_ms, d_win_s)

    def _overlapped_rounds(self, k, wid, dev, carry, ps, rounds, abort,
                           bookkeep, pull_h, win_h, commit_h):
        """Double-buffered worker loop: while window n computes, a
        per-worker comms thread commits window n-1 and pulls the center
        for window n+1. Hides commit+pull latency behind compute — the
        win that matters when the PS is remote (remote_ps.py) or the
        codec makes encode/decode non-trivial.

        Semantics: the center a window consumes is one window OLDER with
        respect to the worker's OWN commits than in the serialized loop
        (center for window n+1 is pulled before commit n folds). Clocks
        stay exact — staleness is measured from the actual pull/commit
        clock pair, so the histogram reflects the extra self-staleness
        rather than hiding it; CadenceTrigger still fires on true fold
        clocks (one window later in this worker's observation stride).

        Elastic note: this path gets the transport's reconnect/retry and
        stamps worker identity (lease renewal), but NOT the compute-only
        degradation ladder — the double-buffered hand-off has no place to
        park a backlog without stalling the compute loop it exists to
        keep busy. An outage longer than the retry budget surfaces as
        PSUnavailable; use the serialized loop for churn-heavy fleets.
        """
        elastic = getattr(ps, "elastic", False)
        _STOP = object()
        req: queue_lib.Queue = queue_lib.Queue(maxsize=1)
        resp: queue_lib.Queue = queue_lib.Queue(maxsize=1)

        def comms_loop():
            # one request in flight at a time: commit the finished window
            # (if any), then pull the next center. Exceptions travel to
            # the compute loop through the resp queue.
            try:
                while True:
                    item = req.get()
                    if item is _STOP:
                        return
                    commit, pull_clock = item
                    clock_at_fold = -1
                    if commit is not None:
                        t0 = time.perf_counter()
                        if elastic:
                            clock_at_fold = ps.commit(
                                commit, last_update=pull_clock, worker=wid)
                        else:
                            clock_at_fold = ps.commit(commit,
                                                      last_update=pull_clock)
                        dt = time.perf_counter() - t0
                        commit_h.record(dt)
                        # overlapped comms still feed the phase profile;
                        # attribution reads them as hidden-behind-compute
                        telemetry.histogram("profile.phase.commit_s",
                                            worker=wid).record(dt)
                    t0 = time.perf_counter()
                    center, clock = ps.pull()
                    dt = time.perf_counter() - t0
                    pull_h.record(dt)
                    telemetry.histogram("profile.phase.pull_s",
                                        worker=wid).record(dt)
                    resp.put((center, clock, clock_at_fold))
            except Exception as e:
                resp.put(e)

        ct = threading.Thread(target=comms_loop, daemon=True,
                              name=f"host-async-comms-{wid}")
        ct.start()
        try:
            req.put((None, 0))  # prime: pull window 0's center
            fold = 0
            pending = None  # (pull_clock, ms, win_s) awaiting its fold clock
            for batches in prefetch(rounds, depth=1):
                if abort.is_set():
                    return  # a sibling died: stop wasting windows
                got = resp.get()
                if isinstance(got, Exception):
                    raise got
                center, clock, clock_at_fold = got
                if pending is not None:
                    # the previous window's commit has now folded; its
                    # clock arrived with this response
                    bookkeep(clock_at_fold, *pending)
                t1 = time.perf_counter()
                center_dev = jax.device_put(center, dev)
                self._note_window_flops(carry, center_dev, batches,
                                        np.int32(wid * 1_000_003 + fold))
                carry, commit, ms = self.window_fn(
                    carry, center_dev, batches,
                    np.int32(wid * 1_000_003 + fold))
                jax.block_until_ready(commit)
                win_s = time.perf_counter() - t1
                win_h.record(win_s)
                pending = (clock, ms, win_s)
                req.put((commit, clock))
                fold += 1
            if pending is not None:
                got = resp.get()  # drain the final window's commit
                if isinstance(got, Exception):
                    raise got
                bookkeep(got[2], *pending)
        finally:
            req.put(_STOP)
            ct.join()


def run_cross_process(runner: HostAsyncRunner, init_params, epoch_shards,
                      *, worker_offset: int, checkpointer=None,
                      checkpoint_folds: int = 0, start_clock: int = 0,
                      service_port: int = 0,
                      history_timeout: float = 600.0,
                      watchdog=None, ps_shards: int = 1,
                      ps_placement: str = "process0",
                      ps_standby: bool = False,
                      snapshot_extra=None) -> tuple:
    """Pod-scale TRUE-async: this process's worker threads against ONE live
    center owned by process 0 (VERDICT r4 ask #2 — the reference's
    workers-on-separate-machines semantics).

    Process 0 hosts the device-resident PS behind a
    :class:`~distkeras_tpu.parallel.remote_ps.ParameterServerService`; its
    own workers hit the PS object directly (no loopback tax), every other
    process's workers pull/commit through a RemoteParameterServer client.
    Staleness is real cross-host interleaving on the server clock.

    End of run: every process uploads its commit-clock-tagged windows;
    ``history_get`` doubles as the completion barrier (it blocks until all
    processes uploaded) and returns the clock-merged global history plus
    the final center — so every process returns IDENTICAL
    ``(params, history, staleness, num_updates)``, matching the sync
    path's process-transparency. Checkpointing runs only on process 0
    (it owns the center; snapshot cadence is evaluated at its workers'
    commit clocks, which carry the global count).

    ``ps_shards > 1`` replaces the single service with an elastic fleet
    (parallel/elastic.py): process 0 hosts N shard services (the center's
    leaves size-balanced across them, shard 0 carrying the membership/
    lease/history plane), the address broadcast carries the whole shard
    map, and EVERY process's workers — including process 0's, which give
    up the no-loopback-tax direct path — go through a
    ShardedRemoteParameterServer, so the whole fleet is on the membership
    plane and churn handling is uniform.

    ``ps_placement="spread"`` (DESIGN.md §17) deals the shard services
    round-robin over PROCESSES instead of stacking them all on process 0:
    the token travels first (everyone must authenticate their service
    before any address exists), each process binds its assigned shards,
    and the full address map is all-gathered — so the fleet aggregates
    every host's NIC and survives a non-coordinator host loss outright.
    Degenerates to "process0" at one process.

    ``ps_standby=True`` adds the coordinator-failover plane: a dark
    standby service (on shard 1's process under spread placement — a
    different HOST than the coordinator) receives the coordinator's
    write-behind authority log, and every client gets the standby's
    address so a dead coordinator is re-resolved through the reconnect
    path instead of ending the run (parallel/failover.py).
    """
    from jax.experimental import multihost_utils

    from distkeras_tpu.health.collector import TelemetryCollector
    from distkeras_tpu.parallel import elastic as elastic_mod
    from distkeras_tpu.parallel import remote_ps as rps

    ps_shards = int(ps_shards)
    if ps_shards < 1:
        raise ValueError(f"ps_shards must be >= 1, got {ps_shards}")
    nproc = jax.process_count()
    placement = elastic_mod.shard_placement(ps_shards, nproc, ps_placement)
    spread = any(p != 0 for p in placement)
    pid = jax.process_index()
    codec_name = "raw" if runner.codec is None else runner.codec.name
    service = client = None
    services: list = []

    def _make_ps(part):
        ps = server_for(
            runner.strategy,
            jax.device_put(part, runner.devices[0]))
        ps.num_updates = int(start_clock)
        return ps

    try:
        if spread:
            # multi-host placement: the token travels FIRST (every hosting
            # process must authenticate its services before any address
            # exists), each process binds its assigned shards dark, and the
            # complete address map is all-gathered before the fleet is
            # cross-wired and started
            if pid == 0:
                import secrets

                _, token = rps.share_service_address(
                    [], token=secrets.token_hex(16))
            else:
                _, token = rps.share_service_address(None)
            # the authoritative start state (checkpoint-restored on process
            # 0) must seed EVERY hosting process's shards, not just 0's
            init_params = multihost_utils.broadcast_one_to_all(
                jax.tree.map(np.asarray, device_get_batched(init_params)))
            from distkeras_tpu.parallel.distributed import \
                determine_host_address
            mine = [s for s in range(ps_shards) if placement[s] == pid]
            standby_here = ps_standby and \
                pid == elastic_mod.standby_process(placement)
            services = elastic_mod.make_ps_fleet(
                _make_ps, init_params, ps_shards,
                expected_processes=nproc, token=token,
                straggler=(StragglerDetector()
                           if 0 in mine or standby_here else None),
                advertise_host=determine_host_address(),
                local_shards=mine, standby=standby_here)
            for svc in services:
                # the fleet telemetry sink lives on the coordinator shard,
                # next to membership and history
                if svc.shard == 0 and not svc.is_standby:
                    svc.collector = TelemetryCollector()
            addresses, standby_addr = elastic_mod.gather_fleet_addresses(
                services, ps_shards)
            elastic_mod.connect_fleet(
                services, addresses, standby_address=standby_addr,
                token=token)
            client = elastic_mod.ShardedRemoteParameterServer(
                addresses, init_params, timeout=history_timeout + 60.0,
                token=token, codec=codec_name, standby=standby_addr)
            local_ps = client
        elif pid == 0:
            # symmetric go/no-go (ADVICE r5): if service construction fails
            # here, peers must RAISE at the address broadcast instead of
            # blocking in it until the collective timeout
            try:
                import secrets

                token = secrets.token_hex(16)
                if ps_shards == 1 and not ps_standby:
                    ps = _make_ps(init_params)
                    service = rps.ParameterServerService(
                        ps, init_params,
                        expected_processes=nproc,
                        port=service_port, token=token,
                        collector=TelemetryCollector())
                    service.start()
                    ports: Any = service.port
                else:
                    # a fresh detector: the services see worker-stamped
                    # window durations from every process, the runner's
                    # own detector only this process's — mixing the two
                    # feeds would double-count local workers
                    advertise = "127.0.0.1"
                    if nproc > 1:
                        from distkeras_tpu.parallel.distributed import \
                            determine_host_address
                        advertise = determine_host_address()
                    services = elastic_mod.make_ps_fleet(
                        _make_ps, init_params, ps_shards,
                        expected_processes=nproc,
                        token=token, straggler=StragglerDetector(),
                        advertise_host=advertise, standby=ps_standby)
                    # the fleet telemetry sink lives on the coordinator
                    # shard, next to membership and history
                    services[0].collector = TelemetryCollector()
                    ports = [svc.advertised for svc in services
                             if not svc.is_standby]
                    for svc in services:
                        # standby rides the same broadcast, "~"-marked so
                        # clients wire it as failover target, not a shard
                        if svc.is_standby:
                            ports.append("~" + svc.advertised)
            except Exception:
                rps.share_service_address(None, error=True)
                raise
            addr, _ = rps.share_service_address(ports, token=token)
            if ps_shards == 1 and not ps_standby:
                local_ps = ps
                if runner.codec is not None and runner.codec.name != "raw":
                    # process 0's workers skip the socket but must see the
                    # SAME wire numerics as remote peers, or convergence
                    # depends on which process a worker landed on
                    local_ps = comms.EncodedParameterServer(ps, runner.codec)
            else:
                # loopback sharded client: process 0's workers join the
                # same membership plane as everyone else's
                client = elastic_mod.ShardedRemoteParameterServer(
                    [svc.advertised for svc in services
                     if not svc.is_standby], init_params,
                    timeout=history_timeout + 60.0, token=token,
                    codec=codec_name,
                    standby=next((svc.advertised for svc in services
                                  if svc.is_standby), None))
                local_ps = client
        else:
            addr, token = rps.share_service_address(None)
            entries = addr.split(",")
            standby_addr = next(
                (e[1:] for e in entries if e.startswith("~")), None)
            addresses = [e for e in entries if not e.startswith("~")]
            # socket timeout must outlive the history barrier, or a slow
            # pod turns the server's informative barrier-timeout error
            # into a bare client-side socket.timeout
            if len(addresses) == 1 and standby_addr is None:
                client = rps.RemoteParameterServer(
                    addresses[0], init_params,
                    timeout=history_timeout + 60.0, token=token,
                    codec=codec_name)
            else:
                client = elastic_mod.ShardedRemoteParameterServer(
                    addresses, init_params, timeout=history_timeout + 60.0,
                    token=token, codec=codec_name, standby=standby_addr)
            local_ps = client
            # the authoritative start state lives at the center (matters on
            # resume: process 0 restored it; also seeds EASGD replicas)
            init_params, _ = client.pull()
        runner.run(init_params, epoch_shards,
                   checkpointer=checkpointer if pid == 0 else None,
                   checkpoint_folds=checkpoint_folds if pid == 0 else 0,
                   start_clock=start_clock, ps=local_ps,
                   worker_offset=worker_offset, fetch_final=False,
                   watchdog=watchdog,
                   snapshot_extra=snapshot_extra if pid == 0 else None)
        if pid == 0 and client is None:
            service.put_history(0, runner.merged_windows)
            merged, center, clock = service.get_history_blocking(
                timeout=history_timeout)
        else:
            client.put_history(pid, runner.merged_windows)
            merged, center, clock = client.get_history(
                timeout=history_timeout)
        # fleet telemetry aggregation: every process that does not HOST
        # the coordinator pushes its registry rows to the coordinator's
        # collector (best-effort) after the history barrier, so the push
        # rides an idle, settled fleet
        reg = telemetry.get_registry()
        hosts_coord = service is not None or any(
            svc.shard == 0 and not svc.is_standby for svc in services)
        if reg is not None and client is not None and (
                pid != 0 or not hosts_coord):
            client.put_telemetry(pid, list(reg.rows()))
        # everyone holds the final state before process 0 tears the
        # service down (a late reader must not hit a dead socket); the
        # barrier also orders the pushes above before the merge below
        multihost_utils.sync_global_devices("distkeras_host_async_done")
        if pid == 0:
            # the collector follows the coordinator: after a failover the
            # promoted standby's re-mounted collector (seeded from the
            # replicated mirror) holds the fleet rows, not the dead
            # coordinator's
            collector = service.collector if service is not None else None
            promoted = [svc for svc in services
                        if svc.standby is not None and svc.standby.promoted]
            if promoted:
                collector = promoted[-1].collector
            elif collector is None:
                for svc in services:
                    if svc.shard == 0 and not svc.is_standby:
                        collector = svc.collector
            if collector is not None:
                runner.fleet_telemetry = collector.merged_rows(local_pid=0)
            elif client is not None:
                # spread fleet whose coordinator lives on another host
                runner.fleet_telemetry = client.get_merged_telemetry()
    finally:
        if client is not None:
            client.close()
        if service is not None:
            service.stop()
        for svc in services:
            if svc.replicator is not None:
                svc.replicator.close(timeout=1.0)
            svc.stop()
    history = [step for _, _, steps in merged for step in steps]
    stal = [float(s) for _, s, _ in merged]
    return device_get_batched(center), history, stal, int(clock)


def stage_worker_shards(shards, features_col: str, label_col: str,
                        batch_size: int, window: int) -> list:
    """Host-side staging for the async runner: per-worker lists of
    [window, batch, ...] batch dicts (rounds of λ minibatches)."""
    out = []
    per_round = batch_size * window
    for s in shards:
        rounds = len(s) // per_round
        rs = []
        for r in range(rounds):
            lo = r * per_round
            feats = np.asarray(s[features_col][lo:lo + per_round])
            labs = np.asarray(s[label_col][lo:lo + per_round])
            rs.append({
                "features": feats.reshape((window, batch_size) +
                                          feats.shape[1:]),
                "labels": labs.reshape((window, batch_size) +
                                       labs.shape[1:]),
            })
        out.append(rs)
    return out


def stream_worker_rounds(address: str, worker: int, features_col: str,
                         label_col: str, batch_size: int, window: int,
                         token: Optional[str] = None, dataset=None,
                         max_ranges: int = 2):
    """A lease-driven round source for one worker: returns the ZERO-ARG
    CALLABLE :meth:`HostAsyncRunner.run` accepts as an ``epoch_shards``
    worker entry (streaming admission, DESIGN.md §20).

    Each call opens a fresh :class:`~distkeras_tpu.data.service.
    DataServiceClient` (the client is not thread-safe; one per worker
    thread) and drives lease → materialize → ack against the coordinator
    at ``address``, reshaping leased row ranges into the exact
    ``[window, batch, ...]`` round dicts :func:`stage_worker_shards`
    produces — the worker loop cannot tell staged and streamed rounds
    apart. Rows come from ``dataset`` locally when given, else over the
    wire. Epoch advancement is coordinator-side; the generator ends when
    the coordinator reports the stream exhausted.

    Accounting honesty: a range is acked once the consumer advances past
    it, which can precede the emission of the round holding its final
    rows — rows buffered toward an incomplete round when a worker dies
    are bounded by ``batch_size * window + max_ranges * range_size``, the
    same drop-remainder class of loss :func:`stage_worker_shards` has at
    every shard tail."""
    def rounds():
        from distkeras_tpu.data.service import (DataServiceClient,
                                                stream_ranges)
        per_round = batch_size * window
        client = DataServiceClient(address, worker=worker, token=token)
        client.register()
        cols = [features_col, label_col]
        feats = labs = None  # row backlog pending reshape into rounds
        try:
            for _e, _pos, _start, _stop, rows in stream_ranges(
                    client, dataset=dataset, cols=cols,
                    max_ranges=max_ranges):
                f, l = np.asarray(rows[features_col]), \
                    np.asarray(rows[label_col])
                feats = f if feats is None else np.concatenate([feats, f])
                labs = l if labs is None else np.concatenate([labs, l])
                while len(feats) >= per_round:
                    tf, feats = feats[:per_round], feats[per_round:]
                    tl, labs = labs[:per_round], labs[per_round:]
                    yield {
                        "features": tf.reshape((window, batch_size) +
                                               tf.shape[1:]),
                        "labels": tl.reshape((window, batch_size) +
                                             tl.shape[1:]),
                    }
        finally:
            try:
                client.deregister()
            except Exception:
                pass
            client.close()
    return rounds
