"""ServingEngine — online inference through a dynamically formed micro-batch.

The offline path (`predictors.py`) scores a whole Dataset; this is the
online path the ROADMAP's "heavy traffic" north star needs: individual
requests arrive over time on arbitrary threads and must be answered at low
latency. The pipeline is

    submit(x) -> bounded RequestQueue -> batcher thread coalesces
    (max_batch_size rows | max_wait_ms, whichever first) -> pad to the
    smallest declared shape bucket -> per-bucket AOT-compiled forward on
    the local device/mesh -> scatter rows back to waiting Futures

Why each stage exists:

- **bounded queue + rejection** (batching.py): backpressure is explicit —
  past ``queue_capacity`` in-flight rows, submit raises ``QueueFull``
  instead of letting latency grow without bound;
- **micro-batching**: one forward dispatch amortizes over up to
  ``max_batch_size`` rows; on an accelerator the per-call overhead
  (dispatch + transfer) dominates single-row compute, so batching is the
  difference between hundreds and tens of thousands of rows/s;
- **shape buckets** (buckets.py): dynamic batch sizes would otherwise
  compile one executable per observed size; padding to a declared ladder
  bounds the compile cache at exactly ``len(buckets)`` entries, all
  pre-compiled by ``warmup()`` so no request ever pays a compile;
- **forward sharing**: the pure forward fn is
  ``predictors.make_forward_fn(model)`` — the SAME function the offline
  ModelPredictor jits, so online and offline scores cannot drift.

The compiled executables are built with jax's AOT path
(``jit(f).lower(...).compile()``) and held in an engine-owned dict keyed
by bucket size — the "jit cache" the acceptance test asserts holds exactly
one entry per declared bucket.

Telemetry (DESIGN.md §7): ``serving.queue_depth``, ``serving.batch_size``,
``serving.batch_wait_s``, ``serving.padding_rows``, ``serving.execute_s``,
``serving.request_latency_s``, counters ``serving.submitted``/
``completed``/``rejected``/``deadline_exceeded``/``batches``/``compiles``/
``batch_errors``.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from distkeras_tpu import telemetry
from distkeras_tpu.serving.batching import (
    EngineClosed,
    QueueFull,
    Request,
    RequestQueue,
)
from distkeras_tpu.serving.buckets import DEFAULT_BUCKETS, BucketSpec


class ServingEngine:
    """Online micro-batching inference engine over a jit-compiled forward.

    Args:
      model, params: the trained flax module + params (as returned by the
        trainers); the forward pass is ``model.apply(..., train=False)``
        via :func:`distkeras_tpu.predictors.make_forward_fn`.
      input_shape: per-ROW feature shape (no batch dim), e.g. ``(784,)``.
      input_dtype: row dtype; integer dtypes pass through un-cast (token
        ids), mirroring the offline predictor.
      buckets: declared micro-batch sizes to pad up to (compile cache
        bound). ``max_batch_size`` defaults to the largest bucket and may
        not exceed it.
      max_wait_ms: how long the batcher waits past the first queued
        request before flushing a partial batch — the latency/throughput
        knob.
      queue_capacity: bounded queue size; beyond it ``submit`` raises
        :class:`QueueFull`.
      default_timeout_ms: per-request deadline applied when ``submit`` is
        not given one; ``None`` = no deadline.
      mesh: optional Mesh to shard micro-batches over the worker axis
        (every bucket must divide evenly); ``device`` places a
        single-device engine (default: first local device).
      warmup: pre-compile every bucket at construction (recommended; pass
        False only when tests want to observe lazy compiles).
      telemetry_path: if set, ``shutdown()`` dumps the telemetry registry
        to this JSONL path.
    """

    def __init__(self, model, params, input_shape: Sequence[int], *,
                 input_dtype=np.float32,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_batch_size: Optional[int] = None,
                 max_wait_ms: float = 2.0,
                 queue_capacity: int = 1024,
                 default_timeout_ms: Optional[float] = None,
                 mesh=None, device=None,
                 warmup: bool = True,
                 telemetry_path: Optional[str] = None):
        from distkeras_tpu.predictors import make_forward_fn
        from distkeras_tpu.utils.jax_compat import enable_compilation_cache

        enable_compilation_cache()  # warm-up below compiles every bucket
        self.model = model
        self.input_shape = tuple(int(d) for d in input_shape)
        self.input_dtype = np.dtype(input_dtype)
        self.spec = BucketSpec(buckets)
        self.max_batch_size = int(max_batch_size if max_batch_size is not None
                                  else self.spec.max_size)
        if self.max_batch_size > self.spec.max_size:
            raise ValueError(
                f"max_batch_size={self.max_batch_size} exceeds the largest "
                f"declared bucket {self.spec.max_size}")
        if max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0, got {max_wait_ms}")
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.default_timeout_s = (None if default_timeout_ms is None
                                  else float(default_timeout_ms) / 1e3)
        self.telemetry_path = telemetry_path

        forward = make_forward_fn(model)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from distkeras_tpu.parallel import mesh as mesh_lib

            shards = mesh.shape[mesh_lib.WORKER_AXIS]
            bad = [b for b in self.spec if b % shards]
            if bad:
                raise ValueError(
                    f"buckets {bad} not divisible by the mesh's "
                    f"{shards} worker shards; every padded batch must "
                    f"split evenly across the mesh")
            self._x_sharding = NamedSharding(mesh, P(mesh_lib.WORKER_AXIS))
            self._jit = lambda: jax.jit(
                forward,
                in_shardings=(NamedSharding(mesh, P()), self._x_sharding),
                out_shardings=self._x_sharding)
            self._mesh, self._dev = mesh, None
            self.params = mesh_lib.put_replicated(params, mesh)
        else:
            dev = device if device is not None else jax.local_devices()[0]
            self._x_sharding = dev
            self._jit = lambda: jax.jit(forward)
            self._mesh, self._dev = None, dev
            self.params = jax.device_put(params, dev)
        # live-rollout state (serving/rollout.py, DESIGN.md §18): version
        # of the installed params, swap coherence lock, optional shadow tap
        self.model_version = 0
        self.last_swap_time: Optional[float] = None
        self.mirror_sink = None     # callable(np.ndarray rows) or None
        self._swap_lock = threading.Lock()

        self._compiled: dict = {}          # bucket size -> AOT executable
        self._compile_lock = threading.Lock()
        # bucket -> reusable host staging buffer. Owned by the batcher
        # thread (single consumer); _execute blocks on the batch's device
        # result before returning, so the buffer is never mutated while a
        # forward still reads it.
        self._staging: dict = {}
        self._queue = RequestQueue(queue_capacity)
        self._submitted = telemetry.counter("serving.submitted")
        self._completed = telemetry.counter("serving.completed")
        self._batches = telemetry.counter("serving.batches")
        self._batch_errors = telemetry.counter("serving.batch_errors")
        self._padding = telemetry.histogram("serving.padding_rows")
        self._execute_h = telemetry.histogram("serving.execute_s")
        self._latency_h = telemetry.histogram("serving.request_latency_s")
        self._shutdown_lock = threading.Lock()
        self._shut = False
        if warmup:
            self.warmup()
        self._thread = threading.Thread(target=self._batcher_loop,
                                        daemon=True,
                                        name="distkeras-serving-batcher")
        self._thread.start()

    # -- compile cache ----------------------------------------------------
    def _ensure_compiled(self, bucket: int):
        fn = self._compiled.get(bucket)       # unlocked fast path (CPython)
        if fn is None:
            with self._compile_lock:
                fn = self._compiled.get(bucket)
                if fn is None:
                    with telemetry.span("serving.compile", bucket=bucket):
                        zeros = jax.ShapeDtypeStruct(
                            (bucket,) + self.input_shape, self.input_dtype)
                        fn = self._jit().lower(self.params, zeros).compile()
                    self._compiled[bucket] = fn
                    telemetry.counter("serving.compiles").inc()
        return fn

    def warmup(self) -> Tuple[int, ...]:
        """Pre-compile AND pre-execute every declared bucket so no request
        ever pays a compile or first-touch allocation. Returns the compiled
        bucket sizes."""
        with telemetry.span("serving.warmup"):
            for bucket in self.spec:
                fn = self._ensure_compiled(bucket)
                x = np.zeros((bucket,) + self.input_shape, self.input_dtype)
                jax.block_until_ready(
                    fn(self.params, jax.device_put(x, self._x_sharding)))
        return self.compiled_buckets

    @property
    def compiled_buckets(self) -> Tuple[int, ...]:
        """The jit cache contents — after ``warmup()`` this is exactly the
        declared bucket ladder and never grows (asserted in tests)."""
        return tuple(sorted(self._compiled))

    # -- live weight rollout (serving/rollout.py, DESIGN.md §18) ----------
    def _place_params(self, params):
        """Place a host/foreign tree the same way __init__ placed the
        boot params, so swapped-in weights feed the SAME compiled
        executables (identical shardings → zero recompile)."""
        if self._mesh is not None:
            from distkeras_tpu.parallel import mesh as mesh_lib

            return mesh_lib.put_replicated(params, self._mesh)
        return jax.device_put(params, self._dev)

    def swap_weights(self, params, version: int) -> None:
        """Atomically install ``params`` as ``version``. Validation
        (treedef/shape/dtype against the incumbent) runs FIRST, so a torn
        or mismatched tree raises ValueError with engine state untouched.
        The device transfer completes before the swap lock is taken: the
        batcher keeps serving the old version during the copy, and the
        installation itself is one reference flip that ``_execute`` reads
        exactly once per batch — every batch is entirely version N or
        N+1, never a blend. No recompile: params are a runtime argument
        to the AOT executables."""
        from distkeras_tpu.serving.rollout import validate_tree_like

        t0 = time.perf_counter()
        try:
            validate_tree_like(params, self.params)
        except ValueError:
            telemetry.counter("rollout.torn_swaps_blocked",
                              engine="serving").inc()
            raise
        placed = self._place_params(params)
        jax.block_until_ready(placed)
        with self._swap_lock:
            self.params = placed
            self.model_version = int(version)
            self.last_swap_time = time.time()
        dt = time.perf_counter() - t0
        telemetry.counter("rollout.swaps", engine="serving").inc()
        telemetry.histogram("rollout.swap_s", engine="serving").record(dt)
        telemetry.gauge("rollout.model_version", engine="serving").set(
            int(version))
        telemetry.gauge("rollout.last_swap_time",
                        engine="serving").set(self.last_swap_time)
        telemetry.record_event("rollout", action="swap", engine="serving",
                               version=int(version), seconds=dt)
        from distkeras_tpu.health import recorder as flight_recorder

        flight_recorder.configure(serving_model_version=int(version))

    def shadow_forward(self, params, rows: np.ndarray):
        """Run ``rows`` through the ALREADY-COMPILED bucket executables
        under arbitrary ``params`` (canary scoring: candidate vs
        incumbent on mirrored traffic) without touching the live serving
        path. Runs on the caller's thread — JAX dispatch is thread-safe
        and the bucket ladder is warm, so this never compiles. Returns
        the stacked first-output rows as a host array."""
        rows = np.asarray(rows, dtype=self.input_dtype)
        placed = self._place_params(params)
        outs = []
        for start in range(0, len(rows), self.max_batch_size):
            chunk = rows[start:start + self.max_batch_size]
            n = len(chunk)
            bucket = self.spec.bucket_for(n)
            x = np.zeros((bucket,) + self.input_shape, self.input_dtype)
            x[:n] = chunk
            fn = self._ensure_compiled(bucket)
            y = fn(placed, jax.device_put(x, self._x_sharding))
            outs.append(np.asarray(jax.tree.leaves(y)[0])[:n])
        return np.concatenate(outs, axis=0) if outs else \
            np.zeros((0,), self.input_dtype)

    # -- submission API ---------------------------------------------------
    def _make_request(self, x, timeout_ms, now: float) -> Request:
        row = np.asarray(x, dtype=self.input_dtype)
        if row.shape != self.input_shape:
            raise ValueError(
                f"request row has shape {row.shape}, engine serves "
                f"{self.input_shape}")
        timeout_s = (self.default_timeout_s if timeout_ms is None
                     else float(timeout_ms) / 1e3)
        deadline = None if timeout_s is None else now + timeout_s
        # the submitter's trace rides the Request so the batcher thread
        # (which owns execution) can chain its spans under it
        return Request(row, now, deadline,
                       trace=telemetry.current_trace())

    def submit(self, x, timeout_ms: Optional[float] = None):
        """Enqueue one row; returns a ``concurrent.futures.Future`` whose
        result is that row's model output. Raises :class:`QueueFull` under
        backpressure and :class:`EngineClosed` after shutdown; the future
        fails with :class:`DeadlineExceeded` if the deadline passes before
        execution starts."""
        now = time.monotonic()
        req = self._make_request(x, timeout_ms, now)
        self._queue.put(req)
        self._submitted.inc()
        return req.future

    def submit_many(self, xs, timeout_ms: Optional[float] = None) -> list:
        """Enqueue a batch of rows atomically (all admitted or QueueFull —
        no partial admission); returns one Future per row."""
        now = time.monotonic()
        reqs = [self._make_request(x, timeout_ms, now) for x in xs]
        self._queue.put_many(reqs)
        self._submitted.inc(len(reqs))
        return [r.future for r in reqs]

    # -- batcher / executor -----------------------------------------------
    def _batcher_loop(self):
        while True:
            batch = self._queue.next_batch(self.max_batch_size,
                                           self.max_wait_s)
            if batch is None:
                return  # closed and drained
            self._refresh_queue_gauges()  # live without a health poll
            if not batch:
                continue  # every popped request had expired
            try:
                self._execute(batch)
            except Exception as e:  # a bad batch must not kill the engine
                self._batch_errors.inc()
                for req in batch:
                    if not req.future.done():
                        req.future.set_exception(e)

    def _execute(self, batch):
        n = len(batch)
        bucket = self.spec.bucket_for(n)
        x = self._staging.get(bucket)
        if x is None:
            x = np.zeros((bucket,) + self.input_shape, self.input_dtype)
            self._staging[bucket] = x
        else:
            x[n:] = 0  # zero only the padded tail; live rows get overwritten
        for i, req in enumerate(batch):
            x[i] = req.x
        self._padding.record(bucket - n)
        fn = self._ensure_compiled(bucket)
        t0 = time.perf_counter()
        for req in batch:
            if req.trace is not None:
                # queue-wait ends here: execution is starting
                telemetry.record_trace_span(req.trace, "trace.queue_wait",
                                            req.t_perf, t0 - req.t_perf)
        # one coherent (params, version) read per batch: the swap flips
        # both under the same lock, so the version label below names the
        # exact weights this batch computed on — never a blend
        with self._swap_lock:
            params, version = self.params, self.model_version
        y = fn(params, jax.device_put(x, self._x_sharding))
        y_host = jax.tree.map(np.asarray, y)  # blocks until done
        dt = time.perf_counter() - t0
        self._execute_h.record(dt)
        for req in batch:
            if req.trace is not None:
                # the batched forward serves every row at once: traced
                # rows share the batch's compute interval
                telemetry.record_trace_span(req.trace, "trace.compute",
                                            t0, dt, bucket=bucket,
                                            model_version=version)
        self._batches.inc()
        sink = self.mirror_sink
        if sink is not None:
            # shadow tap for canary scoring: live (unpadded) rows only.
            # Copy — the staging buffer is reused by the next batch.
            try:
                sink(np.array(x[:n]))
            except Exception:  # the canary must never break serving
                telemetry.counter("rollout.mirror_errors").inc()
        now = time.monotonic()
        if isinstance(y_host, np.ndarray):  # the common single-output case:
            for i, req in enumerate(batch):  # row views, no per-row tree walk
                req.future.set_result(y_host[i])
                self._latency_h.record(now - req.t_submit)
        else:
            for i, req in enumerate(batch):
                req.future.set_result(jax.tree.map(lambda a: a[i], y_host))
                self._latency_h.record(now - req.t_submit)
        self._completed.inc(n)

    # -- health -----------------------------------------------------------
    def _refresh_queue_gauges(self) -> Tuple[int, Optional[float]]:
        """Push queue depth + head-of-line age into the gauges. Called
        from the batcher loop after every pop AND from health_status, so
        metrics snapshots are live without a health poll."""
        depth = len(self._queue)
        age = self._queue.oldest_age()
        telemetry.gauge("serving.queue_depth").set(depth)
        telemetry.gauge("serving.oldest_request_age_s").set(
            0.0 if age is None else age)
        return depth, age

    def health_status(self) -> dict:
        """Live queue state for the health plane: depth, head-of-line age,
        compile-cache contents."""
        depth, age = self._refresh_queue_gauges()
        return {
            "queue_depth": depth,
            "oldest_request_age_s": age,
            "queue_capacity": self._queue.capacity,
            "compiled_buckets": list(self.compiled_buckets),
            "model_version": self.model_version,
            "last_swap_time": self.last_swap_time,
            "shut": self._shut,
        }

    # -- lifecycle --------------------------------------------------------
    def shutdown(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop the engine. ``drain=True`` serves everything already
        queued before the batcher exits; ``drain=False`` fails queued
        requests with :class:`EngineClosed`. Idempotent."""
        with self._shutdown_lock:
            if self._shut:
                return
            self._shut = True
        self._queue.close()
        if not drain:
            self._queue.fail_pending(
                EngineClosed("engine shut down without draining"))
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            # the join timed out: a wedged batch is still holding the
            # batcher. Don't leave submitters hanging forever — fail
            # whatever is still queued and make the timeout observable.
            telemetry.counter("serving.shutdown_timeouts").inc()
            telemetry.record_event("serving", outcome="shutdown_timeout",
                                   timeout_s=timeout)
            self._queue.fail_pending(EngineClosed(
                f"batcher thread still running after {timeout}s "
                f"shutdown join"))
        if self.telemetry_path:
            reg = telemetry.get_registry()
            if reg is not None:
                reg.dump_jsonl(self.telemetry_path)

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))


__all__ = ["ServingEngine", "QueueFull", "EngineClosed"]
