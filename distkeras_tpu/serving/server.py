"""Socket front-end for the ServingEngine — requests over the pod fabric.

Thin by design: the engine owns batching, buckets, deadlines and
backpressure; this module only moves rows across a socket. It reuses the
length-prefixed framing AND the shared-token auth scheme of
``parallel/remote_ps.py`` (ADVICE r5) — one wire convention for the whole
repo, no pickle, nothing on the wire can execute code.

Protocol (header JSON + raw blobs, see remote_ps):

    {"op": "infer", "token": ..., "shape": [n, ...], "dtype": "float32",
     "timeout_ms": 50}            + blob: row-major request rows
    -> {"shape": [n, ...], "dtype": ...} + blob: row-major outputs
    -> {"error": "...", "kind": "deadline|queue_full|closed|bad_request"}

    {"op": "stats", "token": ...} -> {"counters": {...}, "gauges": {...}}
    {"op": "ping", "token": ...}  -> {"ok": true}

    {"op": "weights_put", "token": ..., "version": v,
     "target": "serving|generation|both"} + blobs: _TreeCodec leaves
    -> {"ok": ..., "version": v, "staged": ...}   (live rollout, §18)
    {"op": "version", "token": ...} -> {"model_version": v, ...}

    {"op": "kv_export", "token": ..., "length": n} + blob: int32 tokens
    -> {"found": true, "leaves": [[shape, dtype], ...], ...} + blobs:
       one raw host KV page blob per pool leaf (+ optional parked
       last-logits blob), or {"found": false}   (fleet KV handoff, §22)
    {"op": "kv_handoff", "token": ..., "length": n,
     "leaves": [[shape, dtype], ...], ...} + blobs: int32 tokens then
     the kv_export blobs verbatim -> {"ok": bool}  (False = refused →
     the caller degrades to cold prefill, never a half-install)

    {"op": "generate", "token": ..., "length": n, "max_new_tokens": m,
     "timeout_ms": ..., "eos_id": ...} + blob: int32 prompt tokens
    -> zero or more {"stream": true, "tokens": [...]} frames (one per
       emitted token chunk), then ONE typed final frame: either
       {"done": true, "reason": "eos|length|max_len", "num_tokens": k,
        "dtype": "int32"} + blob: the full generated sequence, or
       {"error": "...", "kind": ...}. The final blob equals the
       concatenated stream frames (wire-equality, asserted by test).

plus the three live-health introspection ops (``status`` /
``metrics-snapshot`` / ``recent-spans``, see ``health/endpoints.py``) —
the serving ``status`` digest includes the engine's queue depth and
oldest-request age.

A request's rows ride the engine's ``submit_many`` (atomic admission:
either every row is queued or the whole request is rejected with
``queue_full``), so one TCP client cannot partially starve another.
"""

from __future__ import annotations

import queue
import socket
import threading
import time
from typing import Optional, Tuple

import numpy as np

from distkeras_tpu import telemetry
from distkeras_tpu.comms.retry import DEFAULT_RETRY
from distkeras_tpu.health.endpoints import HEALTH_OPS, handle_health_op
from distkeras_tpu.parallel.remote_ps import (
    check_token,
    recv_message,
    send_message,
)
from distkeras_tpu.serving.batching import (
    DeadlineExceeded,
    EngineClosed,
    QueueFull,
)
from distkeras_tpu.serving.engine import ServingEngine
from distkeras_tpu.serving.generation import GenerationResult


# The serving error taxonomy, declared once: clients and tests dispatch on
# these strings, and the dktlint wire checker asserts the set of "kind"
# values this module actually emits stays exactly equal to this tuple.
ERROR_KINDS = ("auth", "bad_request", "closed", "deadline", "queue_full")


def _error_kind(exc: Exception) -> str:
    if isinstance(exc, DeadlineExceeded):
        return "deadline"
    if isinstance(exc, QueueFull):
        return "queue_full"
    if isinstance(exc, EngineClosed):
        return "closed"
    return "bad_request"


class ServingServer:
    """Accept-loop + handler-thread-per-connection front of a ServingEngine
    (the reference's parameter-server thread shape, reused a third time).

    ``token``: shared secret required in every request header; None
    disables auth (loopback dev only — a bound ServingServer otherwise
    answers anyone who can reach the port).
    """

    def __init__(self, engine: ServingEngine, host: str = "0.0.0.0",
                 port: int = 0, token: Optional[str] = None,
                 generator=None, rollout=None, router=None):
        self.engine = engine
        #: optional GenerationEngine backing the ``generate`` op; None
        #: keeps this a pure one-shot inference server
        self.generator = generator
        #: optional RolloutController (serving/rollout.py): when mounted,
        #: ``weights_put`` stages through it (canary + rollback rails)
        #: instead of swapping the engines directly
        self.rollout = rollout
        #: optional FleetRouter (serving/fleet.py): when mounted, this
        #: server's health ``status`` digest carries the router's fleet
        #: view (replicas/roles/sheds/handoffs/skew) for health.cli
        self.router = router
        self.token = token
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._running = False
        self._threads: list = []

    def start(self) -> None:
        self._running = True
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name="distkeras-serving-accept")
        t.start()
        self._threads.append(t)

    def stop(self, shutdown_engine: bool = False) -> None:
        self._running = False
        try:
            self._sock.close()
        except OSError:
            pass
        if shutdown_engine:
            self.engine.shutdown(drain=True)

    def _accept_loop(self):
        while self._running:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # closed by stop()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True)
            t.start()
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _serve(self, conn: socket.socket):
        inflight = telemetry.gauge("serving.server.inflight_connections")
        inflight.add(1)
        try:
            with conn:
                while True:
                    try:
                        header, blobs = recv_message(conn)
                    except ConnectionError:
                        return
                    if not check_token(self.token, header):
                        telemetry.counter(
                            "serving.server.auth_failures").inc()
                        send_message(conn, {"error": "authentication failed",
                                            "kind": "auth"})
                        return  # drop the connection, not just the request
                    self._dispatch(conn, header, blobs)
        except Exception:
            if self._running:  # surface handler crashes, don't die silently
                raise
        finally:
            inflight.add(-1)

    def _dispatch(self, conn, header: dict, blobs: list):
        op = header.get("op")
        telemetry.counter("serving.server.requests", op=str(op)).inc()
        if op == "infer":
            try:
                self._infer(conn, header, blobs)
            except Exception as e:
                send_message(conn, {"error": str(e),
                                    "kind": _error_kind(e)})
        elif op == "generate":
            try:
                self._generate(conn, header, blobs)
            except Exception as e:
                # synchronous rejections (QueueFull, EngineClosed, bad
                # args) arrive before any stream frame, so the client
                # sees exactly one typed final frame
                send_message(conn, {"error": str(e),
                                    "kind": _error_kind(e)})
        elif op == "weights_put":
            # live rollout (serving/rollout.py, DESIGN.md §18): install a
            # published version over the wire — zero restart, zero recompile
            try:
                send_message(conn, self._weights_put(header, blobs))
            except Exception as e:
                send_message(conn, {"error": str(e),
                                    "kind": _error_kind(e)})
        elif op == "kv_export":
            # fleet KV handoff, prefill side (serving/fleet.py, §22):
            # read the parked prompt KV out of the prefix cache
            try:
                header2, blobs2 = self._kv_export(header, blobs)
                send_message(conn, header2, blobs2)
            except Exception as e:
                send_message(conn, {"error": str(e),
                                    "kind": _error_kind(e)})
        elif op == "kv_handoff":
            # fleet KV handoff, decode side: install shipped pages; the
            # engine refuses (ok=False) on any shape/dtype mismatch
            try:
                send_message(conn, self._kv_handoff(header, blobs))
            except Exception as e:
                send_message(conn, {"error": str(e),
                                    "kind": _error_kind(e)})
        elif op == "version":
            send_message(conn, self._version())
        elif op == "stats":
            send_message(conn, self._stats())
        elif op == "ping":
            send_message(conn, {"ok": True})
        elif op in HEALTH_OPS:
            # live health plane (DESIGN.md §9): same three introspection
            # ops the parameter-server control connection mounts
            extra = {
                "service": "serving",
                "port": self.port,
                **self.engine.health_status(),
            }
            if self.generator is not None:
                extra["decode"] = self.generator.health_status()
            if self.router is not None:
                extra["fleet"] = self.router.status_digest()
            send_message(conn, handle_health_op(op, header,
                                                extra_status=extra))
        else:
            send_message(conn, {"error": f"unknown op {op!r}",
                                "kind": "bad_request"})

    @staticmethod
    def _request_trace(header: dict, engine=None):
        """One trace per request (DESIGN.md §15): adopt the caller's wire
        context when the header carries one, else mint a fresh root — so
        a serving request is traceable whether or not the client traces.
        The serving model version rides the baggage (without clobbering a
        caller-set value), so per-version latency/quality attribution
        falls out of the existing trace plane."""
        ctx = telemetry.extract(header)
        if ctx is None:
            ctx = telemetry.TraceContext.new_root()
        if engine is not None:
            ctx.baggage.setdefault("model_version",
                                   str(engine.model_version))
        return ctx

    def _weights_put(self, header: dict, blobs: list) -> dict:
        """Decode a published weight tree and install it. Routed through
        the mounted RolloutController (canary/rollback rails) when one
        exists; a direct engine swap otherwise. The blob layout rides the
        same ``_TreeCodec`` framing the PS wire uses; a torn blob list
        fails decode or swap validation — it can never half-install."""
        from distkeras_tpu.parallel.remote_ps import _TreeCodec

        version = int(header["version"])
        target = header.get("target", "serving")
        if target not in ("serving", "generation", "both"):
            raise ValueError(f"unknown weights_put target {target!r}")
        if target != "serving" and self.generator is None:
            raise ValueError("no generation engine mounted on this server")
        template = self.engine.params if target == "serving" \
            else self.generator._params
        tree = _TreeCodec(template).decode(blobs, kind="pull")
        if self.rollout is not None:
            ok = self.rollout.stage(version, tree)
            return {"ok": bool(ok), "version": version,
                    "staged": self.rollout.candidate_version == version}
        if target in ("serving", "both"):
            self.engine.swap_weights(tree, version)
        if target in ("generation", "both"):
            self.generator.swap_weights(tree, version)
        return {"ok": True, "version": version, "staged": False}

    def _version(self) -> dict:
        """Live version digest: what every engine on this server is
        serving right now (plus controller state when mounted) — the
        fleet-skew view ``health.cli watch`` renders."""
        out = {
            "model_version": self.engine.model_version,
            "last_swap_time": self.engine.last_swap_time,
        }
        if self.generator is not None:
            out["decode_model_version"] = self.generator.model_version
            out["decode_live_versions"] = sorted(self.generator._versions)
        if self.rollout is not None:
            out["rollout"] = self.rollout.status()
        return out

    def _infer(self, conn, header: dict, blobs: list):
        if len(blobs) != 1:
            raise ValueError(f"infer expects 1 blob, got {len(blobs)}")
        shape = tuple(int(d) for d in header["shape"])
        x = np.frombuffer(blobs[0],
                          dtype=np.dtype(header["dtype"])).reshape(shape)
        if shape[1:] != self.engine.input_shape:
            raise ValueError(
                f"rows of shape {shape[1:]} sent to an engine serving "
                f"{self.engine.input_shape}")
        timeout_ms = header.get("timeout_ms")
        with telemetry.use_trace(self._request_trace(header, self.engine)):
            with telemetry.span("trace.request", op="infer",
                                rows=int(shape[0])):
                futures = self.engine.submit_many(x, timeout_ms=timeout_ms)
                # wall-clock bound for the blocking result() calls: the
                # per-request deadline (if any) plus slack for the
                # executing batch to finish
                wait_s = (None if timeout_ms is None
                          else timeout_ms / 1e3 + 30.0)
                rows = [np.asarray(f.result(timeout=wait_s))
                        for f in futures]
        out = np.stack(rows) if rows else np.empty((0,), np.float32)
        send_message(conn, {"shape": list(out.shape), "dtype": str(out.dtype)},
                     [np.ascontiguousarray(out).tobytes()])

    def _generate(self, conn, header: dict, blobs: list):
        if self.generator is None:
            raise ValueError("no generation engine mounted on this server")
        if len(blobs) != 1:
            raise ValueError(f"generate expects 1 blob, got {len(blobs)}")
        prompt = np.frombuffer(blobs[0], np.int32)
        if prompt.size != int(header["length"]):
            raise ValueError(
                f"prompt blob holds {prompt.size} tokens, header declares "
                f"{header['length']}")
        kw = {}
        if header.get("max_new_tokens") is not None:
            kw["max_new_tokens"] = int(header["max_new_tokens"])
        if header.get("eos_id") is not None:
            kw["eos_id"] = int(header["eos_id"])
        if header.get("timeout_ms") is not None:
            kw["timeout_ms"] = float(header["timeout_ms"])
        # the request's trace: queue-wait/prefill/decode spans come from
        # the engine (explicit context, scheduler thread); the stream
        # flushes below are the server's own children of the same trace
        ctx = self._request_trace(header, self.generator)
        q: "queue.SimpleQueue[int]" = queue.SimpleQueue()
        fut = self.generator.generate(prompt, stream=q.put, trace=ctx, **kw)
        while True:
            try:
                chunk = [q.get(timeout=0.05)]
            except queue.Empty:
                # done implies every stream put already happened: the
                # scheduler hands over what it owes from one queue in
                # order (GenerationEngine._deliver), a request's result or
                # error behind its last token, and flushes that queue
                # before it fails a request. So done-then-empty means no
                # frame can still arrive
                if fut.done() and q.empty():
                    break
                continue
            while True:
                try:
                    chunk.append(q.get_nowait())
                except queue.Empty:
                    break
            t0 = time.perf_counter()
            send_message(conn, {"stream": True, "tokens": chunk})
            telemetry.record_trace_span(
                ctx, "trace.stream_flush", t0, time.perf_counter() - t0,
                tokens=len(chunk))
        exc = fut.exception()
        if exc is not None:
            send_message(conn, {"error": str(exc),
                                "kind": _error_kind(exc)})
            return
        res = fut.result()
        out = np.ascontiguousarray(res.tokens)
        send_message(conn, {"done": True, "reason": res.reason,
                            "num_tokens": int(out.size),
                            "dtype": str(out.dtype)}, [out.tobytes()])

    def _kv_export(self, header: dict, blobs: list):
        """Fetch the parked prompt KV pages (+ last logits) for exactly
        the given token sequence, as raw host blobs. The page pytree is
        flattened in ``jax.tree.leaves`` order; each leaf rides as one
        contiguous blob with ``(shape, dtype)`` metadata in the header —
        bitwise-lossless, same rule as the §19 host-swap blobs."""
        if self.generator is None:
            raise ValueError("no generation engine mounted on this server")
        if len(blobs) != 1:
            raise ValueError(f"kv_export expects 1 blob, got {len(blobs)}")
        tokens = np.frombuffer(blobs[0], np.int32)
        if tokens.size != int(header["length"]):
            raise ValueError(
                f"token blob holds {tokens.size} tokens, header declares "
                f"{header['length']}")
        got = self.generator.export_prefix(tokens)
        if got is None:
            return {"found": False}, []
        import jax

        data, last_logits = got
        leaves = [np.asarray(l) for l in jax.tree.leaves(data)]
        out = {"found": True,
               "model_version": self.generator.model_version,
               "leaves": [[list(l.shape), str(l.dtype)] for l in leaves],
               "has_logits": last_logits is not None}
        payload = [np.ascontiguousarray(l).tobytes() for l in leaves]
        if last_logits is not None:
            ll = np.ascontiguousarray(np.asarray(last_logits))
            out["logits_shape"] = list(ll.shape)
            out["logits_dtype"] = str(ll.dtype)
            payload.append(ll.tobytes())
        return out, payload

    def _kv_handoff(self, header: dict, blobs: list) -> dict:
        """Install shipped prefill KV pages into this server's decode
        engine. Blob 0 is the int32 token sequence; the rest are the
        ``kv_export`` payload verbatim. The engine validates leaf count,
        trailing shape and dtype against its own pool and refuses the
        whole entry on any mismatch — ``ok: false`` means the caller
        cold-prefills, never a half-installed cache entry."""
        if self.generator is None:
            raise ValueError("no generation engine mounted on this server")
        meta = header.get("leaves")
        if not isinstance(meta, list):
            raise ValueError("kv_handoff header missing leaves metadata")
        want = 1 + len(meta) + (1 if header.get("has_logits") else 0)
        if len(blobs) != want:
            raise ValueError(
                f"kv_handoff expects {want} blobs, got {len(blobs)}")
        tokens = np.frombuffer(blobs[0], np.int32)
        if tokens.size != int(header["length"]):
            raise ValueError(
                f"token blob holds {tokens.size} tokens, header declares "
                f"{header['length']}")
        leaves = []
        for (shape, dtype), raw in zip(meta, blobs[1:1 + len(meta)]):
            arr = np.frombuffer(raw, np.dtype(dtype))
            leaves.append(arr.reshape([int(d) for d in shape]))
        last_logits = None
        if header.get("has_logits"):
            last_logits = np.frombuffer(
                blobs[-1], np.dtype(header["logits_dtype"])).reshape(
                    [int(d) for d in header["logits_shape"]])
        ok = self.generator.import_prefix(tokens, leaves,
                                          last_logits=last_logits)
        return {"ok": bool(ok)}

    def _stats(self) -> dict:
        reg = telemetry.get_registry()
        if reg is None:
            return {"counters": {}, "gauges": {}}
        snap = reg.snapshot()
        pick = lambda d: {k: v for k, v in d.items()
                          if k.startswith("serving.")}
        return {"counters": pick(snap["counters"]),
                "gauges": pick(snap["gauges"])}


class ServingClient:
    """Blocking client for the serving wire: ``infer(rows) -> outputs``.

    One connection; callers on multiple threads serialize behind a lock
    (same contention profile as RemoteParameterServer). A dropped
    connection is retried through ``retry`` (a ``comms/retry.py``
    :class:`RetryPolicy`, same rails remote_ps grew in PR 8): the client
    reconnects, re-authenticates (the shared token rides every header)
    and resends the request. Only whole requests are retried — a
    ``generate`` that already streamed tokens raises instead, because
    replaying it could double-emit; the fleet router layers its own
    re-queue on top (serving/fleet.py, DESIGN.md §22). ``retry=None``
    restores the old fail-fast behaviour."""

    def __init__(self, address: str, token: Optional[str] = None,
                 timeout: float = 60.0, retry=DEFAULT_RETRY):
        host, port = address.rsplit(":", 1)
        self.token = token
        self._addr = (host, int(port))
        self._timeout = timeout
        self._retry = retry
        self._sock = socket.create_connection(self._addr, timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._lock = threading.Lock()

    def _reconnect(self, attempt: int) -> None:
        """Replace the dead socket after the policy's backoff delay.
        Caller holds ``self._lock`` and owns the retry budget."""
        try:
            self._sock.close()
        except OSError:
            pass
        time.sleep(self._retry.delay(attempt))  # dktlint: disable=lock-blocking-call
        self._sock = socket.create_connection(  # dktlint: disable=lock-blocking-call
            self._addr, timeout=self._timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        telemetry.counter("serving.client.reconnects").inc()

    def _roundtrip(self, header: dict, blobs=()) -> Tuple[dict, list]:
        # a caller inside an active trace stitches the server's spans
        # under its own trace_id; no-op (and raw-peer-safe) otherwise
        header = telemetry.inject(dict(header))
        if self.token is not None:
            header["token"] = self.token
        # by-design: the lock held over send+recv serializes callers on
        # the single shared connection (documented contention profile)
        with self._lock:
            attempts = self._retry.max_retries if self._retry else 0
            for attempt in range(attempts + 1):
                try:
                    send_message(self._sock, header, blobs)  # dktlint: disable=lock-blocking-call
                    return recv_message(self._sock)  # dktlint: disable=lock-blocking-call
                except (ConnectionError, OSError):
                    if attempt >= attempts:
                        raise
                    telemetry.counter("serving.client.retries").inc()
                    self._reconnect(attempt + 1)

    def infer(self, rows, timeout_ms: Optional[float] = None) -> np.ndarray:
        x = np.ascontiguousarray(np.asarray(rows))
        header = {"op": "infer", "shape": list(x.shape),
                  "dtype": str(x.dtype)}
        if timeout_ms is not None:
            header["timeout_ms"] = float(timeout_ms)
        resp, blobs = self._roundtrip(header, [x.tobytes()])
        if "error" in resp:
            raise RuntimeError(
                f"serving ({resp.get('kind', '?')}): {resp['error']}")
        return np.frombuffer(blobs[0], np.dtype(resp["dtype"])).reshape(
            resp["shape"])

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 on_token=None) -> GenerationResult:
        """Stream one generation; returns the final
        :class:`GenerationResult`. ``on_token`` (if given) is called with
        each token as its stream frame arrives — before the sequence
        finishes, which is the whole point of the streaming wire."""
        p = np.ascontiguousarray(np.asarray(prompt, np.int32).reshape(-1))
        header = {"op": "generate", "length": int(p.size)}
        if max_new_tokens is not None:
            header["max_new_tokens"] = int(max_new_tokens)
        if timeout_ms is not None:
            header["timeout_ms"] = float(timeout_ms)
        if eos_id is not None:
            header["eos_id"] = int(eos_id)
        header = telemetry.inject(header)
        if self.token is not None:
            header = dict(header, token=self.token)
        streamed = []
        # the lock spans the whole frame sequence: one generation owns
        # the connection until its final frame (same serialization
        # contract as _roundtrip). Retries cover send + first frame only
        # — once a token streamed, a replay could double-emit, so a
        # mid-stream drop surfaces to the caller (the fleet router
        # re-queues at its layer, where (cid, seq) dedup applies).
        with self._lock:
            attempts = self._retry.max_retries if self._retry else 0
            for attempt in range(attempts + 1):
                try:
                    send_message(self._sock, header, [p.tobytes()])  # dktlint: disable=lock-blocking-call
                    resp, blobs = recv_message(self._sock)  # dktlint: disable=lock-blocking-call
                    break
                except (ConnectionError, OSError):
                    if attempt >= attempts:
                        raise
                    telemetry.counter("serving.client.retries").inc()
                    self._reconnect(attempt + 1)
            while resp.get("stream"):
                for t in resp["tokens"]:
                    streamed.append(int(t))
                    if on_token is not None:
                        on_token(int(t))
                resp, blobs = recv_message(self._sock)  # dktlint: disable=lock-blocking-call
        if "error" in resp:
            raise RuntimeError(
                f"serving ({resp.get('kind', '?')}): {resp['error']}")
        tokens = np.frombuffer(blobs[0], np.dtype(resp["dtype"]))
        if streamed != tokens.tolist():
            raise RuntimeError(
                f"stream frames ({len(streamed)} tokens) disagree with the "
                f"final frame ({tokens.size} tokens)")
        return GenerationResult(tokens, resp["reason"])

    def put_weights(self, params, version: int,
                    target: str = "serving") -> dict:
        """Push a weight tree as ``version`` (the publish wire leg): the
        server installs it into its engines (through the rollout
        controller's canary rails when one is mounted). ``target``:
        ``"serving"`` | ``"generation"`` | ``"both"``."""
        from distkeras_tpu.parallel.remote_ps import _TreeCodec

        codec = _TreeCodec(params)
        header = {"op": "weights_put", "version": int(version),
                  "target": target}
        resp, _ = self._roundtrip(header, codec.encode(params, kind="pull"))
        if "error" in resp:
            raise RuntimeError(
                f"serving ({resp.get('kind', '?')}): {resp['error']}")
        return resp

    def version(self) -> dict:
        """The server's live version digest (see ``_version``)."""
        resp, _ = self._roundtrip({"op": "version"})
        if "error" in resp:
            raise RuntimeError(f"serving: {resp['error']}")
        return resp

    def stats(self) -> dict:
        resp, _ = self._roundtrip({"op": "stats"})
        return resp

    def status(self) -> dict:
        """The server's live health ``status`` digest (queue depth,
        slots, model version, ...) — the router's load signal."""
        resp, _ = self._roundtrip({"op": "status"})
        if "error" in resp:
            raise RuntimeError(f"serving: {resp['error']}")
        return resp

    def kv_export(self, tokens):
        """Fetch the parked prompt KV for ``tokens`` from this replica's
        prefix cache. Returns the raw ``(header, blobs)`` wire payload
        (``header["found"]`` False when the cache holds no such entry) —
        the router ships it to a decode replica verbatim via
        :meth:`kv_handoff`, no host-side decode in between."""
        t = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
        resp, blobs = self._roundtrip(
            {"op": "kv_export", "length": int(t.size)}, [t.tobytes()])
        if "error" in resp:
            raise RuntimeError(
                f"serving ({resp.get('kind', '?')}): {resp['error']}")
        return resp, blobs

    def kv_handoff(self, tokens, export_header: dict,
                   export_blobs) -> bool:
        """Install a :meth:`kv_export` payload into this replica's
        prefix cache. False means the replica refused the entry
        (shape/dtype mismatch) and the caller should cold-prefill."""
        t = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
        header = {"op": "kv_handoff", "length": int(t.size),
                  "leaves": export_header["leaves"],
                  "has_logits": export_header.get("has_logits", False)}
        if header["has_logits"]:
            header["logits_shape"] = export_header["logits_shape"]
            header["logits_dtype"] = export_header["logits_dtype"]
        resp, _ = self._roundtrip(header, [t.tobytes()] + list(export_blobs))
        if "error" in resp:
            raise RuntimeError(
                f"serving ({resp.get('kind', '?')}): {resp['error']}")
        return bool(resp.get("ok"))

    def ping(self) -> bool:
        resp, _ = self._roundtrip({"op": "ping"})
        if "error" in resp:
            raise RuntimeError(f"serving: {resp['error']}")
        return bool(resp.get("ok"))

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass
