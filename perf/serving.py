"""What the two serving drivers share: the program's GenerationEngine
behind its ServingServer on loopback TCP, held by this process (which holds
the chip), and the load generator as a child process (``perf/loadgen.py``).

Timeline, on the ``time.perf_counter()`` clock both processes share:
``zero`` (load starts) -> ``zero + ramp_s`` (window opens; everything before
it is set-up) -> ``+ seconds`` (window closes, load stops) -> drain (what
was sent finishes, up to ``drain_s``) -> the check against the reference.
The ramp lets the in-flight batch reach its steady size, so that medians
over the window are steady from run to run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

import harness
import stats

HERE = os.path.dirname(os.path.abspath(__file__))
#: counters and the histogram the readers take from the program
COUNTERS = ("serving.decode.steps", "serving.decode.tokens",
            "serving.decode.prefills", "serving.decode.admitted",
            "serving.decode.rejected")
#: finished requests, drawn from the seed, checked against the reference
CHECK_SAMPLE = 8
#: open loop: connections beyond the slots, so that a request is never kept
#: waiting for a free connection while the engine's queue (64) has room
SPARE_CONNECTIONS = 80


def _counters(registry) -> dict:
    snap = registry.snapshot()
    return {k: snap["counters"].get(k, 0) for k in COUNTERS}


def spawn_loadgen() -> subprocess.Popen:
    """The load generator's child process. It starts importing at once; the
    path of its spec goes to its standard input when the server is up."""
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


class Stack:
    """The system under test, held by this process: the configuration's
    model with weights made on the device from the seed, the program's
    GenerationEngine, and its ServingServer on a loopback port."""

    def __init__(self, ctx):
        from distkeras_tpu.serving import (GenerationEngine, ServingEngine,
                                           ServingServer)

        cfg, b = ctx.config, ctx.builder
        self.model = b.build_model(cfg, "serve")
        self.params = b.init_params(self.model, ctx.seed)
        self.gen = self.ref_engine = self.srv = None
        try:
            t = time.perf_counter()
            self.gen = GenerationEngine(self.model, self.params,
                                        **b.serving_kwargs(cfg))
            ctx.log(f"GenerationEngine up in {time.perf_counter() - t:.1f} "
                    f"s: {self.gen.compiled_executables}")
            # ServingServer wants a one-shot engine beside the generator;
            # the smallest there is (a [1, 8] forward), never called
            self.ref_engine = ServingEngine(
                self.model, self.params, input_shape=(8,),
                input_dtype=np.int32, buckets=(1,))
            self.srv = ServingServer(self.ref_engine, host="127.0.0.1",
                                     generator=self.gen)
            self.srv.start()
        except BaseException:
            self.close()
            raise

    def close(self) -> None:
        """Stop serving and drop the engines (and with them the pool);
        the weights stay for the check against the reference."""
        if self.srv is not None:
            self.srv.stop()
        if self.gen is not None:
            self.gen.shutdown(drain=False)
        if self.ref_engine is not None:
            self.ref_engine.shutdown()
        self.srv = self.gen = self.ref_engine = None


def run_load(ctx, stack: Stack, child: subprocess.Popen, mode: str,
             traffic: dict, seconds: float, tag: str) -> dict:
    """One phase of load against the stack: ramp, window of ``seconds``,
    drain. Returns the client's records and the program's counters over
    the window; traces a part of the window when the run is a traced one."""
    from distkeras_tpu import telemetry

    cfg = ctx.config
    serving = cfg["serving"]
    ramp_s = float(traffic["ramp_s"])
    drain_s = float(traffic["drain_s"])
    out_path = os.path.join(harness.OUT_DIR, f"{tag}.records.json")
    spec = {
        "address": f"127.0.0.1:{stack.srv.port}", "mode": mode,
        "seed": ctx.seed, "ramp_s": ramp_s, "seconds": seconds,
        "drain_s": drain_s, "out": out_path,
        "timeout_s": drain_s + seconds + ramp_s + 30.0,
        "check_sample": CHECK_SAMPLE,
        "connections": serving["num_slots"] + SPARE_CONNECTIONS,
        "params": dict(traffic, vocab=min(cfg["vocab_size"], 50257),
                       max_prompt=max(serving["prefill_buckets"]),
                       max_total=cfg["n_positions"])}
    spec_path = os.path.join(harness.OUT_DIR, f"{tag}.spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    child.stdin.write(spec_path + "\n")
    child.stdin.flush()
    line = child.stdout.readline().strip()
    if line != "READY":
        raise RuntimeError(f"load generator said {line!r}, not READY")

    registry = telemetry.get_registry()
    zero = time.perf_counter() + 0.2
    child.stdin.write(f"GO {zero!r}\n")
    child.stdin.flush()
    w0, w1 = zero + ramp_s, zero + ramp_s + seconds
    time.sleep(max(0.0, w0 - time.perf_counter()))
    before = _counters(registry)
    stopper = None
    if ctx.tracer:
        delay = float(traffic.get("trace_delay_s", 2.0))
        length = float(traffic.get("trace_seconds", 5.0))
        harness.run_after(w0 + delay - time.perf_counter(), ctx.tracer.start)
        stopper = harness.run_after(
            w0 + delay + length - time.perf_counter(), ctx.tracer.stop)
    time.sleep(max(0.0, w1 - time.perf_counter()))
    after = _counters(registry)
    step_h = registry.histogram("serving.decode.step_s").stats()
    if stopper:
        stopper.join(timeout=60)
    peak = harness.memory_peak_bytes(ctx.chips)

    line = child.stdout.readline().strip()
    if line != "DONE":
        raise RuntimeError(f"load generator said {line!r}, not DONE")
    child.wait(timeout=30)
    with open(out_path) as f:
        got = json.load(f)
    late = got["lateness"]
    ctx.log(f"load generator: {len(got['records'])} requests over "
            f"{got['horizon_s']:.0f} s, drained at {got['drained_s']:.1f} s; "
            f"it ran late by p50 {stats.percentile(late, 50) * 1e3:.2f} ms, "
            f"p95 {stats.percentile(late, 95) * 1e3:.2f} ms, max "
            f"{max(late) * 1e3:.2f} ms")
    steps = after["serving.decode.steps"] - before["serving.decode.steps"]
    toks = after["serving.decode.tokens"] - before["serving.decode.tokens"]
    return {"records": got["records"], "t_zero": zero, "window": (w0, w1),
            "window_rel": (ramp_s, ramp_s + seconds), "drain_s": drain_s,
            "counters": {k: after[k] - before[k] for k in COUNTERS},
            "step_s_p50": step_h["p50"], "step_s_p95": step_h["p95"],
            "decode_lanes_mean": toks / steps if steps else None,
            "memory_peak_bytes": peak}


def serve(ctx, mode: str) -> list:
    """Run one serving cell; fills ``ctx.window`` and ``ctx.facts`` and
    returns the client's records."""
    child = spawn_loadgen()     # imports while this process builds the engine
    stack = None
    try:
        stack = Stack(ctx)
        load = run_load(ctx, stack, child, mode, ctx.traffic, ctx.seconds,
                        ctx.workload)
        ctx.window = load.pop("window")
        ctx.facts.update(load)
        ctx.log(f"program counters over the window: {load['counters']}; "
                f"scheduler step p50 {load['step_s_p50']}, p95 "
                f"{load['step_s_p95']}")
        records = load["records"]
        stack.close()       # requests cut at the close stop decoding here
        worst, exact, total = _check(ctx, stack.params, records)
        tol = ctx.config["tolerance"]["serve_logit_gap"]
        ctx.log(f"reference: {total} tokens of "
                f"{sum(1 for r in records if 'prompt' in r)} sampled "
                f"requests, {exact} exactly the reference argmax, worst "
                f"logit gap {worst:.4f} (tolerance {tol})")
        ctx.facts["check_ok"] = total > 0 and worst <= tol
        return records
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
        if stack is not None:
            stack.close()


def _check(ctx, params, records):
    """For the sampled finished requests: each emitted token's reference
    logit against its position's maximum, over prompt plus answer in one
    full forward padded to the context length (causal, so the padding
    changes nothing before it)."""
    import jax

    cfg = ctx.config
    t_max = cfg["n_positions"]
    gaps_fn = jax.jit(lambda p, ids: ctx.reference.token_gaps(p, ids, cfg))
    worst, exact, total = 0.0, 0, 0
    for r in records:
        if "prompt" not in r:
            continue
        n, out = len(r["prompt"]), r["tokens"]
        ids = np.zeros(t_max, np.int32)
        ids[:n] = r["prompt"]
        ids[n:n + len(out)] = out[:t_max - n]
        gaps = np.asarray(gaps_fn(params, ids))[n - 1:n - 1 + len(out)]
        if not np.isfinite(gaps).all():
            return float("inf"), exact, total
        worst = max(worst, float(gaps.max()))
        exact += int((gaps == 0).sum())
        total += len(gaps)
    return worst, exact, total


def window_counts(ctx, records, ended: bool = False):
    """``(attempted, failed, wrong_length)`` over the requests due in the
    window (open loop) or, with ``ended``, over the requests that ended in
    it (closed loop, where load stops at the close and what is then in
    flight is cut: neither attempted nor failed)."""
    t0, t1 = ctx.facts["window_rel"]
    if ended:
        mine = [r for r in records
                if (r["done"] is not None and t0 <= r["done"] < t1)
                or (r["error"] is not None and t0 <= r["due"] < t1)]
    else:
        mine = stats.due_in(records, t0, t1)
    failed = sum(1 for r in mine if r["error"] is not None)
    wrong = sum(1 for r in mine if r["error"] is None
                and len(r["token_times"]) != r["max_new"])
    return len(mine), failed, wrong
