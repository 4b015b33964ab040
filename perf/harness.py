"""What every driver shares: finding a cell's files by name, the device
check, the compile clock, the compile cache, the traced sub-window, and the
peak of device memory. The yardstick's own code; from the program it takes
only ``enable_compilation_cache`` (the program fixes its cache directory in
code, so the benchmark has to take that one).
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import threading
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: what a run leaves behind (specs, records, traces); git-ignored
OUT_DIR = os.path.join(ROOT, "perf_out")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``perf/<kind>/<name>.py``, found by name."""
    if not os.path.exists(os.path.join(HERE, kind, name + ".py")):
        raise SystemExit(f"perf/{kind}/{name}.py does not exist")
    return importlib.import_module(f"{kind}.{name}")


def load_cell(workload: str) -> dict:
    """The cell's own file, its traffic mix and its configuration, merged
    by name: ``perf/workloads/<cell>.json`` names ``config`` and
    ``traffic``; keys of the cell's ``overrides`` replace the mix's."""
    path = os.path.join(HERE, "workloads", workload + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"no such cell: perf/workloads/{workload}.json")
    cell = load_json("workloads", workload + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    traffic.update(cell.get("overrides", {}))
    config = load_json("configs", cell["config"] + ".json")
    return {"name": workload, "cell": cell, "traffic": traffic,
            "config": config, "chips": int(cell["chips"]),
            "rehearsal": bool(config.get("rehearsal"))}


def listed_metrics(workload: str) -> Optional[dict]:
    """``{"end_to_end": [...], "per_layer": [...]}``: the metric names
    BENCHMARK.json gives this cell, or None for a cell it does not list
    (a rehearsal cell, or one a later PR is still drafting)."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SystemExit(f"{path} is missing")
    with open(path) as f:
        bench = json.load(f)
    if workload not in [w["name"] for w in bench["workloads"]]:
        return None
    pick = lambda rows: [m["name"] for m in rows
                         if workload in m.get("workloads", [workload])]
    return {"end_to_end": pick(bench["end_to_end"]),
            "per_layer": pick(bench["per_layer"])}


# -- device ------------------------------------------------------------------

def check_device(chips: int, rehearsal: bool) -> dict:
    """The devices this run may use, as JAX reports them. A cell of
    BENCHMARK.json runs only on a TPU whose ``device_kind`` has a row in
    ``perf/peaks.json``, with at least the chips it asks for; anything else
    exits non-zero, naming what was found, before any model is built.
    Rehearsal cells run wherever they are started and report no number."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    peaks = load_json("peaks.json")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s); JAX sees "
                         f"{len(devices)} x {dev.device_kind!r} "
                         f"({dev.platform})")
    if rehearsal:
        return dict(info, peaks=None)
    if dev.platform != "tpu":
        raise SystemExit(f"this cell measures a TPU; jax.devices()[0] is "
                         f"{dev.device_kind!r} on platform {dev.platform!r}")
    if dev.device_kind not in peaks:
        raise SystemExit(f"no row for device_kind {dev.device_kind!r} in "
                         f"perf/peaks.json; add one with its source")
    return dict(info, peaks=peaks[dev.device_kind])


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest chip, from ``memory_stats()``."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# -- compilation -------------------------------------------------------------

class CompileClock:
    """Every pass through ``compile_or_get_cached`` (an XLA compile, or a
    load from the persistent cache) with the instant it ended and its
    seconds, and the persistent cache's hits, from ``jax.monitoring``'s own
    events. Copied from chip_smoke.py (PR 21), with the instants added so
    that compilations can be counted inside a window after the fact."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.events = []          # (perf_counter at the end, seconds)
        self.hits = 0
        self.by_event = {}        # every duration event JAX reports: sums
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        self.by_event[event] = self.by_event.get(event, 0.0) + float(secs)
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), float(secs)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def seconds_before(self, t: float) -> float:
        return sum(s for at, s in self.events if at <= t)

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, _ in self.events if t0 < at <= t1)


def setup_compile_cache() -> str:
    """The persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else the program's own fixed ``.xla_cache/`` at the root of the
    checkout (``utils/jax_compat``). In this process every compile gets an
    entry, however short: the program's un-jitted ``model.init`` makes some
    hundreds of sub-second compiles, which JAX's default threshold of one
    second would compile again in every run."""
    import jax

    from distkeras_tpu.utils import jax_compat

    path = jax_compat.enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# -- tracing -----------------------------------------------------------------

class TracedWindow:
    """One traced sub-window: ``start()`` and ``stop()`` from any thread.
    Host and device tracing only (no Python tracer: it would record every
    call of every client-handling thread and slow the host it measures)."""

    def __init__(self, name: str, keep: Optional[str] = None):
        self.dir = os.path.join(OUT_DIR, "trace", name)
        self.keep = keep
        self.t_start = self.t_stop = None
        self.reduced: Optional[dict] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, chips: int) -> Optional[dict]:
        """Read the trace back, reduce it, and delete it (it is large)."""
        import trace_reduce

        if self.t_stop is None:
            return None
        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            return None
        if self.keep:
            os.makedirs(self.keep, exist_ok=True)
            shutil.copy(path, self.keep)
        self.reduced = trace_reduce.reduce(
            trace_reduce.load(path), self.t_stop - self.t_start, chips)
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced


def run_after(delay_s: float, fn) -> threading.Thread:
    th = threading.Thread(target=lambda: (time.sleep(max(0.0, delay_s)),
                                          fn()), daemon=True)
    th.start()
    return th


