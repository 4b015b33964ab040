"""One run of one cell of the benchmark.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name (``perf/workloads/<cell>.json`` ->
``perf/traffic/<mix>.json``, ``perf/configs/<config>.json`` and the
configuration's builder, flops and reference modules), checks the device,
hands over to the driver the traffic mix names (``perf/drivers/<driver>.py``)
and prints free text on earlier lines and, as the LAST line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, under ``--trace 1``, ``breakdown``.

``--trace 0`` reports the cell's end-to-end metrics, taken with the profiler
off. ``--trace 1`` traces a part of the window and reports the per-layer
metrics: every ``perf/metrics/*.json`` that moves an end-to-end metric this
cell's driver reports, and whose reader (``perf/readers/<reader>.py``) finds
something to read. There is no list of cells, configurations or metrics in
code. See perf/README.md.

It exits non-zero and prints no result when the cell is one of
BENCHMARK.json's and JAX finds no TPU of a kind in ``perf/peaks.json``, or
fewer chips than the cell asks for. Rehearsal configurations
(``"rehearsal": true``; not in BENCHMARK.json) run anywhere and print null
for every number.
"""

import time as _time

_T0 = _time.perf_counter()  # process start, as nearly as Python can say

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import harness  # noqa: E402

#: a run may take 360 s (1200 s when it compiles); a hang should die with
#: tracebacks, not hold the chip until the caller kills it
HANG_LIMIT_S = 1150


class Context:
    """What a driver and the readers are handed."""

    def __init__(self, args, cell: dict, device: dict):
        self.workload = cell["name"]
        self.cell, self.traffic = cell["cell"], cell["traffic"]
        self.config, self.chips = cell["config"], cell["chips"]
        self.rehearsal = cell["rehearsal"]
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.device = device
        self.peaks = device["peaks"]
        self.t_process_start = _T0
        self.clock = harness.CompileClock()
        code = self.config.get("code", self.config["name"])
        self.builder = harness.load_module("builders", code)
        self.flops = harness.load_module("flops", code)
        self.reference = harness.load_module("reference", code)
        self.tracer = harness.TracedWindow(self.workload, args.keep_trace) \
            if self.trace else None
        # filled by the driver
        self.window = None        # (t0, t1) on the perf_counter clock
        self.end_to_end = {}      # name -> {"value": .., "unit": ..}
        self.facts = {}           # what the readers read besides the trace

    def log(self, message: str) -> None:
        """An earlier line of standard output: free text, never the result."""
        print(f"[perf {_time.perf_counter() - self.t_process_start:7.1f}s] "
              f"{message}", flush=True)


def read_per_layer(ctx: Context, reduced) -> dict:
    """Every metric file whose moved metric this run's driver reports and
    whose reader returns a value."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["moves"] not in ctx.end_to_end:
            continue
        reader = harness.load_module("readers", spec["reader"])
        value = reader.read(ctx, reduced, **spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the xplane file here before it is deleted")
    args = ap.parse_args()
    faulthandler.dump_traceback_later(HANG_LIMIT_S, exit=True)
    if not os.path.isdir(os.path.join(harness.ROOT, "distkeras_tpu")):
        raise SystemExit(f"the program (distkeras_tpu/) is not in "
                         f"{harness.ROOT}: nothing to measure")
    cell = harness.load_cell(args.workload)
    listed = harness.listed_metrics(args.workload)
    if listed is not None and cell["rehearsal"]:
        raise SystemExit("a rehearsal configuration cannot be a cell of "
                         "BENCHMARK.json")
    device = harness.check_device(cell["chips"], cell["rehearsal"])
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    cache_dir = harness.setup_compile_cache()
    ctx = Context(args, cell, device)
    ctx.log(f"cell {ctx.workload}: config {ctx.config['name']}, traffic "
            f"{ctx.cell['traffic']}, {ctx.chips} chip(s) of "
            f"{device['count']} x {device['kind']!r} ({device['platform']}); "
            f"seed {ctx.seed}, {ctx.seconds:g} s, trace {int(ctx.trace)}; "
            f"compile cache {cache_dir}")

    driver = harness.load_module("drivers", ctx.traffic["driver"])
    result = driver.run(ctx)        # {"correct", "attempted", "failed"}

    t0, t1 = ctx.window
    in_window = ctx.clock.count_between(t0, t1)
    ctx.facts["compiles_in_window"] = in_window
    ctx.facts["compile_s"] = ctx.clock.seconds_before(t0)
    ctx.log(f"compile: {ctx.facts['compile_s']:.1f} s in "
            f"{sum(1 for at, _ in ctx.clock.events if at <= t0)} requests "
            f"before the window ({ctx.clock.hits} persistent-cache hits in "
            f"all), {in_window} compilation(s) inside the window")
    ctx.log("seconds by JAX duration event, whole run: " + ", ".join(
        f"{k.rsplit('/', 1)[-1]} {v:.1f}"
        for k, v in sorted(ctx.clock.by_event.items()) if v >= 0.05))
    if in_window:
        result["correct"] = False
    reduced = ctx.tracer.reduce(ctx.chips) if ctx.tracer else None
    if ctx.trace:
        metrics = read_per_layer(ctx, reduced)
    else:
        metrics = dict(ctx.end_to_end)
    for name, m in sorted(metrics.items()):
        ctx.log(f"  {name} = {m['value']} {m['unit']}"
                + ("   (CPU rehearsal: not a measurement)"
                   if ctx.rehearsal else ""))
    if listed is not None:
        want = listed["per_layer" if ctx.trace else "end_to_end"]
        missing = [k for k in want if k not in metrics]
        if missing and not ctx.trace:
            raise SystemExit(f"BENCHMARK.json lists {missing} for this cell "
                             f"and the run did not produce them")
        if missing:     # a reader that found nothing to read: left out
            ctx.log(f"nothing to read for {missing}")
        metrics = {k: metrics[k] for k in want if k in metrics}
    if ctx.rehearsal:
        metrics = {k: dict(m, value=None) for k, m in metrics.items()}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": ctx.facts.get("memory_peak_bytes")}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": dev}
    if ctx.trace:
        import trace_reduce

        if not reduced or not reduced.get("devices"):
            if not ctx.rehearsal:
                raise SystemExit("the trace holds no device operation")
            dev.update(busy_s=None, window_s=None)
        else:
            dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            line["breakdown"] = trace_reduce.breakdown(reduced)
            ctx.log(f"device busy {reduced['busy_s']:.3f} s of a traced "
                    f"window of {reduced['window_s']:.3f} s (idle share "
                    f"{1 - reduced['busy_s'] / reduced['window_s']:.3f}); "
                    f"lines {reduced['lines']}")
            ctx.log(f"executables: {reduced['modules']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
