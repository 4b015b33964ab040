"""Render a telemetry JSONL artifact into the staleness/latency tables.

The operator's tool for the *system*-side artifact every trainer writes
(``Trainer(telemetry_path=...)`` or ``trainer.dump_telemetry(path)``); the
*compute*-side profiler trace is reduced by ``perf/trace_reduce.py``. The
headline sections: per-commit staleness distribution, PS commit/pull
counts, per-worker window durations, prefetch queue occupancy.

Usage:
  python -m distkeras_tpu.health.summary <run.telemetry.jsonl> [--top N]
  python -m distkeras_tpu.health.summary <run.telemetry.jsonl> --format prom
  python -m distkeras_tpu.health.summary <p0.jsonl> <p1.jsonl> ... --merge

``--format prom`` renders the artifact in the Prometheus text exposition
format instead of the human tables (same exporter as the live
``health.cli metrics --format prom`` path), so a post-run artifact can be
pushed through a Pushgateway or diffed against a live scrape.

``--merge`` is the cross-process tracing view (DESIGN.md §15): give it
one artifact per process (or a single collector-merged artifact whose
rows already carry ``pid``) and it groups the traced spans by
``trace_id``, printing each trace's spans in start order with their
process, parent linkage, and duration — the textual twin of the merged
Chrome trace.

No third-party deps: the artifact is plain JSON lines (schema in
distkeras_tpu/telemetry.py and DESIGN.md §5b).
"""

from __future__ import annotations

import argparse
import collections
import os
import sys


def load_rows(path: str) -> list:
    from distkeras_tpu.telemetry import load_jsonl

    return load_jsonl(path)


def _full_name(row: dict) -> str:
    labels = row.get("labels") or {}
    if not labels:
        return row["name"]
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{row['name']}{{{inner}}}"


def _fmt(v, unit_s: bool) -> str:
    if v is None:
        return "-"
    if unit_s:  # durations print in ms
        return f"{v * 1e3:.3f}"
    return f"{v:.6g}"


def ops_view(rows: list) -> str:
    """The ``--ops`` section: op-level roofline shares from the
    ``profile.op.*`` metric family (published by
    ``profiling.RooflineReport.publish()``). Honest about absence: a
    fired ``profile.op.inventory_unavailable`` counter means the backend
    exposed no cost model, not that the run was compute-clean."""
    out = ["\n## op roofline (profile.op.* family)"]
    shares = [r for r in rows if r.get("kind") == "gauge"
              and r["name"] == "profile.op.share"]
    coverage = next((r for r in rows if r.get("kind") == "gauge"
                     and r["name"] == "profile.op.coverage"), None)
    unavailable = next(
        (r for r in rows if r.get("kind") == "counter"
         and r["name"] == "profile.op.inventory_unavailable"), None)
    if not shares:
        if unavailable:
            out.append("no cost model on this backend "
                       "(profile.op.inventory_unavailable fired "
                       f"{unavailable['value']}x); op attribution "
                       "degraded to phase level")
        else:
            out.append("no profile.op.* rows in this artifact "
                       "(run attribution.py --ops --run, or call "
                       "RooflineReport.publish())")
        return "\n".join(out)
    if coverage is not None:
        out.append(f"coverage: {coverage['value']:.3f} of modeled "
                   "compute-phase FLOPs attributed to op rows")
    ranked = sorted(shares, key=lambda r: (-r["value"], _full_name(r)))
    width = max(len((r.get("labels") or {}).get("op", "?")) for r in ranked)
    out.append(f"{'op':{width}s} {'share':>7s}  bound")
    for r in ranked:
        labels = r.get("labels") or {}
        out.append(f"{labels.get('op', '?'):{width}s} "
                   f"{r['value']:7.3f}  {labels.get('bound', '?')}")
    return "\n".join(out)


def summarize(rows: list, top: int = 20, ops_section: bool = False) -> str:
    """The whole report as one string (printed by main, asserted by tests)."""
    counters = [r for r in rows if r.get("kind") == "counter"]
    gauges = [r for r in rows if r.get("kind") == "gauge"]
    hists = [r for r in rows if r.get("kind") == "histogram"]
    spans = [r for r in rows if r.get("kind") == "span"]
    meta = next((r for r in rows if r.get("kind") == "meta"), {})

    out = []
    out.append(f"# telemetry summary (schema {meta.get('schema', '?')}; "
               f"{len(counters)} counters, {len(gauges)} gauges, "
               f"{len(hists)} histograms, {len(spans)} span events)")

    if counters:
        out.append("\n## counters")
        width = max(len(_full_name(r)) for r in counters)
        for r in sorted(counters, key=_full_name):
            out.append(f"{_full_name(r):{width}s}  {r['value']}")

    if gauges:
        out.append("\n## gauges")
        width = max(len(_full_name(r)) for r in gauges)
        for r in sorted(gauges, key=_full_name):
            out.append(f"{_full_name(r):{width}s}  {r['value']:g}")

    if hists:
        out.append("\n## histograms  (durations in ms; counts/values raw)")
        width = max(len(_full_name(r)) for r in hists)
        out.append(f"{'name':{width}s} {'count':>8s} {'p50':>10s} "
                   f"{'p95':>10s} {'max':>10s} {'mean':>10s}")
        for r in sorted(hists, key=_full_name):
            secs = r["name"].endswith("_s")
            mean = (r["sum"] / r["count"]) if r["count"] else None
            out.append(
                f"{_full_name(r):{width}s} {r['count']:8d} "
                f"{_fmt(r['p50'], secs):>10s} {_fmt(r['p95'], secs):>10s} "
                f"{_fmt(r['max'], secs):>10s} {_fmt(mean, secs):>10s}")

    # time-series rows (health/timeseries.py MetricStore.rows(), found in
    # postmortem bundles and soak reports): one sparkline per series
    series = [r for r in rows if r.get("kind") == "timeseries"
              and r.get("points")]
    if series:
        from distkeras_tpu.health.timeseries import sparkline

        out.append("\n## time series  (newest points, min..max per line)")

        def series_name(r):
            base = _full_name(r)
            field = r.get("field", "value")
            return base if field == "value" else f"{base}.{field}"

        width = max(len(series_name(r)) for r in series)
        for r in sorted(series, key=series_name):
            vals = [p[1] for p in r["points"]]
            out.append(f"{series_name(r):{width}s}  "
                       f"{sparkline(vals)}  "
                       f"[{min(vals):g}..{max(vals):g}] "
                       f"n={len(vals)} tier={r.get('tier', 'raw')}")

    # the headline table: staleness actually experienced at the center
    stal = [r for r in hists if r["name"] == "ps.commit.staleness"
            and r["count"]]
    if stal:
        out.append("\n## staleness (commits folded between pull and fold)")
        for r in stal:
            out.append(f"commits {r['count']}  p50 {r['p50']:g}  "
                       f"p95 {r['p95']:g}  max {r['max']:g}  "
                       f"mean {r['sum'] / r['count']:.2f}")

    if ops_section:
        out.append(ops_view(rows))

    if spans:
        out.append(f"\n## spans (top {top} by total duration)")
        agg = collections.defaultdict(lambda: [0, 0.0])
        for r in spans:
            a = agg[_full_name(r)]
            a[0] += 1
            a[1] += r["dur_s"]
        width = max(len(k) for k in agg)
        out.append(f"{'name':{width}s} {'count':>7s} {'total_ms':>11s}")
        for name, (n, tot) in sorted(agg.items(),
                                     key=lambda kv: -kv[1][1])[:top]:
            out.append(f"{name:{width}s} {n:7d} {tot * 1e3:11.3f}")

    return "\n".join(out)


def merge_view(rows: list, top: int = 20) -> str:
    """Group traced spans by trace_id across processes (the ``--merge``
    report). Spans print in start order; ``ts`` offsets are relative to
    the trace's first span WITHIN each process (perf_counter origins are
    per-process, so cross-process offsets are not comparable — the pid
    column is the honest boundary)."""
    traces = collections.defaultdict(list)
    for r in rows:
        if r.get("kind") == "span" and "trace_id" in r:
            traces[r["trace_id"]].append(r)
    out = [f"# merged trace view: {len(traces)} traces, "
           f"{sum(len(v) for v in traces.values())} traced spans, "
           f"{len({r.get('pid', 0) for v in traces.values() for r in v})} "
           f"processes"]
    # longest traces first: those are the windows that crossed the wire
    ranked = sorted(traces.items(), key=lambda kv: (-len(kv[1]), kv[0]))
    for trace_id, spans in ranked[:top]:
        spans = sorted(spans, key=lambda r: (r.get("pid", 0), r["t0"]))
        pids = sorted({r.get("pid", 0) for r in spans})
        out.append(f"\n## trace {trace_id}  ({len(spans)} spans, "
                   f"processes {pids})")
        t0_by_pid = {}
        for r in spans:
            t0_by_pid.setdefault(r.get("pid", 0), r["t0"])
        width = max(len(_full_name(r)) for r in spans)
        out.append(f"{'pid':>3s} {'+ms':>10s} {'dur_ms':>10s} "
                   f"{'name':{width}s}  parent")
        for r in spans:
            pid = r.get("pid", 0)
            rel = (r["t0"] - t0_by_pid[pid]) * 1e3
            out.append(
                f"{pid:3d} {rel:10.3f} {r['dur_s'] * 1e3:10.3f} "
                f"{_full_name(r):{width}s}  "
                f"{r.get('parent_id', '-')} -> {r.get('span_id', '-')}")
    if len(ranked) > top:
        out.append(f"\n({len(ranked) - top} more traces not shown; "
                   f"raise --top)")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="summarize a distkeras_tpu telemetry JSONL artifact")
    ap.add_argument("paths", nargs="+", metavar="path",
                    help="telemetry .jsonl written by "
                    "Trainer(telemetry_path=...) / dump_telemetry(); "
                    "--merge accepts one per process")
    ap.add_argument("--top", type=int, default=20,
                    help="span rows (or --merge traces) to show "
                         "(default 20)")
    ap.add_argument("--format", choices=("text", "prom"), default="text",
                    help="'text' = human tables (default); 'prom' = "
                         "Prometheus text exposition (health/export.py)")
    ap.add_argument("--merge", action="store_true",
                    help="cross-process trace view: group spans by "
                         "trace_id (rows from the i-th artifact default "
                         "to pid=i when untagged)")
    ap.add_argument("--ops", action="store_true",
                    help="append the op-level roofline section "
                         "(profile.op.* gauges from "
                         "RooflineReport.publish())")
    args = ap.parse_args(argv)
    # per-process family expansion: flush_at_exit suffixes artifacts with
    # .p{process_index}, so `run.jsonl` names a FAMILY on a shared FS —
    # expand a missing bare path to its sorted .p* siblings, each tagged
    # with the pid parsed from its suffix
    paths = []
    for path in args.paths:
        if not os.path.exists(path):
            import glob as glob_lib
            import re

            family = sorted(
                p for p in glob_lib.glob(path + ".p*")
                if re.fullmatch(r"\.p\d+", p[len(path):]))
            if family:
                paths.extend((p, int(p.rsplit(".p", 1)[1])) for p in family)
                continue
        paths.append((path, None))
    if len(paths) > 1 and not args.merge:
        sys.exit("multiple artifacts only make sense with --merge")
    rows = []
    for i, (path, pid) in enumerate(paths):
        try:
            file_rows = load_rows(path)
        except OSError as e:
            sys.exit(f"cannot read {path}: {e}")
        if pid is None:
            pid = i
        for r in file_rows:
            if "pid" not in r and len(paths) > 1:
                r = dict(r, pid=pid)
            rows.append(r)
    if not rows:
        sys.exit(f"{args.paths[0]}: empty artifact")
    try:
        if args.merge:
            print(merge_view(rows, top=args.top))
        elif args.format == "prom":
            from distkeras_tpu.health.export import rows_to_prometheus

            sys.stdout.write(rows_to_prometheus(rows))
        else:
            print(summarize(rows, top=args.top, ops_section=args.ops))
    except BrokenPipeError:  # e.g. `... | head`: exit quietly
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())


if __name__ == "__main__":
    main()
