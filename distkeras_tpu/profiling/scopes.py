"""Device time by program scope.

The profiler's device events carry an instruction's text and not the
``jax.named_scope`` it was traced under; the compiled executable carries
both (``metadata={op_name="jit(prefill)/.../attn.latent/dot_general"}`` on
every instruction of ``Compiled.as_text()``). This module makes the join:

- :func:`register` — the serving engine hands over each executable as it is
  compiled (one dictionary insert; nothing is read). The registry is
  process-wide, bounded, and replaced by key, so a process that builds
  many engines holds the newest executables and no more.
- :func:`scope_tables` — turns what is registered into tables, one row an
  instruction that runs as a device event of its own
  (``cost_model.parse_hlo_ops``), each under the innermost declared scope
  of its ``op_name`` path. Lazy: ``as_text()`` of a large executable is not
  free, so nothing is read before somebody asks, after the measured window.
- :func:`dump` / :func:`load` — the tables as JSON.
- :func:`join` — the exact join: every operation event of an ``.xplane.pb``
  goes to the run of ``XLA Modules`` that contains it, and by its
  instruction's name and type to one row of that executable's table;
  device seconds, modelled FLOPs and bytes by (executable kind, scope).

``python -m distkeras_tpu.profiling.scopes <xplane.pb> <tables.json>``
prints that as a table (README "Device time by scope").

The declared names live beside the code they cover: a ``SCOPES`` tuple in
each of :data:`DECLARED_IN`.

The persistent compilation cache's key leaves metadata out by default, so
an executable loaded from an entry that another program wrote carries THAT
program's ``op_name`` paths. Whoever wants its own scopes in a table
compiles inside :func:`own_metadata`.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import importlib
import json
import logging
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from distkeras_tpu.profiling.cost_model import OpCost, _comment_re, \
    _instr_re, parse_hlo_ops

#: the modules whose ``SCOPES`` tuples are the declared names
DECLARED_IN = ("distkeras_tpu.models.gpt", "distkeras_tpu.models.latent_moe",
               "distkeras_tpu.models.hybrid",
               "distkeras_tpu.serving.generation")

#: executables the registry holds; the oldest insert goes first
MAX_EXECUTABLES = 64

#: operations that only contain others: their device events span their
#: bodies' and no table has a row for them
CONTAINERS = frozenset({"while", "conditional", "call"})

UNKNOWN = "(unknown)"       # an event no table of its executable's kind has


def declared_scopes() -> Tuple[str, ...]:
    """Every declared scope name, in the order the modules list them."""
    names: Dict[str, None] = {}
    for module in DECLARED_IN:
        names.update(dict.fromkeys(importlib.import_module(module).SCOPES))
    return tuple(names)


def own_metadata():
    """Context in which a compile's persistent-cache key holds the
    program's metadata, ``op_name`` paths among it, so that a hit gives
    back an executable that carries THIS program's scopes and not those of
    whoever compiled the same operations first."""
    try:
        from jax._src import config as jax_config

        return jax_config.compilation_cache_include_metadata_in_key(True)
    except (ImportError, AttributeError):      # another JAX: the default key
        return contextlib.nullcontext()


@dataclass
class ScopeTable:
    """One executable: ``kind`` is its ``HloModule`` name (``jit_prefill``:
    what the profiler's ``XLA Modules`` line calls its runs), ``key`` says
    which of that kind (``prefill=512``), ``rows`` its instructions."""
    kind: str
    key: str
    rows: List[OpCost] = field(default_factory=list)


_lock = threading.Lock()
#: (kind as registered, key) -> a ``Compiled`` not read yet, or its table
_registry: "collections.OrderedDict[Tuple[str, str], object]" = \
    collections.OrderedDict()
#: seconds :func:`scope_tables` has spent reading executables so far
build_seconds = 0.0


def register(kind: str, key: str, compiled) -> None:
    """Keep ``compiled`` (``jit(f).lower(..).compile()``) for a table,
    under ``kind`` (``"jit_" + f.__name__``) and ``key``. A later insert
    under the same pair replaces this one."""
    with _lock:
        _registry.pop((kind, key), None)
        _registry[(kind, key)] = compiled
        while len(_registry) > MAX_EXECUTABLES:
            _registry.popitem(last=False)


def registered() -> int:
    with _lock:
        return len(_registry)


def clear() -> None:
    with _lock:
        _registry.clear()


_module_re = re.compile(r"^HloModule\s+([\w.\-]+)")


def table_of(hlo_text: str, key: str = "", kind: str = "",
             declared: Optional[Iterable[str]] = None) -> ScopeTable:
    """The table of one executable's optimized HLO text. ``kind`` is only
    for a text without an ``HloModule`` line."""
    m = _module_re.match(hlo_text)
    rows, _ = parse_hlo_ops(
        hlo_text, declared=declared_scopes() if declared is None
        else declared)
    return ScopeTable(m.group(1) if m else kind, key, rows)


def scope_tables() -> List[ScopeTable]:
    """A table for every registered executable, oldest insert first. The
    first call after an insert reads that executable's text (and lets the
    executable go: the table stands in its place)."""
    global build_seconds
    with _lock:
        items = list(_registry.items())
    declared = None
    tables = []
    for pair, value in items:
        if not isinstance(value, ScopeTable):
            t0 = time.perf_counter()
            declared = declared or declared_scopes()
            try:
                value = table_of(value.as_text(), pair[1], pair[0], declared)
            except Exception:   # whoever asks is past its measured window:
                # an executable without a readable text costs it a table
                # of no rows, said here, and not its run
                logging.getLogger(__name__).warning(
                    "no scope table for %s %s", *pair, exc_info=True)
                value = ScopeTable(pair[0], pair[1])
            with _lock:
                if pair in _registry:
                    _registry[pair] = value
                build_seconds += time.perf_counter() - t0
        tables.append(value)
    return tables


_ROW_KEYS = ("name", "opcode", "out_type", "scope", "scope_inferred",
             "flops", "bytes_accessed")


def dump(path: str, tables: Optional[List[ScopeTable]] = None) -> None:
    tables = scope_tables() if tables is None else tables
    with open(path, "w") as f:
        json.dump({"tables": [
            {"kind": t.kind, "key": t.key,
             "rows": [[getattr(r, k) for k in _ROW_KEYS] for r in t.rows]}
            for t in tables]}, f)


def load(path: str) -> List[ScopeTable]:
    with open(path) as f:
        data = json.load(f)
    return [ScopeTable(t["kind"], t["key"], [
        OpCost(output_bytes=0.0, **dict(zip(_ROW_KEYS, row)))
        for row in t["rows"]]) for t in data["tables"]]


# -- through a reduction that keeps seconds by a key of the text -------------

def split_by_key(seconds_by_key: Dict[str, float], tables: List[ScopeTable],
                 key_of) -> Tuple[Dict[Tuple[str, str], float],
                                  Dict[str, Tuple[float, list]],
                                  Dict[str, float]]:
    """Device seconds by (kind, scope) where all that is left of a trace
    is seconds by a KEY of the instruction's text (``perf/trace_reduce``
    sums the events of every executable under ``short_name``: the name
    without its number and the first output's shape). ``key_of`` is that
    function; each row's text is formed as the chip's events have it and
    handed to it. A key's seconds go to a (kind, scope) only where EVERY
    row with that key has the same kind and scope. Returns ``(given,
    ambiguous, unknown)``: ``ambiguous[key] = (seconds, the pairs that
    share it)``, ``unknown`` the keys no table has. Nothing is split and
    nothing is guessed."""
    owners: Dict[str, set] = {}
    for t in tables:
        for r in t.rows:
            owners.setdefault(
                key_of(f"%{r.name} = {r.out_type} {r.opcode}("),
                set()).add((t.kind, r.scope))
    given: Dict[Tuple[str, str], float] = {}
    ambiguous: Dict[str, Tuple[float, list]] = {}
    unknown: Dict[str, float] = {}
    for key, secs in seconds_by_key.items():
        pairs = owners.get(key)
        if not pairs:
            unknown[key] = secs
        elif len(pairs) == 1:
            (pair,) = pairs
            given[pair] = given.get(pair, 0.0) + secs
        else:
            ambiguous[key] = (secs, sorted(pairs))
    return given, ambiguous, unknown


# -- the exact join ----------------------------------------------------------

def plain_type(out_type: str) -> str:
    """An output type without layouts and spaces:
    ``bf16[8,128]{1,0:T(8,128)}`` -> ``bf16[8,128]``."""
    return re.sub(r"\{[^{}]*\}", "", out_type).replace(" ", "")


def event_identity(text: str) -> Tuple[str, str, str]:
    """``(name, plain type, opcode)`` of a device event named by its
    instruction's text (``%fusion.12 = bf16[8,128]{1,0} fusion(...)``);
    an event named otherwise is ``(text, "", "")``. (A long tuple type
    carries ``/*index=5*/`` comments, as the executable's text does.)"""
    m = _instr_re.match(_comment_re.sub("", text))
    if not m:
        return text.strip().lstrip("%"), "", ""
    return m.group("name"), plain_type(m.group("type")), m.group("opcode")


def module_kind(run: str) -> str:
    """``jit_decode(1234567)`` -> ``jit_decode``."""
    return run.split("(", 1)[0]


@dataclass
class Joined:
    """Device seconds by (executable kind, scope), from :func:`join`."""
    #: (kind, scope) -> [seconds, events, flops, bytes]; scope ``""`` is an
    #: instruction under no declared scope, :data:`UNKNOWN` an event that
    #: no table of its kind has; kind ``""`` an event outside every run
    cells: Dict[Tuple[str, str], List[float]] = field(default_factory=dict)
    #: kind -> [runs, seconds] of the ``XLA Modules`` line
    modules: Dict[str, List[float]] = field(default_factory=dict)
    #: (kind, table key) -> instruction name -> seconds
    instructions: Dict[Tuple[str, str], Dict[str, float]] = field(
        default_factory=dict)
    #: the events no table had, by (kind, name and type): seconds
    unknown: Dict[Tuple[str, str], float] = field(default_factory=dict)

    def share(self, kind: str, prefixes: Iterable[str]) -> Optional[float]:
        """100 x the seconds of ``kind`` under scopes that start with one
        of ``prefixes``, over the seconds of ``kind``'s runs."""
        runs = self.modules.get(kind)
        if not runs or not runs[1]:
            return None
        prefixes = tuple(prefixes)
        return 100.0 * sum(
            c[0] for (k, scope), c in self.cells.items()
            if k == kind and scope != UNKNOWN
            and scope.startswith(prefixes)) / runs[1]

    def render(self) -> str:
        lines = [f"{'executable':<16}{'scope':<24}{'seconds':>10}"
                 f"{'% of kind':>10}{'events':>9}{'GFLOP':>11}{'GB':>9}"]
        for kind in sorted(self.modules, key=lambda k: -self.modules[k][1]):
            runs, total = self.modules[kind]
            lines.append(f"{kind:<16}{'(' + str(int(runs)) + ' runs)':<24}"
                         f"{total:>10.4f}{100.0:>10.1f}")
            mine = sorted(((scope, c) for (k, scope), c in self.cells.items()
                           if k == kind), key=lambda sc: -sc[1][0])
            for scope, (secs, events, flops, nbytes) in mine:
                lines.append(
                    f"{'':<16}{scope or '(none)':<24}{secs:>10.4f}"
                    f"{100.0 * secs / total if total else 0.0:>10.1f}"
                    f"{int(events):>9}{flops / 1e9:>11.1f}"
                    f"{nbytes / 1e9:>9.2f}")
            given = sum(c[0] for _, c in mine)
            lines.append(f"{'':<16}{'(between operations)':<24}"
                         f"{total - given:>10.4f}"
                         f"{100.0 * (total - given) / total if total else 0:>10.1f}")
        outside = [(s, c) for (k, s), c in self.cells.items()
                   if k not in self.modules]
        for scope, c in outside:
            lines.append(f"{'(no run)':<16}{scope:<24}{c[0]:>10.4f}")
        for (kind, what), secs in sorted(self.unknown.items(),
                                         key=lambda kv: -kv[1])[:20]:
            lines.append(f"  unknown in {kind or '(no run)'}: {what} "
                         f"{secs:.4f} s")
        return "\n".join(lines)


def join_events(ops, modules, tables: List[ScopeTable],
                into: Optional[Joined] = None) -> Joined:
    """The exact join over one device's events. ``ops`` and ``modules`` are
    ``(name, start_s, dur_s)`` lists (``perf/trace_reduce.load``'s form):
    the ``XLA Ops`` and ``XLA Modules`` lines. An operation belongs to the
    run whose interval holds its start. The runs of one fingerprint
    (``jit_prefill(123)``) are one executable: of the tables of its kind,
    the one that has most of the instructions seen in those runs, by name
    and type. An event's seconds then go to its row's scope, with the
    row's modelled FLOPs and bytes once an event."""
    out = into or Joined()
    runs = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in runs]
    by_run: Dict[str, List[Tuple[Tuple[str, str], float]]] = {}
    for name, start, dur in runs:
        row = out.modules.setdefault(module_kind(name), [0, 0.0])
        row[0] += 1
        row[1] += dur
    for text, start, dur in ops:
        name, typ, opcode = event_identity(text)
        if (opcode or re.sub(r"\.\d+$", "", name)) in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < runs[i][1] + runs[i][2]
        by_run.setdefault(runs[i][0] if inside else "", []).append(
            ((name, typ), dur))
    indexes = [(t, {(r.name, plain_type(r.out_type)): r for r in t.rows})
               for t in tables]
    for run, events in by_run.items():
        kind = module_kind(run)
        seen = {ident for ident, _ in events}
        table, index = max(
            ((t, ix) for t, ix in indexes if t.kind == kind),
            key=lambda tix: len(seen & tix[1].keys()),
            default=(None, {}))
        for ident, dur in events:
            row = index.get(ident)
            cell = out.cells.setdefault(
                (kind, UNKNOWN if row is None else row.scope),
                [0.0, 0, 0.0, 0.0])
            cell[0] += dur
            cell[1] += 1
            if row is None:
                what = f"{ident[0]} {ident[1]}".strip()[:120]
                out.unknown[(kind, what)] = \
                    out.unknown.get((kind, what), 0.0) + dur
                continue
            cell[2] += row.flops
            cell[3] += row.bytes_accessed
            per = out.instructions.setdefault((table.kind, table.key), {})
            per[row.name] = per.get(row.name, 0.0) + dur
    return out


def join(xplane_path: str, tables: List[ScopeTable],
         device_plane: str = "/device:TPU:") -> Joined:
    """:func:`join_events` over every device plane of an ``.xplane.pb``
    (read through ``jax.profiler.ProfileData``), summed."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = Joined()
    for plane in data.planes:
        if not plane.name.startswith(device_plane):
            continue
        lines = {line.name: [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                             for e in line.events] for line in plane.lines
                 if line.name in ("XLA Ops", "XLA Modules")}
        join_events(lines.get("XLA Ops", []), lines.get("XLA Modules", []),
                    tables, into=out)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="device seconds by executable and program scope")
    ap.add_argument("xplane", help="an .xplane.pb of a profiler session")
    ap.add_argument("tables", help="the JSON that scopes.dump() wrote in "
                                   "the traced process")
    args = ap.parse_args(argv)
    print(join(args.xplane, load(args.tables)).render())
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
