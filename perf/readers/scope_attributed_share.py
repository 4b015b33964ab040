"""Device seconds that the program's scope tables give to exactly one
executable kind and one declared scope, as a share of the device's busy
seconds, in percent: how much of the device's time ``scope_share`` can see
at all through ``reduced["op_seconds"]`` (its docstring says how the join
is made). What it cannot: keys that instructions of different kinds or
scopes share (``ambiguous``), keys in no table (``unknown``: another
executable than the serving ones), and instructions traced under no
declared scope.

Before it returns it logs, on earlier lines, the whole table: every (kind,
scope) with its seconds and its share of that kind's runs, then the
ambiguous and the unknown keys with theirs: what PERF.md section 5 is
written from. With ``--keep-trace DIR`` the tables go to
``DIR/scope_tables.json`` beside the trace, for
``python -m distkeras_tpu.profiling.scopes``. None where ``scope_share``
has nothing to join."""

import os

from readers.scope_share import split

#: ambiguous and unknown keys printed, largest first
LOGGED_KEYS = 24


def read(ctx, reduced):
    found = split(ctx, reduced)
    if found is None or not reduced.get("busy_s"):
        return None
    given, ambiguous, unknown, built = found
    modules, busy = reduced["modules"], reduced["busy_s"]
    from distkeras_tpu.profiling import scopes

    ctx.log(f"scopes: tables of {scopes.registered()} executables; this "
            f"reader's scope_tables() took {built:.2f} s, "
            f"{scopes.build_seconds:.2f} s went into reading executables "
            f"in all")
    keep = getattr(ctx.tracer, "keep", None)
    if keep:
        scopes.dump(os.path.join(keep, "scope_tables.json"))
    for kind in sorted(modules, key=lambda k: -modules[k]["seconds"]):
        total = modules[kind]["seconds"]
        mine = sorted(((scope, s) for (k, scope), s in given.items()
                       if k == kind), key=lambda kv: -kv[1])
        rest = total - sum(s for _, s in mine)
        ctx.log(f"scopes: {kind}: {modules[kind]['runs']:g} runs, "
                f"{total:.4f} s = {100 * total / busy:.1f} % of busy")
        for scope, s in mine + [("(not given: ambiguous, unknown, or "
                                 "between operations)", rest)]:
            ctx.log(f"scopes:   {kind:<14}{scope or '(no declared scope)':<22}"
                    f"{s:>9.4f} s {100 * s / total if total else 0:>6.1f} %")
    for what, keys in (("ambiguous", {k: v[0] for k, v in ambiguous.items()}),
                       ("unknown", unknown)):
        ctx.log(f"scopes: {what}: {len(keys)} keys, "
                f"{sum(keys.values()):.4f} s = "
                f"{100 * sum(keys.values()) / busy:.1f} % of busy")
        for key, s in sorted(keys.items(),
                             key=lambda kv: -kv[1])[:LOGGED_KEYS]:
            who = "; ".join(f"{k}:{sc or '-'}" for k, sc in
                            ambiguous[key][1]) if what == "ambiguous" else ""
            ctx.log(f"scopes:   {what} {key!r} {s:.4f} s {who}")
    named = sum(s for (_, scope), s in given.items() if scope)
    total = sum(given.values()) + sum(unknown.values()) \
        + sum(v[0] for v in ambiguous.values())
    ctx.log(f"scopes: one kind and one declared scope {named:.4f} s, no "
            f"declared scope {sum(given.values()) - named:.4f} s, all keys "
            f"{total:.4f} s = {100 * total / busy:.1f} % of busy "
            f"{busy:.4f} s")
    return 100.0 * named / busy
