"""Device seconds of one executable's instructions under some program
scopes, as a share of that executable's own device time, in percent.

The program says which ``jax.named_scope`` each instruction of each serving
executable was traced under (``distkeras_tpu.profiling.scopes``: a table an
executable, built after the window from the compiled text). What is left of
the trace here is ``reduced["op_seconds"]``, seconds by
``trace_reduce.short_name`` over all executables, so the join is by that key:
a key's seconds go to a (kind, scope) only where EVERY instruction of every
table with that key has the same kind and scope; a key shared by two is
``ambiguous``, one in no table ``unknown``, and neither counts here. The
denominator is ``reduced["modules"][module]["seconds"]``, the runs of that
executable alone, so the share does not move with how many prefills the
traced seconds happened to hold.

``args``: ``module`` (``jit_prefill``), ``scopes`` (a list of prefixes:
``["attn."]``). None where the program has no ``profiling.scopes``, where
no executable of ``module`` ran, where no key was given to those scopes, or
where the share would read over 100 (a table that lacks an executable: said
on an earlier line, never clamped).
"""


def split(ctx, reduced):
    """``(given, ambiguous, unknown, seconds of building the tables)``
    (``scopes.split_by_key``), or None where there is nothing to join."""
    import time

    import trace_reduce

    ops = (reduced or {}).get("op_seconds")
    if not ops or not reduced.get("modules"):
        return None
    try:
        from distkeras_tpu.profiling import scopes
    except ImportError:
        if not getattr(ctx, "scopes_absence_said", False):
            ctx.scopes_absence_said = True      # once a run, not a metric
            ctx.log("scopes: this program has no "
                    "distkeras_tpu.profiling.scopes")
        return None
    t0 = time.perf_counter()
    tables = [t for t in scopes.scope_tables() if t.rows]
    built = time.perf_counter() - t0
    if not tables:
        ctx.log("scopes: no executable was registered")
        return None
    return scopes.split_by_key(ops, tables, trace_reduce.short_name) \
        + (built,)


def read(ctx, reduced, module: str, scopes: list):
    found = split(ctx, reduced)
    runs = (reduced or {}).get("modules", {}).get(module)
    if found is None or not runs or not runs["seconds"]:
        return None
    prefixes = tuple(scopes)
    seconds = sum(s for (kind, scope), s in found[0].items()
                  if kind == module and scope.startswith(prefixes))
    if not seconds:     # the model has no such mechanism: nothing to read
        return None
    share = 100.0 * seconds / runs["seconds"]
    if share > 100.0:
        ctx.log(f"scopes: {seconds:.4f} s of keys given to {module} under "
                f"{list(scopes)} against {runs['seconds']:.4f} s of its "
                f"runs: a key of another executable, which no table holds, "
                f"was taken for this one's; not reported")
        return None
    return share
