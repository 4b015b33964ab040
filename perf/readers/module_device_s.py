"""Device seconds per run of one executable (``jit_<function>``, all its
compiled shapes together), from the trace's ``XLA Modules`` line."""


def read(ctx, reduced, module: str):
    row = (reduced or {}).get("modules", {}).get(module)
    if not row or not row["runs"]:
        return None
    return row["seconds"] / row["runs"]
