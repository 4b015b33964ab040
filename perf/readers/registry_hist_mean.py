"""Mean of one histogram of the program's telemetry registry: its exact sum
over its exact count since the engine started (``registry_hist``). None
where the program has no such histogram, or it holds no sample."""

from readers import registry_hist


def read(ctx, reduced, name: str):
    row = registry_hist.histograms().get(name)
    return row["sum"] / row["count"] if row and row["count"] else None
