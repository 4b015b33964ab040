"""One field of one histogram of the program's telemetry registry:
``p50`` or ``p95`` (over the newest <= 1024 samples the histogram keeps),
``sum`` or ``count`` (exact, since the engine started). The engine runs in
the benchmark's own process and the registry outlives it, so the reading is
taken when the run is over. None where the program has no such histogram,
or it holds no sample."""


def histograms() -> dict:
    """Every histogram's statistics by name, as the registry's snapshot
    gives them. A look at the snapshot, not ``registry.histogram(name)``:
    that would create what it looks for."""
    from distkeras_tpu import telemetry

    registry = telemetry.get_registry()
    return registry.snapshot()["histograms"] if registry else {}


def read(ctx, reduced, name: str, field: str):
    row = histograms().get(name)
    return row[field] if row and row["count"] else None
