"""One counter of the program's telemetry registry as a share of another,
in percent: exact counts since the engine started, read when the run is
over (the engine runs in the benchmark's own process and the registry
outlives it). None where the program has no such counter, or the
denominator is zero."""


def read(ctx, reduced, over: str, under: str):
    from distkeras_tpu import telemetry

    registry = telemetry.get_registry()
    counters = registry.snapshot()["counters"] if registry else {}
    if not counters.get(under) or over not in counters:
        return None
    return 100.0 * counters[over] / counters[under]
