"""Share of its memory roofline that the decode step reaches, in percent.
A decode step is bound by bytes: the least time it could take is the bytes
it *needs* (the weights once, plus the cached keys and values of the
positions its lanes really hold; ``perf/flops/<config>.py``) over the
chip's HBM bandwidth. That, over the step's device seconds from the trace.
The positions are summed from the client's records over the tokens that
arrived inside the traced window, and shared among the decode runs in it."""

import stats


def read(ctx, reduced, module: str = "jit_decode"):
    row = (reduced or {}).get("modules", {}).get(module)
    records = ctx.facts.get("records")
    if not row or not row["runs"] or not records or ctx.peaks is None \
            or ctx.tracer is None:
        return None
    zero = ctx.facts["t_zero"]
    positions = stats.context_positions_between(
        records, ctx.tracer.t_start - zero, ctx.tracer.t_stop - zero)
    needed = ctx.flops.decode_step_bytes(ctx.config, positions / row["runs"])
    floor_s = needed / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (row["seconds"] / row["runs"])
