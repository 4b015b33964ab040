"""A percentile of the gaps between token arrivals that the clients of the
window's requests saw, from the load generator's records."""

import stats


def read(ctx, reduced, q: float):
    records = ctx.facts.get("records")
    if not records:
        return None
    t0, t1 = ctx.facts["window_rel"]
    return stats.percentile(stats.gap_values(records, t0, t1), q)
