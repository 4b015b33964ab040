"""Device seconds of the epoch executable per optimizer step: the runs of
the executable that took most device time and lie whole inside the trace,
their device time over their number and over the steps one run scans.
Where the trace holds no whole run (device calls far apart, as when the
host's staging holds the chip back), the device's busy seconds over the
number of times its most expensive instruction ran: that one runs once a
step."""


def read(ctx, reduced):
    steps = ctx.facts.get("steps_per_call")
    if not reduced or not reduced.get("devices") or not steps:
        return None
    if reduced.get("whole_runs"):
        run = max(reduced["whole_runs"].values(), key=lambda r: r["seconds"])
        return run["seconds"] / run["runs"] / steps
    if reduced.get("marker_runs"):
        return reduced["busy_s_first_device"] / reduced["marker_runs"]
    return None
