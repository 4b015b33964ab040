"""Share of the traced window in which a device sat in a collective
operation while nothing else ran on it, in percent, averaged over devices.
Nothing to read where the trace holds no collective (one chip)."""


def read(ctx, reduced):
    if not reduced or not reduced.get("collective_s"):
        return None
    return 100.0 * reduced["exposed_collective_s"] / reduced["window_s"]
