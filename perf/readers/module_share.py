"""Device time of one executable as a share of the device's busy time, in
percent."""


def read(ctx, reduced, module: str):
    row = (reduced or {}).get("modules", {}).get(module)
    if not row or not reduced.get("busy_s"):
        return None
    return 100.0 * row["seconds"] / reduced["busy_s"]
