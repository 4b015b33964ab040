"""Device seconds of the operations whose name matches a pattern, as a
share of the device's busy time, in percent. ``reduced["op_seconds"]`` is
keyed by an instruction's name without its number and its first output's
shape (``fusion f32[128,64,64,128]``), so a pattern on the shape picks the
operations that produce a tensor of that form: a pooled state's rows, say.
None where no operation matches."""

import re


def read(ctx, reduced, pattern: str):
    ops = (reduced or {}).get("op_seconds") or {}
    if not reduced or not reduced.get("busy_s"):
        return None
    match = re.compile(pattern)
    seconds = sum(s for name, s in ops.items() if match.search(name))
    return 100.0 * seconds / reduced["busy_s"] if seconds else None
