"""Model FLOP/s utilisation of prefill on the device, in percent: the
operations the prompts' real tokens need (``prefill_flops`` of
``perf/flops/<config>.py``: shapes only, padding not counted) over the
device seconds of the prefill executable's runs that lie whole inside the
trace and the chip's bfloat16 peak (``perf/peaks.json``). The prompts are
the client's records whose first token arrived inside the traced window, the
latest as many as there are whole runs (a prefill that began before the
trace has its first token inside it and no whole run). None where the
configuration's flops module has no ``prefill_flops``, or nothing ran."""


def read(ctx, reduced, module: str = "jit_prefill"):
    row = (reduced or {}).get("whole_runs", {}).get(module)
    records = ctx.facts.get("records")
    needed = getattr(ctx.flops, "prefill_flops", None)
    if not row or not row["runs"] or not row["seconds"] or not records \
            or needed is None or ctx.peaks is None or ctx.tracer is None:
        return None
    zero = ctx.facts["t_zero"]
    lo, hi = ctx.tracer.t_start - zero, ctx.tracer.t_stop - zero
    first = sorted((r["token_times"][0], r["prompt_len"]) for r in records
                   if r["token_times"] and lo <= r["token_times"][0] < hi)
    lens = [n for _, n in first][-int(row["runs"]):]
    if not lens:
        return None
    flops = needed(ctx.config, float(sum(lens)),
                   float(sum(n * n for n in lens)))
    return 100.0 * flops / (row["seconds"] * ctx.peaks["bf16_flops"])
