"""Model FLOP/s utilisation of the training step on the device, in percent:
the operations one optimizer step needs (``perf/flops/<config>.py``, shapes
only, recomputation not counted) over the step's device seconds and the
chip's bfloat16 peak (``perf/peaks.json``)."""

from readers import train_step_device_s


def read(ctx, reduced):
    step_s = train_step_device_s.read(ctx, reduced)
    if step_s is None or ctx.peaks is None:
        return None
    return 100.0 * ctx.facts["flops_per_step"] / (
        step_s * ctx.peaks["bf16_flops"])
