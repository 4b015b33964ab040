"""Share of the window that the trainer spent outside its ``trainer.epoch``
spans (shuffling, staging, bookkeeping between epochs), in percent. From
the program's spans; the window runs from the end of epoch 1 to the end of
the last epoch."""


def read(ctx, reduced):
    spans = ctx.facts.get("spans")
    if not spans or ctx.window is None:
        return None
    t0, t1 = ctx.window
    inside = sum(end - start for start, end in spans["epoch"][1:])
    return 100.0 * (1.0 - inside / (t1 - t0))
