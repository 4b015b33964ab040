"""Driver of the training cells: the program's trainer, through its public
entry point, on a data set made from the seed.

Set-up (all inside ``setup_s``): make the data set; a warm-up
``Trainer(...).train()`` of two epochs on one device call's worth of rows
(the first compiles or loads the epoch function, the second times a steady
call); then the one timed ``train()`` on the whole data set, with
``num_epoch`` sized from the warm-up to fill ``--seconds``. The window runs
from the end of epoch 1 of the timed call to the end of its last epoch,
read from the program's ``trainer.epoch`` spans; the per-epoch metric fetch
the trainer makes is the completion barrier (PERF.md: ``block_until_ready``
and a scalar fetch agree on this machine). Shuffling and staging between
epochs count. Nothing in the program is patched.

The data set's size and the trainer's recipe are the configuration's
(``train_data.chunks_per_chip`` device calls per chip per epoch). Traffic
parameter: ``trace_seconds``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import harness

#: the end-to-end metrics a cell of this driver reports
REPORTS = ("train_samples_per_s_chip", "setup_s")


def _epoch_spans(registry):
    spans = list(registry.spans)
    pick = lambda name: [(t0, t0 + dur) for n, t0, dur, _ in spans
                         if n == name]
    return pick("trainer.stage"), pick("trainer.epoch")


def run(ctx) -> dict:
    import jax

    import distkeras_tpu
    from distkeras_tpu import Dataset, telemetry

    cfg, traffic, b = ctx.config, ctx.traffic, ctx.builder
    model = b.build_model(cfg, "train")
    kw = b.trainer_kwargs(cfg)
    trainer_cls = getattr(distkeras_tpu, kw.pop("class"))
    t = time.perf_counter()
    per_call = b.samples_per_chunk(cfg) * ctx.chips
    calls_per_epoch = int(cfg["train_data"]["chunks_per_chip"])
    columns, held = b.make_train_data(cfg, per_call * calls_per_epoch,
                                      ctx.seed)
    ds = Dataset(columns)
    steps_per_call = b.samples_per_chunk(cfg) // kw["batch_size"]
    ctx.log(f"data set: {len(ds)} samples ("
            f"{sum(v.nbytes for v in columns.values()) / 2**30:.2f} GiB on "
            f"the host), {calls_per_epoch} device call(s) of "
            f"{steps_per_call} steps per epoch, made in "
            f"{time.perf_counter() - t:.1f} s")

    # warm-up: two epochs on one device call's worth of rows; the first
    # compiles, the second times a steady call and sizes the timed one
    trainer = trainer_cls(model, num_workers=ctx.chips, num_epoch=2,
                          seed=ctx.seed, **kw)
    telemetry.reset()
    trainer.train(Dataset({k: v[:per_call] for k, v in columns.items()}),
                  shuffle=True)
    stages, epochs = _epoch_spans(telemetry.get_registry())
    epoch_s = (epochs[1][1] - stages[1][0]) * calls_per_epoch
    num_epoch = 1 + max(2, round(ctx.seconds / epoch_s))
    ctx.log(f"warm-up: epoch 1 {epochs[0][1] - stages[0][0]:.1f} s "
            f"(compiles), epoch 2 {epochs[1][1] - stages[1][0]:.2f} s for "
            f"one device call -> {num_epoch} epochs in the timed call")

    registry = telemetry.reset()
    trainer.num_epoch = num_epoch
    if ctx.tracer:
        # trace from the end of epoch 1, found by polling the program's
        # spans (train() holds this thread)
        trace_s = float(traffic["trace_seconds"])

        def traced():
            while not _epoch_spans(registry)[1]:
                time.sleep(0.005)
            ctx.tracer.start()
            time.sleep(trace_s)
            ctx.tracer.stop()

        watcher = threading.Thread(target=traced, daemon=True)
        watcher.start()
    params = trainer.train(ds, shuffle=True)
    if ctx.tracer:
        watcher.join(timeout=60)
    ctx.facts["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.chips)

    stages, epochs = _epoch_spans(registry)
    assert len(epochs) == num_epoch, (len(epochs), num_epoch)
    t0, t1 = epochs[0][1], epochs[-1][1]
    ctx.window = (t0, t1)
    samples = (num_epoch - 1) * len(ds)
    rate = samples / (t1 - t0) / ctx.chips
    ctx.end_to_end = {
        "train_samples_per_s_chip": {"value": rate,
                                     "unit": "samples/s/chip"},
        "setup_s": {"value": t0 - ctx.t_process_start, "unit": "s"}}
    tokens = b.tokens_per_sample(cfg)
    ctx.log(f"window {t1 - t0:.2f} s: epochs 2..{num_epoch}, {samples} "
            f"samples, {rate:.2f} samples/s/chip"
            + (f" ({rate * tokens:.0f} tokens/s/chip)" if tokens else "")
            + f"; epoch walls "
            f"{[round(e[1] - s[0], 2) for s, e in zip(stages, epochs)][:12]}")

    steps_per_epoch = calls_per_epoch * steps_per_call
    losses = np.asarray([h["loss"] for h in trainer.get_history()])
    assert losses.size == num_epoch * steps_per_epoch, losses.size
    by_epoch = losses.reshape(num_epoch, steps_per_epoch)
    window_losses = by_epoch[1:]
    failed = int((~np.isfinite(window_losses)).sum())
    falling = bool(np.isfinite(losses).all()
                   and by_epoch[-1].mean() < by_epoch[0].mean())
    ctx.log(f"loss: first epoch {by_epoch[0].mean():.4f}, last "
            f"{by_epoch[-1].mean():.4f}; {failed} non-finite step(s)")

    # the returned parameters against the plain reference, outside the window
    tol = cfg["tolerance"]["train_forward_rel"]
    features = held["features"]
    got = jax.jit(lambda p, x: model.apply({"params": p}, x, train=False))(
        params, features)
    want = jax.jit(lambda p, x: ctx.reference.forward(p, x, cfg))(
        params, features)
    rel = float(jax.numpy.max(jax.numpy.abs(got - want))
                / jax.numpy.max(jax.numpy.abs(want)))
    ctx.log(f"reference: max |system - reference| / max |reference| = "
            f"{rel:.5f} on {features.shape[0]} held sample(s) "
            f"(tolerance {tol})")

    ctx.facts.update(
        spans={"stage": stages, "epoch": epochs},
        steps_per_call=steps_per_call, calls_per_epoch=calls_per_epoch,
        batch_size=kw["batch_size"],
        flops_per_step=ctx.flops.train_flops_per_step(cfg))
    return {"correct": falling and np.isfinite(rel) and rel <= tol,
            "attempted": int(window_losses.size), "failed": failed}
