"""Bare train-step MFU probe — chip-side ground truth per model.

The end-to-end config numbers (distkeras-tpu-bench) honestly include input
staging over the host→device link; even the staging-cancelled
``--marginal`` mode is only reliable when per-epoch compute exceeds the
link's staging variance.
This probe is the other bound: ONE jitted scan of train steps on
device-resident data — no staging in the timed window at all — giving the
compute ceiling the trainer harness should approach on a real TPU host.

Usage: python benchmarks/step_probe.py [vit|resnet|bert|cnn|gpt|all|sweep]
       [--batch N] [--steps N] [--accum 1,4] [--remat none,blocks]
       [--find-max-batch]
Prints one JSON line per model with samples/s and MFU (fetch-synced timing,
analytic FLOPs — same methodology as bench.py, validated by
observability.calibrate_peak). When --batch/--steps are not given, each
family uses its CANONICAL settings (the ones its BASELINE.md floor is
defined at — e.g. resnet needs batch 128, gpt OOMs above batch 8).

``sweep`` mode is the memory-for-compute matrix (DESIGN.md §10) crossed
with the low-precision axis (DESIGN.md §11): one JSON line per (model,
accum_steps, remat, precision) config with samples/s, XLA's static
peak-scratch bytes (``memory_analysis`` — works on every backend), live
peak HBM (``device.memory_stats`` — TPU only), and with --find-max-batch a
doubling search for the largest batch each config can compile and run.
With ``--buckets`` the sweep instead probes gradient-bucket collective
overlap: one row per (precision, bucket_bytes) timing the sync-DP epoch
step over all local devices, where ``none`` is the GSPMD baseline
(implicit grad all-reduce) and each byte size is the explicit shard_map
step with per-bucket psums (parallel/collectives.py). Adding
``--overlap`` turns that into the JOINT grid (ROADMAP item 1(c)): every
bucket size crossed with the async wire leg serialized and overlapped
(:func:`joint_probe`), measuring whether the two schedules compose.
``--attention xla|flash`` pins the attention kernel switch for the
attention families (gpt/bert/vit; comma-axis in sweep mode).

JSONL row schema (absent keys were not measurable on this backend; a
config that raises emits an ``error`` row instead and the process exits
nonzero — OOMs are REPORTED, never crashes):

- all rows: ``model``, ``batch``, ``steps_per_call``, ``samples_per_sec``
- probe rows: ``mfu`` (TPU only; analytic FLOPs / dtype-aware peak)
- sweep rows: ``accum_steps``, ``remat``, ``precision`` (null = model
  default), ``mfu_dtype`` (which peak column an MFU claim is honest
  against), ``temp_bytes`` (XLA static scratch), ``hbm_*`` (TPU only),
  ``mfu`` (TPU only)
- --find-max-batch rows: ``largest_batch``, ``search_limit``
- --buckets rows: ``mode`` ("gspmd" | "bucketed"), ``bucket_bytes``
  (null for gspmd), ``num_workers``, ``precision``
- --buckets --overlap rows: plus ``comms_overlap``, ``epoch_s``,
  ``comms_s``, ``total_s``, ``composition`` (total / (epoch + comms);
  1.0 = serialized, lower = the wire leg hid behind the epoch)
- rows probing a pinned attention kernel carry ``attention``
- error rows: the swept axes + ``error`` ("ExcType: message")
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

try:
    import distkeras_tpu  # noqa: F401  (pip-installed)
except ImportError:  # running from a source checkout: use the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def build_family(name: str, batch: int, remat: str = "none",
                 precision: str = None, attention: str = None) -> tuple:
    """(model, loss, x, y) for one probe family; ``remat`` is threaded to
    the model's rematerialization field (models/remat.py) where the family
    has one (cnn has no block structure to checkpoint), ``precision`` to
    its mixed-precision field (distkeras_tpu/precision.py), ``attention``
    ("xla" | "flash") to its attention kernel switch (ops/attention.py)
    where the family has attention at all."""
    import jax.numpy as jnp

    if attention not in (None, "xla", "flash"):
        raise ValueError(f"attention={attention!r}; expected xla|flash")
    if attention is not None and name in ("resnet", "cnn"):
        raise ValueError(f"{name} has no attention op to switch")
    if name == "vit":
        from distkeras_tpu.models import vit_base

        model = vit_base(remat=remat, precision=precision,
                         attention=attention)
        loss = "categorical_crossentropy"
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, (batch, 224, 224, 3), dtype=np.uint8)
        y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    elif name == "resnet":
        from distkeras_tpu.models import resnet50_nf

        model = resnet50_nf(remat=remat, precision=precision)
        loss = "categorical_crossentropy"
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, (batch, 224, 224, 3), dtype=np.uint8)
        y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    elif name == "bert":
        from distkeras_tpu.models import bert_base

        model, loss = (bert_base(remat=remat, precision=precision,
                                 attention=attention), "masked_lm")
        rng = np.random.default_rng(0)
        x = rng.integers(1, model.vocab_size, (batch, 128)).astype(np.int16)
        y = np.where(rng.random((batch, 128)) < 0.15, x, -1).astype(np.int16)
    elif name == "cnn":
        # BASELINE config 2's family (CIFAR CNN): a small model whose MFU
        # ceiling is its shapes, not the harness — probe for completeness
        from distkeras_tpu.models import cifar10_cnn

        if remat != "none":
            raise ValueError("cnn has no block structure to rematerialize")
        model, loss = (cifar10_cnn(dtype=jnp.bfloat16, precision=precision),
                       "categorical_crossentropy")
        rng = np.random.default_rng(0)
        x = rng.standard_normal((batch, 32, 32, 3)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    elif name == "gpt":
        # long-context chip-side artifact: GPT-2-small shapes at seq 2048.
        # Default stays the fused flash path (single-chip complement of
        # the cross-chip ring attention); --attention xla pins the plain
        # causal path so the two kernels are A/B-able at the step level
        from distkeras_tpu.models.gpt import CausalLM

        gpt_attn = {"xla": "full", "flash": "flash",
                    None: "flash"}[attention]
        model = CausalLM(vocab_size=50304, max_len=2048, num_layers=12,
                         num_heads=12, width=768, mlp_dim=3072,
                         attention=gpt_attn, remat=remat,
                         precision=precision)
        loss = "masked_lm"
        rng = np.random.default_rng(0)
        x = rng.integers(1, model.vocab_size, (batch, 2048)).astype(np.int32)
        y = np.concatenate([x[:, 1:], np.full((batch, 1), -1, np.int32)],
                           axis=1)
    else:
        raise ValueError(f"unknown model {name!r}")
    return model, loss, x, y


def probe(name: str, batch: int, steps: int = 8,
          attention: str = None) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import engine, observability

    model, loss, x, y = build_family(name, batch, attention=attention)
    tx = optax.adamw(1e-3)
    grad_fn = engine.make_grad_fn(model, loss)
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    state = engine.create_train_state(model, jax.random.key(0),
                                      {"features": xd}, tx)

    @jax.jit
    def run(params, opt_state, x, y):
        def one(c, _):
            p, o = c
            (l, _), g = grad_fn(p, {"features": x, "labels": y}, None)
            up, o = tx.update(g, o, p)
            return (optax.apply_updates(p, up), o), l

        (p, o), ls = jax.lax.scan(one, (params, opt_state), None,
                                  length=steps)
        return p, o, jnp.sum(ls)

    flops = observability.count_flops(
        lambda p, b: grad_fn(p, b, None)[1], state.params,
        {"features": xd, "labels": yd}) * steps
    p, o, s = run(state.params, state.opt_state, xd, yd)
    float(np.asarray(s))  # compile + settle (fetch = completion barrier)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        p, o, s = run(p, o, xd, yd)
        float(np.asarray(s))
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    out = {"model": name, "batch": batch, "steps_per_call": steps,
           "samples_per_sec": round(batch * steps / dt, 1)}
    if attention is not None:
        out["attention"] = attention
    peak = observability.device_peak_flops()
    if peak:
        out["mfu"] = round(flops / dt / peak, 4)
    return out


def phase_probe(name: str, batch: int, steps: int = 8,
                iters: int = 3, attention: str = None) -> dict:
    """Step-time decomposition of the bare-step window (DESIGN.md §15).

    Times each window's phases separately — ``h2d`` (host batch onto the
    device, fetch-synced), ``compute`` (the jitted scan, fetch-synced),
    and on multi-device hosts ``collective`` (a grad-sized psum across
    all local devices — the sync the DP path would pay at this model's
    gradient size) — publishing every sample into the
    ``profile.phase.*_s`` histograms (the same names host_async's worker
    loop feeds) and returning one JSON row with per-phase seconds and
    fractions of the window. benchmarks/attribution.py renders either
    source into the same gap-to-peak report.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import engine, observability, telemetry

    if telemetry.get_registry() is None:
        telemetry.install(telemetry.MetricsRegistry())
    model, loss, x, y = build_family(name, batch, attention=attention)
    tx = optax.adamw(1e-3)
    grad_fn = engine.make_grad_fn(model, loss)
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    state = engine.create_train_state(model, jax.random.key(0),
                                      {"features": xd}, tx)

    @jax.jit
    def run(params, opt_state, x, y):
        def one(c, _):
            p, o = c
            (l, _), g = grad_fn(p, {"features": x, "labels": y}, None)
            up, o = tx.update(g, o, p)
            return (optax.apply_updates(p, up), o), l

        (p, o), ls = jax.lax.scan(one, (params, opt_state), None,
                                  length=steps)
        return p, o, jnp.sum(ls)

    devices = jax.devices()
    psum = None
    if len(devices) > 1:
        psum = jax.pmap(lambda t: jax.tree.map(
            lambda a: jax.lax.psum(a, "d"), t), axis_name="d")
        rep = jax.device_put_replicated(state.params, devices)
        jax.block_until_ready(psum(rep))  # compile outside the window
    flops = observability.count_flops(
        lambda p, b: grad_fn(p, b, None)[1], state.params,
        {"features": xd, "labels": yd}) * steps
    p, o, s = run(state.params, state.opt_state, xd, yd)
    float(np.asarray(s))  # compile + settle
    prof = {ph: telemetry.histogram(f"profile.phase.{ph}_s")
            for ph in ("h2d", "compute", "collective", "window")}
    phases = {ph: [] for ph in prof}
    for _ in range(iters):
        t_start = time.perf_counter()
        xi = jax.block_until_ready(jnp.asarray(x))
        yi = jax.block_until_ready(jnp.asarray(y))
        t1 = time.perf_counter()
        p, o, s = run(p, o, xi, yi)
        float(np.asarray(s))
        t2 = time.perf_counter()
        if psum is not None:
            rep = jax.block_until_ready(psum(rep))
            t3 = time.perf_counter()
            phases["collective"].append(t3 - t2)
            prof["collective"].record(t3 - t2)
        phases["h2d"].append(t1 - t_start)
        prof["h2d"].record(t1 - t_start)
        phases["compute"].append(t2 - t1)
        prof["compute"].record(t2 - t1)
        win = time.perf_counter() - t_start
        phases["window"].append(win)
        prof["window"].record(win)
    med = lambda v: sorted(v)[len(v) // 2] if v else None
    window = med(phases["window"])
    out = {"model": name, "batch": batch, "steps_per_call": steps,
           "window_s": round(window, 6),
           "samples_per_sec": round(batch * steps / window, 1)}
    if attention is not None:
        out["attention"] = attention
    for ph in ("h2d", "compute", "collective"):
        m = med(phases[ph])
        if m is not None:
            out[f"phase_{ph}_s"] = round(m, 6)
            out[f"phase_{ph}_frac"] = round(m / window, 4)
    peak = observability.device_peak_flops()
    if peak:
        out["mfu"] = round(flops / med(phases["compute"]) / peak, 4)
    return out


#: canonical per-family settings — the shapes each family's BASELINE.md
#: floor is defined at (resnet's MXU sweet spot is b128; gpt OOMs above
#: b8 at seq 2048). CLI --batch/--steps override.
CANONICAL = {"vit": dict(batch=64, steps=96),
             "resnet": dict(batch=128, steps=96),
             "bert": dict(batch=64, steps=96),
             "cnn": dict(batch=512, steps=96),
             "gpt": dict(batch=8, steps=24)}


def _is_oom(e: BaseException) -> bool:
    msg = str(e).upper()
    return ("RESOURCE_EXHAUSTED" in msg or "OUT OF MEMORY" in msg
            or "ALLOCATION" in msg and "FAILED" in msg)


def sweep_probe(name: str, batch: int, steps: int, accum_steps: int,
                remat: str, compile_only: bool = False,
                precision: str = None, attention: str = None) -> dict:
    """One (model, accum, remat, precision) cell of the sweep matrix.

    Reports samples/s (fetch-synced, like :func:`probe`), XLA's static
    peak-scratch bytes from ``memory_analysis`` (every backend — the
    CPU-testable remat signal), and live peak HBM from ``memory_stats``
    (TPU only). ``compile_only`` stops after compilation + the memory
    numbers — the largest-batch search uses it so each doubling costs one
    compile, not a timed run.

    ``precision`` stamps the model's mixed-precision field and mirrors the
    trainer step exactly: a loss-scaling policy gets the overflow-guarded
    optimizer and the step reads the live scale out of ``opt_state``; the
    reported MFU is measured against that policy's honest peak column
    (``mfu_dtype`` in the row).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import engine, observability
    from distkeras_tpu import precision as precision_lib

    if batch % accum_steps:
        raise ValueError(f"accum_steps={accum_steps} must divide "
                         f"batch={batch}")
    model, loss, x, y = build_family(name, batch, remat=remat,
                                     precision=precision,
                                     attention=attention)
    policy = precision_lib.get_policy(precision)
    tx = optax.adamw(1e-3)
    if policy is not None and policy.loss_scale != 1.0:
        tx = precision_lib.overflow_guard(tx, policy)
    if accum_steps > 1:
        grad_fn = engine.make_accum_grad_fn(model, loss, accum_steps,
                                            precision=precision)
    else:
        grad_fn = engine.make_grad_fn(model, loss, precision=precision)
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    state = engine.create_train_state(model, jax.random.key(0),
                                      {"features": xd}, tx)

    @jax.jit
    def run(params, opt_state, x, y):
        def one(c, _):
            p, o = c
            (l, _), g = grad_fn(p, {"features": x, "labels": y}, None,
                                loss_scale=precision_lib.current_scale(o))
            up, o = tx.update(g, o, p)
            return (optax.apply_updates(p, up), o), l

        (p, o), ls = jax.lax.scan(one, (params, opt_state), None,
                                  length=steps)
        return p, o, jnp.sum(ls)

    mfu_dtype = policy.mfu_dtype if policy is not None else "bf16"
    out = {"model": name, "batch": batch, "accum_steps": accum_steps,
           "remat": remat, "precision": precision,
           "mfu_dtype": mfu_dtype, "steps_per_call": steps}
    if attention is not None:
        out["attention"] = attention
    compiled = run.lower(state.params, state.opt_state, xd, yd).compile()
    mem = observability.compiled_memory_bytes(compiled)
    if mem:
        out["temp_bytes"] = mem["temp_bytes"]
    if compile_only:
        return out
    p, o, s = compiled(state.params, state.opt_state, xd, yd)
    float(np.asarray(s))  # settle (fetch = completion barrier)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        p, o, s = compiled(p, o, xd, yd)
        float(np.asarray(s))
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    out["samples_per_sec"] = round(batch * steps / dt, 1)
    peak = observability.device_peak_flops(dtype=mfu_dtype)
    if peak:
        flops = observability.count_flops(
            lambda pp, b: grad_fn(pp, b, None)[1], state.params,
            {"features": xd, "labels": yd}) * steps
        out["mfu"] = round(flops / dt / peak, 4)
    hbm = observability.hbm_stats()  # live allocator peak — TPU only
    if hbm:
        out.update({f"hbm_{k}": v for k, v in hbm.items()})
    return out


def largest_batch(name: str, steps: int, accum_steps: int, remat: str,
                  start: int, limit: int = 1 << 16) -> dict:
    """Doubling search for the largest batch a config compiles AND runs.

    Probes in-process, relying on XLA raising RESOURCE_EXHAUSTED cleanly
    (it does on TPU; a failed allocation doesn't poison the client).
    Meaningful on a real accelerator; on CPU the host allocator swaps long
    before it raises, so the search is capped at ``limit``.
    """
    best, b = None, start
    while b <= limit:
        try:
            sweep_probe(name, b, min(steps, 4), accum_steps, remat,
                        compile_only=False)
            best = b
            b *= 2
        except Exception as e:  # noqa: BLE001 — OOM probing is the point
            if _is_oom(e):
                break
            raise
    return {"model": name, "accum_steps": accum_steps, "remat": remat,
            "largest_batch": best, "search_limit": limit}


def overlap_probe(name: str, batch: int, steps: int,
                  bucket_bytes, precision: str = None) -> dict:
    """One bucket-size cell of the gradient-overlap sweep (--buckets).

    Times the sync data-parallel epoch step over ALL local devices:
    ``bucket_bytes=None`` is the GSPMD baseline (XLA's implicit grad
    all-reduce), an int is the explicit shard_map step whose grad psums
    are issued per size-targeted bucket (parallel/collectives.py) so the
    collectives overlap backward. The two trajectories are bitwise-equal
    (tests/test_overlap.py) — only the schedule differs, which is exactly
    what this probe measures.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import engine
    from distkeras_tpu import precision as precision_lib
    from distkeras_tpu.parallel import mesh as mesh_lib
    from distkeras_tpu.parallel import tensor

    mesh = mesh_lib.make_mesh()  # all local devices, pure data-parallel
    num_workers = mesh.shape[mesh_lib.WORKER_AXIS]
    if batch % num_workers:
        raise ValueError(f"batch={batch} must divide over the "
                         f"{num_workers} local devices")
    model, loss, x, y = build_family(name, batch, precision=precision)
    policy = precision_lib.get_policy(precision)
    tx = optax.adamw(1e-3)
    if policy is not None and policy.loss_scale != 1.0:
        tx = precision_lib.overflow_guard(tx, policy)
    epoch_fn, place_state, place_data = tensor.build_pjit_epoch_fn(
        model, loss, tx, mesh, precision=precision,
        bucket_bytes=bucket_bytes)
    xd = jnp.asarray(x)
    state = place_state(engine.create_train_state(
        model, jax.random.key(0), {"features": xd}, tx))
    data = place_data({
        "features": np.broadcast_to(x[None], (steps,) + x.shape),
        "labels": np.broadcast_to(y[None], (steps,) + y.shape)})

    state, ms = epoch_fn(state, data, 0)
    float(np.asarray(ms["loss"]).sum())  # compile + settle
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        state, ms = epoch_fn(state, data, 0)
        float(np.asarray(ms["loss"]).sum())
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    return {"model": name, "batch": batch, "steps_per_call": steps,
            "mode": "gspmd" if bucket_bytes is None else "bucketed",
            "bucket_bytes": bucket_bytes, "num_workers": num_workers,
            "precision": precision,
            "samples_per_sec": round(batch * steps / dt, 1)}


def joint_probe(name: str, batch: int, steps: int, bucket_bytes,
                comms_overlap: bool, precision: str = None,
                attention: str = None, comms_codec: str = "int8") -> dict:
    """One cell of the joint ``bucket_bytes x comms_overlap`` grid — the
    co-scheduling sweep ROADMAP item 1(c) calls for: do the in-step
    collective schedule (PR 6's gradient buckets) and the cross-step wire
    work (PR 3's overlapped commit/pull) COMPOSE, or do they fight for
    the same host/interconnect resources?

    The epoch leg is :func:`overlap_probe`'s sync-DP step at the given
    bucket size. The comms leg is the async runner's per-round wire work
    at this model's gradient size — an int8 encode + decode of every
    grad-shaped leaf (what host_async's comms thread does between
    windows). ``comms_overlap=False`` runs the legs back-to-back (the
    serialized schedule), ``True`` runs the comms leg in a thread while
    the epoch computes (PR 3's schedule). The row reports both legs'
    seconds plus ``composition`` = total / (epoch + comms): 1.0 means
    fully serialized, ~max(e,c)/(e+c) means fully hidden. On a CPU host
    both legs share the same cores, so composition ~1.0 is the honest
    expected result — the grid exists to run on a TPU host where the
    epoch leg is off-CPU (results/README.md provenance rule).
    """
    import threading

    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import comms, engine
    from distkeras_tpu import precision as precision_lib
    from distkeras_tpu.parallel import mesh as mesh_lib
    from distkeras_tpu.parallel import tensor

    mesh = mesh_lib.make_mesh()
    num_workers = mesh.shape[mesh_lib.WORKER_AXIS]
    if batch % num_workers:
        raise ValueError(f"batch={batch} must divide over the "
                         f"{num_workers} local devices")
    model, loss, x, y = build_family(name, batch, precision=precision,
                                     attention=attention)
    policy = precision_lib.get_policy(precision)
    tx = optax.adamw(1e-3)
    if policy is not None and policy.loss_scale != 1.0:
        tx = precision_lib.overflow_guard(tx, policy)
    epoch_fn, place_state, place_data = tensor.build_pjit_epoch_fn(
        model, loss, tx, mesh, precision=precision,
        bucket_bytes=bucket_bytes)
    xd = jnp.asarray(x)
    state = place_state(engine.create_train_state(
        model, jax.random.key(0), {"features": xd}, tx))
    data = place_data({
        "features": np.broadcast_to(x[None], (steps,) + x.shape),
        "labels": np.broadcast_to(y[None], (steps,) + y.shape)})

    codec = comms.get_codec(comms_codec)
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        jax.tree.map(np.asarray, jax.device_get(state.params)))]
    specs = [(l.shape, l.dtype) for l in leaves]

    def comms_leg():
        t0 = time.perf_counter()
        blobs = [codec.encode(l, kind="commit") for l in leaves]
        for b, (s, d) in zip(blobs, specs):
            codec.decode(bytes(b), s, d, kind="commit")
        return time.perf_counter() - t0

    state, ms = epoch_fn(state, data, 0)
    float(np.asarray(ms["loss"]).sum())  # compile + settle
    comms_leg()                          # warm the codec path too
    totals, epochs, comm_ts = [], [], []
    for _ in range(3):
        comms_s = [None]
        t0 = time.perf_counter()
        if comms_overlap:
            th = threading.Thread(
                target=lambda: comms_s.__setitem__(0, comms_leg()))
            th.start()
        state, ms = epoch_fn(state, data, 0)
        float(np.asarray(ms["loss"]).sum())
        t_epoch = time.perf_counter() - t0
        if comms_overlap:
            th.join()
        else:
            comms_s[0] = comms_leg()
        totals.append(time.perf_counter() - t0)
        epochs.append(t_epoch)
        comm_ts.append(comms_s[0])
    med = lambda v: sorted(v)[len(v) // 2]
    total, epoch_s, comms_t = med(totals), med(epochs), med(comm_ts)
    out = {"model": name, "batch": batch, "steps_per_call": steps,
           "mode": "gspmd" if bucket_bytes is None else "bucketed",
           "bucket_bytes": bucket_bytes, "comms_overlap": comms_overlap,
           "comms_codec": comms_codec, "num_workers": num_workers,
           "precision": precision,
           "epoch_s": round(epoch_s, 6), "comms_s": round(comms_t, 6),
           "total_s": round(total, 6),
           "composition": round(total / (epoch_s + comms_t), 4),
           "samples_per_sec": round(batch * steps / total, 1)}
    if attention is not None:
        out["attention"] = attention
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("which", nargs="?", default="all",
                    choices=list(CANONICAL) + ["all", "sweep"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None,
                    help="scanned steps per timed device call; keep the "
                         "call >=1s so the host dispatch is noise")
    ap.add_argument("--model", default="resnet", choices=list(CANONICAL),
                    help="sweep mode: which family to sweep")
    ap.add_argument("--accum", default="1,4",
                    help="sweep mode: comma-separated accum_steps values")
    ap.add_argument("--remat", default="none,blocks",
                    help="sweep mode: comma-separated remat policies")
    ap.add_argument("--precision", default="none",
                    help="sweep mode: comma-separated precision policies "
                         "(none|f32|bf16|int8|fp8-sim; 'none' = the "
                         "model's default compute dtype)")
    ap.add_argument("--buckets", default=None,
                    help="sweep mode: comma-separated grad-bucket byte "
                         "sizes ('none' = GSPMD baseline); replaces the "
                         "accum x remat matrix with the overlap sweep")
    ap.add_argument("--overlap", action="store_true",
                    help="with --buckets: run the joint bucket_bytes x "
                         "comms_overlap grid (ROADMAP item 1(c)) — each "
                         "bucket size timed with the async wire leg "
                         "serialized AND overlapped")
    ap.add_argument("--attention", default=None,
                    help="attention kernel axis (xla|flash, "
                         "comma-separated in sweep mode) for the "
                         "attention families (gpt/bert/vit)")
    ap.add_argument("--find-max-batch", action="store_true",
                    help="sweep mode: also run the doubling largest-batch "
                         "search per config (accelerator-backed runs)")
    ap.add_argument("--phases", action="store_true",
                    help="probe mode: decompose each window into "
                         "profile.phase.* (h2d / compute / collective) "
                         "instead of the single timed call")
    args = ap.parse_args()
    parse_axis = lambda s: [None if v.strip() in ("none", "") else v.strip()
                            for v in s.split(",")]
    if args.which == "sweep":
        cfg = dict(CANONICAL[args.model])
        if args.batch is not None:
            cfg["batch"] = args.batch
        if args.steps is not None:
            cfg["steps"] = args.steps
        precisions = parse_axis(args.precision)
        attentions = parse_axis(args.attention) if args.attention else [None]
        failed = False
        if args.buckets is not None:
            buckets = [None if b is None else int(b)
                       for b in parse_axis(args.buckets)]
            overlaps = [False, True] if args.overlap else [None]
            for prec in precisions:
                for bucket in buckets:
                    for over in overlaps:
                        try:
                            if over is None:
                                row = overlap_probe(
                                    args.model, cfg["batch"], cfg["steps"],
                                    bucket, precision=prec)
                            else:
                                row = joint_probe(
                                    args.model, cfg["batch"], cfg["steps"],
                                    bucket, comms_overlap=over,
                                    precision=prec,
                                    attention=attentions[0])
                            print(json.dumps(row), flush=True)
                        except Exception as e:
                            failed = True
                            print(json.dumps(
                                {"model": args.model,
                                 "bucket_bytes": bucket,
                                 "comms_overlap": over, "precision": prec,
                                 "error": f"{type(e).__name__}: {e}"}),
                                flush=True)
            sys.exit(1 if failed else 0)
        accums = [int(a) for a in args.accum.split(",")]
        remats = [r.strip() for r in args.remat.split(",")]
        for remat in remats:
            for accum in accums:
                for prec in precisions:
                    for attn in attentions:
                        try:
                            print(json.dumps(sweep_probe(
                                args.model, cfg["batch"], cfg["steps"],
                                accum, remat, precision=prec,
                                attention=attn)), flush=True)
                            if args.find_max_batch:
                                print(json.dumps(largest_batch(
                                    args.model, cfg["steps"], accum,
                                    remat, start=cfg["batch"])),
                                    flush=True)
                        except Exception as e:
                            failed = True
                            print(json.dumps(
                                {"model": args.model, "accum_steps": accum,
                                 "remat": remat, "precision": prec,
                                 "attention": attn,
                                 "error": f"{type(e).__name__}: {e}"}),
                                flush=True)
        sys.exit(1 if failed else 0)
    names = list(CANONICAL) if args.which == "all" else [args.which]
    for name in names:
        cfg = dict(CANONICAL[name])
        if args.batch is not None:
            cfg["batch"] = args.batch
        if args.steps is not None:
            cfg["steps"] = args.steps
        try:
            fn = phase_probe if args.phases else probe
            print(json.dumps(fn(name, cfg["batch"], steps=cfg["steps"],
                                attention=args.attention)))
        except Exception as e:
            print(json.dumps({"model": name,
                              "error": f"{type(e).__name__}: {e}"}))
            sys.exit(1)


if __name__ == "__main__":
    main()
