"""chip_smoke.py — the quickest proof the system still starts on the chip.

    python chip_smoke.py

One process, no flags, no platform set here, no child that imports JAX (a
chip belongs to one process). Drives the main paths once through the entry
points a user calls, at the full width of models the repo supports (depth
and data cut to size, weights random from a seed), on every chip the
process sees, and checks the results by the repo's own means:

  device      platform is "tpu"; device_kind is in the peaks table;
              memory_stats() reports a limit
  train       README quickstart: ADAG(resnet50_nf(), ...).train(ds) on uint8
              224x224x3 images, 1000 classes, a few steps over two device
              calls; loss finite and falling, params moved, HBM printed; one
              device call timed under both completion barriers
  strategies  every trainer family on an MLP (Single, Averaging, Ensemble,
              DOWNPOUR, ADAG, DynSGD, AEASGD, EAMSGD, Pjit, host_async)
  serve       GenerationEngine(gpt_small()) behind ServingServer /
              ServingClient.generate on loopback, concurrent prompts of
              different lengths, checked on LOGITS against a full forward
  kernels     each in-repo Pallas kernel compiled WITHOUT interpret at a
              shape a real model gives it, against its XLA reference
  cache       where compiled executables persist, entries before and after

One line per leg: PASS/FAIL, wall seconds, seconds spent compiling (or
loading from the persistent cache). Exit 0 and a last stdout line
``{"ok": true, "device": {...}}`` only if every leg passed; with no
accelerator it exits non-zero, naming the platform, before building any
model.
"""

from __future__ import annotations

import faulthandler
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

#: the caller's limit is 1200 s; a hang should die with tracebacks, not
#: hold the chip until someone else kills it
HANG_LIMIT_S = 1150

# -- sizes --------------------------------------------------------------------
# train: the README recipe at full width. window x rounds = 4 steps per
# device call, 2 calls; per chip that is 1024 images (154 MB uint8 on the
# host). LABELS holds the few class ids in use: an image's brightness says
# which, so the labels can be learned from the pixels within a few steps.
TRAIN = dict(batch=128, side=224, classes=1000, window=2, rounds=2, calls=2)
LABELS = (7, 250, 500, 900)
# serve: GPT-2-small as the repo ships it (12 layers, width 768, vocab
# 50304, max_len 1024, bf16). (prompt length, tokens asked): both prefill
# buckets and more requests than slots, so lanes are shared and reused.
SERVE = dict(num_slots=4, slot_ladder=(2, 4), prefill_buckets=(16, 64),
             ref_len=80)
REQUESTS = ((5, 8), (12, 12), (16, 6), (33, 10), (60, 8), (9, 9))
# Tolerance of the serve check, in logits. The engine decodes token by
# token against a max_len-wide KV cache in bf16; the reference is one bf16
# forward over prompt+answer. Same weights, same math, but every matmul
# rounds its bf16 output (2^-8 relative) at another shape, twelve layers
# deep, so the two paths' logits differ slightly on logits of O(1)
# (LayerNorm'd features into a lecun-normal head) and a near-tie flips the
# argmax: measured on a v5e, 52 of 53 tokens were the reference argmax and
# the other sat 0.006 below it. An emitted token must be within LOGIT_TOL
# (8x that) of its position's reference maximum; a wrong position, a
# stale cache row or a broken mask picks a token O(1) below it.
LOGIT_TOL = 0.05
# kernels: bf16 carries 8 bits; inputs, softmax weights and outputs each
# round at 2^-8 and the kernel and XLA round at different points. Largest
# elementwise difference allowed, as a share of the reference's largest
# magnitude:
BF16_TOL = 2.0 ** -5
# the int8 kernel and its XLA reference both accumulate exactly in int32
# and differ only in one final f32 multiply
INT8_RTOL = 1e-6


# -- compile accounting ------------------------------------------------------

class CompileClock:
    """Seconds inside ``compile_or_get_cached`` (an XLA compile, or a load
    from the persistent cache), compile requests, and persistent-cache
    hits, from jax.monitoring's own events."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.hits


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(path))


def rel_err(got, ref) -> float:
    """max |got - ref| as a share of max |ref| (f32 on the host)."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), "non-finite values"
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# -- legs --------------------------------------------------------------------

def leg_device():
    import jax

    from distkeras_tpu import observability
    from distkeras_tpu.data import native

    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = observability.device_peaks(devs[0])  # unknown kind raises
    limits = []
    for d in devs:
        stats = observability.hbm_stats(d)
        assert stats and stats.get("limit_bytes"), \
            f"{d}: memory_stats() gave no bytes_limit: {stats}"
        limits.append(stats["limit_bytes"])
    return (f"{len(devs)} x {kind!r}; peaks bf16 {peaks['bf16'] / 1e12:.0f} "
            f"TFLOP/s, HBM {peaks['hbm'] / 1e9:.0f} GB/s; HBM limit "
            f"{min(limits) / 2**30:.2f} GiB/chip; host gather: "
            f"{'native' if native.available() else 'numpy'}")


def make_images(n: int, side: int, classes: int, seed: int = 0):
    """uint8 images whose brightness encodes one of LABELS, plus noise."""
    rng = np.random.default_rng(seed)
    which = rng.integers(0, len(LABELS), n)
    level = (40 + 50 * which).astype(np.int16)[:, None, None, None]
    noise = rng.integers(-20, 21, (n, side, side, 3), dtype=np.int16)
    images = np.clip(level + noise, 0, 255).astype(np.uint8)
    labels = np.zeros((n, classes), np.float32)
    labels[np.arange(n), np.asarray(LABELS)[which]] = 1.0
    return images, labels


def leg_train():
    import jax

    from distkeras_tpu import ADAG, Dataset, observability
    from distkeras_tpu.models import resnet50_nf
    from distkeras_tpu.parallel import substrate

    c = TRAIN
    devs = jax.devices()
    steps = c["window"] * c["rounds"] * c["calls"]
    images, labels = make_images(len(devs) * c["batch"] * steps, c["side"],
                                 c["classes"])
    ds = Dataset({"features": images, "label": labels})
    t = ADAG(resnet50_nf(num_classes=c["classes"]), worker_optimizer="sgd",
             learning_rate=0.05, batch_size=c["batch"],
             communication_window=c["window"], staging_rounds=c["rounds"],
             metrics=())
    before = t._init_params(ds).params  # same seed as train() starts from
    params = t.train(ds, shuffle=True)

    assert t.mesh.size == len(devs), \
        f"ADAG mesh spans {t.mesh.size} of {len(devs)} devices"
    losses = [h["loss"] for h in t.get_history()]
    assert len(losses) == steps, (len(losses), steps)
    assert np.isfinite(losses).all(), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    moved = sum(float(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(before)))
    assert moved > 0.0, "parameters did not change"

    # One more device call of the compiled epoch function, timed under
    # both completion barriers (a scalar fetch, because
    # block_until_ready returned early on an earlier installation). The
    # third number is what a fetch still costs once block_until_ready has
    # returned: near zero if block_until_ready really waits.
    center, carries = t._init_carries(params)
    data, _ = next(iter(substrate.stage_epoch_chunks(
        ds.repartition(len(devs)), "features", "label", c["batch"],
        c["window"], t.mesh, chunk_rounds=c["rounds"])))

    def fetch(tree) -> float:
        return float(np.asarray(jax.tree.leaves(tree)[0]).ravel()[0])

    jax.block_until_ready(data)
    t0 = time.perf_counter()
    center, carries, ms = t._epoch_fn(center, carries, data, np.int32(0))
    jax.block_until_ready((center, ms))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    fetch(center)
    t_after = time.perf_counter() - t0
    t0 = time.perf_counter()
    center, carries, ms = t._epoch_fn(center, carries, data, np.int32(0))
    fetch(center)
    t_fetch = time.perf_counter() - t0

    # every chip took part: bytes live on each one right now
    stats = [observability.hbm_stats(d) for d in devs]
    in_use = [st["allocated_bytes"] for st in stats]
    peak = [st["peak_bytes"] for st in stats]
    assert all(b > 0 for b in in_use), f"idle device: bytes_in_use {in_use}"
    return (f"mesh {t.mesh.size}; {steps} steps loss {losses[0]:.3f} -> "
            f"{losses[-1]:.3f}; peak HBM/chip "
            f"{[round(p / 2**30, 2) for p in peak]} GiB, in use "
            f"{[round(b / 2**30, 2) for b in in_use]} GiB; one "
            f"{c['window'] * c['rounds']}-step call: block_until_ready "
            f"{t_block:.3f}s (fetch after it {t_after:.4f}s), scalar fetch "
            f"{t_fetch:.3f}s")


def leg_strategies():
    """One small job per trainer family, on every chip."""
    import jax

    from distkeras_tpu import (ADAG, AEASGD, AveragingTrainer, DOWNPOUR,
                               DynSGD, EAMSGD, EnsembleTrainer, PjitTrainer,
                               SingleTrainer, synthetic_mnist)
    from distkeras_tpu.models import MLP

    n_dev = len(jax.devices())
    ds = synthetic_mnist(n=2048)
    model = lambda: MLP(features=(128,))  # noqa: E731
    common = dict(worker_optimizer="sgd", learning_rate=0.05,
                  batch_size=64, num_epoch=2, metrics=())
    async_kw = dict(common, num_workers=n_dev, communication_window=4)
    runs = [
        ("single", SingleTrainer(model(), **common), True),
        ("averaging", AveragingTrainer(model(), **async_kw), False),
        ("ensemble", EnsembleTrainer(model(), **async_kw), False),
        ("downpour", DOWNPOUR(model(), **async_kw), True),
        ("adag", ADAG(model(), **async_kw), True),
        ("dynsgd", DynSGD(model(), **async_kw), True),
        ("aeasgd", AEASGD(model(), rho=1.0, **async_kw), True),
        ("eamsgd", EAMSGD(model(), rho=1.0, momentum=0.9, **async_kw), True),
        ("pjit", PjitTrainer(model(), **common), True),
        ("host_async", DOWNPOUR(model(), mode="host_async", **async_kw),
         True),
    ]
    notes = []
    for name, trainer, shuffle in runs:
        trainer.train(ds, shuffle=shuffle)
        history = trainer.get_history()
        assert history, f"{name}: empty history"
        losses = [h["loss"] for h in history]
        assert np.isfinite(losses).all(), f"{name}: non-finite loss"
        notes.append(f"{name} {losses[0]:.2f}->{losses[-1]:.2f}")
    placed = {str(d) for d in trainer._async_runner.worker_devices}
    assert len(placed) == n_dev, \
        f"host_async workers ran on {sorted(placed)}, not {n_dev} devices"
    return f"{'; '.join(notes)}; host_async devices {sorted(placed)}"


def leg_serve(clock: CompileClock):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.gpt import gpt_small
    from distkeras_tpu.serving import (GenerationEngine, ServingClient,
                                       ServingEngine, ServingServer)

    c = SERVE
    model = gpt_small()
    params = jax.jit(model.init)(jax.random.key(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    gen = GenerationEngine(model, params, num_slots=c["num_slots"],
                           slot_ladder=c["slot_ladder"],
                           prefill_buckets=c["prefill_buckets"])
    # the reference: the one-shot engine serving the SAME weights' full
    # forward, [ref_len] token ids -> [ref_len, vocab] logits
    ref = ServingEngine(model, params, input_shape=(c["ref_len"],),
                        input_dtype=np.int32, buckets=(1,))
    srv = ServingServer(ref, host="127.0.0.1", generator=gen)
    srv.start()
    try:
        assert gen.compiled_executables == {
            "prefill": tuple(c["prefill_buckets"]),
            "decode": tuple(c["slot_ladder"])}, gen.compiled_executables
        assert ref.compiled_buckets == (1,), ref.compiled_buckets
        compiles_warm = clock.compiles

        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, model.vocab_size, n).astype(np.int32)
                   for n, _ in REQUESTS]
        answers = [None] * len(REQUESTS)
        errors = []

        def ask(i):
            try:
                cli = ServingClient(f"127.0.0.1:{srv.port}", timeout=300.0)
                try:
                    answers[i] = cli.generate(
                        prompts[i], max_new_tokens=REQUESTS[i][1])
                finally:
                    cli.close()
            except Exception as e:  # re-raised on the main thread below
                errors.append((i, e))

        threads = [threading.Thread(target=ask, args=(i,), daemon=True)
                   for i in range(len(REQUESTS))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        assert not any(th.is_alive() for th in threads), "a request hung"
        if errors:
            raise errors[0][1]

        cli = ServingClient(f"127.0.0.1:{srv.port}", timeout=300.0)
        worst, exact, total = 0.0, 0, 0
        try:
            for (n, want), prompt, res in zip(REQUESTS, prompts, answers):
                tokens = np.asarray(res.tokens)
                assert tokens.size == want and res.reason == "length", \
                    f"asked {want} tokens, got {tokens.size} ({res.reason})"
                assert ((tokens >= 0) & (tokens < model.vocab_size)).all()
                ids = np.zeros((1, c["ref_len"]), np.int32)
                ids[0, :n] = prompt
                ids[0, n:n + want] = tokens
                logits = cli.infer(ids)[0]              # [ref_len, vocab]
                assert np.isfinite(logits).all(), "reference logits"
                at = logits[n - 1:n - 1 + want]         # predicts tokens[i]
                gaps = at.max(axis=-1) - at[np.arange(want), tokens]
                worst = max(worst, float(gaps.max()))
                exact += int((gaps == 0).sum())
                total += want
        finally:
            cli.close()
        assert worst <= LOGIT_TOL, \
            f"an emitted token sits {worst:.3f} below the reference max"
        assert clock.compiles == compiles_warm, \
            f"{clock.compiles - compiles_warm} compilations after warm-up"
        return (f"{len(REQUESTS)} concurrent requests, {total} tokens, "
                f"{exact} exactly the reference argmax, worst logit gap "
                f"{worst:.4f} (tol {LOGIT_TOL}); executables "
                f"{gen.compiled_executables}; 0 compiles after warm-up")
    finally:
        srv.stop()
        gen.shutdown()
        ref.shutdown()


def leg_kernels():
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops import attention as attn
    from distkeras_tpu.ops.pallas import flash_attention as fa
    from distkeras_tpu.ops.pallas import groupnorm as gn
    from distkeras_tpu.ops.pallas import int8_matmul as im

    rng = np.random.default_rng(0)
    notes = []

    def normal(shape, dtype):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def check(name, got, ref, tol):
        err = rel_err(got, ref)
        assert err <= tol, f"{name}: error {err:.2e} over tolerance {tol:.2e}"
        notes.append(f"{name} {err:.1e}")

    # training flash attention, forward and backward, at a GPT-small
    # shape (sequence 2048, 12 heads of 64, bf16); batch cut to 2
    # because the XLA reference materializes [b, h, T, T] f32 logits
    q, k, v, w = (normal((2, 2048, 12, 64), jnp.bfloat16) for _ in range(4))
    assert fa.fits(q.shape, q.dtype)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    def ref_fn(q, k, v):
        return fa.reference_attention(q, k, v, causal=True)

    def ours(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    ref_out = jax.jit(ref_fn)(q, k, v)
    check("flash fwd", jax.jit(ours)(q, k, v), ref_out, BF16_TOL)
    ref_g = jax.jit(jax.grad(loss(ref_fn), argnums=(0, 1, 2)))(q, k, v)
    our_g = jax.jit(jax.grad(loss(ours), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), our_g, ref_g):
        check(f"flash {name}", a, b, BF16_TOL)
    # the upstream kernel attention="flash" reaches today
    check("upstream flash fwd",
          jax.jit(attn.flash_attention_causal)(q, k, v), ref_out, BF16_TOL)
    del ref_out, ref_g, our_g

    # paged decode at gpt_small's page geometry: 12 heads of 64, 1024
    # positions in 16-token pages, a decode step's 2 query positions
    b, t, h, d, ps, pmax = 4, 2, 12, 64, 16, 64
    pages = b * pmax + 1
    qd = normal((b, t, h, d), jnp.bfloat16)
    kp, vp = (normal((pages, ps, h, d), jnp.bfloat16) for _ in range(2))
    table = jnp.asarray(rng.permutation(pages - 1)[:b * pmax]
                        .reshape(b, pmax), jnp.int32)
    index = jnp.asarray([5, 300, 777, ps * pmax - t], jnp.int32)
    assert fa.paged_fits(qd.shape, kp.shape, table.shape, qd.dtype)

    def paged_ref(qd, kp, vp, table, index):
        def gather(p):
            return p[table].reshape(b, pmax * ps, h, d)

        pos = index[:, None] + jnp.arange(t)[None, :]
        mask = (jnp.arange(pmax * ps)[None, None, None, :]
                <= pos[:, None, :, None])
        return attn.dot_product_attention(qd, gather(kp), gather(vp),
                                          mask=mask)

    check("paged decode",
          jax.jit(fa.paged_flash_attention)(qd, kp, vp, table, index),
          jax.jit(paged_ref)(qd, kp, vp, table, index), BF16_TOL)

    # the rectangular pool's decode attention at gpt2-medium's pool: 16
    # heads of 64 a 1024-wide line, 1024 positions a row, a decode step's
    # 2 query positions; lanes from the scratch row's length 0 to a row's
    # end, rows out of lane order; the reference gathers whole rows
    from distkeras_tpu.models.gpt import _attend_rows
    from distkeras_tpu.ops.cache_rows import gather_rows
    from distkeras_tpu.ops.pallas import decode_attention as da

    lanes, t, h, width, max_len = 8, 2, 16, 1024, 1024
    ql = normal((lanes, t, width), jnp.bfloat16)
    kl, vl = (normal((lanes + 1, max_len, width), jnp.bfloat16)
              for _ in range(2))
    rows = jnp.asarray([3, 8, 0, 7, 1, 6, 2, 5], jnp.int32)
    held = jnp.asarray([5, 0, 126, 127, 128, 700, max_len - 2,
                        max_len - 1], jnp.int32)
    assert da.dispatch(ql, kl, h)

    def rows_ref(ql, kl, vl, rows, held):
        pos = held[:, None] + jnp.arange(t)[None, :]
        return _attend_rows(ql, gather_rows(kl, rows), gather_rows(vl, rows),
                            pos, h)

    check("pool decode attention",
          jax.jit(lambda *a: da.pool_attention(*a, h))(ql, kl, vl, rows,
                                                       held),
          jax.jit(rows_ref)(ql, kl, vl, rows, held), BF16_TOL)
    del kl, vl

    # int8 matmul-dequant at GPT-2-small's MLP-in Dense: [1024 tokens, 768]
    # x [768, 3072], every dimension a multiple of the 256 block
    (qx, qw, sxw), = im.reference_rows(sizes=((1024, 768, 3072),))
    assert im.fits(qx.shape, qw.shape)
    got = np.asarray(im.int8_matmul_dequant(jnp.asarray(qx),
                                            jnp.asarray(qw), sxw))
    want = np.asarray(im.xla_int8_matmul_dequant(jnp.asarray(qx),
                                                 jnp.asarray(qw), sxw))
    np.testing.assert_allclose(got, want, rtol=INT8_RTOL)
    notes.append("int8 matmul ok")

    # fused GroupNorm at a ResNet-50 stage-2 activation: 28x28 positions,
    # 512 channels in 32 groups, forward and backward
    x = normal((8, 28 * 28, 512), jnp.bfloat16)
    gamma = normal((512,), jnp.float32)
    beta = normal((512,), jnp.float32)
    dy = normal(x.shape, jnp.bfloat16)
    y, stats = jax.jit(lambda *a: gn._pallas_fwd(*a, 32, 1e-6))(
        x, gamma, beta)
    ref_y, vjp = jax.vjp(lambda *a: gn._reference(*a, 32, 1e-6),
                         x, gamma, beta)
    check("groupnorm fwd", y, ref_y, BF16_TOL)
    grads = jax.jit(lambda *a: gn._pallas_bwd(*a, 32, 1e-6))(
        x, gamma, stats, dy)
    for name, a, b_ in zip(("dx", "dgamma", "dbeta"), grads, vjp(dy)):
        check(f"groupnorm {name}", a, b_, BF16_TOL)
    return "rel err " + ", ".join(notes)


# -- driver ------------------------------------------------------------------

def main() -> int:
    faulthandler.dump_traceback_later(HANG_LIMIT_S, exit=True)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU: jax.devices()[0].platform is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    from distkeras_tpu.utils import jax_compat

    clock = CompileClock()
    cache_dir = jax_compat.enable_compilation_cache()
    entries_before = cache_entries(cache_dir)
    default_before = cache_entries(jax_compat.DEFAULT_CACHE_DIR)

    def leg_cache():
        after = cache_entries(cache_dir)
        assert jax.config.jax_compilation_cache_dir == cache_dir
        exported = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if exported:
            assert cache_dir == exported, (cache_dir, exported)
            assert (cache_entries(jax_compat.DEFAULT_CACHE_DIR)
                    == default_before), "entries outside the exported dir"
        assert after > 0, f"no entries in {cache_dir}"
        return (f"{cache_dir}: {entries_before} entries before, {after} "
                f"after; {clock.hits} of {clock.compiles} compile requests "
                f"were cache hits")

    failed = []
    for name, leg in (("device", leg_device), ("train", leg_train),
                      ("strategies", leg_strategies),
                      ("serve", lambda: leg_serve(clock)),
                      ("kernels", leg_kernels), ("cache", leg_cache)):
        t0 = time.perf_counter()
        secs0, compiles0, hits0 = clock.snapshot()
        try:
            verdict, note = "PASS", leg()
        except Exception as e:
            traceback.print_exc()
            verdict, note = "FAIL", f"{type(e).__name__}: {e}"
            failed.append(name)
        secs, compiles, hits = clock.snapshot()
        print(f"[{verdict}] {name:<10} wall {time.perf_counter() - t0:6.1f}s"
              f"  compile {secs - secs0:6.1f}s ({compiles - compiles0} "
              f"requests, {hits - hits0} cache hits)  {note}", flush=True)
    if failed:
        print(f"chip_smoke: failed legs: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
