"""Staleness vs wall-clock trade-off benchmark — the async zoo's raison d'être.

BASELINE.md names TWO halves of the primary metric: samples/sec/chip (the
train cell of BENCHMARK.json) and **"async staleness vs wall-clock"** — the
curve that justifies choosing a communication window and an async mode at all.
This harness serves the second half (VERDICT r4 ask #1): it sweeps

    strategy x communication_window x num_workers x {sync, host_async}

and reports, per point,

- the **staleness distribution** actually experienced (mean/p95/max over
  every commit: deterministic rotation positions in sync mode, real
  server-clock gaps in host_async mode — same units, commits folded between
  a worker's pull and its own fold),
- the **held-out-loss vs wall-clock curve** (evaluated at epoch
  boundaries, eval time excluded from the wall),
- **time-to-target**: first epoch boundary whose held-out loss <= target,
- **loss-at-budget**: held-out loss at the last boundary within the budget.

Reference parity note: dist-keras could only ever observe this trade-off as
an accident of TCP timing; here both the deterministic emulation and the
live-center mode measure it on purpose (SURVEY.md §5 race/staleness
testing). Run ``python -m distkeras_tpu.benchmarks.staleness_tradeoff``;
it writes one JSON document (``--out``); none is committed.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Any, Optional, Sequence

import jax
import numpy as np

from distkeras_tpu import engine
from distkeras_tpu.data.dataset import Dataset, synthetic_mnist
from distkeras_tpu.ops import losses as losses_lib
from distkeras_tpu.ops import optimizers as opt_lib
from distkeras_tpu.parallel import mesh as mesh_lib
from distkeras_tpu.parallel import strategies as strategies_lib
from distkeras_tpu.parallel import substrate
from distkeras_tpu.utils.fetch import device_get_batched

MODES = ("sync", "host_async")


def _strategy_for(name: str, learning_rate: float, rho: float,
                  momentum: float):
    kw = {}
    if name in ("aeasgd", "eamsgd"):
        kw["rho"] = rho
    if name == "eamsgd":
        kw["momentum"] = momentum
    return strategies_lib.get(name, learning_rate=learning_rate, **kw)


def _fetch_sync(tree) -> float:
    """Completion barrier via an actual device->host fetch of one scalar
    (on this installation it agrees with ``block_until_ready``)."""
    return float(np.asarray(jax.tree.leaves(tree)[0]).ravel()[0])


def _make_eval_fn(model, loss):
    loss_fn = losses_lib.get(loss)

    def eval_loss(params, feats, labels):
        logits = model.apply({"params": params}, feats, train=False)
        return loss_fn(logits, labels)

    return jax.jit(eval_loss)


def _sync_mesh(num_workers: int):
    """Largest worker-axis size <= device count that divides num_workers;
    the surplus workers stack as parallelism factor (substrate guarantees
    K workers on D devices == K workers on K devices)."""
    d = len(jax.devices())
    mesh_workers = min(num_workers, d)
    while num_workers % mesh_workers:
        mesh_workers -= 1
    return mesh_lib.make_mesh(num_workers=mesh_workers)


def run_point(*, strategy: str, window: int, num_workers: int, mode: str,
              model, train_ds: Dataset, heldout: Dataset,
              loss: str = "categorical_crossentropy",
              learning_rate: float = 0.05, batch_size: int = 32,
              epochs: int = 8, seed: int = 0,
              rho: float = 5.0, momentum: float = 0.9,
              features_col: str = "features",
              label_col: str = "label") -> dict:
    """One sweep point: train ``epochs`` passes, measure the wall per epoch
    (compile paid before timing; eval excluded), collect every commit's
    staleness, and evaluate held-out loss at each epoch boundary."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    tx = opt_lib.get("sgd", learning_rate)
    strat = _strategy_for(strategy, learning_rate, rho, momentum)
    eval_fn = _make_eval_fn(model, loss)
    hx = jax.device_put(np.asarray(heldout[features_col]))
    hy = jax.device_put(np.asarray(heldout[label_col]))
    sample = {"features": np.asarray(
        train_ds[features_col][:min(batch_size, len(train_ds))])}
    state = engine.create_train_state(model, jax.random.key(seed), sample, tx)

    staleness: list[float] = []
    curve: list[dict] = []
    wall = 0.0
    n_commits = 0

    if mode == "sync":
        mesh = _sync_mesh(num_workers)
        center, carries = substrate.init_center_and_carries(
            state.params, tx, strat, mesh, num_workers)
        epoch_fn = substrate.build_epoch_fn(
            model, loss, tx, strat, mesh, num_workers, window, metrics=(),
            dropout_seed=seed)
        data, rounds = substrate.stage_epoch_data(
            train_ds.repartition(num_workers), features_col, label_col,
            batch_size, window, mesh)
        # pay compilation on throwaway DEEP copies: epoch_fn donates its
        # state args, and device_put aliases the source buffer on devices
        # where the data already lives, so a second init_center_and_carries
        # would share shards with the real center (donating it would delete
        # them); jnp.copy forces fresh buffers
        import jax.numpy as jnp

        wc = jax.tree.map(jnp.copy, center)
        wca = jax.tree.map(jnp.copy, carries)
        wc, wca, _ = epoch_fn(wc, wca, data, np.int32(0))
        _fetch_sync(wc)
        _fetch_sync(eval_fn(center, hx, hy))
        round_offset = 0
        for _ in range(epochs):
            t0 = time.perf_counter()
            center, carries, ms = epoch_fn(center, carries, data,
                                           np.int32(round_offset))
            _fetch_sync(center)
            wall += time.perf_counter() - t0
            round_offset += rounds
            host_ms = device_get_batched(ms)
            staleness.extend(
                float(s) for s in np.asarray(host_ms["staleness"]).ravel())
            n_commits += rounds * num_workers
            curve.append({"wall_s": wall,
                          "heldout_loss": float(eval_fn(center, hx, hy))})
        samples = epochs * rounds * num_workers * window * batch_size
    else:
        from distkeras_tpu.parallel import host_async

        runner = host_async.HostAsyncRunner(
            model, loss, tx, strat, window, metrics=(), seed=seed,
            devices=jax.devices())
        shards = host_async.stage_worker_shards(
            train_ds.repartition(num_workers), features_col, label_col,
            batch_size, window)
        rounds = len(shards[0])
        # pay the shared window_fn compile before timing
        wcarry = strat.init_carry(state.params, tx)
        out = runner.window_fn(wcarry, state.params, shards[0][0],
                               np.int32(0))
        jax.block_until_ready(out[1])
        _fetch_sync(eval_fn(state.params, hx, hy))
        params, clock = state.params, 0
        for _ in range(epochs):
            t0 = time.perf_counter()
            params, _hist, stal, clock = runner.run(params, [shards],
                                                    start_clock=clock)
            wall += time.perf_counter() - t0
            staleness.extend(stal)
            n_commits += len(stal)
            curve.append({"wall_s": wall,
                          "heldout_loss": float(eval_fn(params, hx, hy))})
        samples = epochs * rounds * num_workers * window * batch_size

    stal_arr = np.asarray(staleness, np.float64) if staleness else \
        np.zeros((1,))
    return {
        "strategy": strategy, "window": window, "num_workers": num_workers,
        "mode": mode, "epochs": epochs, "batch_size": batch_size,
        "rounds_per_epoch": rounds, "commits": n_commits,
        "staleness_mean": round(float(stal_arr.mean()), 4),
        "staleness_p95": round(float(np.percentile(stal_arr, 95)), 4),
        "staleness_max": round(float(stal_arr.max()), 4),
        "total_wall_s": round(wall, 4),
        "samples_per_sec": round(samples / wall, 2) if wall > 0 else None,
        "final_heldout_loss": round(curve[-1]["heldout_loss"], 6),
        "curve": [{"wall_s": round(c["wall_s"], 4),
                   "heldout_loss": round(c["heldout_loss"], 6)}
                  for c in curve],
    }


def derive(points: Sequence[dict], target_loss: Optional[float] = None,
           wall_budget: Optional[float] = None) -> dict:
    """Attach the two headline scalars to every point.

    ``target_loss`` defaults to 1.05x the best final held-out loss in the
    sweep (so at least one point reaches it); ``wall_budget`` defaults to
    the largest FIRST epoch-boundary wall across points (so every point has
    at least one measurement inside the budget — fast points report a late
    boundary, slow points their first).
    """
    if target_loss is None:
        target_loss = 1.05 * min(p["final_heldout_loss"] for p in points)
    if wall_budget is None:
        wall_budget = max(p["curve"][0]["wall_s"] for p in points)
    for p in points:
        p["time_to_target_s"] = next(
            (c["wall_s"] for c in p["curve"]
             if c["heldout_loss"] <= target_loss), None)
        within = [c for c in p["curve"] if c["wall_s"] <= wall_budget]
        p["loss_at_budget"] = within[-1]["heldout_loss"] if within else None
    return {"target_loss": round(float(target_loss), 6),
            "wall_budget_s": round(float(wall_budget), 4),
            "points": list(points)}


def sweep(*, strategies: Sequence[str], windows: Sequence[int],
          workers: Sequence[int], modes: Sequence[str] = MODES,
          n_train: int = 4096, n_heldout: int = 1024,
          model=None, batch_size: int = 32, learning_rate: float = 0.05,
          epochs: int = 8, seed: int = 0,
          target_loss: Optional[float] = None,
          wall_budget: Optional[float] = None,
          verbose: bool = False) -> dict:
    """The full grid. One model instance and one train/held-out split are
    shared by every point, so differences are attributable to the sweep
    axes alone."""
    if model is None:
        from distkeras_tpu.models.mlp import MLP

        model = MLP(features=(64,), num_classes=10)
    full = synthetic_mnist(n=n_train + n_heldout, seed=seed)
    cols = {c: np.asarray(full[c]) for c in full.columns}
    train_ds = Dataset({c: v[:n_train] for c, v in cols.items()})
    heldout = Dataset({c: v[n_train:] for c, v in cols.items()})
    points = []
    for mode in modes:
        for s in strategies:
            for k in workers:
                for w in windows:
                    p = run_point(strategy=s, window=w, num_workers=k,
                                  mode=mode, model=model, train_ds=train_ds,
                                  heldout=heldout, batch_size=batch_size,
                                  learning_rate=learning_rate, epochs=epochs,
                                  seed=seed)
                    if verbose:
                        print(f"# {mode:10s} {s:9s} K={k} w={w:3d}: "
                              f"stal {p['staleness_mean']:.2f} "
                              f"p95 {p['staleness_p95']:.1f}  "
                              f"final {p['final_heldout_loss']:.4f}  "
                              f"wall {p['total_wall_s']:.2f}s")
                    points.append(p)
    out = derive(points, target_loss, wall_budget)
    out["protocol"] = {
        "n_train": n_train, "n_heldout": n_heldout,
        "batch_size": batch_size, "learning_rate": learning_rate,
        "epochs": epochs, "seed": seed,
        "platform": jax.devices()[0].platform,
        "device_count": len(jax.devices()),
        "notes": "wall excludes compilation (warmup call) and held-out "
                 "evaluation; staleness is per-commit (rotation position "
                 "in sync mode, server-clock gap in host_async mode)"}
    return out


def main(argv: Optional[Sequence[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--strategies", default="downpour,adag,aeasgd,eamsgd,"
                    "dynsgd")
    ap.add_argument("--windows", default="1,2,4,8,16,32")
    ap.add_argument("--workers", default="4,8")
    ap.add_argument("--modes", default="sync,host_async")
    ap.add_argument("--n-train", type=int, default=8192)
    ap.add_argument("--n-heldout", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--learning-rate", type=float, default=0.05)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--target-loss", type=float, default=None)
    ap.add_argument("--wall-budget", type=float, default=None)
    ap.add_argument("--out", default="staleness_tradeoff.json")
    args = ap.parse_args(argv)
    result = sweep(
        strategies=[s for s in args.strategies.split(",") if s],
        windows=[int(w) for w in args.windows.split(",") if w],
        workers=[int(k) for k in args.workers.split(",") if k],
        modes=[m for m in args.modes.split(",") if m],
        n_train=args.n_train, n_heldout=args.n_heldout,
        batch_size=args.batch_size, learning_rate=args.learning_rate,
        epochs=args.epochs, seed=args.seed, target_loss=args.target_loss,
        wall_budget=args.wall_budget, verbose=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"# wrote {args.out} ({len(result['points'])} points)")


if __name__ == "__main__":
    main()
