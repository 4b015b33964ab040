"""TRUE asynchronous training across processes — the live-center pattern.

The reference's defining deployment: workers on SEPARATE machines training
against a live parameter server on the driver, each at its own pace
(``distkeras/parameter_servers.py`` socket PS — unverified, mount empty).
The TPU-native equivalent (round 5): N processes join the coordination
service, process 0's device-resident center is fronted by a socket
parameter service (``parallel/remote_ps.py``), and every process's worker
threads pull/commit against it concurrently — staleness is real cross-host
server-clock distance, and the merged history is identical on every
process. ``data_layout="host_sharded"`` composes: each process's dataset
holds only its own workers' rows.

This demo self-spawns TWO coordinated processes on a virtual CPU mesh so
it runs anywhere; on a real pod, delete the spawning block — the launcher
starts one copy of ``worker()`` per host and ``distributed.initialize()``
self-detects the cluster.

Run:  python examples/true_async_multihost.py
"""

import os
import socket
import subprocess
import sys

try:
    import distkeras_tpu  # noqa: F401  (pip-installed)
except ImportError:  # running from a source checkout: use the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def worker(process_id: int, coordinator: str) -> None:
    """What each host runs. On a real pod this whole function is your
    driver script and initialize() needs no arguments."""
    from distkeras_tpu.parallel import distributed

    distributed.initialize(coordinator_address=coordinator,
                           num_processes=2, process_id=process_id)
    import numpy as np

    from distkeras_tpu import ADAG
    from distkeras_tpu.data import Dataset, synthetic_mnist
    from distkeras_tpu.models import MLP

    # This process's HALF of the data (host-sharded contract). For
    # per-epoch cross-host re-dealing of shard FILES, pass a
    # data.GlobalShards pool instead of a Dataset.
    full = synthetic_mnist(n=4096)
    lo, hi = (0, 2048) if process_id == 0 else (2048, 4096)
    ds_local = Dataset({c: np.asarray(full[c][lo:hi]) for c in full.columns})

    # num_workers is GLOBAL: 4 worker threads split 2+2 over the two
    # processes, all committing to process 0's live center. No mesh —
    # asynchrony is thread scheduling, not a collective schedule.
    t = ADAG(MLP(features=(64,)), worker_optimizer="sgd", learning_rate=0.05,
             metrics=(), batch_size=16, communication_window=2, num_epoch=3,
             num_workers=4, mode="host_async", data_layout="host_sharded")
    t.train(ds_local, shuffle=True)
    stal = t.staleness_history
    print(f"[proc {process_id}] {t.num_updates} commits to the live center, "
          f"staleness mean {np.mean(stal):.2f} max {max(stal):.0f}, "
          f"loss {t.history[0]['loss']:.4f} -> {t.history[-1]['loss']:.4f}")


def main() -> int:
    if len(sys.argv) > 1:  # child invocation: ["--worker", pid, coordinator]
        worker(int(sys.argv[2]), sys.argv[3])
        return 0

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", str(pid),
         coordinator], env=env) for pid in (0, 1)]
    try:
        rcs = [p.wait(timeout=600) for p in procs]
    finally:
        for p in procs:  # a hung/dead worker must not orphan its sibling
            if p.poll() is None:
                p.kill()
    return 1 if any(rc != 0 for rc in rcs) else 0


if __name__ == "__main__":
    sys.exit(main())
