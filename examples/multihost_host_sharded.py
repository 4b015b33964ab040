"""Multi-host training with host-sharded input — the pod-scale pattern.

The reference ran one Spark driver + N executors, each executor reading only
its partitions (``distkeras/trainers.py`` repartition + mapPartitions —
unverified, mount empty). The TPU-native equivalent: N processes join the
jax coordination service, build one global mesh, and each process's dataset
holds ONLY its own workers' rows (``data_layout="host_sharded"`` — see
DESIGN.md §3). The public trainer API is unchanged; the trajectory equals a
single-process run over the concatenated data.

This demo self-spawns TWO coordinated processes on a virtual CPU mesh so it
runs anywhere (no pod needed); on a real pod, delete the spawning block —
the launcher starts one copy of ``worker()`` per host and
``distributed.initialize()`` self-detects the cluster.

Run:  python examples/multihost_host_sharded.py
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
    import jax
    import numpy as np

    from distkeras_tpu import ADAG
    from distkeras_tpu.data import Dataset, synthetic_mnist
    from distkeras_tpu.models import MLP

    mesh = distributed.multihost_mesh(num_workers=8)
    # This process's HALF of the data — in real use, read only the shard
    # files this host owns (Dataset.from_files + the streaming shuffle keep
    # it O(chunk) in host RAM). Rows must align with the process's worker
    # positions: process 0 owns mesh positions 0-3 -> the first half.
    full = synthetic_mnist(n=4096)
    lo, hi = (0, 2048) if process_id == 0 else (2048, 4096)
    ds_local = Dataset({c: np.asarray(full[c][lo:hi]) for c in full.columns})

    t = ADAG(MLP(features=(64,)), worker_optimizer="sgd", learning_rate=0.05,
             metrics=(), batch_size=16, communication_window=2, num_epoch=3,
             mesh=mesh, data_layout="host_sharded")
    t.train(ds_local)
    print(f"[proc {process_id}] {len(t.history)} steps, "
          f"loss {t.history[0]['loss']:.4f} -> {t.history[-1]['loss']:.4f}")


def main() -> int:
    if len(sys.argv) > 1:  # child invocation: ["--worker", pid, coordinator]
        worker(int(sys.argv[2]), sys.argv[3])
        return 0

    # parent: spawn two coordinated processes on a 4-device CPU mesh each
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
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
