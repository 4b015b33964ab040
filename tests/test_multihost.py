"""REAL multi-process distributed backend test.

Everything else in the suite runs multi-chip on one process (the virtual
CPU mesh). This spawns TWO actual processes that join the jax
coordination service via ``parallel.distributed.initialize`` — the DCN
path the reference delegated to Spark cluster mode — build a global mesh
spanning both, and run a cross-process ``psum`` whose result proves the
collective crossed the process boundary.
"""

import os
import socket
import subprocess
import sys
import textwrap


def _run_two_procs(tmp_path, worker_src: str, timeout: int = 240) -> list:
    """Spawn two coordinated worker processes; return their outputs.

    Children are killed in a finally block so a hung collective cannot
    orphan processes holding the coordinator port for the rest of the run.
    """
    script = tmp_path / "worker.py"
    script.write_text(worker_src)
    with socket.socket() as s:  # pick a free port
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(pid), port, repo],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in (0, 1)]
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    return outs


WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    assert jax.process_count() == 2
    assert len(jax.devices()) == 8  # 4 local x 2 processes, globally visible
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.experimental import multihost_utils
    mesh = distributed.multihost_mesh(num_workers=8)
    local = np.full((4, 1), float(pid + 1), np.float32)
    arr = multihost_utils.host_local_array_to_global_array(
        local, mesh, P("workers"))
    out = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "workers"), mesh=mesh,
        in_specs=P("workers"), out_specs=P()))(arr)
    total = float(np.asarray(multihost_utils.process_allgather(
        out.sum(), tiled=True)).ravel()[0])
    # 4 shards of 1.0 (proc 0) + 4 shards of 2.0 (proc 1), summed again
    # over the replicated (1,1) result: 12
    assert total == 12.0, total
    print(f"OK proc={pid} psum={total}")
""")


def test_two_process_coordination_and_cross_process_psum(tmp_path):
    outs = _run_two_procs(tmp_path, WORKER)
    assert "OK proc=0 psum=12.0" in outs[0]
    assert "OK proc=1 psum=12.0" in outs[1]


TRAIN_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from distkeras_tpu import engine
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.ops import optimizers as opt_lib
    from distkeras_tpu.parallel import strategies, substrate
    from distkeras_tpu.parallel.distributed import multihost_mesh

    mesh = multihost_mesh(num_workers=8)          # 4 devices x 2 processes
    model = MLP(features=(16,), num_classes=10)
    tx = opt_lib.get("sgd", 0.05)
    strategy = strategies.get("adag", learning_rate=0.05)
    ds = synthetic_mnist(n=512)                   # identical on both procs
    state = engine.create_train_state(
        model, jax.random.key(0),
        {"features": jnp.zeros((8, 784), jnp.float32)}, tx)
    center, carries = substrate.init_center_and_carries(
        state.params, tx, strategy, mesh, 8)
    epoch_fn = substrate.build_epoch_fn(
        model, "categorical_crossentropy", tx, strategy, mesh,
        num_workers=8, window=2, metrics=())
    data, rounds = substrate.stage_epoch_data(
        ds.repartition(8), "features", "label", batch_size=8, window=2,
        mesh=mesh)
    center, carries, ms = epoch_fn(center, carries, data, np.int32(0))
    loss = float(np.asarray(multihost_utils.process_allgather(
        ms["loss"].mean(), tiled=True)).ravel()[0])
    checksum = float(np.asarray(multihost_utils.process_allgather(
        sum(jnp.sum(jnp.abs(l)) for l in jax.tree.leaves(center)),
        tiled=True)).ravel()[0])
    print(f"TRAINOK proc={pid} loss={loss:.6f} checksum={checksum:.6f}")
""")


def test_two_process_adag_epoch_matches_single_process(tmp_path):
    """One ADAG epoch (8 workers, psum center fold) across TWO processes
    equals the same epoch on one process's virtual 8-device mesh — the
    distributed communication backend really is process-transparent."""
    import re

    outs = _run_two_procs(tmp_path, TRAIN_WORKER)
    vals = {}
    for out in outs:
        m = re.search(r"TRAINOK proc=(\d) loss=([\d.]+) checksum=([\d.]+)",
                      out)
        assert m, out[-2000:]
        vals[m.group(1)] = (float(m.group(2)), float(m.group(3)))
    assert vals["0"] == vals["1"]  # both processes see the same result

    # single-process oracle on the in-process 8-device mesh
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu import engine
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.ops import optimizers as opt_lib
    from distkeras_tpu.parallel import mesh as mesh_lib, strategies, substrate

    mesh = mesh_lib.make_mesh(num_workers=8)
    model = MLP(features=(16,), num_classes=10)
    tx = opt_lib.get("sgd", 0.05)
    strategy = strategies.get("adag", learning_rate=0.05)
    ds = synthetic_mnist(n=512)
    state = engine.create_train_state(
        model, jax.random.key(0),
        {"features": jnp.zeros((8, 784), jnp.float32)}, tx)
    center, carries = substrate.init_center_and_carries(
        state.params, tx, strategy, mesh, 8)
    epoch_fn = substrate.build_epoch_fn(
        model, "categorical_crossentropy", tx, strategy, mesh,
        num_workers=8, window=2, metrics=())
    data, _ = substrate.stage_epoch_data(
        ds.repartition(8), "features", "label", batch_size=8, window=2,
        mesh=mesh)
    center, carries, ms = epoch_fn(center, carries, data, np.int32(0))
    loss_ref = float(np.asarray(ms["loss"]).mean())
    checksum_ref = float(sum(jnp.sum(jnp.abs(l))
                             for l in jax.tree.leaves(center)))
    loss_mh, checksum_mh = vals["0"]
    np.testing.assert_allclose(loss_mh, loss_ref, rtol=1e-5)
    np.testing.assert_allclose(checksum_mh, checksum_ref, rtol=1e-5)


FULL_TRAINER_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import ADAG
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel.distributed import multihost_mesh

    # the PUBLIC trainer API, unchanged, on a mesh spanning 2 processes
    t = ADAG(MLP(features=(16,)), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=8,
             communication_window=2, num_epoch=2,
             mesh=multihost_mesh(num_workers=8))
    t.train(synthetic_mnist(n=512))
    losses = [round(h["loss"], 6) for h in t.history]
    checksum = float(sum(np.abs(np.asarray(l)).sum()
                         for l in jax.tree.leaves(t.params)))
    print(f"FULLOK proc={pid} h0={losses[0]} hN={losses[-1]} "
          f"n={len(losses)} checksum={checksum:.6f}")
""")


HOST_SHARDED_WORKER = textwrap.dedent("""
    import os, sys, tempfile
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import ADAG
    from distkeras_tpu.data import Dataset, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel.distributed import multihost_mesh

    # each process writes and loads ONLY its half of the dataset: process 0
    # holds rows [0, 256) (mesh positions 0-3), process 1 rows [256, 512)
    # (positions 4-7) — disjoint file-backed halves, the pod-scale input
    # contract (no host ever sees the other half)
    full = synthetic_mnist(n=512)
    lo, hi = (0, 256) if pid == 0 else (256, 512)
    d = tempfile.mkdtemp()
    paths = {}
    for col in ("features", "label"):
        p = os.path.join(d, f"{col}.npy")
        np.save(p, np.asarray(full[col][lo:hi]))
        paths[col] = p
    ds_local = Dataset.from_files(paths)

    t = ADAG(MLP(features=(16,)), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=8,
             communication_window=2, num_epoch=2,
             mesh=multihost_mesh(num_workers=8),
             data_layout="host_sharded")
    t.train(ds_local)
    losses = [round(h["loss"], 6) for h in t.history]
    checksum = float(sum(np.abs(np.asarray(l)).sum()
                         for l in jax.tree.leaves(t.params)))
    print(f"SHARDOK proc={pid} h0={losses[0]} hN={losses[-1]} "
          f"n={len(losses)} checksum={checksum:.6f}")
""")


def test_two_process_host_sharded_disjoint_data_matches_oracle(tmp_path):
    """The host-sharded input contract (VERDICT r3 ask #1): each process
    loads a DISJOINT half of a file-backed dataset, stages only its own
    workers' shards (put_host_sharded — no host materializes the other
    half), and the training trajectory still matches the single-process
    full-dataset oracle exactly."""
    import re

    outs = _run_two_procs(tmp_path, HOST_SHARDED_WORKER, timeout=300)
    vals = {}
    for out in outs:
        m = re.search(r"SHARDOK proc=(\d) h0=([\d.]+) hN=([\d.]+) n=(\d+) "
                      r"checksum=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = tuple(float(x) for x in m.groups()[1:])
    assert vals["0"] == vals["1"]  # both processes converge on one result

    # single-process oracle: full dataset, default replicated layout
    import jax
    import numpy as np

    from distkeras_tpu import ADAG
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    t = ADAG(MLP(features=(16,)), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=8,
             communication_window=2, num_epoch=2, num_workers=8)
    t.train(synthetic_mnist(n=512))
    h0, hN, n, checksum = vals["0"]
    assert n == len(t.history)
    np.testing.assert_allclose(h0, t.history[0]["loss"], rtol=1e-4)
    np.testing.assert_allclose(hN, t.history[-1]["loss"], rtol=1e-4)
    ref = float(sum(np.abs(np.asarray(l)).sum()
                    for l in jax.tree.leaves(t.params)))
    np.testing.assert_allclose(checksum, ref, rtol=1e-5)


PJIT_SHARDED_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import Dataset, PjitTrainer
    from distkeras_tpu.data import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel.distributed import multihost_mesh

    # host-sharded GSPMD contract: global batch 32 over 8 worker positions
    # (4 per process); each process holds, per step, ITS positions' 16-row
    # sub-batch — i.e. the full dataset's rows [s*32+pid*16 : s*32+(pid+1)*16)
    full = synthetic_mnist(n=512)
    B, half = 32, 16
    steps = 512 // B
    rows = np.concatenate([np.arange(s * B + pid * half,
                                     s * B + (pid + 1) * half)
                           for s in range(steps)])
    ds_local = Dataset({c: np.asarray(full[c])[rows] for c in full.columns})

    t = PjitTrainer(MLP(features=(16,), dropout_rate=0.0),
                    worker_optimizer="sgd", learning_rate=0.1,
                    metrics=(), batch_size=B, num_epoch=2,
                    mesh=multihost_mesh(num_workers=8),
                    data_layout="host_sharded")
    t.train(ds_local)
    losses = [round(h["loss"], 6) for h in t.history]
    checksum = float(sum(np.abs(np.asarray(l)).sum()
                         for l in jax.tree.leaves(t.params)))
    print(f"PJITOK proc={pid} h0={losses[0]} hN={losses[-1]} "
          f"n={len(losses)} checksum={checksum:.6f}")
""")


def test_two_process_pjit_host_sharded_matches_oracle(tmp_path):
    """The GSPMD path's host-sharded input contract: two processes each
    hold only their worker positions' per-step sub-batches; the PjitTrainer
    trajectory matches the single-process full-dataset oracle."""
    import re

    outs = _run_two_procs(tmp_path, PJIT_SHARDED_WORKER, timeout=300)
    vals = {}
    for out in outs:
        m = re.search(r"PJITOK proc=(\d) h0=([\d.]+) hN=([\d.]+) n=(\d+) "
                      r"checksum=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = tuple(float(x) for x in m.groups()[1:])
    assert vals["0"] == vals["1"]

    import jax
    import numpy as np

    from distkeras_tpu import PjitTrainer
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    t = PjitTrainer(MLP(features=(16,), dropout_rate=0.0),
                    worker_optimizer="sgd", learning_rate=0.1,
                    metrics=(), batch_size=32, num_epoch=2, num_workers=8)
    t.train(synthetic_mnist(n=512))
    h0, hN, n, checksum = vals["0"]
    assert n == len(t.history)
    np.testing.assert_allclose(h0, t.history[0]["loss"], rtol=1e-4)
    np.testing.assert_allclose(hN, t.history[-1]["loss"], rtol=1e-4)
    ref = float(sum(np.abs(np.asarray(l)).sum()
                    for l in jax.tree.leaves(t.params)))
    np.testing.assert_allclose(checksum, ref, rtol=1e-5)


HOST_ASYNC_WORKER = textwrap.dedent("""
    import os, sys, tempfile
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    import jax.numpy as jnp
    from distkeras_tpu import ADAG
    from distkeras_tpu.data import Dataset, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.ops import losses as losses_lib

    # host_sharded x host_async: each process holds ONLY its 2 workers'
    # rows; its threads commit to process 0's LIVE center over the
    # parameter service — true cross-host asynchrony
    full = synthetic_mnist(n=2304)
    lo, hi = (0, 1024) if pid == 0 else (1024, 2048)
    ds_local = Dataset({c: np.asarray(full[c])[lo:hi]
                        for c in full.columns})
    heldout = Dataset({c: np.asarray(full[c])[2048:]
                       for c in full.columns})

    model = MLP(features=(32,))
    t = ADAG(model, worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=32,
             communication_window=2, num_epoch=6, num_workers=4,
             mode="host_async", data_layout="host_sharded")
    t.train(ds_local, shuffle=True)

    loss_fn = losses_lib.get("categorical_crossentropy")
    hx = jnp.asarray(heldout["features"]); hy = jnp.asarray(heldout["label"])
    final = float(loss_fn(model.apply({"params": t.params}, hx,
                                      train=False), hy))
    init = model.init(jax.random.key(t.seed), jnp.zeros((16, 784)),
                      train=False)["params"]
    init_l = float(loss_fn(model.apply({"params": init}, hx,
                                       train=False), hy))
    checksum = float(sum(np.abs(np.asarray(l)).sum()
                         for l in jax.tree.leaves(t.params)))
    stal = t.staleness_history
    print(f"ASYNCOK proc={pid} n={len(t.history)} updates={t.num_updates} "
          f"stal_n={len(stal)} stal_sum={sum(stal):.1f} "
          f"init={init_l:.6f} heldout={final:.6f} checksum={checksum:.6f}")
""")


def test_two_process_true_async_live_center(tmp_path):
    """VERDICT r4 ask #2: workers in TWO processes commit CONCURRENTLY to
    one live center (process 0's parameter service) with real server-clock
    staleness; history merges by commit clock identically on both
    processes; convergence is judged on the CENTER's held-out loss."""
    import re

    outs = _run_two_procs(tmp_path, HOST_ASYNC_WORKER, timeout=300)
    vals = {}
    for out in outs:
        m = re.search(r"ASYNCOK proc=(\d) n=(\d+) updates=(\d+) "
                      r"stal_n=(\d+) stal_sum=([\d.]+) init=([\d.]+) "
                      r"heldout=([\d.]+) checksum=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = tuple(float(x) for x in m.groups()[1:])
    # both processes hold the SAME merged result (history, clock, params)
    assert vals["0"] == vals["1"]
    n, updates, stal_n, stal_sum, init_l, heldout, _ = vals["0"]
    # 2 workers/process x 8 rounds/epoch x 6 epochs x 2 processes commits
    assert updates == 192 and stal_n == 192
    # per-step history: every window contributes window=2 steps
    assert n == 384
    # real concurrency: SOME commit must have seen another fold in flight
    # (192 interleaved commits from 4 threads in 2 processes)
    assert stal_sum > 0
    # the live-center run learns: below uniform-guess entropy (ln 10) and
    # clearly below the initial center's held-out loss
    assert heldout < 2.3 and heldout < init_l - 0.25


GLOBAL_SHARDS_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    pool_dir = os.environ["GS_POOL_DIR"]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import ADAG
    from distkeras_tpu.data import GlobalShards
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.parallel.distributed import multihost_mesh

    gs = GlobalShards({
        "features": [os.path.join(pool_dir, f"f{i}.npy") for i in range(8)],
        "label": [os.path.join(pool_dir, f"l{i}.npy") for i in range(8)],
    }, seed=5)
    # this host's shard sets: re-dealt between epochs, union = whole pool
    a = [gs.epoch_assignment(e) for e in (0, 1)]
    t = ADAG(MLP(features=(16,), dropout_rate=0.0), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=8,
             communication_window=2, num_epoch=2,
             mesh=multihost_mesh(num_workers=8),
             data_layout="host_sharded")
    t.train(gs)
    checksum = float(sum(np.abs(np.asarray(l)).sum()
                         for l in jax.tree.leaves(t.params)))
    print(f"GSOK proc={pid} e0={sorted(a[0][pid])} e1={sorted(a[1][pid])} "
          f"u0={sorted(a[0][0]+a[0][1])} u1={sorted(a[1][0]+a[1][1])} "
          f"n={len(t.history)} checksum={checksum:.6f}")
""")


def test_two_process_global_shards_mixes_across_hosts(tmp_path):
    """VERDICT r4 ask #5: under GlobalShards, host 0's epoch-1 row set
    differs from its epoch-0 set while each epoch's global multiset is the
    whole pool; the two-process trajectory equals the single-process
    oracle over the same (identically permuted) pool."""
    import re

    import numpy as np

    pool = _make_shard_pool(tmp_path, seed=7)
    try:
        outs = _run_two_procs(tmp_path, GLOBAL_SHARDS_WORKER, timeout=300)
    finally:
        del os.environ["GS_POOL_DIR"]
    vals = {}
    for out in outs:
        m = re.search(r"GSOK proc=(\d) e0=(\[[^\]]*\]) e1=(\[[^\]]*\]) "
                      r"u0=(\[[^\]]*\]) u1=(\[[^\]]*\]) n=(\d+) "
                      r"checksum=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = m.groups()[1:]
    full = str(list(range(8)))
    e0, e1, u0, u1, n, checksum = vals["0"]
    # host 0 was re-dealt between epochs; the global multiset is preserved
    assert e0 != e1
    assert u0 == full and u1 == full
    assert vals["0"][4:] == vals["1"][4:]  # same history len + params

    # single-process oracle: same pool object stages the full permuted
    # pool per epoch (P=1 assignment = the whole permutation)
    import jax

    from distkeras_tpu import ADAG
    from distkeras_tpu.data import GlobalShards
    from distkeras_tpu.models.mlp import MLP

    gs = GlobalShards({
        "features": [str(pool / f"f{i}.npy") for i in range(8)],
        "label": [str(pool / f"l{i}.npy") for i in range(8)]}, seed=5)
    t = ADAG(MLP(features=(16,), dropout_rate=0.0), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=8,
             communication_window=2, num_epoch=2, num_workers=8,
             data_layout="host_sharded")
    t.train(gs)
    ref = float(sum(np.abs(np.asarray(l)).sum()
                    for l in jax.tree.leaves(t.params)))
    assert int(n) == len(t.history)
    np.testing.assert_allclose(float(checksum), ref, rtol=1e-5)


PREDICT_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import Dataset, ModelPredictor
    from distkeras_tpu.data import synthetic_mnist
    from distkeras_tpu.evaluators import AccuracyEvaluator, LossEvaluator
    from distkeras_tpu.models.mlp import MLP

    # host-sharded inference: this process holds ONLY its half of the rows
    full = synthetic_mnist(n=512)
    lo, hi = (0, 256) if pid == 0 else (256, 512)
    ds_local = Dataset({c: np.asarray(full[c])[lo:hi]
                        for c in full.columns})
    model = MLP(features=(16,), dropout_rate=0.0)
    params = model.init(jax.random.key(0),
                        np.zeros((1, 784), np.float32),
                        train=False)["params"]
    scored = ModelPredictor(model, params, batch_size=64).predict(ds_local)
    pred = np.asarray(scored["prediction"])
    checksum = float(np.abs(pred).sum())
    acc_local = AccuracyEvaluator(label_col="label_index").evaluate(scored)
    acc_global = AccuracyEvaluator(label_col="label_index",
                                   across_processes=True).evaluate(scored)
    loss_global = LossEvaluator(across_processes=True).evaluate(scored)
    print(f"PREDOK proc={pid} checksum={checksum:.6f} "
          f"acc_local={acc_local:.6f} acc_global={acc_global:.6f} "
          f"loss_global={loss_global:.6f}")
""")


def test_two_process_host_sharded_inference_matches_oracle(tmp_path):
    """VERDICT r4 ask #7: two processes score DISJOINT halves; the merged
    prediction column equals the single-process scoring of the full
    dataset, and across_processes=True evaluators return the same global
    accuracy/loss on both processes — equal to the oracle's."""
    import re

    outs = _run_two_procs(tmp_path, PREDICT_WORKER, timeout=300)
    vals = {}
    for out in outs:
        m = re.search(r"PREDOK proc=(\d) checksum=([\d.]+) "
                      r"acc_local=([\d.]+) acc_global=([\d.]+) "
                      r"loss_global=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = tuple(float(x) for x in m.groups()[1:])

    # oracle: single process scores the FULL dataset with the same params
    import jax
    import numpy as np

    from distkeras_tpu import ModelPredictor
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.evaluators import AccuracyEvaluator, LossEvaluator
    from distkeras_tpu.models.mlp import MLP

    full = synthetic_mnist(n=512)
    model = MLP(features=(16,), dropout_rate=0.0)
    params = model.init(jax.random.key(0),
                        np.zeros((1, 784), np.float32),
                        train=False)["params"]
    scored = ModelPredictor(model, params, batch_size=64).predict(full)
    pred = np.asarray(scored["prediction"])
    # merge = position-ordered concat: per-half checksums must match
    np.testing.assert_allclose(vals["0"][0], np.abs(pred[:256]).sum(),
                               rtol=1e-5)
    np.testing.assert_allclose(vals["1"][0], np.abs(pred[256:]).sum(),
                               rtol=1e-5)
    acc_ref = AccuracyEvaluator(label_col="label_index").evaluate(scored)
    loss_ref = LossEvaluator().evaluate(scored)
    for pid in ("0", "1"):
        _, _, acc_global, loss_global = vals[pid]
        np.testing.assert_allclose(acc_global, acc_ref, atol=1e-6)
        np.testing.assert_allclose(loss_global, loss_ref, atol=1e-5)
    # the halves genuinely differ locally (so the aggregation is real)
    assert vals["0"][1] != vals["1"][1] or vals["0"][0] != vals["1"][0]


def _make_shard_pool(tmp_path, seed: int):
    """8 shard files x 64 rows under tmp_path/pool; exported to workers
    via GS_POOL_DIR. Returns the pool path (caller deletes the env var)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    pool = tmp_path / "pool"
    pool.mkdir()
    for i in range(8):
        np.save(pool / f"f{i}.npy",
                rng.standard_normal((64, 784)).astype(np.float32))
        np.save(pool / f"l{i}.npy",
                np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)])
    os.environ["GS_POOL_DIR"] = str(pool)
    return pool


GS_ASYNC_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    pool_dir = os.environ["GS_POOL_DIR"]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import ADAG
    from distkeras_tpu.data import GlobalShards
    from distkeras_tpu.models.mlp import MLP

    gs = GlobalShards({
        "features": [os.path.join(pool_dir, f"f{i}.npy") for i in range(8)],
        "label": [os.path.join(pool_dir, f"l{i}.npy") for i in range(8)],
    }, seed=9)
    a = [gs.epoch_assignment(e) for e in (0, 1)]
    t = ADAG(MLP(features=(32,), dropout_rate=0.0), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=16,
             communication_window=2, num_epoch=2, num_workers=4,
             mode="host_async", data_layout="host_sharded")
    t.train(gs)
    checksum = float(sum(np.abs(np.asarray(l)).sum()
                         for l in jax.tree.leaves(t.params)))
    redealt = int(set(a[0][pid]) != set(a[1][pid]))
    union_ok = int(sorted(a[0][0] + a[0][1]) == list(range(8)) and
                   sorted(a[1][0] + a[1][1]) == list(range(8)))
    print(f"GSASYNC proc={pid} updates={t.num_updates} "
          f"redealt={redealt} union={union_ok} checksum={checksum:.6f}")
""")


def test_two_process_global_shards_with_live_center(tmp_path):
    """GlobalShards x host_async x two processes: shard files re-deal to
    hosts per epoch WHILE worker threads commit to process 0's live
    center; both compositions' invariants hold at once."""
    import re

    _make_shard_pool(tmp_path, seed=11)
    try:
        outs = _run_two_procs(tmp_path, GS_ASYNC_WORKER, timeout=300)
    finally:
        del os.environ["GS_POOL_DIR"]
    vals = {}
    for out in outs:
        m = re.search(r"GSASYNC proc=(\d) updates=(\d+) redealt=(\d) "
                      r"union=(\d) checksum=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = tuple(float(x) for x in m.groups()[1:])
    # merged result identical on both processes (live-center contract)
    assert vals["0"] == vals["1"]
    updates, redealt, union_ok, _ = vals["0"]
    # 4 workers x 4 rounds/epoch x 2 epochs against ONE center
    assert updates == 32
    # host 0's shard set changed between epochs; pool preserved per epoch
    assert redealt == 1 and union_ok == 1


ASYNC_RESUME_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]; repo = sys.argv[3]
    phase = os.environ["AR_PHASE"]; ckdir = os.environ["AR_CKDIR"]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distkeras_tpu.parallel import distributed
    distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
    import numpy as np
    from distkeras_tpu import ADAG
    from distkeras_tpu.data import Dataset, synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    full = synthetic_mnist(n=1024)
    lo, hi = (0, 512) if pid == 0 else (512, 1024)
    ds_local = Dataset({c: np.asarray(full[c])[lo:hi]
                        for c in full.columns})
    t = ADAG(MLP(features=(32,), dropout_rate=0.0), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=16,
             communication_window=2, num_epoch=2, num_workers=4,
             mode="host_async", data_layout="host_sharded",
             checkpoint_dir=ckdir, checkpoint_folds=8)
    if phase == "stale":
        # stale non-empty dir + resume=False: process 0's private
        # checkpoint error must reach EVERY process (symmetric raise),
        # not leave the peers hanging in the service-address broadcast
        try:
            t.train(ds_local)
        except ValueError as e:
            assert ("resume=True" in str(e)) or ("see their logs" in str(e))
            print(f"RESUMEOK phase=stale proc={pid} updates=-1 h0=0.0")
            sys.exit(0)
        raise AssertionError("stale checkpoint dir was not rejected")
    t.train(ds_local, resume=(phase == "2"))
    print(f"RESUMEOK phase={phase} proc={pid} updates={t.num_updates} "
          f"h0={t.history[0]['loss']:.4f}")
""")


def test_two_process_host_async_resume(tmp_path):
    """Pod-scale async fault story: a completed two-process live-center run
    leaves snapshots on process 0; a second two-process run with
    resume=True restores the center, CONTINUES the commit clock, and
    starts from the trained state (first losses far below a fresh init)."""
    import os
    import re

    ckdir = str(tmp_path / "ck")
    os.environ["AR_CKDIR"] = ckdir

    def run_phase(phase):
        os.environ["AR_PHASE"] = phase
        try:
            outs = _run_two_procs(tmp_path, ASYNC_RESUME_WORKER,
                                  timeout=300)
        finally:
            del os.environ["AR_PHASE"]
        vals = {}
        for out in outs:
            m = re.search(r"RESUMEOK phase=(\w+) proc=(\d) "
                          r"updates=(-?\d+) h0=([\d.]+)", out)
            assert m, out[-2000:]
            vals[m.group(2)] = (int(m.group(3)), float(m.group(4)))
        assert vals["0"] == vals["1"]  # merged result identical
        return vals["0"]

    try:
        up1, h0_1 = run_phase("1")
        # 4 workers x 8 rounds/epoch x 2 epochs
        assert up1 == 64
        up2, h0_2 = run_phase("2")
        # stale dir + resume=False: BOTH processes raise cleanly (the
        # worker exits 0 only after catching the expected ValueError)
        run_phase("stale")
    finally:
        del os.environ["AR_CKDIR"]
    # the clock CONTINUED from the restored snapshot
    assert up2 == 128
    # phase 2 started from the TRAINED center, not a fresh init (~2.5)
    assert h0_2 < h0_1 - 0.3


def test_two_process_full_trainer_matches_single_process(tmp_path):
    """The PUBLIC ADAG trainer — staging, epochs, metric recording, final
    param fetch — runs unchanged on a two-process mesh and reproduces the
    single-process trajectory."""
    import re

    outs = _run_two_procs(tmp_path, FULL_TRAINER_WORKER, timeout=300)
    vals = {}
    for out in outs:
        m = re.search(r"FULLOK proc=(\d) h0=([\d.]+) hN=([\d.]+) n=(\d+) "
                      r"checksum=([\d.]+)", out)
        assert m, out[-2000:]
        vals[m.group(1)] = tuple(float(x) for x in m.groups()[1:])
    assert vals["0"] == vals["1"]

    # single-process oracle through the same public API
    import numpy as np

    from distkeras_tpu import ADAG
    from distkeras_tpu.data.dataset import synthetic_mnist
    from distkeras_tpu.models.mlp import MLP

    t = ADAG(MLP(features=(16,)), worker_optimizer="sgd",
             learning_rate=0.05, metrics=(), batch_size=8,
             communication_window=2, num_epoch=2, num_workers=8)
    t.train(synthetic_mnist(n=512))
    import jax

    h0, hN, n, checksum = vals["0"]
    assert n == len(t.history)
    np.testing.assert_allclose(h0, t.history[0]["loss"], rtol=1e-4)
    np.testing.assert_allclose(hN, t.history[-1]["loss"], rtol=1e-4)
    ref = float(sum(np.abs(np.asarray(l)).sum()
                    for l in jax.tree.leaves(t.params)))
    np.testing.assert_allclose(checksum, ref, rtol=1e-5)
