"""Job/Punchcard + distributed-backend helper tests."""

import json

import numpy as np

from distkeras_tpu.job_deployment import Job, Punchcard
from distkeras_tpu.models.mlp import MLP
from distkeras_tpu.parallel import distributed
from distkeras_tpu.data.dataset import synthetic_mnist


def _tiny_model():
    return MLP(features=(16,), num_classes=10)


def _tiny_data():
    return synthetic_mnist(n=256)


def test_job_runs_single_trainer():
    job = Job("smoke", "SingleTrainer", _tiny_model(), _tiny_data,
              batch_size=64, num_epoch=1)
    params = job.run()
    assert params is not None
    assert job.training_time > 0
    assert len(job.history) == 4  # 256/64 steps
    d = job.describe()
    assert d["job_name"] == "smoke" and d["trainer"] == "SingleTrainer"


def test_job_distributed_trainer():
    job = Job("adag", "ADAG", _tiny_model(), _tiny_data,
              batch_size=16, num_workers=4, communication_window=2)
    params = job.run()
    assert all(np.all(np.isfinite(x)) for x in
               [np.asarray(v) for v in _leaves(params)])


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def test_punchcard_json_roundtrip(tmp_path):
    spec = [{
        "job_name": "mnist-mlp",
        "trainer": "SingleTrainer",
        "model": "distkeras_tpu.models.mlp:mnist_mlp",
        "data": "distkeras_tpu.data.dataset:synthetic_mnist",
        "batch_size": 128,
        "num_epoch": 1,
    }]
    path = tmp_path / "punchcard.json"
    path.write_text(json.dumps(spec))
    card = Punchcard(path=str(path))
    results = card.run()
    assert len(results) == 1
    assert results[0]["training_time"] > 0


def test_process_info_and_host_address():
    info = distributed.process_info()
    assert info["process_count"] == 1
    assert info["global_device_count"] >= 8
    assert isinstance(info["host_address"], str) and info["host_address"]


def test_multihost_mesh_single_process():
    mesh = distributed.multihost_mesh(num_workers=4, model_parallelism=2)
    assert mesh.shape == {"workers": 4, "model": 2}


def test_initialize_noop_single_process():
    distributed.initialize()  # must not raise on one process


def test_punchcard_save_bundle_roundtrip(tmp_path):
    """save_bundle writes punchcard JSON + entry script + env note; a
    Punchcard reloaded from the bundle runs the queue (VERDICT r2 ask #5)."""
    import os

    card = Punchcard(jobs=[Job(
        "bundled-mnist", "SingleTrainer",
        model="distkeras_tpu.models.mlp:mnist_mlp",
        data="distkeras_tpu.data.dataset:synthetic_mnist",
        batch_size=128, num_epoch=1)])
    out = card.save_bundle(str(tmp_path / "bundle"))
    names = sorted(os.listdir(out))
    assert names == ["ENVIRONMENT.md", "punchcard.json", "run_punchcard.py"]

    reloaded = Punchcard(path=os.path.join(out, "punchcard.json"))
    # lossless spec round-trip (re-serializable: the bundle contract)
    assert [j.to_spec() for j in reloaded.jobs] == \
        [j.to_spec() for j in card.jobs]
    results = reloaded.run()
    assert len(results) == 1 and results[0]["training_time"] > 0
    # entry script is syntactically valid python
    compile(open(os.path.join(out, "run_punchcard.py")).read(),
            "run_punchcard.py", "exec")


def test_job_with_live_model_rejects_bundling():
    import pytest

    job = Job("live", "SingleTrainer", _tiny_model(), _tiny_data,
              batch_size=64)
    with pytest.raises(TypeError, match="dotted"):
        job.to_spec()


def test_local_launcher_submit_poll_results(tmp_path):
    """The submit-and-poll transport (reference job_deployment shape): a
    saved bundle is launched in a fresh interpreter, polled to completion,
    and its results fetched — SURVEY §2 item 17's missing verb pair."""
    import os
    import sys

    from distkeras_tpu.job_deployment import JobHandle, LocalLauncher

    card = Punchcard(jobs=[Job(
        "launched-mnist", "SingleTrainer",
        model="distkeras_tpu.models.mlp:mnist_mlp",
        data="distkeras_tpu.data.dataset:synthetic_mnist",
        batch_size=256, num_epoch=1)])
    bundle = card.save_bundle(str(tmp_path / "bundle"))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the child runs on the CPU
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    handle = LocalLauncher(env=env).submit(bundle)
    assert handle.poll() in ("RUNNING", "SUCCEEDED")
    status = handle.wait(timeout=240)
    # diagnostic: before terminal finalize the log is still at its .tmp path
    log = handle.log_path if os.path.exists(handle.log_path) \
        else handle._log_tmp
    assert status == "SUCCEEDED", open(log).read()[-2000:]
    results = handle.results()
    assert len(results) == 1
    assert results[0]["job_name"] == "launched-mnist"
    assert results[0]["training_time"] > 0
    # results also landed as a file inside the bundle (pollable artifact)
    assert os.path.exists(handle.results_path)


def test_local_launcher_failed_job_surfaces_log(tmp_path):
    import pytest

    from distkeras_tpu.job_deployment import LocalLauncher

    with pytest.raises(FileNotFoundError, match="bundle"):
        LocalLauncher().submit(str(tmp_path))  # not a bundle

    # a bundle whose entry dies must report FAILED and carry the log
    bundle = tmp_path / "bad"
    bundle.mkdir()
    (bundle / "run_punchcard.py").write_text(
        "import sys; print('dying', file=sys.stderr); sys.exit(3)\n")
    handle = LocalLauncher().submit(str(bundle))
    assert handle.wait(timeout=60) == "FAILED"
    with pytest.raises(RuntimeError, match="dying"):
        handle.results()


def test_local_launcher_refuses_when_parent_holds_the_tpu(tmp_path,
                                                          monkeypatch):
    """One process per chip: a launcher process that has initialised JAX
    on the TPU cannot hand the chip to its job, and says so at submit
    instead of spawning a child that fails or hangs at backend init."""
    import os

    import pytest

    from distkeras_tpu import job_deployment

    assert job_deployment._holds_tpu() is False  # this process is on CPU
    bundle = tmp_path / "b"
    bundle.mkdir()
    (bundle / "run_punchcard.py").write_text("print('[]')\n")
    monkeypatch.setattr(job_deployment, "_holds_tpu", lambda: True)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    with pytest.raises(RuntimeError, match="one process per chip"):
        job_deployment.LocalLauncher(env=env).submit(str(bundle))
    # a child pinned off the chip is fine
    handle = job_deployment.LocalLauncher(
        env=dict(env, JAX_PLATFORMS="cpu")).submit(str(bundle))
    assert handle.wait(timeout=60) == "SUCCEEDED"
