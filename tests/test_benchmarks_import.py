"""Import-smoke every CLI/benchmark module on CPU so tools can't rot
silently (a bad import would otherwise only surface on the TPU host)."""

import glob
import importlib
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPTS = sorted(glob.glob(os.path.join(REPO, "benchmarks", "*.py")))
PKG_MODULES = sorted(
    "distkeras_tpu.benchmarks." + os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(REPO, "distkeras_tpu", "benchmarks",
                                    "*.py"))
    if os.path.basename(p) != "__init__.py")


def test_discovery_found_the_tools():
    # the floor protects against the glob silently matching nothing
    assert len(SCRIPTS) >= 21, SCRIPTS
    assert "distkeras_tpu.benchmarks.run_config" in PKG_MODULES
    # the serving load generator (ISSUE 2) must be under the smoke glob
    assert any(os.path.basename(p) == "serving_load.py" for p in SCRIPTS)
    # the comms benchmark (ISSUE 3) too
    assert any(os.path.basename(p) == "comms_bench.py" for p in SCRIPTS)
    # the live health-plane probe (ISSUE 4) too
    assert any(os.path.basename(p) == "health_probe.py" for p in SCRIPTS)
    # the memory-for-compute sweep (ISSUE 5) rides step_probe
    assert any(os.path.basename(p) == "step_probe.py" for p in SCRIPTS)
    # the int8-kernel ablation gate (ISSUE 6) too
    assert any(os.path.basename(p) == "int8_matmul_ablate.py"
               for p in SCRIPTS)
    # the elastic-fleet churn probe (ISSUE 8) too
    assert any(os.path.basename(p) == "elastic_probe.py" for p in SCRIPTS)
    # the generative decode benchmark (ISSUE 9) too
    assert any(os.path.basename(p) == "decode_bench.py" for p in SCRIPTS)
    # the step-time attribution renderer (ISSUE 10) too
    assert any(os.path.basename(p) == "attribution.py" for p in SCRIPTS)
    # the perf-regression sentinel (ISSUE 11) too
    assert any(os.path.basename(p) == "regression_gate.py"
               for p in SCRIPTS)
    # the coordinator-failover probe (ISSUE 12) too
    assert any(os.path.basename(p) == "failover_probe.py"
               for p in SCRIPTS)
    # the live-rollout probe (ISSUE 13) too
    assert any(os.path.basename(p) == "rollout_probe.py"
               for p in SCRIPTS)
    # the paged-KV memory probe (ISSUE 14) too
    assert any(os.path.basename(p) == "paged_memory_probe.py"
               for p in SCRIPTS)
    # the streaming-data-service churn probe (ISSUE 15) too
    assert any(os.path.basename(p) == "data_probe.py" for p in SCRIPTS)
    # the op-inventory roofline sweep (ISSUE 16) too
    assert any(os.path.basename(p) == "roofline_probe.py" for p in SCRIPTS)
    # the routed-serving-fleet probe (ISSUE 17) too
    assert any(os.path.basename(p) == "fleet_probe.py" for p in SCRIPTS)
    # the shared kernel-ablation harness (ISSUE 18) too
    assert any(os.path.basename(p) == "kernel_ablate.py" for p in SCRIPTS)
    # the chaos-soak observatory harness (ISSUE 19) too
    assert any(os.path.basename(p) == "soak.py" for p in SCRIPTS)


def test_step_probe_exposes_sweep_api():
    """The accum x remat sweep (ISSUE 5) and its precision/overlap axes
    (ISSUE 6) must stay addressable: sweep mode in the CLI and the
    sweep_probe/largest_batch/overlap_probe entry points."""
    import inspect

    path = os.path.join(REPO, "benchmarks", "step_probe.py")
    spec = importlib.util.spec_from_file_location("step_probe_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.sweep_probe)
    assert callable(mod.largest_batch)
    assert callable(mod.build_family)
    assert callable(mod.overlap_probe)
    assert callable(mod.joint_probe)
    assert "precision" in inspect.signature(mod.sweep_probe).parameters
    assert "precision" in inspect.signature(mod.build_family).parameters
    # the attention kernel axis and the joint bucket x overlap grid
    # (ISSUE 18) must stay addressable
    assert "attention" in inspect.signature(mod.sweep_probe).parameters
    assert "attention" in inspect.signature(mod.build_family).parameters
    assert "comms_overlap" in inspect.signature(mod.joint_probe).parameters


def test_decode_bench_exposes_decode_leg_api():
    """The decode accelerations (ISSUE 14) must stay addressable: the
    prefix/longtail/speculative legs next to the original three modes,
    and the paged memory probe's probe/sweep entry points."""
    path = os.path.join(REPO, "benchmarks", "decode_bench.py")
    spec = importlib.util.spec_from_file_location("decode_bench_legs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for leg in ("run_naive", "run_static", "run_continuous",
                "run_prefix", "run_longtail", "run_speculative",
                "run_interference", "run_kv_capacity", "run_sampled"):
        assert callable(getattr(mod, leg)), leg

    path = os.path.join(REPO, "benchmarks", "paged_memory_probe.py")
    spec = importlib.util.spec_from_file_location("paged_probe_api", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.probe) and callable(mod.sweep)
    assert callable(mod.longtail_lengths)


@pytest.mark.parametrize("path", SCRIPTS,
                         ids=[os.path.basename(p) for p in SCRIPTS])
def test_import_repo_benchmark_script(path, monkeypatch):
    """Repo-root benchmarks/ are standalone scripts (no package); load each
    through its file spec. Every one guards main() under __main__, so
    importing must be side-effect free and CPU-safe. The script dir goes on
    sys.path (as `python benchmarks/x.py` would) for sibling imports."""
    monkeypatch.syspath_prepend(os.path.dirname(path))
    name = "smoke_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert hasattr(mod, "__doc__")


@pytest.mark.parametrize("module", PKG_MODULES)
def test_import_package_benchmark_module(module):
    assert importlib.import_module(module) is not None


def _load_comms_bench():
    path = os.path.join(REPO, "benchmarks", "comms_bench.py")
    spec = importlib.util.spec_from_file_location("comms_bench_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_comms_bench_int8_bytes_reduction():
    """PR 3 acceptance (fast variant): the int8 codec must cut bytes on
    the wire by >= 3x vs raw on a float32 delta pytree."""
    rows = _load_comms_bench().bench_codecs("mlp", reps=1)
    by = {r["codec"]: r for r in rows}
    assert by["int8"]["ratio"] >= 3.0, by["int8"]
    assert by["raw"]["ratio"] == 1.0


@pytest.mark.slow
def test_comms_bench_full_sweep_resnet():
    """PR 3 acceptance (full variant): the ResNet-18 delta pytree through
    every codec, plus the loopback-socket and overlap-throughput runs."""
    mod = _load_comms_bench()
    rows = mod.bench_codecs("resnet18", reps=2)
    by = {r["codec"]: r for r in rows}
    assert by["int8"]["ratio"] >= 3.0, by["int8"]
    mod.bench_loopback(reps=5)
    over = mod.bench_overlap(rtt_ms=5.0, rounds=16)
    assert over[1]["windows_per_s"] > over[0]["windows_per_s"], over
