"""Test harness config: run everything on a virtual 8-device CPU mesh.

This is the TPU-native analogue of the reference's Spark local[N] mode (its
only multi-worker-without-a-cluster story, per SURVEY.md §4): N XLA host
devices stand in for N TPU chips so every sharding/collective path compiles
and executes without hardware.

The CPU platform is forced twice, before the first backend init: the
environment variable (inherited by the subprocesses tests spawn) and
``jax.config`` (this process). The persistent compilation cache is switched
off, for this process and the ones it spawns: tier-1 compiles CPU
executables, and the in-checkout cache directory (utils/jax_compat.py) is
copied whole to the chip machine.
"""

import os
import re

# Keep in sync with __graft_entry__.dryrun_multichip: upgrade (never keep) a
# pre-set smaller host device count, so a stale XLA_FLAGS can't starve the
# 8-device mesh. Stdlib-only: must run before the first `import jax`, and the
# package itself imports jax, so this can't live in distkeras_tpu.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
_pat = r"--xla_force_host_platform_device_count=(\d+)"
_m = re.search(_pat, _flags)
if _m is None:
    _flags += " --xla_force_host_platform_device_count=8"
elif int(_m.group(1)) < 8:
    _flags = re.sub(_pat, "--xla_force_host_platform_device_count=8", _flags)
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_sessionstart(session):
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"tests must run on CPU, got {devs}"
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"


@pytest.fixture(scope="session")
def devices():
    return jax.devices()
