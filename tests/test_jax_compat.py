"""The compile-cache rule (utils/jax_compat.enable_compilation_cache).

If ``JAX_COMPILATION_CACHE_DIR`` is exported JAX already holds it and the
function sets nothing; otherwise the cache goes to one fixed directory
inside the checkout. The cache directory is process-global jax config, so
every in-process test restores it — and conftest.py keeps the cache itself
switched off, so nothing here (or anywhere in tier-1) writes an entry.
"""

import os
import subprocess
import sys

import jax
import pytest

from distkeras_tpu.utils import jax_compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_means_the_function_sets_nothing(restore_cache_dir,
                                                     tmp_path):
    """JAX reads the variable into its config at import; stand in for that
    here, then check the function returns it and leaves config alone."""
    exported = str(tmp_path / "exported")
    jax.config.update("jax_compilation_cache_dir", exported)
    assert jax_compat.enable_compilation_cache() == exported
    assert jax.config.jax_compilation_cache_dir == exported
    assert not os.path.exists(exported)  # and nothing was created


def test_unset_means_the_fixed_in_checkout_directory(restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    want = os.path.join(REPO, ".xla_cache")
    assert jax_compat.DEFAULT_CACHE_DIR == want
    assert jax_compat.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax_compat.enable_compilation_cache() == want  # idempotent


def test_two_processes_agree_and_the_environment_wins(tmp_path):
    """The directory is part of every cache key: two processes must
    resolve the same one (no pid, time or temp name in it), and a real
    exported JAX_COMPILATION_CACHE_DIR must be the one in use."""
    code = ("from distkeras_tpu.utils import jax_compat; "
            "print(jax_compat.enable_compilation_cache())")
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    exported = str(tmp_path / "from_env")
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                              env=env, stdout=subprocess.PIPE, text=True)
             for env in (base, base,
                         dict(base, JAX_COMPILATION_CACHE_DIR=exported))]
    outs = [p.communicate(timeout=120)[0].strip().splitlines()[-1]
            for p in procs]
    assert all(p.returncode == 0 for p in procs)
    assert outs[0] == outs[1] == os.path.join(REPO, ".xla_cache")
    assert outs[2] == exported


def test_cache_exported_at_package_top_level():
    import distkeras_tpu

    assert distkeras_tpu.enable_compilation_cache \
        is jax_compat.enable_compilation_cache
