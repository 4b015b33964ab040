"""Import smoke of the live yardstick: every Python file under ``perf/``.

``perf/run.py`` loads a cell's driver, builder, flops, reference and readers
by name at run time, so a file no tier-1 test imports (``drivers/serve_open``,
``sweep_knee``, ``aot_check``, the ``resnet50_nf`` builder...) otherwise
fails first on the chip, where a failed import costs a whole check. Each file
is loaded through its file spec with ``perf/`` on ``sys.path`` (as
``python perf/x.py`` would have it). Importing must have no side effect:
nothing printed, nothing written to the working directory. This test reads
``perf/``; it edits nothing there.
"""

import glob
import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(REPO, "perf")
FILES = sorted(os.path.relpath(p, PERF) for p in
               glob.glob(os.path.join(PERF, "**", "*.py"), recursive=True))


def test_discovery_found_the_yardstick():
    """The glob must see ``run.py``, every driver a traffic mix names and
    every reader a metric names: an empty or misplaced glob would let the
    parametrised test below pass on nothing."""
    named = {"run.py"}
    for kind, key in (("traffic", "driver"), ("metrics", "reader")):
        for path in glob.glob(os.path.join(PERF, kind, "*.json")):
            with open(path) as f:
                named.add(os.path.join(key + "s", json.load(f)[key] + ".py"))
    assert len(named) > 10, named
    assert named <= set(FILES), sorted(named - set(FILES))


@pytest.mark.parametrize("relpath", FILES)
def test_import_perf_file(relpath, monkeypatch, tmp_path, capfd):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.chdir(tmp_path)
    monkeypatch.syspath_prepend(PERF)
    before = set(sys.modules)
    name = "perf_import_" + relpath[:-3].replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(PERF, relpath))
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    finally:
        # perf's siblings import each other by bare names (``stats``,
        # ``serving``, ``run``, the ``drivers`` namespace...): keep them
        # out of the other tests' way
        for key in set(sys.modules) - before:
            found = sys.modules[key]
            where = [getattr(found, "__file__", None) or "",
                     *getattr(found, "__path__", [])]
            if any(w.startswith(PERF + os.sep) for w in where):
                del sys.modules[key]
    out, err = capfd.readouterr()
    assert out == "" and err == "", (out, err)
    assert os.listdir(tmp_path) == []
