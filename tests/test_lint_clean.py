"""The dktlint self-hosting gate (tier-1): the repo must lint clean.

This is the CI teeth of DESIGN.md §12 — `python -m distkeras_tpu.analysis`
exits 0 on the repo, every checker actually scanned a non-trivial corpus
(no vacuous pass), and the layering config still carries the health
no-jax contract that used to live as a bespoke test in tests/test_health.py.
"""

import fnmatch
import glob
import importlib
import os

import pytest

from distkeras_tpu.analysis.core import (EXCLUDE_PARTS, collect_modules,
                                         default_checkers, run_suite)
from distkeras_tpu.analysis.layering import LAYER_RULES
from distkeras_tpu.analysis.registry import load_declared_names
from distkeras_tpu.analysis.wire import PROTOCOLS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ANALYSIS_MODULES = sorted(
    "distkeras_tpu.analysis." + os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(REPO, "distkeras_tpu", "analysis",
                                    "*.py"))
    if os.path.basename(p) not in ("__init__.py", "__main__.py"))


@pytest.fixture(scope="module")
def modules():
    return collect_modules(REPO)


@pytest.fixture(scope="module")
def report(modules):
    baseline = os.path.join(REPO, ".dktlint-baseline.json")
    return run_suite(REPO, baseline_path=baseline, modules=modules)


def test_repo_lints_clean(report):
    assert report.findings == [], "\n" + "\n".join(
        f.render() for f in report.findings)


def test_scan_is_not_vacuous(modules, report):
    # the corpus floor protects against the walker silently matching
    # nothing (the analogue of test_perf_import's discovery floor)
    assert report.checked_files >= 100, report.checked_files
    rels = {m.relpath for m in modules}
    for must in ("distkeras_tpu/telemetry.py",
                 "distkeras_tpu/parallel/remote_ps.py",
                 "distkeras_tpu/serving/server.py",
                 "distkeras_tpu/health/endpoints.py",
                 "distkeras_tpu/models/mlp.py"):
        assert must in rels, must
    # the lint suite and its fixture tests stay out of their own scan
    for part in EXCLUDE_PARTS:
        assert not any(part in r for r in rels), part


def test_intentional_findings_are_suppressed_not_absent(report):
    """The by-design patterns (client sends under the connection lock,
    lazy jax in codec paths, the MoE->tensor sharding bridge) must be
    *suppressed* findings: still visible to the checkers, justified
    inline. If a refactor removes the pattern, this floor drops — update
    it alongside."""
    assert len(report.suppressed) >= 5, [
        f.render() for f in report.suppressed]
    suppressed_rules = {f.rule for f in report.suppressed}
    assert "lock-blocking-call" in suppressed_rules
    assert "layer-forbidden-import" in suppressed_rules


def test_registry_is_populated(modules):
    declared, prefixes = load_declared_names(modules)
    assert len(declared) >= 60, len(declared)
    assert "span." in prefixes and "observability.hbm_" in prefixes
    # the runtime reads the same literal (single source of truth)
    from distkeras_tpu import telemetry
    assert telemetry.METRIC_NAMES == declared
    assert telemetry.METRIC_PREFIXES == prefixes
    assert telemetry.declared_kind("ps.commit.count") == "counter"
    assert telemetry.declared_kind("span.anything.duration_s") == "histogram"
    assert telemetry.declared_kind("totally.adhoc") is None


def test_runtime_rejects_kind_mismatch():
    from distkeras_tpu import telemetry
    reg = telemetry.MetricsRegistry()
    with pytest.raises(TypeError, match="declared as a counter"):
        reg.gauge("ps.commit.count")
    # undeclared ad-hoc names stay legal (tests mint them freely)
    reg.counter("adhoc.test.metric").inc()


def test_layering_carries_the_health_no_jax_rule():
    """The contract ported from tests/test_health.py: every health module
    (and telemetry, and comms) is covered by a jax-forbidding layer rule."""
    health_sources = glob.glob(os.path.join(
        REPO, "distkeras_tpu", "health", "*.py"))
    assert len(health_sources) >= 5  # endpoints/export/heartbeat/watchdog/..
    covered = [p for (p, forbidden, _) in LAYER_RULES if "jax" in forbidden]
    for src in health_sources + [
            os.path.join(REPO, "distkeras_tpu", "telemetry.py")]:
        rel = os.path.relpath(src, REPO).replace(os.sep, "/")
        assert any(fnmatch.fnmatch(rel, pat) for pat in covered), rel


def test_wire_config_names_all_four_servers():
    servers = {p for proto in PROTOCOLS for p in proto.server_paths}
    assert servers == {"distkeras_tpu/parallel/remote_ps.py",
                       "distkeras_tpu/serving/server.py",
                       "distkeras_tpu/health/endpoints.py",
                       "distkeras_tpu/data/service.py"}


def test_committed_baseline_is_empty():
    """The repo lints clean outright: the committed baseline exists (the
    mechanism is exercised) but carries no grandfathered findings."""
    import json
    path = os.path.join(REPO, ".dktlint-baseline.json")
    assert os.path.exists(path), "commit .dktlint-baseline.json"
    data = json.loads(open(path).read())
    assert data["fingerprints"] == [], data["fingerprints"]


def test_analysis_discovery_found_the_checkers():
    assert len(ANALYSIS_MODULES) >= 5, ANALYSIS_MODULES
    for name in ("core", "jit_purity", "locks", "wire", "registry",
                 "layering"):
        assert f"distkeras_tpu.analysis.{name}" in ANALYSIS_MODULES


@pytest.mark.parametrize("module", ANALYSIS_MODULES)
def test_import_analysis_module(module):
    # import-smoke (test_perf_import.py pattern): the lint suite
    # must import on a jax-less host — it only uses the stdlib
    assert importlib.import_module(module) is not None


def test_every_rule_belongs_to_exactly_one_checker():
    seen = {}
    for checker in default_checkers():
        for rule in checker.rules:
            assert rule not in seen, (rule, seen[rule], checker.name)
            seen[rule] = checker.name
    assert len(seen) >= 13, seen
