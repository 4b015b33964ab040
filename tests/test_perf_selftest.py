"""The benchmark's own self-test as a tier-1 test (ISSUE 24): BENCHMARK.json,
``perf/metrics/``, ``perf/readers/`` and the trace reduction can no longer
drift unseen. ``python perf/selftest.py`` checks the files against each
other and the yardstick's arithmetic on the CPU, in seconds; its
``--rehearse`` (every driver end to end, minutes) stays a by-hand run."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_perf_selftest_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perf", "selftest.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "ok   test_files_agree" in out.stdout
    assert out.stdout.rstrip().endswith("perf/selftest.py: all passed")
