"""chip_smoke.py off the chip: it must refuse, by name, before any work.

The script's legs only mean something on a TPU (they run in the chip
tool, see README "Running"); what tier-1 can pin is the contract's other
half — with no accelerator it exits non-zero, names the platform it
found, prints no result line and builds no model.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu_before_building_anything():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # -X importtime logs every import on stderr: the package (and with it
    # every model) must never be imported on the refusal path
    proc = subprocess.run(
        [sys.executable, "-X", "importtime", "chip_smoke.py"], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no legs, no {"ok": ...} line
    assert "distkeras_tpu" not in proc.stderr
    assert "flax" not in proc.stderr
