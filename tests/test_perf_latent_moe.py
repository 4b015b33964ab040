"""The benchmark's rehearsal cell of the latent-attention, routed-expert
family, end to end on the CPU (ISSUE 27): ``perf/run.py`` on
``mistral4_tiny_serve_closed`` builds ``mistral_small_4_tiny`` through the
``latent_moe`` builder, serves it through GenerationEngine + ServingServer
under the closed-loop driver and the one traffic generator, checks sampled
requests against the plain reference, and reads the per-layer metrics
(``perf/selftest.py --rehearse`` does the same for the cells its own list
names). A rehearsal prints null for every number: nothing here is a time.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "perf", "run.py"), "--workload",
         "mistral4_tiny_serve_closed", "--seed", "2147483659", "--seconds",
         "2", "--trace", str(trace)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values()), line
    want = {"serve_tokens_per_s", "setup_s"} if trace == 0 else {
        "moe_experts_active_mean", "moe_held_share",
        "moe_load_max_over_mean_p50", "sched_iter_host_p50_s",
        "compiles_in_window"}
    assert want <= set(line["metrics"]), sorted(line["metrics"])


def test_precision_control_reads_the_run_it_follows():
    """``perf/precision_control.py`` after a run of the cell: the gaps
    the run judged (``ok`` under both limits, as the run said) and those of
    the reference computed in bfloat16 in the program's place, through the
    driver's own ``judge``. At this size the control proves nothing about
    the limits (some 90 tokens); that it runs is the test."""
    run = lambda script, *args: subprocess.run(
        [sys.executable, os.path.join(REPO, "perf", script), "--workload",
         "mistral4_tiny_serve_closed", "--seed", "2147483693", *args],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    out = run("run.py", "--seconds", "2", "--trace", "0")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "reference, the bulk:" in out.stdout
    out = run("precision_control.py")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["program"]["ok"] is True and line["program"]["tokens"] > 0
    assert line["control"]["tokens"] == line["program"]["tokens"]
    assert set(line["control"]) >= {"worst", "capped_mean", "ok"}


TOLERANCE = {"serve_logit_gap": 1.0, "serve_gap_mean": {"cap": 0.1,
                                                       "limit": 0.01}}


@pytest.mark.parametrize("gaps, ok", [
    ([0.0] * 95 + [0.1] * 5, True),          # mean 0.005, worst 0.1
    ([0.0] * 99 + [0.9], True),              # one flipped expert: capped
    ([0.0] * 99 + [1.1], False),             # by the worst token
    ([0.0] * 80 + [0.06] * 20, False),       # by the bulk: mean 0.012
    ([0.0] * 99 + [float("nan")], False),
    ([], False)], ids=["sound", "one_capped", "worst", "bulk", "nan",
                       "nothing_checked"])
def test_judge_holds_both_limits(gaps, ok):
    """``drivers/serve_closed_bulk.judge``: the worst token under
    ``serve_logit_gap`` and the mean of the capped gaps under
    ``serve_gap_mean``; either alone refuses, and so does an empty or a
    non-finite set."""
    import numpy as np

    sys.path[:0] = [os.path.join(REPO, "perf")]
    try:
        from drivers import serve_closed_bulk
    finally:
        sys.path.pop(0)
    verdict = serve_closed_bulk.judge(np.asarray(gaps, np.float32),
                                      TOLERANCE)
    assert verdict["ok"] is ok and verdict["tokens"] == len(gaps)
