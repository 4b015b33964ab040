"""The benchmark's files of the ``nemotron_h`` family on the CPU (ISSUE 32):
the rehearsal cell end to end through ``perf/run.py`` (the builder, the
program's HybridLM through GenerationEngine + ServingServer, the closed-loop
bulk driver, the check against the plain reference, the per-layer metrics),
the needed bytes and operations against hand-worked values, and the new
readers on nothing to read. A rehearsal prints null for every number:
nothing here is a time.
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(REPO, "perf")


@pytest.fixture
def perf_modules(monkeypatch):
    """``perf/`` on the path as ``python perf/run.py`` has it; its modules
    out of the other tests' way afterwards."""
    monkeypatch.syspath_prepend(PERF)
    before = set(sys.modules)
    import harness
    yield harness
    for key in set(sys.modules) - before:
        where = getattr(sys.modules[key], "__file__", None) or ""
        if where.startswith(PERF + os.sep):
            del sys.modules[key]


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload",
         "nemotron3_tiny_serve_closed", "--seed", "2147483659", "--seconds",
         "2", "--trace", str(trace)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values()), line
    want = {"serve_tokens_per_s", "setup_s"} if trace == 0 else {
        "prefill_real_share", "moe_held_share", "moe_experts_active_mean",
        "sched_iter_host_p50_s", "compiles_in_window"}
    assert want <= set(line["metrics"]), sorted(line["metrics"])


def test_the_listed_cell_refuses_a_machine_without_the_chip():
    out = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload",
         "nemotron3_serve_rag_closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and "measures a TPU" in out.stderr


def test_the_configuration_keeps_the_published_widths(perf_modules):
    """Every width as the source states it; what was cut is listed."""
    cfg = perf_modules.load_json("configs", "nemotron3_nano.json")
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "norm_eps": 1e-5}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "max_position_embeddings"])
    assert set(cfg["reduced"]) <= set(cfg["changed"])
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert whole.startswith(cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    assert cfg["n_routed_experts"] * cfg["expert_share"]["of"] == 128
    assert {"no positional term", "state dtype", "weights"} \
        <= set(cfg["assumed"])


def test_needed_bytes_and_operations_by_hand(perf_modules):
    flops = perf_modules.load_module("flops", "nemotron_h")
    cfg = perf_modules.load_json("configs", "nemotron3_nano.json")
    # the issue's arithmetic: M 38.75 M parameters in its two projections,
    # * 23.40 M, an expert 9.978 M, the shared one 19.96 M
    assert flops.mamba_matrix_params(cfg) == 2688 * 10304 + 4096 * 2688
    assert flops.attention_matrix_params(cfg) == 2 * 2688 * 4096 \
        + 2 * 2688 * 256
    assert flops.expert_params(cfg) == 2 * 2688 * 1856
    assert flops.shared_expert_params(cfg) == 2 * 2688 * 3712
    # 14.94 MB of state a lane, 2048 B a position over two attention blocks
    assert flops.state_bytes_per_lane(cfg) == 7 * (64 * 64 * 128 * 4
                                                   + 3 * 6144 * 2)
    assert flops.cache_bytes_per_position(cfg) == 2 * 1024
    # 128 lanes x 6 of 128: a held expert is idle with (1 - 6/128)^128
    share = flops.expected_active_share(cfg, 128)
    assert abs(share - (1 - (122 / 128) ** 128)) < 1e-12 and share > 0.99
    weights = flops.decode_weight_bytes(cfg)
    step = flops.decode_step_bytes(cfg, 1000.0)
    assert step == weights + 1000 * 2048 + 2 * 128 * 7 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2)
    # everything but the embedding table, within a percent of 2 B a
    # parameter (the router is float32, a few experts idle)
    held = 7 * (38.75e6 + 20.30e6 + 64 * 9.978e6) + 2 * 23.40e6 \
        + 65536 * 2688
    assert abs(weights / (2 * held) - 1) < 0.01
    tiny = {"hidden_size": 4, "mamba_num_heads": 2, "mamba_head_dim": 2,
            "n_groups": 1, "ssm_state_size": 3, "conv_kernel": 4,
            "chunk_size": 8, "num_attention_heads": 2, "head_dim": 2,
            "num_key_value_heads": 1, "moe_intermediate_size": 5,
            "moe_shared_expert_intermediate_size": 6, "n_shared_experts": 1,
            "n_routed_experts": 2, "expert_share": {"index": 0, "of": 2},
            "num_experts_per_tok": 2, "hybrid_override_pattern": "M*E"}
    # M: in 4 x (4 + 10 + 2) + out 4 x 4 = 80 parameters, the scan 8 x 3 +
    # 8 x 4 + 4 x 4 x 3 = 104, the taps 2 x 4 x 10 = 80; *: 2 x 4 x 4 + 2 x
    # 4 x 2 = 48 parameters; E: shared 2 x 4 x 6 = 48, router 4 x 4, and
    # 2 x 4 x 5 = 40 an expert at 2 x 2 / 4 = 1 held expert a token
    per_token = (160 + 104 + 80) + 96 + (96 + 32 + 80)
    assert flops.prefill_flops(tiny, 10) == 10 * per_token + 2 * 4 * 100
    assert flops.prefill_flops(tiny, 10, 52.0) == 10 * per_token + 2 * 4 * 52


@pytest.mark.parametrize("reader, args", [
    ("prefill_device_mfu", {}),
    ("op_pattern_share", {"pattern": "f32"}),
    ("registry_counter_share", {"over": "serving.prefill.tokens",
                                "under": "serving.prefill.positions"})])
def test_new_readers_find_nothing_and_say_so(perf_modules, reader, args):
    """A reader returns None, and never raises, where its counter,
    operation or ``ctx.flops`` function is absent: on an empty registry
    and an empty ``reduced``, and with a flops module of another family."""
    from distkeras_tpu import telemetry

    telemetry.reset()
    read = perf_modules.load_module("readers", reader).read
    ctx = types.SimpleNamespace(
        facts={}, flops=types.SimpleNamespace(), peaks=None, tracer=None,
        config={})
    for reduced in (None, {}, {"devices": 0},
                    {"busy_s": 1.0, "op_seconds": {}, "whole_runs": {},
                     "modules": {}}):
        assert read(ctx, reduced, **args) is None
    # something to read, but a family whose flops module lacks the function
    ctx.facts = {"records": [{"token_times": [1.0], "prompt_len": 8}],
                 "t_zero": 0.0}
    ctx.peaks = {"bf16_flops": 1e12}
    ctx.tracer = types.SimpleNamespace(t_start=0.0, t_stop=2.0)
    reduced = {"busy_s": 1.0, "op_seconds": {"fusion s32[4]": 0.5},
               "whole_runs": {"jit_prefill": {"runs": 1, "seconds": 0.1}}}
    if reader == "prefill_device_mfu":
        assert read(ctx, reduced) is None
        ctx.flops = types.SimpleNamespace(
            prefill_flops=lambda cfg, tokens, squares: 1e9 * tokens)
        assert read(ctx, reduced) == pytest.approx(100 * 8e9 / (0.1 * 1e12))
    elif reader == "op_pattern_share":
        assert read(ctx, reduced, **args) is None
        assert read(ctx, reduced, pattern=r"s32\[\d+\]") == 50.0
