"""The benchmark's files of the ``xing4`` family on the CPU: the rehearsal
cell end to end through ``perf/run.py`` (the builder, the program's
LatentMoELM with four residual streams through GenerationEngine +
ServingServer, the closed-loop bulk driver, the check against the plain
reference, the per-layer metrics), the files against ``BENCHMARK.json`` and
the source, and the needed bytes and operations against hand-worked values
at the published sizes. A rehearsal prints null for every number: nothing
here is a time.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(REPO, "perf")
CELL = "xing4_serve_longdoc_closed"
SOURCE = ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
          "config.json")
#: the language model's keys of the source's config.json, as published
PUBLISHED = {
    "attention_bias": False,
    "ep_size": 1,
    "first_k_dense_replace": 2,
    "hidden_act": "silu",
    "hidden_size": 3584,
    "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "max_position_embeddings": 262144,
    "model_type": "xing4_0",
    "moe_intermediate_size": 1024,
    "moe_layer_freq": 1,
    "n_group": 1,
    "n_routed_experts": 64,
    "n_shared_experts": 1,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 40,
    "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1,
    "hc_mult": 4,
    "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 32,
        "beta_slow": 1,
        "factor": 64,
        "mscale": 1,
        "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096,
        "type": "yarn"
    },
    "routed_scaling_factor": 2,
    "scoring_func": "sigmoid",
    "tie_word_embeddings": False,
    "topk_group": 1,
    "topk_method": "noaux_tc",
    "v_head_dim": 128,
    "vocab_size": 131072
}


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


def _run(script, *args, timeout=300):
    return subprocess.run(
        [sys.executable, os.path.join(PERF, script), *args],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(trace):
    """``correct`` here holds the limit a flip cannot decide alone (the
    capped mean; the configuration's worst-token limit is out of reach),
    so that the rehearsal adds no unsteady test."""
    out = _run("run.py", "--workload", "xing4_tiny_serve_closed", "--seed",
               "2147483659", "--seconds", "2", "--trace", str(trace))
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
    out = _run("run.py", "--workload", CELL, "--seed", "1", "--seconds",
               "1", "--trace", "0", timeout=120)
    assert out.returncode != 0 and "measures a TPU" in out.stderr


def test_a_program_with_one_stream_refuses_the_configuration(
        perf_modules, monkeypatch):
    """The parent's LatentMoELM has no ``hc_mult``: the builder says so and
    exits, so that a run of the new cell on it fails at once."""
    from distkeras_tpu.models import latent_moe

    cfg = perf_modules.load_json("configs", "xing4_29b_a4b.json")
    builder = perf_modules.load_module("builders", "xing4")
    monkeypatch.setattr(latent_moe, "LatentMoELM", type("Parent", (), {}))
    with pytest.raises(SystemExit, match="one residual stream"):
        builder.build_model(cfg, "serve")


def test_the_configuration_keeps_the_published_widths(perf_modules):
    """Every value of the source's config under the same key, but the four
    that are cut and listed; the cut is stated."""
    cfg = perf_modules.load_json("configs", "xing4_29b_a4b.json")
    published = PUBLISHED
    assert cfg["source"] == SOURCE
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "num_nextn_predict_layers", "max_position_embeddings"]
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert {k: cfg[k] for k in published if k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert {k: cfg[k] for k in reduced} == {
        "num_hidden_layers": 6, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 0, "max_position_embeddings": 5376}
    assert set(cfg["reduced"]) <= set(cfg["changed"])
    assert cfg["expert_share"] == {"index": 0, "of": 1}
    assert cfg["n_positions"] == cfg["max_position_embeddings"] \
        >= max(cfg["serving"]["prefill_buckets"]) + 192
    assert "1 chip shares a layer" in cfg["deployment"]
    assert {"final combination", "mapping", "sinkhorn", "rotary", "router",
            "dtype", "weights"} <= set(cfg["assumed"])


def test_the_cell_is_the_long_document_traffic(perf_modules):
    """The mix's numbers as the cell states them (callers, lengths, sample,
    ramp), the serving recipe, and the cell's place in ``BENCHMARK.json``."""
    cell = perf_modules.load_cell(CELL)
    t = cell["traffic"]
    assert cell["chips"] == 1 and t["driver"] == "serve_closed_bulk"
    assert (t["callers"], t["check_sample"], t["ramp_s"], t["drain_s"],
            t["trace_delay_s"], t["trace_seconds"]) == (96, 64, 20, 0, 2, 5)
    assert t["prompt"] == {"dist": "lognormal", "median": 4096,
                           "sigma": 0.15, "min": 3072, "max": 5120}
    assert t["output"] == {"dist": "lognormal", "median": 96, "sigma": 0.3,
                           "min": 48, "max": 192}
    serving = cell["config"]["serving"]
    assert (serving["num_slots"], serving["slot_ladder"],
            serving["prefill_buckets"]) == (
        64, [16, 32, 64], [3584, 4096, 4608, 5120])
    assert serving["queue_capacity"] >= 2 * t["callers"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert next(w for w in bench["workloads"] if w["name"] == CELL) == {
        "name": CELL, "config": "xing4_29b_a4b", "traffic": "longdoc_closed",
        "chips": 1, "why": cell["cell"]["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == "xing4_29b_a4b")
    assert entry["file"] == "perf/configs/xing4_29b_a4b.json"
    assert entry["reduced"] == cell["config"]["reduced"]
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])]
    own = ["prefill_hc_device_share", "decode_hc_device_share"]
    assert [name for name in mine if name in own] == own
    for name in own:
        spec = perf_modules.load_json("metrics", name + ".json")
        assert (spec["reader"], spec["args"]["scopes"]) == (
            "scope_share", ["hc."])
    # every metric the sibling cell reports, this one reports too
    sibling = {m["name"] for m in bench["per_layer"]
               if "mistral4_serve_doc_closed" in m.get("workloads", [])}
    assert sibling | {"prefill_real_share", "prefill_device_mfu"} \
        <= set(mine)
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]


def test_needed_bytes_and_operations_by_hand(perf_modules):
    flops = perf_modules.load_module("flops", "xing4")
    cfg = perf_modules.load_json("configs", "xing4_29b_a4b.json")
    # the parameters, part by part
    assert flops.attention_params(cfg) == 28_409_856
    assert flops.dense_mlp_params(cfg) == 99_090_432
    assert flops.expert_params(cfg) == 11_010_048
    # a mapping: phi 14336 x 24, the 24 biases, the 3 scalars
    assert flops.hc_params(cfg) == 14336 * 24 + 24 + 3
    # 4.79 B parameters in 9.59 GB of bfloat16 (float32 vectors, routers
    # and the 12 mappings add 21 MB)
    held = 6 * 28_409_856 + 99_090_432 + 5 * 65 * 11_010_048 \
        + 2 * 131072 * 3584
    assert abs(held - 4.79e9) < 0.005e9
    extra = flops.weight_bytes(cfg) - 2 * held
    assert 12 * 4 * 344_091 < extra < 25e6
    assert abs(flops.weight_bytes(cfg) - 9.59e9) < 0.01e9
    # a pool row: a 640-value line (576 padded to whole tiles) a position
    # a layer; 65 rows
    assert flops.pool_bytes_per_row(cfg) == 2 * 6 * 5376 * 640
    assert abs(65 * flops.pool_bytes_per_row(cfg) - 2.684e9) < 0.001e9
    share = flops.expected_active_share(cfg, 64)
    assert abs(share - (1 - (60 / 64) ** 64)) < 1e-12
    weights = flops.decode_weight_bytes(cfg)
    assert abs(weights - (flops.weight_bytes(cfg) - 2 * 131072 * 3584
                          - (1 - share) * 5 * 64 * 2 * 11_010_048)) < 1
    # the streams: 12 sub-layers, 4 x 3584 float32 read and written, 64
    # lanes; 1152 B of line a held position over the 6 layers
    assert flops.decode_step_bytes(cfg, 1000.0) == weights \
        + 64 * 12 * 2 * 4 * 4 * 3584 + 1000 * 6 * 2 * 576
    # a 4096-token prompt: projections and experts, the 12 mappings, and
    # attention over the causal half of the square
    mapping = 14336 * 24 * 2 + 3584 * (3 * 4 + 2 * 4 + 2 * 16 + 2 * 4) \
        + 20 * 2 * 2 * 16
    assert flops.hc_flops_per_token(cfg) == 12 * mapping
    per_token = 2 * (6 * 28_409_856 + 99_090_432
                     + 5 * (5 * 11_010_048 + 3584 * 64)) + 12 * mapping
    attended = 6 * 2 * 32 * (128 + 64 + 128) * 4096 ** 2 / 2
    assert flops.prefill_flops(cfg, 4096.0) == 4096 * per_token + attended
    assert abs(flops.prefill_flops(cfg, 4096.0) / 1e12 - 5.55) < 0.01
