"""The benchmark's files of the ``dots3_note`` family on the CPU (ISSUE 35):
the rehearsal cell end to end through ``perf/run.py`` (the builder, the
program's LatentMoELM over layers of two kinds through GenerationEngine +
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
CELL = "dots3_serve_packdoc_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    out = _run("run.py", "--workload", "dots3_tiny_serve_closed", "--seed",
               "2147483659", "--seconds", "2", "--trace", str(trace))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values()), line
    want = {"serve_tokens_per_s", "setup_s"} if trace == 0 else {
        "sparse_attended_share", "prefill_real_share", "moe_held_share",
        "sched_iter_host_p50_s", "compiles_in_window"}
    assert want <= set(line["metrics"]), sorted(line["metrics"])


def test_a_planted_fault_runs_the_cell_with_the_selection_skipped():
    """``perf/planted_fault.py`` is ``perf/run.py`` after an edit to the
    program in its process: the rehearsal cell runs through it, and with
    the selection skipped the lanes attend every position they hold (the
    rehearsal's limits are too wide to read the fault; the chip's are
    not)."""
    out = _run("planted_fault.py", "--fault", "skip", "--workload",
               "dots3_tiny_serve_closed", "--seed", "7", "--seconds", "2",
               "--trace", "1")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "sparse_attended_share" in line["metrics"]


def test_the_listed_cell_refuses_a_machine_without_the_chip():
    out = _run("run.py", "--workload", CELL, "--seed", "1", "--seconds",
               "1", "--trace", "0", timeout=120)
    assert out.returncode != 0 and "measures a TPU" in out.stderr


def test_the_configuration_keeps_the_published_widths(perf_modules):
    """Every value of the catalog's config under the same key, but the five
    that are cut and listed; the cut is stated."""
    cfg = perf_modules.load_json("configs", "dots3_note.json")
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "dots3-note-prev")
    published = row["config"]
    assert cfg["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "layer_types", "n_routed_experts",
               "vocab_size", "max_position_embeddings"]
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert {k: cfg[k] for k in published if k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert {k: cfg[k] for k in reduced} == {
        "num_hidden_layers": 5, "n_routed_experts": 32, "vocab_size": 19008,
        "max_position_embeddings": 11264,
        "layer_types": published["layer_types"][:5]}
    # the leading dense layer, then one whole period of the pattern
    assert published["layer_types"][1:5] == published["layer_types"][5:9] \
        == ["full_attention"] + ["sliding_attention"] * 3
    assert set(cfg["reduced"]) <= set(cfg["changed"])
    assert cfg["n_routed_experts"] * cfg["expert_share"]["of"] == 256
    assert cfg["vocab_size"] * 8 == 152064
    assert cfg["n_positions"] == cfg["max_position_embeddings"] \
        >= max(cfg["serving"]["prefill_buckets"]) + 768
    assert cfg["serving"]["ring_cells"] >= cfg["sliding_window_size"] + 63 \
        and cfg["serving"]["ring_cells"] % 128 == 0
    for said in ("8 v5e chips", "split 32 a chip", "split in 8",
                 "2 : 3 here against 13 : 33"):
        assert said in cfg["deployment"], said
    assert {"attention_gate_type headwise", "apply_mla_qkv_lora_rescale",
            "indexer rotation", "index key norm", "sliding_window_size",
            "router", "dtype", "weights"} <= set(cfg["assumed"])


def test_the_builder_draws_what_reads_a_rescaled_latent_at_unit_variance(
        perf_modules):
    """``q_b``, ``index_q`` and ``kv_b`` read latents the rescale has
    multiplied by ``(hidden / rank)^1/2``: drawn with variance 1 / hidden
    their outputs have the variance any other projection's has; every other
    matrix keeps 1 / fan-in."""
    import jax.numpy as jnp
    import numpy as np

    cfg = perf_modules.load_json("configs", "dots3_note_tiny.json")
    builder = perf_modules.load_module("builders", "dots3_note")
    model = builder.build_model(cfg, "serve")
    assert model.kinds == ("F", "F", "S", "S") and model.rank_rescale
    assert model.window_sizes.ring == 80 and model.head_gate
    params = builder.init_params(model, 3)
    std = lambda a: float(jnp.std(a.astype(jnp.float32)))
    for layer in ("attn_0", "attn_2"):
        for name in ("q_b", "kv_b"):
            assert abs(std(params[layer][name]) * 64 ** 0.5 - 1) < 0.1
        assert abs(std(params[layer]["q_a"]) * 64 ** 0.5 - 1) < 0.1
        assert abs(std(params[layer]["o"])
                   * params[layer]["o"].shape[0] ** 0.5 - 1) < 0.1
    assert abs(std(params["attn_1"]["index_q"]) * 64 ** 0.5 - 1) < 0.1
    assert abs(std(params["attn_1"]["index_k"]) * 64 ** 0.5 - 1) < 0.15
    again = builder.init_params(model, 3)
    np.testing.assert_array_equal(np.asarray(params["attn_0"]["q_b"]),
                                  np.asarray(again["attn_0"]["q_b"]))


def test_the_cell_is_the_issue_s_traffic(perf_modules):
    """The issue's numbers, letter for letter, and the cell's place in
    ``BENCHMARK.json``."""
    cell = perf_modules.load_cell(CELL)
    t = cell["traffic"]
    assert cell["chips"] == 1 and t["driver"] == "serve_closed_bulk"
    assert (t["callers"], t["check_sample"], t["ramp_s"], t["drain_s"],
            t["trace_delay_s"], t["trace_seconds"]) == (40, 16, 45, 20, 2, 5)
    assert t["prompt"] == {"dist": "lognormal", "median": 8192,
                           "sigma": 0.1, "min": 6144, "max": 10240}
    assert t["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.15,
                           "min": 384, "max": 768}
    serving = cell["config"]["serving"]
    assert (serving["num_slots"], serving["slot_ladder"],
            serving["prefill_buckets"]) == (
        32, [8, 16, 32], [6144, 7168, 8192, 9216, 10240])
    assert t["prompt"]["min"] > cell["config"]["index_topk"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "dots3_note", "traffic": "packdoc_closed",
        "chips": 1, "why": cell["cell"]["why"]}
    assert bench["configs"][-1]["file"] == "perf/configs/dots3_note.json"
    assert bench["configs"][-1]["reduced"] == cell["config"]["reduced"]
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])]
    # the three this cell brought, in the order it appended them (later
    # PRs append theirs behind)
    own = ["sparse_attended_share", "sparse_select_device_share",
           "window_attend_device_share"]
    assert [name for name in mine if name in own] == own
    assert {"decode_step_roofline", "prefill_device_mfu",
            "prefill_real_share", "moe_held_share"} <= set(mine)
    assert "ssm_state_device_share" not in mine
    # every metric the sibling cell reports, this one reports too
    sibling = {m["name"] for m in bench["per_layer"]
               if "mistral4_serve_doc_closed" in m.get("workloads", [])}
    assert sibling <= set(mine)


def test_needed_bytes_and_operations_by_hand(perf_modules):
    flops = perf_modules.load_module("flops", "dots3_note")
    cfg = perf_modules.load_json("configs", "dots3_note.json")
    # the issue's table, part by part (the gates counted with the attention)
    assert flops.attention_params(cfg) == 134_021_120 + 655_360
    assert flops.attention_params(cfg, "swa_") == 90_505_216 + 327_680
    assert flops.indexer_params(cfg) == 9_371_648
    assert flops.expert_params(cfg) == 23_592_960
    assert flops.dense_mlp_params(cfg) == 212_336_640
    # 4.087 B parameters in 8.17 GB of bfloat16 (the float32 routers, norms
    # and LayerNorms add 10 MB)
    held = (2 * (134_021_120 + 655_360 + 9_371_648)
            + 3 * (90_505_216 + 327_680) + 212_336_640
            + 4 * (23_592_960 + 1_310_720 + 32 * 23_592_960)
            + 2 * 19008 * 5120)
    assert abs(held - 4.087e9) < 0.005e9
    assert 0 < flops.weight_bytes(cfg) - 2 * held < 14e6
    assert abs(flops.weight_bytes(cfg) - 8.17e9) < 0.03e9
    # a pool row: two full layers' lines and keys, three rings; 33 rows
    assert flops.pool_bytes_per_row(cfg) == 2 * (
        2 * 11264 * (640 + 128) + 3 * 640 * 1152)
    assert abs(33 * flops.pool_bytes_per_row(cfg) - 1.288e9) < 0.001e9
    # 32 lanes x 8 of 256: a held expert is idle with (1 - 1/32)^32
    share = flops.expected_active_share(cfg, 32)
    assert abs(share - (1 - (31 / 32) ** 32)) < 1e-12
    weights = flops.decode_weight_bytes(cfg)
    assert abs(weights - (flops.weight_bytes(cfg) - 2 * 19008 * 5120
                          - (1 - share) * 4 * 32 * 2 * 23_592_960)) < 1
    # a full layer: 256 B of index key for every position the lanes hold,
    # 1152 B of line for the 2048 a lane selects; a window layer 2176 B of
    # line for the 513 in its window
    assert flops.decode_step_bytes(cfg, 32 * 9000.0) == weights \
        + 2 * (256 * 32 * 9000 + 1152 * 32 * 2048) + 3 * 2176 * 32 * 513
    assert flops.decode_step_bytes(cfg, 1000.0) == weights \
        + 2 * 1408 * 1000 + 3 * 2176 * 1000
    # a query at position p attends min(p + 1, k)
    assert flops.attended_pairs(4.0, 10.0, 100.0) == 32
    assert flops.attended_pairs(4.0, 3.0, 9.0) == 4.5
    # an 8192-token prompt: 15.9 TFLOP of projections and experts, 1.1 of
    # index scores and 3.0 of attention over what is selected or in the
    # window, where the causal squares would take 10.4
    per_token = 2 * (2 * (134_676_480 + 9_371_648) + 3 * 90_832_896
                     + 212_336_640 + 4 * (2 * 23_592_960 + 1_310_720))
    index = 2 * 64 * 128 * 8192 ** 2
    pairs_f = 2048 * 8192 - 2048 ** 2 / 2
    pairs_s = 513 * 8192 - 513 ** 2 / 2
    attended = 2 * 2 * 128 * 320 * pairs_f + 3 * 2 * 64 * 384 * pairs_s
    assert flops.prefill_flops(cfg, 8192.0) == \
        8192 * per_token + index + attended
    assert abs(flops.prefill_flops(cfg, 8192.0) / 1e12 - 19.9) < 0.1
