"""Self-test of the yardstick, on the CPU, in seconds:

    JAX_PLATFORMS=cpu python perf/selftest.py            # arithmetic and files
    JAX_PLATFORMS=cpu python perf/selftest.py --rehearse # and every driver,
                                                         # end to end, tiny

It checks the trace reduction on hand-made events (busy union, gaps and
their labels, collective time exposed, sums per operation and executable)
and on a trace recorded here; the metric arithmetic (percentiles, spread,
latency from the due time, same-frame tokens); the functions that compute
operations and bytes, against hand-worked values and against the program's
own jaxpr walker (``observability.count_flops``) at a small size; the
traffic generator; and that BENCHMARK.json agrees with the files under
``perf/``. ``--rehearse`` then runs every ``*_tiny`` cell through
``perf/run.py`` (the data-parallel one on four virtual devices). Nothing it
prints is a measurement of the chip.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import harness  # noqa: E402
import stats  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic  # noqa: E402


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def test_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.length([(0, 2), (3, 4)]) == 3
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (5, 6)], [(0, 1)]) == [(5, 6)]
    ops = [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.0),     # overlap
           ("all-reduce.3", 2.0, 1.0), ("fusion.4", 2.5, 1.0),
           ("all-reduce-start.5", 5.0, 0.5)]
    assert tr.busy_intervals(ops) == [(0.0, 1.5), (2.0, 3.5), (5.0, 5.5)]
    assert tr.idle_gaps(ops, 0.0, 6.0) == [(1.5, 2.0), (3.5, 5.0), (5.5, 6.0)]
    # collectives run over [2, 3] and [5, 5.5]; fusion.4 covers [2.5, 3]
    assert close(tr.exposed_collective_seconds(ops), 0.5 + 0.5)
    full = ("%copy.564 = bf16[33,1024,16,64]{3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[33,1024,16,64]{1,3,2,0:T(8,128)(2,1)} %pool_0___k__.1)")
    assert tr.short_name(full) == "copy bf16[33,1024,16,64]"
    assert tr.short_name("%while.2073 = (s32[]{:T(128)}, f32[1,1024]{1,0}) "
                         "while(%tuple.1)") == "while s32[], .."
    assert tr.short_name("fusion.4") == "fusion"
    assert [e[0] for e in tr.leaf_ops([(full, 0, 1), ("%while.9 = (s32[]) "
                                                      "while(%t)", 0, 5)])] \
        == ["copy bf16[33,1024,16,64]"]
    sums = tr.sum_by_name(ops + [("fusion.1", 7.0, 0.25)])
    assert sums["fusion.1"] == 1.25 and tr.top(sums, 1) == [["fusion.1", 1.25]]
    mods = [("jit_decode(11)", 0.0, 0.02), ("jit_decode(22)", 0.03, 0.04),
            ("jit_prefill(33)", 0.08, 0.01)]
    stat = tr.module_stats(mods)
    assert stat["jit_decode"]["runs"] == 2
    assert close(stat["jit_decode"]["seconds"], 0.06)
    assert [m[0] for m in tr.whole_runs(mods, 0.0, 0.09)] == \
        ["jit_decode(22)"]      # the first starts on the edge, the last ends
    host = [("outer", 0.0, 10.0), ("fetch logits", 1.4, 0.7),
            ("argmax", 3.6, 1.0)]
    labels = tr.label_gaps([(1.5, 2.0), (3.5, 5.0), (20.0, 20.25)], host)
    # the most-overlapping event wins; of two that cover a gap whole, the
    # shortest; a gap no host event touches stays unattributed
    assert labels == {"fetch logits": 0.5, "outer": 1.5,
                      "unattributed": 0.25}, labels
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods,
                                           "lines": {}},
                         "/device:TPU:1": {"ops": [], "modules": [],
                                           "lines": {}}},
             "host": host}
    red = tr.reduce(trace, window_s=6.0)
    assert red["devices"] == 1 and close(red["busy_s"], 3.5)
    # every instruction here ran once; the most expensive took 1 s
    assert red["marker_runs"] == 1 and close(red["busy_s_first_device"], 3.5)
    assert close(red["exposed_collective_s"], 1.0)
    b = tr.breakdown(red)
    # fusion.1, .2 and .4 sum under one short name: 3 s on the one device
    assert b["device_ops"][0] == ["fusion", 3.0], b["device_ops"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_trace():
    """A trace recorded here parses: a CPU has no device plane, so the
    reduction reports no device, and the host's events are there."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = tr.find_xplane(d)
        assert path, "no xplane file written"
        trace = tr.load(path)
    assert trace["host"], "no host event in the recorded trace"
    if jax.devices()[0].platform == "cpu":
        assert tr.reduce(trace, 1.0) == {"devices": 0}


def test_stats():
    assert stats.percentile([], 95) is None
    assert stats.percentile([5, 1, 3], 50) == 3
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile(list(range(1, 21)), 95) == 19
    assert stats.percentile([7], 95) == 7
    assert stats.median([1, 2, 3, 10]) == 2.5
    # quartiles of 1..5 are 2 and 4, the median 3
    assert close(stats.quartile_spread([5, 1, 4, 2, 3]), 2 / 3)
    recs = [
        # due inside the window; sent late; first token 0.5 s after due
        {"due": 1.0, "sent": 1.2, "done": 3.0, "error": None,
         "prompt_len": 10, "max_new": 4,
         "token_times": [1.5, 1.6, 1.60001, 1.9]},
        # refused: counts as the limit
        {"due": 2.0, "sent": 2.0, "done": None, "error": "queue_full",
         "prompt_len": 5, "max_new": 3, "token_times": []},
        # due before the window: not this window's request
        {"due": 0.5, "sent": 0.5, "done": 1.2, "error": None,
         "prompt_len": 7, "max_new": 2, "token_times": [0.9, 1.1]},
    ]
    assert stats.ttft_values(recs, 1.0, 3.0, 20.0) == [0.5, 20.0]
    gaps = stats.gap_values(recs, 1.0, 3.0)
    assert len(gaps) == 2 and close(gaps[0], 0.1) and close(gaps[1], 0.29999)
    assert stats.tokens_between(recs, 1.0, 3.0) == 5   # 4 + the 1.1 arrival
    # tokens 1..3 of the first request decode against 11, 12, 13 positions;
    # token 1 of the third (arrival 1.1) against 8
    assert stats.context_positions_between(recs, 1.0, 3.0) == 11 + 12 + 13 + 8


def load_config(name):
    return harness.load_json("configs", name + ".json")


def test_flops_by_hand():
    g = harness.load_module("flops", "gpt2_medium")
    cfg = {"n_embd": 4, "n_inner": 8, "n_layer": 2, "vocab_size": 10,
           "train_data": {"sequence_length": 3},
           "trainer": {"batch_size": 2}}
    # per layer: qkv 2*4*12 + out 2*4*4 + mlp 2*(2*4*8) = 256, attention
    # over 3 positions 4*3*4 = 48 -> 304; head 2*4*10 = 80
    assert g.forward_flops_per_token(cfg, 3) == 2 * 304 + 80 == 688
    assert g.train_flops_per_sample(cfg) == 3 * 3 * 688
    assert g.train_flops_per_step(cfg) == 2 * 3 * 3 * 688
    # block matrices 4*12 + 16 + 32 + 32 = 128 in bf16, vectors 12 + 4 + 8
    # + 4 + 16 = 44 in f32; head (40 + 10) f32; final norm 8 f32
    assert g.decode_weight_bytes(cfg) == 2 * (2 * 128 + 4 * 44) + 4 * 50 + 32
    assert g.kv_bytes_per_position(cfg) == 2 * 2 * 4 * 2
    assert g.decode_step_bytes(cfg, 10) == g.decode_weight_bytes(cfg) + 320
    r = harness.load_module("flops", "resnet50_nf")
    full = load_config("resnet50_nf")
    # ResNet-50 at 224x224 is the well-known 4.09 GMACs forward
    assert abs(r.forward_flops_per_sample(full) / 2e9 - 4.09) < 0.01
    tiny = {"image_size": 8, "width": 2, "stage_sizes": [1], "num_classes": 3}
    # stem 4*4*(49*3*2) = 4704; pool -> 2x2; block: conv1 4*2*2 = 16,
    # conv2 4*9*2*2 = 144, conv3 4*2*8 = 64, proj 4*2*8 = 64; head 8*3 = 24
    assert [layer[1] for layer in r.conv_layers(tiny)] == [4704, 16, 144, 64, 64,
                                                      24]
    assert r.train_flops_per_sample(tiny) == 2 * (2 * 4704 + 3 * 312)


def test_flops_against_the_program():
    """The closed forms against the program's jaxpr walker, forward and
    backward of the tiny configurations (remat off: it recomputes)."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu import engine, observability

    for name, shape, dtype in (("gpt2_tiny", (2, 64), jnp.int32),
                               ("resnet50_tiny", (2, 32, 32, 3), jnp.uint8)):
        cfg = load_config(name)
        cfg["train_model"] = dict(cfg["train_model"], remat="none") \
            if "remat" in cfg["train_model"] else cfg["train_model"]
        code = cfg["code"]
        model = harness.load_module("builders", code).build_model(cfg, "train")
        x = jnp.zeros(shape, dtype)
        params = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x, train=False))["params"]
        params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params)
        labels = (jnp.zeros(shape, jnp.int32) if name.startswith("gpt")
                  else jnp.zeros((2, cfg["num_classes"]), jnp.float32))
        grad = engine.make_grad_fn(model, cfg["trainer"]["loss"])
        counted = observability.count_flops(
            grad, params, {"features": x, "labels": labels})
        flops = harness.load_module("flops", code)
        # a strided convolution's input gradient is lowered over a dilated
        # input; the walker counts the inserted zeros (perf/flops/resnet50_nf)
        closed = 2 * getattr(flops, "train_flops_as_lowered",
                             flops.train_flops_per_sample)(cfg)
        assert abs(counted / closed - 1) < 0.01, (name, counted, closed)


def test_traffic():
    params = {"prompt": {"dist": "lognormal", "median": 192, "sigma": 0.9,
                         "min": 16, "max": 768},
              "output": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                         "min": 8, "max": 256},
              "vocab": 1000, "max_prompt": 768, "max_total": 1024}
    import itertools

    import numpy as np

    def take(mix, sid, n):
        return list(itertools.islice(mix.stream(sid), n))

    a, b = take(traffic.Mix(params, 3), 0, 500), \
        take(traffic.Mix(params, 3), 0, 500)
    assert all((x["prompt"] == y["prompt"]).all()
               and x["max_new"] == y["max_new"] for x, y in zip(a, b))
    c = take(traffic.Mix(params, 4), 0, 500)
    assert any(len(x["prompt"]) != len(y["prompt"]) for x, y in zip(a, c))
    lens = np.array([len(r["prompt"]) for r in a])
    outs = np.array([r["max_new"] for r in a])
    assert lens.min() >= 16 and lens.max() <= 768
    assert outs.min() >= 8 and outs.max() <= 256
    assert (lens + outs <= 1024).all()
    assert 150 < np.median(lens) < 240 and 80 < np.median(outs) < 115
    assert all(1 <= int(r["prompt"].min()) and int(r["prompt"].max()) < 1000
               for r in a)
    rng = np.random.default_rng(0)
    t = traffic.arrival_times(rng, {"process": "poisson", "rate": 50}, 200.0)
    assert abs(len(t) / 200.0 - 50) < 2 and (np.diff(t) > 0).all()


def test_files_agree():
    """BENCHMARK.json against the files it names and the files against
    each other: nothing here is a list kept in code."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        data = harness.load_json(*c["file"].split("/")[1:])
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"]), c["name"]
        assert not data.get("rehearsal")
        for kind in ("builders", "flops", "reference"):
            harness.load_module(kind, data.get("code", data["name"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    four_chip = 0
    reports = {}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["cell"]["config"] == w["config"] in configs
        assert cell["cell"]["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"] and cell["cell"]["why"] == w["why"]
        assert len(w["why"]) <= 200, (w["name"], len(w["why"]))
        four_chip += w["chips"] == 4
        driver = harness.load_module("drivers", cell["traffic"]["driver"])
        reports[w["name"]] = set(driver.REPORTS)
        assert set(driver.REPORTS) <= set(e2e)
    assert four_chip <= max(1, len(bench["workloads"]) // 4)
    for name, m in e2e.items():
        cells = set(m.get("workloads", reports))
        assert cells == {w for w, r in reports.items() if name in r}, name
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    files = {os.path.basename(p)[:-5] for p in
             glob.glob(os.path.join(HERE, "metrics", "*.json"))}
    # a file no entry names belongs to a cell that is not listed yet
    assert files >= {m["name"] for m in bench["per_layer"]}, \
        {m["name"] for m in bench["per_layer"]} - files
    for m in bench["per_layer"]:
        spec = harness.load_json("metrics", m["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        harness.load_module("readers", spec["reader"])
        cells = set(m.get("workloads", reports))
        assert cells <= {w for w, r in reports.items() if m["moves"] in r}, \
            m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in reports:                       # every cell has a per-layer metric
        assert any(w in m.get("workloads", reports)
                   for m in bench["per_layer"])


TINY_CELLS = (("resnet50_tiny_dp4", 4), ("gpt2_tiny_train", 1),
              ("gpt2_tiny_serve_open", 1), ("gpt2_tiny_serve_closed", 1))


def rehearse():
    """Every driver end to end at a tiny size, both trace modes."""
    for cell, devices in TINY_CELLS:
        for trace in (0, 1):
            env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
                f"--xla_force_host_platform_device_count={devices}"))
            out = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 cell, "--seed", "1", "--seconds", "3", "--trace",
                 str(trace)], env=env, capture_output=True, text=True,
                timeout=600)
            assert out.returncode == 0, (cell, out.stderr[-2000:])
            line = json.loads(out.stdout.strip().splitlines()[-1])
            want = {"correct", "attempted", "failed", "metrics", "device"}
            assert set(line) == want, (cell, set(line))
            assert line["correct"] is True and line["attempted"] > 0, line
            assert line["failed"] == 0 and line["metrics"], line
            assert all(m["value"] is None
                       for m in line["metrics"].values()), line
            assert line["device"]["platform"] == "cpu"
            print(f"ok   rehearsal {cell} --trace {trace}: "
                  f"{sorted(line['metrics'])}", flush=True)


def main(argv) -> int:
    tests = [test_intervals, test_stats, test_flops_by_hand, test_traffic,
             test_files_agree, test_recorded_trace,
             test_flops_against_the_program]
    for test in tests:
        test()
        print(f"ok   {test.__name__}", flush=True)
    if "--rehearse" in argv:
        rehearse()
    print("perf/selftest.py: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
