"""The second reading of a serving cell's limits: the reference, computed in
the nearest precision below the one the configuration states, put in the
program's place.

    python perf/precision_control.py --workload <cell> --seed <n> [--dump f]

after ``perf/run.py`` has run that cell with that seed: it reads the sampled
finished requests and the gaps the run left in ``perf_out/``, makes the
weights again from the seed, and for every position of those requests'
answers asks the reference in bfloat16 which token it would emit after
the context the program saw there. Those tokens' gaps under the float32
reference, and the program's own as the run judged them, go through the
driver's ``judge`` (``drivers/serve_closed_bulk.py``), and both verdicts
are printed as one JSON line. A limit is sound when the first is ``ok`` and
the second is not.
Cells of the ``latent_moe`` family (a reference with ``choices`` and
``token_gaps(..., chosen=)``). It measures nothing and is no part of a run.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import numpy as np  # noqa: E402

import harness  # noqa: E402
from drivers import serve_closed_bulk as driver  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dump", default=None,
                    help="write both sets of gaps here as JSON")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    cell = harness.load_cell(args.workload)
    cfg = cell["config"]
    code = cfg.get("code", cfg["name"])
    builder = harness.load_module("builders", code)
    reference = harness.load_module("reference", code)
    harness.setup_compile_cache()
    with open(os.path.join(harness.OUT_DIR,
                           f"{args.workload}.records.json")) as f:
        records = json.load(f)["records"]
    params = builder.init_params(builder.build_model(cfg, "serve"), args.seed)
    below = jax.jit(lambda p, ids: reference.choices(
        p, ids, cfg, jnp.bfloat16))
    with open(driver.gaps_path(args.workload)) as f:
        program = np.asarray(json.load(f), np.float32)
    gaps = {"program": program,
            "control": driver.sampled_gaps(cfg, reference, params, records,
                                           chooser=below)}
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump({k: [float(g) for g in v] for k, v in gaps.items()}, f)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_dtype": "bfloat16",
                      "platform": jax.devices()[0].platform,
                      **{k: driver.judge(v, cfg["tolerance"])
                         for k, v in gaps.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
