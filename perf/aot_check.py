"""Ahead-of-time compiles for a described v5e, made here without a chip:
what the TPU compiler refuses, and what ``memory_analysis()`` says a
program needs, costs no chip time. It compiles; it cannot run, and nothing
it prints is a chip measurement.

    JAX_PLATFORMS=cpu python perf/aot_check.py train  <config> [chips] [key=value ...]
    JAX_PLATFORMS=cpu python perf/aot_check.py decode <config> [num_slots ...]

``train`` compiles the trainer's epoch function (substrate.build_epoch_fn,
the program ADAG.train jits) for one device call of the configuration's
shapes; ``key=value`` overrides a key of ``train_model`` or ``trainer``
(``remat=none batch_size=4``). ``decode`` compiles GenerationEngine's
top-of-ladder decode step and largest prefill for each ``num_slots`` given.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 2.0 ** 30
#: bytes_limit that memory_stats() reported on the v5e (PERF.md, PR 21)
CHIP_LIMIT_GIB = 15.75


def described_devices(n: int):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return list(topo.devices)[:n]


def report(name: str, compiled, seconds: float) -> float:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{name}: compiled in {seconds:.0f}s; arguments "
          f"{m.argument_size_in_bytes / GIB:.2f} GiB, outputs "
          f"{m.output_size_in_bytes / GIB:.2f}, aliased "
          f"{m.alias_size_in_bytes / GIB:.2f}, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} -> {total / GIB:.2f} GiB per "
          f"device of {CHIP_LIMIT_GIB}", flush=True)
    return total / GIB


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def check_train(cfg: dict, chips: int, overrides: dict) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import optimizers as opt_lib
    from distkeras_tpu.parallel import strategies, substrate

    for key, value in overrides.items():
        group = "trainer" if key in cfg["trainer"] else "train_model"
        cfg[group][key] = type(cfg[group].get(key, value))(value)
    builder = importlib.import_module("builders." + cfg["code"])
    model = builder.build_model(cfg, "train")
    kw = builder.trainer_kwargs(cfg)
    mesh = Mesh(np.asarray(described_devices(chips)).reshape(chips, 1),
                ("workers", "model"))
    tx = opt_lib.get(kw["worker_optimizer"], kw["learning_rate"])
    strategy = strategies.get(kw["class"].lower(),
                              learning_rate=kw["learning_rate"])
    fn = substrate.build_epoch_fn(
        model, kw["loss"], tx, strategy, mesh, chips,
        kw["communication_window"], kw["metrics"])
    columns, _ = builder.make_train_data(cfg, kw["batch_size"], 0)
    feat, lab = columns["features"], columns["label"]
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.asarray(feat[:1]),
                           train=False)["params"])
    carry = jax.eval_shape(lambda p: strategy.init_carry(p, tx), params)

    def sds(tree, spec, lead=()):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                lead + tuple(a.shape), a.dtype,
                sharding=NamedSharding(mesh, spec)), tree)

    rounds = kw["staging_rounds"]
    shape = (rounds, chips, kw["communication_window"], kw["batch_size"])
    data = {
        "features": jax.ShapeDtypeStruct(
            shape + feat.shape[1:], feat.dtype,
            sharding=NamedSharding(mesh, P(None, "workers"))),
        "labels": jax.ShapeDtypeStruct(
            shape + lab.shape[1:], lab.dtype,
            sharding=NamedSharding(mesh, P(None, "workers")))}
    args = (sds(params, P()), sds(carry, P("workers"), (chips,)), data,
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P())))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    report(f"{cfg['name']} epoch function, {chips} chip(s), "
           f"{cfg['train_model']} batch {kw['batch_size']} x "
           f"{kw['communication_window']} x {rounds}", compiled,
           time.perf_counter() - t0)
    if chips > 1:
        text = compiled.as_text()
        print("collectives in the program:",
              {k: text.count(k + "(") + text.count(k + "-start(")
               for k in ("all-reduce", "all-gather", "reduce-scatter",
                         "collective-permute", "all-to-all")})


def check_decode(cfg: dict, slot_counts) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from distkeras_tpu.models import gpt as gpt_lib
    from distkeras_tpu.serving import generation

    builder = importlib.import_module("builders." + cfg["code"])
    model = builder.build_model(cfg, "serve")
    one = SingleDeviceSharding(described_devices(1)[0])
    put = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32, sharding=one)
    params = put(jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    print(f"weights {weights / GIB:.2f} GiB", flush=True)
    for n in slot_counts:
        pool = put(jax.eval_shape(lambda: gpt_lib.init_cache(model, n + 1)))
        t0 = time.perf_counter()
        dec = jax.jit(generation.make_decode_fn(model),
                      donate_argnums=(1,)).lower(
                          params, pool, i32(n), i32(n), i32(n)).compile()
        report(f"decode, {n} lanes of {n} slots", dec,
               time.perf_counter() - t0)
        lb = max(cfg["serving"]["prefill_buckets"])
        t0 = time.perf_counter()
        pre = jax.jit(generation.make_prefill_fn(model),
                      donate_argnums=(1,)).lower(
                          params, pool, i32(1, lb), i32(), i32()).compile()
        report(f"prefill, bucket {lb}, {n} slots", pre,
               time.perf_counter() - t0)


def main(argv) -> int:
    what, cfg = argv[0], load_config(argv[1])
    rest = argv[2:]
    if what == "train":
        chips = int(rest[0]) if rest and "=" not in rest[0] else 1
        overrides = dict(a.split("=", 1) for a in rest if "=" in a)
        check_train(cfg, chips, overrides)
    elif what == "decode":
        check_decode(cfg, [int(a) for a in rest]
                     or [cfg["serving"]["num_slots"]])
    else:
        raise SystemExit(__doc__)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
