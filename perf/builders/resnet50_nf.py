"""Builder of the ``resnet50_nf`` family: the program's norm-free ResNet at
the sizes a configuration file states, and seeded ImageNet-shaped inputs.

Sizes come from the configuration (``"code": "resnet50_nf"``); a size
variant (``resnet50_tiny``) is a data file.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def build_model(cfg: dict, mode: str):
    from distkeras_tpu.models.resnet import BottleneckBlock, ResNet

    return ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                  block=BottleneckBlock, num_classes=cfg["num_classes"],
                  width=cfg["width"], norm=cfg["norm"],
                  **cfg.get("train_model", {}))


def trainer_kwargs(cfg: dict) -> dict:
    kw = dict(cfg["trainer"], staging_rounds=cfg["staging_rounds"])
    kw["metrics"] = tuple(kw.get("metrics", ()))
    return kw


def samples_per_chunk(cfg: dict) -> int:
    t = cfg["trainer"]
    return t["batch_size"] * t["communication_window"] * cfg["staging_rounds"]


def make_train_data(cfg: dict, n: int, seed: int):
    """``(columns, held)``: ``n`` images to train on and 8 held ones for the
    check against the reference; uint8, brightness naming one of a
    few classes (as chip_smoke.py makes them), plus noise from a seeded
    bank, and one-hot float32 labels. Made in bulk, in uint8 and on eight
    threads, because this is set-up that every run pays."""
    d = cfg["train_data"]
    side, classes = cfg["image_size"], cfg["num_classes"]
    in_use = np.asarray(d["classes_in_use"])
    n_held = 8
    rng = np.random.default_rng([seed, 1])
    total = n + n_held
    bank = rng.integers(0, 41, (d["noise_bank"], side, side, 3),
                        dtype=np.uint8)
    which = rng.integers(0, len(in_use), total)
    level = (20 + 50 * which).astype(np.uint8)       # at most 170 + 40
    pick = rng.integers(0, len(bank), total)
    images = np.empty((total, side, side, 3), np.uint8)

    def fill(lo: int) -> None:
        hi = min(lo + 512, total)
        np.add(bank[pick[lo:hi]], level[lo:hi, None, None, None],
               out=images[lo:hi])

    # NumPy releases the interpreter lock inside the gather and the add
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(0, total, 512)))
    labels = np.zeros((n + n_held, classes), np.float32)
    labels[np.arange(n + n_held), in_use[which]] = 1.0
    columns = {"features": images[:n], "label": labels[:n]}
    held = {"features": images[n:], "label": labels[n:]}
    return columns, held


def tokens_per_sample(cfg: dict):
    return None
