"""The one general traffic generator. A traffic mix is a data file
(``perf/traffic/<name>.json``) of parameters; this module turns it and a
seed into requests. No JAX: the load generator's child process imports it.

Parameters it understands:

``prompt`` / ``output``   length distributions, ``{"dist": "lognormal",
                          "median": 192, "sigma": 0.9, "min": 16, "max":
                          768}``: heavy-tailed, rounded and clipped
``arrivals``              open loop: ``{"process": "poisson", "rate": r}``
``callers``               closed loop: number of callers, each sending its
                          next request when the last completes
``max_prompt``, ``max_total``  the engine's limits (largest prefill bucket,
                          context length); lengths are clipped to them
``vocab``                 token ids are drawn from ``1..vocab-1``

It knows what the benchmark's cells use and nothing else. A mix that needs
another distribution, arrival process or kind of sharing brings its own
generator and driver files with the chip runs that prove them.
"""

from __future__ import annotations

import math
from typing import Iterator

import numpy as np

#: requests drawn at a time by a stream
BLOCK = 64


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.exp(math.log(spec["median"])
               + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(rng: np.random.Generator, spec: dict,
                  horizon_s: float) -> np.ndarray:
    """Arrival instants in ``[0, horizon_s)`` at mean rate ``spec["rate"]``."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate"])
    n = int(rate * horizon_s * 1.5 + 50)
    while True:
        times = np.cumsum(rng.exponential(1.0 / rate, n))
        if times[-1] >= horizon_s:
            return times[times < horizon_s]
        n *= 2


class Mix:
    """Seeded source of requests for one traffic mix."""

    def __init__(self, params: dict, seed: int):
        self.p = params
        self.seed = int(seed)
        self.vocab = int(params["vocab"])
        self.max_prompt = int(params["max_prompt"])
        self.max_total = int(params["max_total"])

    def stream(self, stream_id: int) -> Iterator[dict]:
        """The endless request stream of one independent source (the
        open-loop schedule, or one closed-loop caller): ``{"prompt",
        "max_new"}``, drawn lazily in blocks of ``BLOCK``."""
        rng = np.random.default_rng([self.seed, 11, stream_id])
        while True:
            plen = draw_lengths(rng, self.p["prompt"], BLOCK)
            olen = draw_lengths(rng, self.p["output"], BLOCK)
            block = []
            for pl, ol in zip(plen, olen):
                pl = int(min(pl, self.max_prompt))
                ol = int(max(1, min(ol, self.max_total - pl)))
                block.append({"prompt": rng.integers(
                    1, self.vocab, pl).astype(np.int32), "max_new": ol})
            yield from block
