"""Reading the rows of a rectangular cache leaf where they lie: what every
model family that keeps its cache in ``KVCachePool`` shares (models/gpt.py,
models/latent_moe.py)."""

from __future__ import annotations

import jax.numpy as jnp

#: largest slice, in elements, that the TPU compiler gathers where it
#: lies; a larger one it first cuts into pieces by copying the whole
#: operand (tests/test_decode_layout.py reads the compiled step)
GATHER_SLICE_ELEMS = 1 << 18


def gather_rows(leaf, rows):
    """``leaf[rows]`` of a ``[n, max_len, width]`` cache leaf, taken in
    runs of positions of at most :data:`GATHER_SLICE_ELEMS` elements (a
    row is contiguous, so a run is a view of it). ``rows=None`` is lane
    i = row i: the leaf itself."""
    if rows is None:
        return leaf
    n, max_len, width = leaf.shape
    runs = 1
    while (max_len // runs) * width > GATHER_SLICE_ELEMS \
            and max_len % (2 * runs) == 0:
        runs *= 2
    idx = (rows[:, None] * runs + jnp.arange(runs)[None, :]).reshape(-1)
    taken = leaf.reshape(n * runs, max_len // runs, width)[idx]
    return taken.reshape(rows.shape[0], max_len, width)
