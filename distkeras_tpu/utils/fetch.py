"""Batched device→host fetch — one transfer instead of one per leaf.

Every blocking device→host read costs a host round trip, and
``jax.device_get`` on a pytree issues one per leaf — a trained ResNet-50
has ~160 of them. ``device_get_batched`` concatenates
the raveled leaves per dtype in ONE jitted computation, pulls each dtype
group with a single fetch, and splits/reshapes host-side.

The concat does cost one extra on-device copy of the tree; for end-of-run
fetches (trained params, accumulated metrics) that trade favors the single
round trip.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnums=0)
def _concat(n: int, *arrs):
    del n  # static key: distinguishes call signatures for the jit cache
    return jnp.concatenate([a.ravel() for a in arrs])


#: arity cap per concatenate: bounds trace/compile cost when fetching
#: O(steps)-sized metric histories while still collapsing a param tree
#: (~10^2 leaves) into one transfer
_MAX_CONCAT_ARGS = 1024


@lru_cache(maxsize=32)
def _replicator(mesh):
    """Per-mesh cached jitted identity with replicated out_shardings —
    the cross-host gather of the multi-process fetch path."""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.jit(lambda *xs: xs,
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def device_get_batched(tree):
    """``jax.device_get`` with per-dtype batched transfers.

    Non-array leaves and trees with <= 2 device leaves pass through to the
    plain path (no win to be had). Weak-typed/committed-ness of the leaves
    is irrelevant host-side; shapes and dtypes are preserved exactly.
    Leaves are concatenated in groups of at most ``_MAX_CONCAT_ARGS`` so a
    huge history tree cannot produce an unboundedly wide XLA program.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    na_idx = [i for i, l in enumerate(leaves)
              if isinstance(l, jax.Array) and not l.is_fully_addressable]
    if na_idx:
        # multi-process mesh: make those leaves fully addressable with a
        # compiled replication per mesh (the collective crosses hosts),
        # leaving every other leaf untouched, then fall through to the
        # batched transfer below. The jitted identity is cached per mesh
        # (fresh jit objects would retrace every call) and fed at most
        # _MAX_CONCAT_ARGS leaves per invocation (same wide-program bound
        # as the concat path).
        by_mesh: dict = {}
        for i in na_idx:
            by_mesh.setdefault(leaves[i].sharding.mesh, []).append(i)
        for m, ids in by_mesh.items():
            rep_fn = _replicator(m)
            for lo in range(0, len(ids), _MAX_CONCAT_ARGS):
                chunk = ids[lo:lo + _MAX_CONCAT_ARGS]
                rep = rep_fn(*[leaves[i] for i in chunk])
                for i, r in zip(chunk, rep):
                    leaves[i] = r
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
    array_idx = [i for i, l in enumerate(leaves)
                 if isinstance(l, jax.Array) and l.size > 0]
    if len(array_idx) <= 2:
        return jax.device_get(tree)

    groups: dict = {}
    for i in array_idx:
        groups.setdefault(jnp.result_type(leaves[i]), []).append(i)
    out = list(leaves)
    for dt, ids in groups.items():
        for chunk_lo in range(0, len(ids), _MAX_CONCAT_ARGS):
            chunk = ids[chunk_lo:chunk_lo + _MAX_CONCAT_ARGS]
            arrs = [leaves[i] for i in chunk]
            flat = np.asarray(_concat(len(arrs), *arrs))  # ONE fetch
            offsets = np.cumsum([0] + [a.size for a in arrs])
            for i, lo, hi in zip(chunk, offsets[:-1], offsets[1:]):
                out[i] = flat[lo:hi].reshape(leaves[i].shape)
    # remaining device leaves (empty arrays) + non-arrays
    for i, l in enumerate(out):
        if isinstance(l, jax.Array):
            out[i] = np.asarray(l)
    return jax.tree_util.tree_unflatten(treedef, out)
