"""Tensor parallelism: param partition rules + GSPMD train step.

Not a reference-parity obligation (dist-keras has no TP — SURVEY.md §2), but
a first-class capability of this framework: BASELINE config 5 names
"pjit-sharded data-parallel" for ViT-L, and large transformer models need
their matmuls split over the ``model`` mesh axis.

Design (the scaling-book recipe): pick a mesh (workers × model), annotate
param shardings by PATH RULES (regex -> PartitionSpec), shard the batch over
``workers``, jit, and let GSPMD insert the collectives (all-reduce of grads
over workers, all-gather/reduce-scatter around the model-sharded matmuls).
No hand-written collectives on this path at all.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu import engine
from distkeras_tpu import precision as precision_lib
from distkeras_tpu.parallel import collectives
from distkeras_tpu.parallel import mesh as mesh_lib
from distkeras_tpu.utils.jax_compat import shard_map

Rules = Sequence[Tuple[str, P]]

# Default rules for the in-tree model zoo (transformer + conv families).
# First match wins; unmatched params replicate. Megatron-style pairing:
# column-parallel into the nonlinearity, row-parallel out of it.
DEFAULT_RULES: Rules = (
    (r"attn/qkv/kernel$", P(None, mesh_lib.MODEL_AXIS)),
    (r"attn/out/kernel$", P(mesh_lib.MODEL_AXIS, None)),
    (r"mlp/fc1/kernel$", P(None, mesh_lib.MODEL_AXIS)),
    (r"mlp/fc2/kernel$", P(mesh_lib.MODEL_AXIS, None)),
    (r"tok_embed/embedding$", P(mesh_lib.MODEL_AXIS, None)),  # vocab-sharded
    (r"mlm_head/kernel$", P(None, mesh_lib.MODEL_AXIS)),
    (r"head/kernel$", P(None, mesh_lib.MODEL_AXIS)),
    (r"dense.*/kernel$", P(None, mesh_lib.MODEL_AXIS)),
)


def path_str(path) -> str:
    """jax tree path -> 'a/b/c' string for rule matching."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def partition_specs(params: Any, rules: Optional[Rules] = None,
                    mesh: Optional[Mesh] = None) -> Any:
    """PartitionSpec pytree for ``params`` by first-match path rules.

    A matched spec is kept only if every named axis divides the corresponding
    param dimension (tiny test models fall back to replication rather than
    erroring out).
    """
    rules = DEFAULT_RULES if rules is None else tuple(rules)
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    axis_sizes = dict(mesh.shape) if mesh is not None else {}

    def spec_for(path, leaf):
        name = path_str(path)
        for pat, spec in compiled:
            if pat.search(name):
                return spec if _spec_fits(spec, leaf, axis_sizes) else P()
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _spec_fits(spec: P, leaf, axis_sizes: dict) -> bool:
    """True when every named axis of ``spec`` divides the matching dim."""
    if len(spec) > np.ndim(leaf):
        return False
    for dim, axis in enumerate(spec):
        if axis is None:
            continue
        size = axis_sizes.get(axis)
        if size and np.shape(leaf)[dim] % size != 0:
            return False
    return True


def shard_params(params: Any, mesh: Mesh,
                 rules: Optional[Rules] = None) -> Any:
    """Place ``params`` on the mesh according to the rules."""
    specs = partition_specs(params, rules, mesh)
    return mesh_lib.put_global(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P)))


def build_pjit_epoch_fn(model, loss, tx: optax.GradientTransformation,
                        mesh: Mesh, metrics: Sequence[str] = (),
                        rules: Optional[Rules] = None,
                        dropout_seed: int = 0, accum_steps: int = 1,
                        precision: Optional[str] = None,
                        bucket_bytes: Optional[int] = None):
    """Sync data-parallel (× tensor-parallel) epoch: scan over staged steps.

    Returns ``(epoch_fn, place_state, place_data)``:
    - ``epoch_fn(state, data, step_offset) -> (state, metrics)`` — jitted,
      state donated; ``data`` leaves are [steps, batch, ...] with batch
      sharded over ``workers``.
    - ``place_state(state)`` / ``place_data(data)`` put pytrees on the mesh
      with the matching shardings.

    ``accum_steps > 1`` scans each step over that many microbatches
    (engine.make_accum_grad_fn), splitting the per-step batch on its leading
    axis — under GSPMD that axis is already sharded over ``workers``, so each
    device accumulates over its own rows and the psum stays once per
    optimizer step.

    ``precision`` selects a PrecisionPolicy for the loss-scaling side of the
    grad fns (the model's own ``precision`` field governs its compute; the
    trainer stamps both from one knob). With a guard-wrapped optimizer the
    step reads the live scale out of ``opt_state``; otherwise the static
    policy scale applies.

    ``bucket_bytes`` switches the step from GSPMD's implicit grad
    all-reduce to an EXPLICIT shard_map data-parallel step whose gradient
    psums are issued per size-targeted bucket (parallel/collectives.py), so
    each bucket's all-reduce overlaps the rest of backward. Explicit
    collectives and GSPMD's model-axis collectives do not compose, so this
    mode requires a pure data-parallel mesh (``model`` axis of size 1).

    This is the honest sync-DP fast path (BASELINE config 5): one compiled
    program, grads all-reduced by GSPMD, params optionally model-sharded.
    """
    metric_names = tuple(metrics)
    accum_steps = int(accum_steps)
    if accum_steps > 1:
        grad_fn = engine.make_accum_grad_fn(model, loss, accum_steps,
                                            metric_names, precision=precision)
    else:
        grad_fn = engine.make_grad_fn(model, loss, precision=precision)
    base_key = jax.random.key(dropout_seed)
    num_workers = mesh.shape[mesh_lib.WORKER_AXIS]
    if bucket_bytes is not None and mesh.shape.get(mesh_lib.MODEL_AXIS, 1) > 1:
        raise ValueError(
            f"bucket_bytes={bucket_bytes} requests explicit bucketed grad "
            f"all-reduce, which requires a pure data-parallel mesh; this "
            f"mesh shards the model axis over "
            f"{mesh.shape[mesh_lib.MODEL_AXIS]} devices (GSPMD's implicit "
            f"model-parallel collectives do not compose with explicit "
            f"shard_map psums — drop bucket_bytes or use model=1)")

    def one_step_body(st, batch, rng, fold):
        """Shared step body; ``fold(loss, grads, aux, batch)`` injects the
        cross-worker reduction (identity under GSPMD, bucketed psum under
        shard_map)."""
        scale = precision_lib.current_scale(st.opt_state)
        (loss_val, aux), grads = grad_fn(st.params, batch,
                                         {"dropout": rng},
                                         loss_scale=scale)
        loss_val, grads, metric_out = fold(loss_val, grads, aux, batch)
        updates, opt_state = tx.update(grads, st.opt_state, st.params)
        params = optax.apply_updates(st.params, updates)
        out = {"loss": loss_val}
        out.update(metric_out)
        return engine.TrainState(step=st.step + 1, params=params,
                                 opt_state=opt_state), out

    def gspmd_fold(loss_val, grads, aux, batch):
        out = {}
        for name in metric_names:
            if accum_steps > 1:
                out[name] = engine.finalize_metric(aux[name])
            else:
                out[name] = engine.compute_metric(name, aux,
                                                  batch["labels"])
        return loss_val, grads, out

    def bucketed_fold(loss_val, grads, aux, batch):
        # per-shard means over equal-sized shards: pmean == global mean
        grads = collectives.bucketed_psum(grads, mesh_lib.WORKER_AXIS,
                                          bucket_bytes)
        grads = jax.tree.map(lambda g: g / num_workers, grads)
        loss_val = jax.lax.pmean(loss_val, mesh_lib.WORKER_AXIS)
        out = {}
        for name in metric_names:
            if accum_steps > 1:
                # (num, den) terms sum exactly across workers
                out[name] = engine.finalize_metric(
                    jax.lax.psum(aux[name], mesh_lib.WORKER_AXIS))
            else:
                out[name] = jax.lax.pmean(
                    engine.compute_metric(name, aux, batch["labels"]),
                    mesh_lib.WORKER_AXIS)
        return loss_val, grads, out

    def make_epoch(fold, decorrelate_rng):
        def epoch(state, data, step_offset):
            def one_step(st, xs):
                batch, i = xs
                rng = jax.random.fold_in(base_key, step_offset + i)
                if decorrelate_rng:
                    rng = jax.random.fold_in(
                        rng, jax.lax.axis_index(mesh_lib.WORKER_AXIS))
                return one_step_body(st, batch, rng, fold)

            steps = jax.tree.leaves(data)[0].shape[0]
            idx = jnp.arange(steps, dtype=jnp.int32)
            return jax.lax.scan(one_step, state, (data, idx))
        return epoch

    if bucket_bytes is None:
        epoch = make_epoch(gspmd_fold, decorrelate_rng=False)
    else:
        epoch = shard_map(
            make_epoch(bucketed_fold, decorrelate_rng=True),
            mesh=mesh,
            in_specs=(P(), P(None, mesh_lib.WORKER_AXIS), P()),
            out_specs=(P(), P()),
            # the body sums gradients itself (bucketed_psum); with the
            # varying-axes check on, grads of the replicated params arrive
            # already summed and would be summed twice
            check_vma=False)

    data_sharding = NamedSharding(mesh, P(None, mesh_lib.WORKER_AXIS))

    def place_state(state):
        # Optimizer-state subtrees that mirror the param tree (adam's mu/nu,
        # momentum buffers — optax states are params-shaped pytrees) take the
        # params' shardings STRUCTURALLY, leaf for leaf — otherwise TP's
        # memory savings are lost to replicated 2x-param optimizer state.
        # Matching by tree structure (not leaf shape) keeps two same-shaped,
        # differently-sharded params from colliding onto one spec.
        specs = partition_specs(state.params, rules, mesh)
        param_treedef = jax.tree.structure(state.params)
        axis_sizes = dict(mesh.shape)
        is_spec = lambda x: isinstance(x, P)

        def params_like(sub):
            try:
                return jax.tree.structure(sub) == param_treedef
            except Exception:
                return False

        def opt_subtree_shardings(sub):
            if params_like(sub):
                return jax.tree.map(
                    lambda spec, leaf: NamedSharding(
                        mesh,
                        spec if _spec_fits(spec, leaf, axis_sizes) else P()),
                    specs, sub, is_leaf=is_spec)
            return jax.tree.map(lambda _: NamedSharding(mesh, P()), sub)

        return engine.TrainState(
            step=mesh_lib.put_global(state.step, NamedSharding(mesh, P())),
            params=shard_params(state.params, mesh, rules),
            opt_state=mesh_lib.put_global(
                state.opt_state,
                jax.tree.map(opt_subtree_shardings, state.opt_state,
                             is_leaf=params_like)))

    def place_data(data):
        return mesh_lib.put_global(data, data_sharding)

    epoch_fn = jax.jit(epoch, donate_argnums=(0,))
    return epoch_fn, place_state, place_data


def stage_steps(dataset, features_col: str, label_col: str, batch_size: int,
                max_steps: Optional[int] = None) -> tuple:
    """[steps, batch, ...] arrays from a Dataset (global batch; the mesh
    shards the batch dim over workers at device_put). Whole-epoch-resident;
    see :func:`stage_step_chunks` for O(chunk) staging."""
    n = len(dataset)
    steps = n // batch_size
    if max_steps is not None:
        steps = min(steps, max_steps)
    if steps == 0:
        raise ValueError(f"{n} rows cannot form one batch of {batch_size}")
    cut = steps * batch_size

    def stack(col):
        arr = np.asarray(dataset[col][:cut])
        return arr.reshape((steps, batch_size) + arr.shape[1:])

    return {"features": stack(features_col),
            "labels": stack(label_col)}, steps


def stage_step_chunks(dataset, features_col: str, label_col: str,
                      batch_size: int, chunk_steps: Optional[int] = None,
                      max_steps: Optional[int] = None):
    """Yield ``(host_data, steps)`` chunks of at most ``chunk_steps`` steps,
    keeping staging memory O(chunk) instead of O(epoch). The caller places
    each chunk with the epoch fn's ``place_data`` (an async ``device_put``),
    so staging chunk *i+1* overlaps compute on chunk *i*. The final chunk
    may be ragged (one extra compilation)."""
    n = len(dataset)
    steps = n // batch_size
    if max_steps is not None:
        steps = min(steps, max_steps)
    if steps == 0:
        raise ValueError(f"{n} rows cannot form one batch of {batch_size}")
    if chunk_steps is None:
        chunk_steps = steps
    # columns stay lazy (views/memmaps/ShardedColumns); materialize per
    # chunk so file-backed datasets stream from disk in O(chunk) pieces
    arrs = {"features": dataset[features_col],
            "labels": dataset[label_col]}
    for start in range(0, steps, chunk_steps):
        cnt = min(chunk_steps, steps - start)
        lo = start * batch_size
        hi = lo + cnt * batch_size
        yield {key: np.asarray(a[lo:hi]).reshape(
                   (cnt, batch_size) + tuple(a.shape[1:]))
               for key, a in arrs.items()}, cnt
