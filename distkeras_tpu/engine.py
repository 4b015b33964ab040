"""Core step engine: TrainState + jit-compiled update steps.

This is the TPU-native replacement for what the reference delegates to Keras:
``model.compile`` + ``train_on_batch`` inside each Spark executor
(``distkeras/workers.py`` — unverified, mount empty; see SURVEY.md). Instead
of an eager per-batch call into a TF1 session, the whole update step —
forward, backward, optimizer — is a single pure function traced once by XLA,
so it tiles onto the MXU and fuses elementwise work into the matmuls.

Design rules honored here:
- static shapes only; the data pipeline pads/drops ragged tails,
- no Python control flow inside the step,
- state is donated so XLA updates parameters in place in HBM.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from distkeras_tpu.ops import losses as losses_lib
from distkeras_tpu import precision as precision_lib
from distkeras_tpu.utils.trees import global_norm

Batch = dict  # {"features": ..., "labels": ...} plus model-specific keys
ApplyFn = Callable[..., jax.Array]


@struct.dataclass
class TrainState:
    """Replicated training state: the analogue of one worker's compiled model.

    The parameter-server 'center variable' of the reference is a TrainState's
    ``params`` living replicated (or sharded) on device, not a pickled dict on
    a driver socket thread.
    """

    step: jax.Array
    params: Any
    opt_state: Any


def create_train_state(model, rng, sample_batch: Batch,
                       tx: optax.GradientTransformation) -> TrainState:
    """Initialize params + optimizer state from a sample batch (shapes only)."""
    x = sample_batch["features"]
    variables = model.init(rng, x, train=False)
    params = variables["params"]
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=tx.init(params))


def make_loss_fn(model, loss) -> Callable:
    """(params, batch, rngs) -> (scalar loss, logits). Resolves Keras-style
    loss names. Logits ride along as aux so metrics reuse the forward pass.

    The forward pass runs with ``mutable=["losses"]`` so auxiliary losses
    sown by modules (e.g. the Switch-MoE load-balance term, already scaled
    by the module's own weight) are folded into the objective — every
    trainer gets them for free."""
    loss_fn = losses_lib.get(loss)

    def compute(params, batch: Batch, rngs: Optional[dict] = None):
        kwargs = {"rngs": rngs} if rngs else {}
        logits, mutated = model.apply(
            {"params": params}, batch["features"], train=True,
            mutable=["losses"], **kwargs)
        total = loss_fn(logits, batch["labels"])
        for aux in jax.tree.leaves(mutated.get("losses", {})):
            total = total + jnp.sum(aux)
        return total, logits

    return compute


def compute_metric_terms(name: str, logits: jax.Array,
                         labels: jax.Array) -> tuple:
    """(numerator, denominator) f32 pair of one metric over one (micro)batch.

    The pair is SUMMABLE: adding the terms of k microbatches and finalizing
    (:func:`finalize_metric`) gives exactly the metric of the concatenated
    batch — the property gradient accumulation needs, which a mean of
    per-microbatch ratios does NOT have for masked accuracy (microbatches
    carry different valid-position counts).
    """
    if name in ("accuracy", "acc", "categorical_accuracy", "masked_accuracy"):
        pred = jnp.argmax(logits, axis=-1)
        if labels.ndim == logits.ndim - 1:  # integer labels
            valid = labels >= 0
            hit = jnp.where(valid, (pred == labels), False)
            return (jnp.sum(hit.astype(jnp.float32)),
                    jnp.sum(valid.astype(jnp.float32)))
        true = jnp.argmax(labels, axis=-1)
        return (jnp.sum((pred == true).astype(jnp.float32)),
                jnp.float32(pred.size))
    if name == "loss":  # already reported separately
        raise ValueError("'loss' is always recorded; don't list it in metrics")
    raise ValueError(f"Unknown metric {name!r}; supported: 'accuracy', "
                     "'masked_accuracy'")


def finalize_metric(terms: tuple) -> jax.Array:
    """num/den of accumulated metric terms (den clamped: an all-masked
    batch reports 0, not NaN)."""
    num, den = terms
    return num / jnp.maximum(den, 1.0)


def compute_metric(name: str, logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Keras-style training metrics over one batch.

    Integer-label accuracy ignores positions with label < 0 (the masked_lm
    ignore convention) so 'accuracy' is meaningful for MLM training too;
    'masked_accuracy' is an explicit alias.
    """
    return finalize_metric(compute_metric_terms(name, logits, labels))


def make_train_step(model, loss, tx: optax.GradientTransformation,
                    with_metrics: bool = True,
                    metrics: tuple = (),
                    dropout_seed: int = 0,
                    accum_steps: int = 1,
                    precision=None) -> Callable:
    """Build the jitted single-replica train step.

    Returns ``step(state, batch) -> (state, metrics)`` where metrics is a dict
    of scalar device arrays (loss, grad_norm, requested metrics). Already
    jitted with donated state. A per-step dropout rng is derived by folding
    the step counter into ``dropout_seed``, so stochastic layers just work.

    ``accum_steps=k`` splits each batch into k microbatches scanned
    sequentially, summing gradients in f32 and applying the optimizer ONCE —
    the memory-for-compute trade (NUMERICS.md: equals the full-batch step on
    the mean-loss objective). The batch's leading dim must be divisible by k.
    """
    one_step = _make_step_body(model, loss, tx, with_metrics, metrics,
                               dropout_seed, accum_steps,
                               precision=precision)
    return jax.jit(one_step, donate_argnums=(0,))


def _split_microbatches(batch: Batch, k: int) -> Batch:
    """[k*m, ...] batch leaves -> [k, m, ...]; loud error on a ragged split."""

    def split(x):
        b = x.shape[0]
        if b % k != 0:
            raise ValueError(
                f"accum_steps={k} must divide the per-step batch "
                f"(got a leaf with leading dim {b})")
        return x.reshape((k, b // k) + x.shape[1:])

    return jax.tree.map(split, batch)


def make_accum_grad_fn(model, loss, accum_steps: int,
                       metric_names: tuple = (),
                       precision=None) -> Callable:
    """Gradient-accumulation counterpart of :func:`make_grad_fn`, same
    contract: ``(params, batch, rngs) -> ((loss, aux), grads)`` — so every
    strategy's ``local_step`` composes with it unchanged.

    The [k*m, ...] batch is scanned as k microbatches of m rows; per-
    microbatch grads are summed in f32 and divided by k, which equals the
    full-batch mean-loss gradient exactly (equal microbatch sizes make the
    mean of means the overall mean). Peak activation memory is that of ONE
    microbatch. ``aux`` is ``{metric: (num, den)}`` f32 term pairs (see
    :func:`compute_metric_terms`) rather than logits — re-materializing
    full-batch logits (for MLM, [batch, seq, vocab]) would hand back the
    memory the microbatching just saved.

    The dropout key is folded per microbatch index, so stochastic layers
    see k independent masks (they cannot see the one full-batch mask — the
    parity guarantee is for the deterministic objective; see NUMERICS.md).

    Aux losses sown from batch statistics (e.g. the Switch-MoE load-balance
    term) are computed per microbatch and averaged — a batch-statistics
    dependence analogous to BatchNorm's, documented rather than hidden.
    """
    compute_loss = make_loss_fn(model, loss)
    k = int(accum_steps)
    if k < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    metric_names = tuple(metric_names)
    policy, scaling = _loss_scaling(precision)

    def grad_fn(params, batch: Batch, rngs: Optional[dict] = None,
                loss_scale=None):
        micro = _split_microbatches(batch, k)
        if scaling is None:
            scale = None
        else:
            scale = jnp.float32(policy.loss_scale) if loss_scale is None \
                else loss_scale

        def body(acc, xs):
            batch_i, i = xs
            rngs_i = None if rngs is None else {
                name: jax.random.fold_in(key, i)
                for name, key in rngs.items()}
            if scale is None:
                (l, logits), g = jax.value_and_grad(
                    compute_loss, has_aux=True)(params, batch_i, rngs_i)
            else:
                # per-microbatch loss scaling; the f32 SUM below is of the
                # scaled grads — unscaled once after the scan (exact for
                # power-of-two scales)
                def scaled(p, b, r):
                    l, logits = compute_loss(p, b, r)
                    return scaling[0](l, scale), (l, logits)

                (_, (l, logits)), g = jax.value_and_grad(
                    scaled, has_aux=True)(params, batch_i, rngs_i)
            terms = {name: compute_metric_terms(name, logits,
                                                batch_i["labels"])
                     for name in metric_names}
            loss_acc, terms_acc, grads_acc = acc
            grads_acc = jax.tree.map(
                lambda a, gi: a + gi.astype(jnp.float32), grads_acc, g)
            terms_acc = jax.tree.map(lambda a, t: a + t, terms_acc, terms)
            return (loss_acc + l.astype(jnp.float32), terms_acc,
                    grads_acc), None

        zeros_like_f32 = lambda t: jax.tree.map(
            lambda x: jnp.zeros(jnp.shape(x), jnp.float32), t)
        init = (jnp.float32(0.0),
                {name: (jnp.float32(0.0), jnp.float32(0.0))
                 for name in metric_names},
                zeros_like_f32(params))
        (loss_sum, terms, grad_sum), _ = jax.lax.scan(
            body, init, (micro, jnp.arange(k, dtype=jnp.int32)))
        if scale is not None:
            grad_sum = scaling[1](grad_sum, scale)
        grads = jax.tree.map(
            lambda g, p: (g / k).astype(jnp.asarray(p).dtype),
            grad_sum, params)
        return (loss_sum / k, terms), grads

    return grad_fn


def _make_step_body(model, loss, tx: optax.GradientTransformation,
                    with_grad_norm: bool, metrics: tuple,
                    dropout_seed: int, accum_steps: int = 1,
                    precision=None) -> Callable:
    """The ONE unjitted step body shared by :func:`make_train_step` and
    :func:`make_epoch_fn` — keeping them numerically identical by
    construction, not by hand-synced copies. ``accum_steps > 1`` swaps the
    full-batch grad for the scanned microbatch accumulation
    (:func:`make_accum_grad_fn`); the optimizer still applies once per step,
    so ``state.step`` counts OPTIMIZER steps either way.

    ``precision=`` threads a loss-scaling policy into the grad fn; when
    ``tx`` is ``precision.overflow_guard``-wrapped, the LIVE loss scale is
    read out of the optimizer state (``current_scale``) and fed forward —
    the dynamic skip-and-rescale loop closes here."""
    metric_names = tuple(metrics)
    base_key = jax.random.key(dropout_seed)
    accum_steps = int(accum_steps)
    if accum_steps > 1:
        accum_grad = make_accum_grad_fn(model, loss, accum_steps,
                                        metric_names, precision=precision)

        def one_step(state: TrainState, batch: Batch):
            rngs = {"dropout": jax.random.fold_in(base_key, state.step)}
            scale = precision_lib.current_scale(state.opt_state)
            (loss_val, terms), grads = accum_grad(state.params, batch, rngs,
                                                  loss_scale=scale)
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            out = {"loss": loss_val}
            if with_grad_norm:
                out["grad_norm"] = global_norm(grads)
            for name in metric_names:
                out[name] = finalize_metric(terms[name])
            return TrainState(step=state.step + 1, params=params,
                              opt_state=opt_state), out

        return one_step
    grad_fn = make_grad_fn(model, loss, precision=precision)

    def one_step(state: TrainState, batch: Batch):
        rngs = {"dropout": jax.random.fold_in(base_key, state.step)}
        scale = precision_lib.current_scale(state.opt_state)
        (loss_val, logits), grads = grad_fn(state.params, batch, rngs,
                                            loss_scale=scale)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        out = {"loss": loss_val}
        if with_grad_norm:
            out["grad_norm"] = global_norm(grads)
        for name in metric_names:
            out[name] = compute_metric(name, logits, batch["labels"])
        return TrainState(step=state.step + 1, params=params,
                          opt_state=opt_state), out

    return one_step


def make_epoch_fn(model, loss, tx: optax.GradientTransformation,
                  metrics: tuple = (), dropout_seed: int = 0,
                  accum_steps: int = 1, precision=None) -> Callable:
    """Scanned single-replica epoch: the whole staged chunk in ONE device
    call.

    ``epoch(state, data) -> (state, metrics)`` where ``data`` leaves are
    [steps, batch, ...] and metrics values are [steps] arrays. Numerics are
    identical to looping :func:`make_train_step` over the same batches by
    construction — both scan/loop the same :func:`_make_step_body` — but a
    whole epoch costs one dispatch instead of one per step (a dispatch is
    a host round trip on any TPU host). ``accum_steps=k`` microbatches
    each step (see :func:`make_train_step`).
    """
    one_step = _make_step_body(model, loss, tx, True, metrics, dropout_seed,
                               accum_steps, precision=precision)

    def epoch(state: TrainState, data: Batch):
        return jax.lax.scan(one_step, state, data)

    return jax.jit(epoch, donate_argnums=(0,))


def _loss_scaling(precision):
    """(policy, (pre, post)) when the policy actively loss-scales, else
    (policy, None). f32/bf16 default to scale 1.0 — no scaling code at
    all, so those paths stay bitwise-identical to precision=None."""
    policy = precision_lib.get_policy(precision)
    if policy is None or policy.loss_scale == 1.0:
        return policy, None
    return policy, precision_lib.scale_grads_fn(policy)


def make_grad_fn(model, loss, precision=None) -> Callable:
    """(params, batch) -> ((loss, logits), grads); building block for the
    parallel substrate where the optimizer application happens per-strategy.

    ``precision=`` (DESIGN.md §11): a quantizing policy scales the loss by
    the policy's loss scale before ``grad`` and unscales the gradients in
    f32 after (exact for the power-of-two scales used), guarding low-
    precision backward passes against underflow-to-zero gradient noise.
    The reported loss is the UNSCALED one. The optional ``loss_scale``
    call kwarg lets a step body feed the LIVE scale from an
    ``overflow_guard``-wrapped optimizer state; strategies that call with
    three arguments get the policy's static scale — documented asymmetry.
    """
    compute_loss = make_loss_fn(model, loss)
    policy, scaling = _loss_scaling(precision)
    if scaling is None:
        def grad_fn(params, batch: Batch, rngs: Optional[dict] = None,
                    loss_scale=None):
            return jax.value_and_grad(compute_loss, has_aux=True)(
                params, batch, rngs)

        return grad_fn
    pre, post = scaling

    def grad_fn(params, batch: Batch, rngs: Optional[dict] = None,
                loss_scale=None):
        scale = jnp.float32(policy.loss_scale) if loss_scale is None \
            else loss_scale

        def scaled(p, b, r):
            l, logits = compute_loss(p, b, r)
            return pre(l, scale), (l, logits)

        (_, (loss_val, logits)), grads = jax.value_and_grad(
            scaled, has_aux=True)(params, batch, rngs)
        return (loss_val, logits), post(grads, scale)

    return grad_fn


def make_eval_step(model) -> Callable:
    """Jitted forward pass: (params, features) -> logits."""

    def forward(params, x):
        return model.apply({"params": params}, x, train=False)

    return jax.jit(forward)
