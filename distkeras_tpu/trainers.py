"""Trainer API — the public face of the framework.

Reference parity: ``distkeras/trainers.py`` (unverified, mount empty; see
SURVEY.md §2) defines ``Trainer`` and its zoo: ``SingleTrainer``,
``AveragingTrainer``, ``EnsembleTrainer``, and the async family ``DOWNPOUR``,
``ADAG``, ``AEASGD``, ``EAMSGD``, ``DynSGD``. The constructor-kwargs shape is
kept (model, loss, worker_optimizer, num_workers, batch_size,
communication_window, ...), but execution is TPU-native:

- a Spark executor becomes a *model replica* living on a mesh axis,
- ``mapPartitionsWithIndex(worker.train)`` becomes a ``shard_map``-ed,
  ``lax.scan``-ed local-step loop compiled once by XLA,
- the socket parameter server becomes device-resident center state updated by
  collective folds (see distkeras_tpu/parallel/),
- per-worker Keras History becomes structured jnp metrics stacked per step.

``trainer.train(dataset)`` returns the trained params pytree; the trainer
also retains ``params``, ``history`` and ``training_time`` (parity with the
reference's ``record_training_time`` bookkeeping).
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence, Union

import jax
import numpy as np
import optax

from distkeras_tpu import engine, telemetry
from distkeras_tpu import precision as precision_lib
from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.telemetry import span
from distkeras_tpu.ops import losses as losses_lib
from distkeras_tpu.ops import optimizers as opt_lib
from distkeras_tpu.utils.fetch import device_get_batched
from distkeras_tpu.utils import jax_compat


class Trainer:
    """Base trainer: holds the model spec, loss, worker optimizer, and
    training-time/history bookkeeping."""

    def __init__(self, model, loss: Union[str, Any] = "categorical_crossentropy",
                 worker_optimizer: Union[str, optax.GradientTransformation] = "sgd",
                 learning_rate: float = 0.01,
                 metrics: Sequence[str] = ("accuracy",),
                 features_col: str = "features", label_col: str = "label",
                 batch_size: int = 32, num_epoch: int = 1, seed: int = 0,
                 loss_weights=None,
                 checkpoint_dir: Optional[str] = None,
                 telemetry_path: Optional[str] = None,
                 precision: Optional[str] = None,
                 weight_publisher=None):
        self.model = model
        #: optional serving/rollout.py WeightPublisher: trained snapshots
        #: are published (monotone-versioned) per sync epoch and at the
        #: end of training, closing the train→serve loop (DESIGN.md §18)
        self.weight_publisher = weight_publisher
        self.loss = loss
        base_loss = losses_lib.get(loss)  # fail fast on unknown loss names
        # Reference Trainer holds loss_weights (Keras multi-output scaling).
        # The zoo is single-output, so the honest subset: one scalar weight
        # scaling the loss (gradients scale with it). Anything that isn't a
        # single number (multi-weight lists/arrays, Keras output-name dicts)
        # is rejected loudly rather than silently dropped.
        if loss_weights is not None:
            ws = list(np.ravel(loss_weights)) \
                if isinstance(loss_weights, (list, tuple, np.ndarray)) \
                else [loss_weights]
            if len(ws) != 1 or isinstance(ws[0], bool) or \
                    not isinstance(ws[0], (int, float, np.number)):
                raise ValueError(
                    f"loss_weights={loss_weights!r}: models here are "
                    f"single-output, so exactly ONE numeric weight is "
                    f"meaningful (a scalar or one-element list)")
            w = float(ws[0])
            self.loss = lambda logits, labels: w * base_loss(logits, labels)
        self.loss_weights = loss_weights
        self.worker_optimizer = worker_optimizer
        self.learning_rate = learning_rate
        self.metrics = tuple(metrics)
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.seed = int(seed)
        self.checkpoint_dir = checkpoint_dir
        # where to dump the telemetry JSONL artifact when train() finishes
        # (None: keep it in-process only — read it with get_telemetry())
        self.telemetry_path = telemetry_path
        if telemetry_path is not None:
            # crash-safe: a run killed mid-train (watchdog
            # checkpoint_and_raise, OOM, SIGTERM-mediated exit) still
            # leaves the artifact that explains it; the normal _stop()
            # dump later overwrites the same path with the same registry
            telemetry.flush_at_exit(telemetry_path)

        self.tx = opt_lib.get(worker_optimizer, learning_rate)
        # mixed-precision policy (DESIGN.md §11): validate EARLY, stamp the
        # policy name onto the model's `precision` field (errors if the model
        # doesn't expose one), and guard-wrap the optimizer with loss-scale
        # bookkeeping only when the policy actually scales (int8/fp8-sim) —
        # f32/bf16 policies keep the optimizer state treedef untouched.
        self.precision = precision_lib.validate_precision(precision)
        if self.precision is not None:
            self.model = precision_lib.apply_to_model(self.model,
                                                      self.precision)
            policy = precision_lib.get_policy(self.precision)
            if policy.loss_scale != 1.0:
                self.tx = precision_lib.overflow_guard(self.tx, policy)
        self.params = None
        self.history: list[dict] = []
        self.training_time: float = 0.0

    # -- checkpointing (per-epoch; the reference had NONE — SURVEY.md §5) ---
    def _checkpointer(self, local_host_only: bool = False, items=None):
        if self.checkpoint_dir is None:
            return None
        from distkeras_tpu.checkpoint import Checkpointer

        return Checkpointer(self.checkpoint_dir,
                            local_host_only=local_host_only, items=items)

    @staticmethod
    def _check_fresh_dir(ckpt) -> None:
        """A pre-existing non-empty checkpoint dir with ``resume=False`` is
        an ERROR: Orbax skips saves for steps that already exist, so keeping
        the stale steps would make the fresh run's snapshots silent no-ops
        (and a crash retry would then resume the stale previous run), while
        deleting them silently would destroy a prior run's checkpoints."""
        if ckpt.latest_step() is not None:
            raise ValueError(
                f"checkpoint_dir {ckpt.directory!r} already contains "
                f"steps {ckpt.all_steps()} but resume=False. Pass "
                "resume=True to continue that run, point checkpoint_dir "
                "at a fresh directory, or clear it explicitly "
                "(distkeras_tpu.checkpoint.Checkpointer(dir).clear())")

    @staticmethod
    def _maybe_resume(ckpt, like: dict, resume: bool) -> tuple:
        """(state_dict, start_epoch): restore the latest epoch checkpoint if
        asked and present. History is NOT checkpointed — a resumed trainer's
        history covers only the epochs it ran."""
        if ckpt is None:
            return like, 0
        if not resume:
            Trainer._check_fresh_dir(ckpt)
            return like, 0
        if ckpt.latest_step() is None:
            return like, 0
        step = ckpt.latest_step()
        return ckpt.restore(like=like), step + 1

    # -- bookkeeping (record_training_time parity) -------------------------
    def _start(self):
        # persistent XLA compilation cache, before the first compile
        # (utils/jax_compat.py: $JAX_COMPILATION_CACHE_DIR, else the
        # checkout's own .xla_cache/)
        jax_compat.enable_compilation_cache()
        # flight-recorder wiring: the telemetry plane can't import jax, so
        # the trainer pushes the process index down (multi-host artifact
        # suffixes) and points the recorder's crash bundles at the same
        # directory the crash checkpoint lands in
        telemetry.set_process_index(jax.process_index())
        from distkeras_tpu.health import recorder as flight_recorder
        import os as _os

        dump_dir = self.checkpoint_dir
        if dump_dir is None and self.telemetry_path is not None:
            dump_dir = _os.path.dirname(self.telemetry_path) or "."
        flight_recorder.configure(
            dump_dir=dump_dir,
            trainer=type(self).__name__,
            precision=self.precision,
            worker_optimizer=str(self.worker_optimizer),
            batch_size=self.batch_size,
            codec=str(getattr(self, "codec", None)),
            num_workers=getattr(self, "num_workers", 1))
        self._t0 = time.perf_counter()

    def _stop(self):
        self.training_time = time.perf_counter() - self._t0
        telemetry.gauge("trainer.training_time_s").set(self.training_time)
        if self.weight_publisher is not None and self.params is not None:
            # final snapshot publish: every trainer sets self.params
            # before _stop(), so the serving plane always sees the run's
            # end state even without per-epoch cadence
            self.weight_publisher.publish(self.params)
        # refresh the HBM gauges (peak over the run lives in the allocator's
        # peak_bytes_in_use counter); no-op on backends without memory_stats
        from distkeras_tpu import observability

        observability.hbm_stats()
        if self.telemetry_path is not None:
            self.dump_telemetry(self.telemetry_path)

    # -- telemetry (system-side observability; see DESIGN.md §5b) ----------
    def get_telemetry(self) -> dict:
        """Snapshot of the process registry (counters/gauges/histograms/
        spans). The registry is process-local, so back-to-back trainers in
        one process accumulate — call ``telemetry.reset()`` between runs
        for per-run numbers. Empty when telemetry is uninstalled."""
        reg = telemetry.get_registry()
        return reg.snapshot() if reg is not None else {}

    def dump_telemetry(self, path: str) -> Optional[str]:
        """Write the JSONL artifact (``python -m distkeras_tpu.health.summary``
        renders it); returns the path, or None when uninstalled."""
        reg = telemetry.get_registry()
        return reg.dump_jsonl(path) if reg is not None else None

    def get_training_time(self) -> float:
        return self.training_time

    def get_history(self) -> list[dict]:
        return self.history

    def get_averaged_history(self) -> dict:
        """history_executors_average parity: mean of each metric over steps
        (and over workers, where worker-major histories are recorded)."""
        if not self.history:
            return {}
        keys = self.history[0].keys()
        return {k: float(np.mean([h[k] for h in self.history])) for k in keys}

    # -- shared plumbing ----------------------------------------------------
    @staticmethod
    def _reject_global_shards(dataset, trainer_name: str):
        """Clear error instead of an opaque AttributeError when a
        GlobalShards pool reaches a trainer whose data path cannot re-deal
        files (Single/Pjit consume row streams, not per-worker shards)."""
        from distkeras_tpu.data.global_shards import GlobalShards

        if isinstance(dataset, GlobalShards):
            raise ValueError(
                f"{trainer_name} does not support GlobalShards (cross-host "
                f"shard re-dealing maps to the async zoo's host_sharded "
                f"per-worker shards); pass a Dataset — e.g. "
                f"Dataset.from_files — or use a DistributedTrainer with "
                f"data_layout='host_sharded'")

    def _init_params(self, dataset: Dataset):
        sample = next(dataset.batches(min(self.batch_size, len(dataset)),
                                      cols=[self.features_col]))
        batch = {"features": sample[self.features_col]}
        rng = jax.random.key(self.seed)
        state = engine.create_train_state(self.model, rng, batch, self.tx)
        return state

    def _batch_dict(self, raw: dict) -> dict:
        return {"features": raw[self.features_col],
                "labels": raw[self.label_col]}

    def _check_trainable(self, dataset: Dataset, effective_batch: int):
        if len(dataset) < effective_batch:
            raise ValueError(
                f"Dataset has {len(dataset)} rows but one step needs "
                f"{effective_batch}; no full batch can be formed "
                f"(static-shape batching drops the ragged tail)")

    #: whole-epoch-resident staging above this estimate warns to use the
    #: chunked knob (staging_rounds / staging_steps) instead of OOMing
    _RESIDENT_WARN_BYTES = 4 << 30

    def _resident_bytes(self, dataset: Dataset) -> int:
        """Estimated host bytes of one epoch's feature+label columns
        (0 when a column defeats the estimate)."""
        try:
            return sum(
                np.dtype(dataset[c].dtype).itemsize *
                int(np.prod(dataset[c].shape))
                for c in (self.features_col, self.label_col))
        except Exception:
            return 0

    def _warn_if_large_resident(self, dataset: Dataset, knob: str):
        total = self._resident_bytes(dataset)
        if total > self._RESIDENT_WARN_BYTES:
            import warnings

            warnings.warn(
                f"Staging the whole epoch device-resident "
                f"(~{total / 2**30:.1f} GiB). Pass {knob}= to bound device "
                f"data memory to O(chunk) with background prefetch.",
                RuntimeWarning, stacklevel=3)

    @staticmethod
    def _epoch_chunk_stream(staged, make_gen, resident: bool):
        """The shared staged/cache/prefetch pattern of every trainer's
        epoch loop: returns ``(chunks, staged)``. ``resident=True``
        materializes the generator once and reuses it every epoch;
        otherwise chunks stream through a depth-1 background prefetch
        (double buffering)."""
        if staged is not None:
            return staged, staged
        gen = make_gen()
        if resident:
            staged = list(gen)
            return staged, staged
        from distkeras_tpu.data.prefetch import prefetch

        return prefetch(gen, depth=1), None

    def train(self, dataset: Dataset, shuffle: bool = False):
        raise NotImplementedError


class DistributedTrainer(Trainer):
    """Base for every multi-replica trainer.

    Reference parity (``DistributedTrainer(num_workers, batch_size,
    features_col, label_col, num_epoch, master_port)``): same kwargs, but a
    "worker" is a mesh-axis replica instead of a Spark executor, and there is
    no master_port — the parameter server is device-resident state folded
    with collectives (the kwarg is accepted and ignored so reference driver
    scripts port cleanly).

    ``strategy_name`` selects the update algebra (see
    parallel/strategies.py + NUMERICS.md).

    Multi-process input contract: ``data_layout="replicated"`` (default —
    every process holds the full dataset) or ``"host_sharded"`` (each
    process's dataset holds only its own workers' rows; see DESIGN.md §3).
    """

    strategy_name: str = "downpour"

    def __init__(self, model, loss="categorical_crossentropy",
                 worker_optimizer="sgd", learning_rate: float = 0.01,
                 metrics=("accuracy",), features_col="features",
                 label_col="label", batch_size: int = 32, num_epoch: int = 1,
                 num_workers: Optional[int] = None,
                 communication_window: int = 5,
                 parallelism_factor: int = 1,
                 master_port: Optional[int] = None,  # parity no-op
                 mesh=None, seed: int = 0, mode: str = "sync",
                 loss_weights=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_folds: Optional[int] = None,
                 staging_rounds: Optional[int] = None,
                 data_layout: str = "replicated",
                 devices=None,
                 telemetry_path: Optional[str] = None,
                 codec: str = "raw",
                 comms_overlap: bool = False,
                 health=None,
                 accum_steps: int = 1,
                 precision: Optional[str] = None,
                 bucket_bytes: Optional[int] = None,
                 ps_shards: int = 1,
                 ps_placement: str = "process0",
                 ps_standby: bool = False,
                 weight_publisher=None,
                 data_service=None,
                 **strategy_kwargs):
        super().__init__(model, loss, worker_optimizer, learning_rate,
                         metrics, features_col, label_col, batch_size,
                         num_epoch, seed, loss_weights=loss_weights,
                         checkpoint_dir=checkpoint_dir,
                         telemetry_path=telemetry_path,
                         precision=precision,
                         weight_publisher=weight_publisher)
        from distkeras_tpu.parallel import mesh as mesh_lib

        if mode not in ("sync", "host_async"):
            raise ValueError(f"mode must be 'sync' or 'host_async', "
                             f"got {mode!r}")
        self.mode = mode
        self.parallelism_factor = int(parallelism_factor)
        if self.parallelism_factor < 1:
            raise ValueError("parallelism_factor must be >= 1")
        if mode == "host_async":
            # thread-per-worker against a live PS; no mesh sharding involved
            if mesh is not None:
                raise ValueError(
                    "mesh and mode='host_async' are contradictory: async "
                    "workers are host threads, not mesh replicas")
            self.mesh = None
            if num_workers is None:
                raise ValueError("host_async mode needs explicit num_workers")
            # host threads oversubscribe a chip natively; the factor just
            # multiplies the thread count (reference: partitions per worker)
            self.num_workers = int(num_workers) * self.parallelism_factor
            # worker k is pinned to devices[k % D] (default: all local
            # devices) so wall-clock asynchrony overlaps across chips
            self.devices = list(devices) if devices else None
        else:
            if devices is not None:
                raise ValueError(
                    "devices= is a host_async knob; sync mode places "
                    "workers via the mesh")
            self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
                num_workers)
            # K logical workers = factor x mesh devices; each device runs
            # `factor` stacked replicas (see substrate.build_epoch_fn)
            self.num_workers = (self.mesh.shape[mesh_lib.WORKER_AXIS]
                                * self.parallelism_factor)
        if checkpoint_folds is not None and mode != "host_async":
            raise ValueError(
                "checkpoint_folds is the host_async snapshot cadence; sync "
                "mode checkpoints at epoch boundaries (checkpoint_dir alone)")
        if checkpoint_folds is not None and checkpoint_dir is None:
            raise ValueError(
                "checkpoint_folds sets the snapshot cadence but "
                "checkpoint_dir is None — there is nowhere to save; pass "
                "checkpoint_dir too (silently taking no snapshots would "
                "defeat the fault tolerance you asked for)")
        # host_async snapshot cadence (commits between snapshots); defaults
        # to one full round of folds (num_workers) when checkpointing is on
        self.checkpoint_folds = checkpoint_folds
        if data_layout not in ("replicated", "host_sharded"):
            raise ValueError(
                f"data_layout must be 'replicated' (every process holds the "
                f"full dataset) or 'host_sharded' (each process's dataset "
                f"holds ONLY its own workers' rows), got {data_layout!r}")
        # host_sharded x host_async IS supported (r5): each process's
        # dataset holds only its own workers' rows and its threads commit
        # to process 0's live center over the parameter service
        # (parallel/remote_ps.py). Single-process it degenerates to
        # replicated (all workers are local).
        # Multi-process input contract. 'replicated': every process holds
        # the same full dataset and put_global carves its part (simple, but
        # each host pays full-epoch host RAM + slicing). 'host_sharded':
        # this process's dataset holds ONLY the rows of its addressable
        # workers (len = local_workers x per-worker rows), the pod-scale
        # contract — a Spark executor reading only its partitions. shuffle=
        # True then shuffles within each host's rows (cross-host shuffling
        # would need a data exchange the reference also never did).
        self.data_layout = data_layout
        # Streaming data plane (DESIGN.md §20): a DataCoordinator object
        # (or "host:port" address of one) replaces up-front staging —
        # worker threads lease permuted row ranges and ack them, so the
        # global shuffle, epoch accounting, and churn recovery live on the
        # coordinator. Orthogonal to (and exclusive with) the static
        # data_layout contracts.
        if data_service is not None:
            if mode != "host_async":
                raise ValueError(
                    "data_service= streams lease-driven rounds to "
                    "host_async worker threads; sync mode stages from a "
                    "local Dataset — use mode='host_async'")
            if data_layout != "replicated":
                raise ValueError(
                    "data_service replaces the data_layout contracts (the "
                    "coordinator leases ranges to every worker wherever "
                    "it runs); leave data_layout='replicated'")
        self.data_service = data_service
        self.communication_window = int(communication_window)
        # None: stage the whole epoch device-resident (fastest for data that
        # fits). An int bounds staging memory to O(staging_rounds) with
        # double-buffered host->device transfer (see stage_epoch_chunks).
        self.staging_rounds = staging_rounds
        self.strategy = self._make_strategy(**strategy_kwargs)
        if mode == "host_async" and not self.strategy.exchanges:
            raise ValueError(
                "host_async mode requires an exchanging strategy "
                "(DOWNPOUR/ADAG/DynSGD/AEASGD/EAMSGD)")
        # wire codec for the PS exchange + comms/compute overlap — both are
        # host_async knobs (the sync path's psum never serializes params)
        from distkeras_tpu import comms as comms_lib

        comms_lib.get_codec(codec)  # validate the name EARLY (fail at
                                    # construction, not first commit)
        if mode != "host_async" and (codec != "raw" or comms_overlap):
            raise ValueError(
                "codec/comms_overlap tune the host_async parameter-server "
                "exchange; sync mode folds commits in-graph (no wire)")
        self.codec = codec
        self.comms_overlap = bool(comms_overlap)
        # sharded parameter-server fleet (DESIGN.md §13): in cross-process
        # host_async, split the center over this many shard services on
        # process 0 (shard 0 carries the membership/lease plane). 1 = the
        # single-service protocol, wire-compatible with prior releases.
        self.ps_shards = int(ps_shards)
        if self.ps_shards < 1:
            raise ValueError(f"ps_shards must be >= 1, got {ps_shards}")
        if self.ps_shards > 1 and mode != "host_async":
            raise ValueError(
                "ps_shards shards the host_async parameter service; sync "
                "mode has no parameter server to shard")
        # shard placement + coordinator failover (DESIGN.md §17): "spread"
        # deals the shard services over processes instead of stacking them
        # on process 0; ps_standby=True runs a dark coordinator replica
        # that promotes via lease handoff when the coordinator dies.
        from distkeras_tpu.parallel.elastic import PLACEMENT_POLICIES

        if ps_placement not in PLACEMENT_POLICIES:
            raise ValueError(f"ps_placement must be one of "
                             f"{PLACEMENT_POLICIES}, got {ps_placement!r}")
        if mode != "host_async" and (ps_placement != "process0"
                                     or ps_standby):
            raise ValueError(
                "ps_placement/ps_standby configure the host_async "
                "parameter-service fleet; sync mode has no parameter "
                "server to place or fail over")
        self.ps_placement = ps_placement
        self.ps_standby = bool(ps_standby)
        # health monitoring (DESIGN.md §9): None | policy string | dict |
        # HealthConfig — normalized here so a bad policy fails at
        # construction. A fresh TrainingWatchdog is built per train() call
        # (trip state must not leak across runs). host_async runs get the
        # full live plane (stall monitor, crash-time checkpoint_fn); sync
        # mode observes the loss stream post-epoch.
        from distkeras_tpu import health as health_lib

        self.health = health_lib.resolve(health)
        # gradient-accumulation microbatching (DESIGN.md §10): each of the
        # λ local steps scans accum_steps microbatches of batch_size /
        # accum_steps rows. Same numbers (NUMERICS.md: mean-loss equivalence),
        # ~accum_steps x smaller activation footprint; λ/window accounting
        # and the staleness schedule are untouched.
        self.accum_steps = int(accum_steps)
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if self.batch_size % self.accum_steps != 0:
            raise ValueError(
                f"accum_steps={self.accum_steps} must divide "
                f"batch_size={self.batch_size}: each step is a scan over "
                f"accum_steps equal microbatches (unequal microbatches would "
                f"break the mean-loss equivalence — see NUMERICS.md)")
        # gradient-bucket collective overlap (DESIGN.md §11): sync mode's
        # in-graph psum is the only place a bucketed all-reduce exists;
        # host_async commits travel the host wire (codec/comms_overlap are
        # that path's knobs)
        if bucket_bytes is not None:
            if mode != "sync":
                raise ValueError(
                    "bucket_bytes tunes the sync substrate's in-graph grad "
                    "psum; host_async exchanges params over the host wire "
                    "(use codec=/comms_overlap= there)")
            bucket_bytes = int(bucket_bytes)
            if bucket_bytes <= 0:
                raise ValueError(
                    f"bucket_bytes must be positive, got {bucket_bytes}")
        self.bucket_bytes = bucket_bytes
        self.num_updates = 0
        self.staleness_history: list[float] = []

    def _make_strategy(self, **kw):
        from distkeras_tpu.parallel import strategies

        return strategies.get(self.strategy_name,
                              learning_rate=self.learning_rate, **kw)

    def _init_carries(self, center_params):
        from distkeras_tpu.parallel import substrate

        return substrate.init_center_and_carries(
            center_params, self.tx, self.strategy, self.mesh, self.num_workers)

    def _record(self, ms: dict, rounds: int):
        """Flatten (workers, rounds, window) metrics into worker-averaged
        per-step history + staleness bookkeeping."""
        stal = ms.pop("staleness")  # (workers, rounds)
        self.staleness_history.extend(
            float(s) for s in stal.mean(axis=0).reshape(-1))
        w, r, win = ms["loss"].shape
        wd = getattr(self, "_watchdog", None)  # sync-path health checks:
        for ri in range(r):                    # post-epoch, on the worker-
            for si in range(win):              # mean loss stream
                step = {k: float(v[:, ri, si].mean()) for k, v in ms.items()}
                self.history.append(step)
                if wd is not None:
                    wd.observe_loss(step["loss"])
        if wd is not None:
            wd.notify_progress()
        if self.strategy.exchanges:  # PS commit clock: only real commits count
            self.num_updates += rounds * self.num_workers

    def _setup_state(self, dataset: Dataset):
        """(center, carries) placement; split out so subclasses with their own
        init (Ensemble) don't pay a wasted full-model init."""
        state = self._init_params(dataset)
        return self._init_carries(state.params)

    def _resume_elastic(self, ckpt, center, carries, resume: bool):
        """Topology-aware resume: ``(center, carries, counters, start_epoch)``
        where counters = [round_offset, num_updates, saved_num_workers].

        Same worker count (the checkpoint's carries probe via
        ``Checkpointer.metadata`` — no array data read): full restore,
        bit-identical continuation, regardless of ``parallelism_factor``
        (K logical workers on D devices equal K on K by construction).

        Different worker count (SURVEY §5 slice-resize: a preempted v4-32
        job resuming on a smaller slice): restore the CENTER + counters
        only, re-initialize every worker replica from the restored center,
        and warn loudly — worker-local state (elastic replicas, momenta,
        optimizer slots) is discarded, the same trajectory break a
        reference worker rejoining a live server saw. Strategies that
        never exchange (Averaging/Ensemble) refuse: their training state
        LIVES in the per-worker replicas, so a center-only restore would
        silently discard the training itself."""
        zero = np.zeros((3,), np.int64)
        if ckpt is None:
            return center, carries, zero, 0
        if not resume:
            self._check_fresh_dir(ckpt)
            return center, carries, zero, 0
        step = ckpt.latest_step()
        if step is None:
            return center, carries, zero, 0
        # steps written before the state/carries item split keep the old
        # single-item layout in the same directory — the step directory
        # itself says which format it is (Checkpointer.step_items)
        legacy = "default" in ckpt.step_items(step)
        if legacy:
            meta = ckpt.metadata(step)
            if not isinstance(meta, dict) or "carries" not in meta or \
                    meta["carries"] is None:
                keys = sorted(meta) if isinstance(meta, dict) else type(meta)
                raise ValueError(
                    f"checkpoint step {step} in {ckpt.directory!r} has no "
                    f"'carries' item (found {keys}); it was written by a "
                    f"different mode/trainer (host_async snapshots are "
                    f"center+clock, PjitTrainer/SingleTrainer save a "
                    f"TrainState). Resume it with the mode it was written "
                    f"in.")
            carries_meta = meta["carries"]
            counters_shape = tuple(meta["counters"].shape)
        else:
            names = ckpt.step_items(step)
            if "state" not in names or "carries" not in names:
                raise ValueError(
                    f"checkpoint step {step} in {ckpt.directory!r} has "
                    f"items {names}, not the state+carries pair this "
                    f"trainer writes; it was written by a different "
                    f"mode/trainer. Resume it with the mode it was "
                    f"written in.")
            carries_meta = ckpt.metadata(step, item="carries")
            counters_shape = tuple(
                ckpt.metadata(step, item="state")["counters"].shape)
        carry_meta = jax.tree.leaves(carries_meta)
        saved_workers = int(carry_meta[0].shape[0])
        # counters length may be 2 (pre-r5 format, no worker count
        # recorded); numpy abstract = host restore, no sharding lookup
        counters_like = np.zeros(counters_shape, np.int64)

        def parse_counters(raw) -> np.ndarray:
            out = zero.copy()
            got = np.asarray(raw).ravel()
            out[:min(3, len(got))] = got[:3]
            if len(got) < 3:
                out[2] = saved_workers
            return out

        if saved_workers == self.num_workers:
            # compare saved vs current carry shapes BEFORE restoring, so a
            # strategy change is a clear naming error while genuine I/O or
            # corruption errors propagate untouched from Orbax
            saved_shapes = sorted(tuple(m.shape) for m in carry_meta)
            cur_shapes = sorted(tuple(np.shape(l))
                                for l in jax.tree.leaves(carries))
            if saved_shapes != cur_shapes:
                raise ValueError(
                    f"checkpoint step {step} matches "
                    f"num_workers={saved_workers} but its carry structure "
                    f"does not match this trainer's "
                    f"strategy ({self.strategy.name!r}); resuming needs "
                    f"the same strategy the checkpoint was written with")
            if legacy:
                snap = ckpt.restore_legacy(
                    like={"center": center, "carries": carries,
                          "counters": counters_like}, step=step)
                return (snap["center"], snap["carries"],
                        parse_counters(snap["counters"]), step + 1)
            snap = ckpt.restore(
                like={"state": {"center": center,
                                "counters": counters_like},
                      "carries": carries}, step=step)
            return (snap["state"]["center"], snap["carries"],
                    parse_counters(snap["state"]["counters"]), step + 1)
        if not self.strategy.exchanges:
            raise ValueError(
                f"Cannot elastically resume {type(self).__name__} across a "
                f"topology change (checkpoint: {saved_workers} workers, "
                f"trainer: {self.num_workers}): with the "
                f"{self.strategy.name!r} strategy the training state lives "
                f"in the per-worker replicas (the center never moves), so "
                f"a center-only restore would discard the training. Resume "
                f"with num_workers={saved_workers}.")
        import warnings

        warnings.warn(
            f"ELASTIC RESUME: checkpoint step {step} was written by a "
            f"{saved_workers}-worker run; this trainer has "
            f"{self.num_workers}. Restoring the CENTER + counters only "
            f"and re-initializing every worker replica from the restored "
            f"center — worker-local state (elastic replicas, momenta, "
            f"optimizer slots) is discarded, so the continuation is a "
            f"documented trajectory break from the uninterrupted run.",
            RuntimeWarning, stacklevel=3)
        # Restore to host numpy: numpy abstracts carry no sharding, so
        # Orbax never consults the checkpoint's sharding file (which
        # references the OLD device topology — the exact thing a
        # slice-resize resume no longer has). Only the center survives,
        # re-placed by _init_carries on the new mesh.
        center_host_like = jax.tree.map(
            lambda x: np.zeros(np.shape(x), np.asarray(x).dtype),
            device_get_batched(center))
        counters_host_like = np.zeros(counters_shape, np.int64)
        if legacy:
            # single-item step: the wrong-topology carries are structurally
            # part of the item, so they are read into host RAM and
            # discarded — the cost the state/carries split removes
            abstract_saved = jax.tree.map(
                lambda m: np.zeros(tuple(m.shape), np.dtype(str(m.dtype))),
                carries_meta)
            snap = ckpt.restore_legacy(
                like={"center": center_host_like,
                      "carries": abstract_saved,
                      "counters": counters_host_like}, step=step, host=True)
            new_center, counters_raw = snap["center"], snap["counters"]
        else:
            # split layout: read ONLY the state item — the stale carries'
            # array data never leaves disk (DESIGN.md §6)
            snap = ckpt.restore(
                like={"state": {"center": center_host_like,
                                "counters": counters_host_like}},
                step=step, host=True, items=("state",))
            new_center = snap["state"]["center"]
            counters_raw = snap["state"]["counters"]
        new_center, new_carries = self._init_carries(new_center)
        return (new_center, new_carries, parse_counters(counters_raw),
                step + 1)

    def train(self, dataset: Dataset, shuffle: bool = False,
              resume: bool = False):
        from distkeras_tpu.data.global_shards import GlobalShards
        from distkeras_tpu.parallel import substrate

        # Cross-host data mixing (r5, VERDICT r4 weak #3): a GlobalShards
        # pool re-deals shard files to hosts every epoch, restoring the
        # reference's global-shuffle semantics under the host-sharded
        # contract. dataset becomes epoch 0's local view; the epoch loop
        # re-resolves per epoch.
        provider = dataset if isinstance(dataset, GlobalShards) else None
        if provider is not None:
            if self.data_layout != "host_sharded":
                raise ValueError(
                    "GlobalShards is the cross-host mixing source for "
                    "data_layout='host_sharded'; with 'replicated' every "
                    "host already sees the full dataset — pass a Dataset "
                    "(e.g. Dataset.from_files) instead")
            dataset = provider.epoch_dataset(0)
        if self.mode == "host_async":
            if self.staging_rounds is not None:
                raise ValueError(
                    "staging_rounds is not supported in host_async mode "
                    "(worker threads stage their shards host-resident); "
                    "use mode='sync' for O(chunk) staging")
            return self._train_host_async(dataset, shuffle, resume,
                                          provider=provider)
        from distkeras_tpu.parallel import mesh as mesh_lib

        self._start()
        if self.data_layout == "host_sharded":
            # this process stages only its own mesh positions' shards
            positions = mesh_lib.local_worker_positions(self.mesh)
            if not positions:
                raise ValueError(
                    "data_layout='host_sharded' but this process owns no "
                    "devices on the mesh's workers axis — it has no shards "
                    "to stage; check the mesh construction (every "
                    "participating process must contribute worker devices)")
            n_shards = len(positions) * self.parallelism_factor
        else:
            positions, n_shards = None, self.num_workers
        if positions is None or jax.process_count() == 1:
            self._check_trainable(
                dataset,
                self.batch_size * self.communication_window * n_shards)
        # else: host_sharded multi-process — a LOCAL raise here would leave
        # peer processes hanging in the collectives ahead; insufficiency is
        # detected symmetrically by the rounds allgather in
        # stage_epoch_chunks (every process sees global min 0 and raises)
        if self.staging_rounds is None:
            self._warn_if_large_resident(dataset, "staging_rounds")
        with span("trainer.init"):
            center, carries = self._setup_state(dataset)
        # carries live in their OWN checkpoint item (DESIGN.md §6): they
        # dominate the snapshot bytes and are exactly what a topology-change
        # resume throws away, so splitting them lets that resume read only
        # the small 'state' item. Pre-split single-item steps stay readable
        # (Checkpointer.restore_legacy).
        ckpt = self._checkpointer(items=("state", "carries"))
        if ckpt is not None:
            try:
                center, carries, counters, start_epoch = \
                    self._resume_elastic(ckpt, center, carries, resume)
            except BaseException:  # don't leak the manager's threads/locks
                ckpt.close()
                raise
        else:
            center, carries, counters, start_epoch = self._resume_elastic(
                ckpt, center, carries, resume)
        # compiled once per trainer instance: every ctor arg the closure
        # depends on is fixed at construction, so repeated train() calls
        # (warm restarts, benchmark loops) reuse the jit cache instead of
        # paying a full recompile each time
        if getattr(self, "_epoch_fn", None) is None:
            # span covers tracing/jit construction; XLA compilation itself
            # is lazy — it lands inside the first trainer.epoch span
            with span("trainer.compile"):
                self._epoch_fn = substrate.build_epoch_fn(
                    self.model, self.loss, self.tx, self.strategy, self.mesh,
                    self.num_workers, self.communication_window, self.metrics,
                    dropout_seed=self.seed, accum_steps=self.accum_steps,
                    precision=self.precision,
                    bucket_bytes=self.bucket_bytes)
        epoch_fn = self._epoch_fn
        self.history = []
        self.staleness_history = []
        # fresh watchdog per train() (no trip-state leak across runs); in
        # sync mode it sees post-epoch means only, so checkpoint_and_raise
        # degrades to raise (the epoch-boundary save just above the trip is
        # the recovery point) — the live plane is mode='host_async'
        self._watchdog = self.health.make_watchdog() \
            if self.health is not None else None
        round_offset = int(counters[0])
        self.num_updates = int(counters[1])
        staged = None  # shuffle=False + whole-epoch staging: stage once
        for epoch in range(start_epoch, self.num_epoch):
            # One code path for both staging modes: staging_rounds=None is
            # the single-chunk case of the generator (whole epoch resident,
            # reusable across epochs when not shuffling). With a chunk
            # bound, the (async) epoch fn is dispatched on chunk i before
            # chunk i+1 is pulled, so host slicing + device_put overlap
            # compute; metric fetches are deferred to the epoch end so they
            # don't serialize the chunks.
            ds_epoch = provider.epoch_dataset(epoch) if provider is not None \
                else dataset
            with span("trainer.stage"):
                # resident mode materializes every chunk here; streaming
                # mode only builds the prefetch generator (the real staging
                # cost then overlaps compute inside trainer.epoch)
                chunks, staged = self._epoch_chunk_stream(
                    staged,
                    lambda: substrate.stage_epoch_chunks(
                        (ds_epoch.shuffle(self.seed + epoch)
                         if shuffle else ds_epoch).repartition(n_shards),
                        self.features_col, self.label_col, self.batch_size,
                        self.communication_window, self.mesh,
                        chunk_rounds=self.staging_rounds,
                        local_positions=positions),
                    resident=(not shuffle and self.staging_rounds is None
                              and provider is None))
            with span("trainer.epoch"):
                pending = []
                for data, rounds in chunks:
                    center, carries, ms = epoch_fn(center, carries, data,
                                                   np.int32(round_offset))
                    round_offset += rounds
                    pending.append((ms, rounds))
                for ms, rounds in pending:
                    self._record(device_get_batched(ms), rounds)
            if ckpt is not None:
                # counters[2] records the topology so a later resume can
                # detect a worker-count change before any shape restore
                ckpt.save(epoch, {
                    "state": {"center": center,
                              "counters": np.array(
                                  [round_offset, self.num_updates,
                                   self.num_workers], np.int64)},
                    "carries": carries})
            if self.weight_publisher is not None:
                # per-epoch publish cadence (DESIGN.md §18): the serving
                # plane canaries each epoch's center while training runs
                self.weight_publisher.publish(device_get_batched(center),
                                              clock=round_offset)
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        with span("trainer.finalize"):
            self.params = self._finalize(center, carries)
        self._stop()
        return self.params

    def _finalize(self, center, carries):
        """Async trainers return the parameter server's center variable."""
        return device_get_batched(center)

    def _train_host_async(self, dataset: Dataset, shuffle: bool,
                          resume: bool = False, provider=None):
        """True wall-clock asynchrony: thread-per-worker against a live PS
        (parallel/host_async.py). Staleness here is real scheduling, not the
        sync substrate's deterministic rotation.

        Checkpointing has no epoch barrier here; instead the PS center +
        server clock are snapshotted every ``checkpoint_folds`` commits
        (default: one full round, ``num_workers`` folds). ``resume=True``
        restores the latest snapshot: workers restart their data passes from
        the beginning, but pull the restored center and continue its clock —
        the same semantics as a reference worker rejoining a live server.

        Multi-process (``jax.process_count() > 1``): ``num_workers`` is the
        GLOBAL thread count, split near-evenly over processes; process 0
        owns the live center behind a socket parameter service and the
        other processes' threads pull/commit through it — TRUE cross-host
        asynchrony with real server-clock staleness (remote_ps.py). Data
        per ``data_layout``: 'replicated' slices this process's workers'
        shards out of the identical full dataset; 'host_sharded' means the
        local dataset holds ONLY this process's workers' rows. Result
        (params/history/staleness/num_updates) is identical on every
        process. Checkpointing/resume runs on process 0 alone (it owns the
        center; remote processes receive the restored center at their
        first pull)."""
        from distkeras_tpu.parallel import host_async

        self._start()
        multi = jax.process_count() > 1
        pid = jax.process_index()
        if multi:
            P = jax.process_count()
            if self.num_workers < P:
                # globally-known condition: raise SYMMETRICALLY on every
                # process (a one-sided raise would hang peers in the
                # collectives ahead)
                raise ValueError(
                    f"num_workers={self.num_workers} < process_count={P}: "
                    f"some process would own no workers")
            counts = [self.num_workers // P + (1 if i < self.num_workers % P
                                               else 0) for i in range(P)]
            worker_offset = sum(counts[:pid])
            local_workers = counts[pid]
        else:
            worker_offset, local_workers = 0, self.num_workers
        stage = None
        if self.data_service is not None:
            # Streaming data plane (DESIGN.md §20): no up-front staging —
            # each worker thread gets a lease-driven round generator
            # against the coordinator. Epochs and the global shuffle are
            # COORDINATOR state (its seed / num_epochs), so trainer-side
            # shuffle= and num_epoch do not apply here.
            if shuffle:
                raise ValueError(
                    "shuffle=True with data_service=: the coordinator "
                    "already owns the global shuffle (its seed= argument); "
                    "a second trainer-side shuffle would be dead code")
            svc = self.data_service
            svc_address = svc if isinstance(svc, str) else svc.address
            svc_token = None if isinstance(svc, str) else svc.token
        elif self.data_layout == "host_sharded" and multi:
            # local dataset = ONLY this process's workers' rows. Data
            # sufficiency is per-process state, so validate it with a tiny
            # allgather and raise on EVERY process (same hazard as the
            # sync path's rounds negotiation: a local raise leaves peers
            # hanging in share_service_address / the history barrier).
            from jax.experimental import multihost_utils

            per_round = self.batch_size * self.communication_window
            min_shard = len(dataset) // local_workers
            oks = np.asarray(multihost_utils.process_allgather(
                np.int64(min_shard // per_round))).ravel()
            if oks.min() == 0:
                short = np.flatnonzero(oks == 0).tolist()
                raise ValueError(
                    f"Process(es) {short} cannot form one round of "
                    f"window={self.communication_window} x "
                    f"batch={self.batch_size} per local worker (this host "
                    f"is process {pid} with {len(dataset)} rows over "
                    f"{local_workers} workers)")

            def stage(ds):
                return host_async.stage_worker_shards(
                    ds.repartition(local_workers), self.features_col,
                    self.label_col, self.batch_size,
                    self.communication_window)
        else:
            self._check_trainable(
                dataset,
                self.batch_size * self.communication_window
                * self.num_workers)

            def stage(ds):
                shards = ds.repartition(self.num_workers)
                return host_async.stage_worker_shards(
                    shards[worker_offset:worker_offset + local_workers],
                    self.features_col, self.label_col, self.batch_size,
                    self.communication_window)

        with span("trainer.init"):
            state = self._init_params(dataset)
        init_params, start_clock = state.params, 0
        # streaming data plane: when the trainer HOLDS the coordinator
        # object (not just its address), the shuffle cursor rides every
        # snapshot and restores on resume — the torn-coordinator recovery
        # path (DESIGN.md §20). Address-only callers checkpoint the cursor
        # themselves via DataServiceClient.cursor().
        coord_obj = self.data_service \
            if (self.data_service is not None
                and not isinstance(self.data_service, str)) else None
        snapshot_extra = None
        if coord_obj is not None:
            def snapshot_extra():
                return {"data_cursor": coord_obj.cursor_carry()}
        # process 0 alone owns the live center's snapshots; Orbax must not
        # expect its peers at any barrier (local_host_only)
        ckpt, ckpt_error = None, None
        if not multi or pid == 0:
            try:
                ckpt = self._checkpointer(local_host_only=multi)
                if ckpt is not None:
                    like = {"center": init_params,
                            "clock": np.zeros((1,), np.int64)}
                    if coord_obj is not None:
                        like["data_cursor"] = coord_obj.cursor_carry()
                    try:
                        snap, _ = self._maybe_resume(ckpt, like, resume)
                    except BaseException:
                        ckpt.close()
                        raise
                    init_params = snap["center"]
                    start_clock = int(np.asarray(snap["clock"])[0])
                    if coord_obj is not None and resume:
                        coord_obj.restore_cursor(snap["data_cursor"])
            except BaseException as e:
                if not multi:
                    raise
                ckpt_error = e  # defer: the peers must hear first
        if multi:
            # Checkpoint state is process-0-private, so a one-sided raise
            # (stale dir with resume=False, corrupt restore) would leave
            # the peers hanging in share_service_address's broadcast;
            # agree on go/no-go symmetrically before any collective.
            from jax.experimental import multihost_utils

            flags = np.asarray(multihost_utils.process_allgather(
                np.int64(0 if ckpt_error is None else 1))).ravel()
            if flags.any():
                if ckpt_error is not None:
                    raise ckpt_error
                raise ValueError(
                    f"checkpoint setup failed on process(es) "
                    f"{np.flatnonzero(flags).tolist()}; see their logs")

        def ds_for(e):
            ds = provider.epoch_dataset(e) if provider is not None \
                else dataset
            return ds.shuffle(self.seed + e) if shuffle else ds

        if self.data_service is not None:
            # one epoch_shards entry; the coordinator streams ALL its
            # epochs through it (workers lease until it reports the
            # stream exhausted), so there is no per-epoch staging and no
            # host-resident copy at all
            with span("trainer.stage"):
                epoch_shards = [[host_async.stream_worker_rounds(
                    svc_address, worker_offset + k, self.features_col,
                    self.label_col, self.batch_size,
                    self.communication_window, token=svc_token)
                    for k in range(local_workers)]]
        elif shuffle or provider is not None:
            # Per-epoch reshuffle and/or cross-host shard re-deal. Workers
            # cross epoch boundaries without barriers, so every epoch's
            # shards are staged host-resident UP FRONT — num_epoch x the
            # local shard bytes. Warn when that estimate is large (the
            # O(chunk) alternative is mode='sync' + staging_rounds).
            per_epoch = self._resident_bytes(dataset)
            if per_epoch * self.num_epoch > self._RESIDENT_WARN_BYTES:
                import warnings

                warnings.warn(
                    f"host_async with per-epoch re-staging holds every "
                    f"epoch's shards host-resident "
                    f"(~{per_epoch * self.num_epoch / 2**30:.1f} GiB for "
                    f"{self.num_epoch} epochs). For large datasets use "
                    f"mode='sync' with staging_rounds= (O(chunk) memory).",
                    RuntimeWarning, stacklevel=3)
            with span("trainer.stage"):
                epoch_shards = [stage(ds_for(e))
                                for e in range(self.num_epoch)]
        else:
            with span("trainer.stage"):
                epoch_shards = [stage(dataset)] * self.num_epoch
        if getattr(self, "_async_runner", None) is None:
            with span("trainer.compile"):
                self._async_runner = host_async.HostAsyncRunner(
                    self.model, self.loss, self.tx, self.strategy,
                    self.communication_window, self.metrics, self.seed,
                    devices=self.devices,
                    codec=self.codec, overlap=self.comms_overlap,
                    accum_steps=self.accum_steps,
                    precision=self.precision)
        runner = self._async_runner
        watchdog = None
        if self.health is not None:
            # fresh per train(): trip state must not leak across runs; the
            # runner binds checkpoint_fn (live-center snapshot) + on_trip
            watchdog = self.health.make_watchdog()
            runner.straggler = self.health.make_straggler_detector()
        folds = (self.checkpoint_folds or self.num_workers) \
            if ckpt is not None else 0
        try:
            with span("trainer.epoch"):  # one span: workers cross epoch
                if multi:                # boundaries without barriers
                    params, history, staleness, num_updates = \
                        host_async.run_cross_process(
                            runner, init_params, epoch_shards,
                            worker_offset=worker_offset, checkpointer=ckpt,
                            checkpoint_folds=folds, start_clock=start_clock,
                            watchdog=watchdog, ps_shards=self.ps_shards,
                            ps_placement=self.ps_placement,
                            ps_standby=self.ps_standby,
                            snapshot_extra=snapshot_extra)
                else:
                    params, history, staleness, num_updates = runner.run(
                        init_params, epoch_shards, checkpointer=ckpt,
                        checkpoint_folds=folds, start_clock=start_clock,
                        watchdog=watchdog, snapshot_extra=snapshot_extra)
        except BaseException:
            # postmortem bundle FIRST (ring + status + fingerprint, next to
            # the crash checkpoint), then finalize in-flight snapshots
            from distkeras_tpu.health import recorder as flight_recorder

            flight_recorder.auto_dump("trainer_exception")
            if ckpt is not None:  # crash path: finalize in-flight snapshots
                try:              # so resume sees the last completed one
                    ckpt.wait()
                finally:          # close even if the flush itself fails, and
                    ckpt.close()  # let the TRAINING error propagate
            raise
        with span("trainer.finalize"):
            # runner.run already merged history + fetched the center; what
            # remains is the final resumability snapshot and save flush
            if ckpt is not None:
                if num_updates > (ckpt.latest_step() or 0):
                    ckpt.save(num_updates,  # params already fetched to host
                              {"center": params,
                               "clock": np.array([num_updates], np.int64)})
                ckpt.wait()
                ckpt.close()
        self.history = history
        self.staleness_history = staleness
        self.num_updates = num_updates
        self.params = params
        self._stop()
        return self.params


class DOWNPOUR(DistributedTrainer):
    """Async data-parallel SGD with windowed delta push/pull (NUMERICS.md)."""

    strategy_name = "downpour"


class ADAG(DistributedTrainer):
    """DOWNPOUR with accumulated-gradient normalization — the reference's
    flagship algorithm (NUMERICS.md)."""

    strategy_name = "adag"


class DynSGD(DistributedTrainer):
    """Staleness-aware async SGD: commits scaled by 1/(staleness+1)."""

    strategy_name = "dynsgd"


class AEASGD(DistributedTrainer):
    """Async elastic-averaging SGD. Extra kwargs: rho (elastic coefficient)."""

    strategy_name = "aeasgd"

    def __init__(self, model, rho: float = 5.0, **kw):
        super().__init__(model, rho=rho, **kw)


class EAMSGD(DistributedTrainer):
    """Elastic averaging with Nesterov momentum on the local replicas.
    Extra kwargs: rho, momentum.

    The local step is the explicit Nesterov rule (η, μ) — momentum lives in
    the worker loop, matching the reference's dedicated EAMSGD worker — so
    ``worker_optimizer`` is NOT applied. Passing a non-default optimizer is
    rejected rather than silently ignored."""

    strategy_name = "eamsgd"

    def __init__(self, model, rho: float = 5.0, momentum: float = 0.9, **kw):
        opt = kw.get("worker_optimizer", "sgd")
        if opt != "sgd":
            raise ValueError(
                f"EAMSGD ignores worker_optimizer (its local step is the "
                f"explicit Nesterov rule v ← μv − η∇f(w + μv); see "
                f"NUMERICS.md), so worker_optimizer={opt!r} would silently "
                f"not be what you asked for. Leave it at the default, or "
                f"use AEASGD if you want an optax worker optimizer.")
        super().__init__(model, rho=rho, momentum=momentum, **kw)


class AveragingTrainer(DistributedTrainer):
    """Train K isolated replicas on K shards, return the arithmetic mean of
    their weights (reference AveragingTrainer semantics)."""

    strategy_name = "independent"

    def _finalize(self, center, carries):
        from distkeras_tpu.utils.trees import tree_scale

        summed = jax.jit(
            lambda c: jax.tree.map(lambda x: x.sum(axis=0), c))(carries.params)
        return device_get_batched(tree_scale(summed, 1.0 / self.num_workers))


class EnsembleTrainer(DistributedTrainer):
    """Train K isolated models, return all K param sets (list). Each replica
    gets a distinct init (seed + worker index) and its own data shard."""

    strategy_name = "independent"

    def _setup_state(self, dataset: Dataset):
        from distkeras_tpu.parallel import mesh as mesh_lib

        col = dataset[self.features_col]  # shape/dtype only — stays lazy
        sample = np.zeros((1,) + tuple(col.shape[1:]), col.dtype)
        keys = jax.random.split(jax.random.key(self.seed), self.num_workers)

        def init_one(k):
            variables = self.model.init(k, sample, train=False)
            return self.strategy.init_carry(variables["params"], self.tx)

        stacked = jax.vmap(init_one)(keys)
        carries = mesh_lib.put_worker_sharded(stacked, self.mesh)
        center = mesh_lib.put_replicated(
            jax.tree.map(lambda x: x[0], device_get_batched(stacked.params)),
            self.mesh)
        return center, carries

    def _finalize(self, center, carries):
        host = device_get_batched(carries.params)
        return [jax.tree.map(lambda x, i=i: x[i], host)
                for i in range(self.num_workers)]


class PjitTrainer(Trainer):
    """Sync data-parallel (× tensor-parallel) trainer on the GSPMD path.

    BASELINE config 5 ("pjit-sharded data-parallel", ViT-L): the batch is
    sharded over the ``workers`` mesh axis, params optionally over ``model``
    via partition rules (parallel/tensor.py), and XLA inserts every
    collective. This is the throughput-first sync alternative to the async
    zoo — no parameter server semantics, just compiled SPMD.
    """

    def __init__(self, model, loss="categorical_crossentropy",
                 worker_optimizer="sgd", learning_rate: float = 0.01,
                 metrics=("accuracy",), features_col="features",
                 label_col="label", batch_size: int = 32, num_epoch: int = 1,
                 num_workers: Optional[int] = None,
                 model_parallelism: int = 1, partition_rules=None,
                 mesh=None, seed: int = 0, loss_weights=None,
                 checkpoint_dir: Optional[str] = None,
                 staging_steps: Optional[int] = None,
                 data_layout: str = "replicated",
                 telemetry_path: Optional[str] = None,
                 accum_steps: int = 1,
                 precision: Optional[str] = None,
                 bucket_bytes: Optional[int] = None):
        super().__init__(model, loss, worker_optimizer, learning_rate,
                         metrics, features_col, label_col, batch_size,
                         num_epoch, seed, loss_weights=loss_weights,
                         checkpoint_dir=checkpoint_dir,
                         telemetry_path=telemetry_path,
                         precision=precision)
        from distkeras_tpu.parallel import mesh as mesh_lib

        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            num_workers, model_parallelism=model_parallelism)
        self.num_workers = self.mesh.shape[mesh_lib.WORKER_AXIS]
        self.partition_rules = partition_rules
        # None: whole epoch device-resident; int: O(staging_steps) chunks
        # with double-buffered device_put (see tensor.stage_step_chunks).
        self.staging_steps = staging_steps
        if data_layout not in ("replicated", "host_sharded"):
            raise ValueError(
                f"data_layout must be 'replicated' or 'host_sharded', "
                f"got {data_layout!r}")
        # Multi-process input contract, mirroring DistributedTrainer:
        # 'replicated' = every process holds the full dataset;
        # 'host_sharded' = this process's dataset holds ONLY its own
        # workers' batch rows, consumed as consecutive per-step sub-batches
        # (global step s = position-ordered concat of every process's rows
        # [s*local_batch : (s+1)*local_batch)). shuffle=True shuffles
        # within each host's rows.
        self.data_layout = data_layout
        if self.batch_size % self.num_workers != 0:
            raise ValueError(
                f"batch_size {self.batch_size} must be divisible by "
                f"num_workers {self.num_workers} (the batch is the GLOBAL "
                f"batch, sharded over the workers axis)")
        # gradient-accumulation microbatching (DESIGN.md §10). Each
        # microbatch must still shard evenly over the workers axis, so the
        # PER-DEVICE batch is what accum_steps has to divide.
        self.accum_steps = int(accum_steps)
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if (self.batch_size // self.num_workers) % self.accum_steps != 0:
            raise ValueError(
                f"accum_steps={self.accum_steps} must divide the per-device "
                f"batch {self.batch_size // self.num_workers} "
                f"(global batch_size {self.batch_size} / num_workers "
                f"{self.num_workers}) so each microbatch shards evenly over "
                f"the workers axis")
        # gradient-bucket overlap (DESIGN.md §11): explicit shard_map DP
        # step with per-bucket psums. Validated here AND in
        # tensor.build_pjit_epoch_fn (the mesh check lives there); the
        # model-parallel incompatibility is a construction-time error.
        if bucket_bytes is not None:
            bucket_bytes = int(bucket_bytes)
            if bucket_bytes <= 0:
                raise ValueError(
                    f"bucket_bytes must be positive, got {bucket_bytes}")
            if self.mesh.shape.get(mesh_lib.MODEL_AXIS, 1) > 1:
                raise ValueError(
                    f"bucket_bytes={bucket_bytes} (explicit bucketed grad "
                    f"all-reduce) requires a pure data-parallel mesh, but "
                    f"model_parallelism="
                    f"{self.mesh.shape[mesh_lib.MODEL_AXIS]} shards params "
                    f"over the model axis — GSPMD's implicit model-parallel "
                    f"collectives do not compose with explicit shard_map "
                    f"psums")
        self.bucket_bytes = bucket_bytes

    def train(self, dataset: Dataset, shuffle: bool = False,
              resume: bool = False):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distkeras_tpu.parallel import mesh as mesh_lib, tensor

        self._reject_global_shards(dataset, "PjitTrainer")
        self._start()
        if self.data_layout == "host_sharded":
            positions = mesh_lib.local_worker_positions(self.mesh)
            if not positions:
                raise ValueError(
                    "data_layout='host_sharded' but this process owns no "
                    "devices on the mesh's workers axis — it has no batch "
                    "rows to stage")
            local_batch = (self.batch_size // self.num_workers) \
                * len(positions)
        else:
            positions, local_batch = None, self.batch_size
        max_steps = None
        if positions is not None and jax.process_count() > 1:
            # negotiate the common step count (and validate symmetrically:
            # a one-sided local raise would hang peers in collectives)
            from jax.experimental import multihost_utils

            step_counts = np.asarray(multihost_utils.process_allgather(
                np.int64(len(dataset) // local_batch))).ravel()
            max_steps = int(step_counts.min())
            if max_steps == 0:
                short = np.flatnonzero(step_counts == 0).tolist()
                raise ValueError(
                    f"Process(es) {short} cannot form one local batch "
                    f"(per-process step counts {step_counts.tolist()}; "
                    f"this host is process {jax.process_index()} with "
                    f"{len(dataset)} rows, local batch {local_batch})")
        else:
            self._check_trainable(dataset, local_batch)
        if self.staging_steps is None:
            self._warn_if_large_resident(dataset, "staging_steps")
        with span("trainer.init"):
            state = self._init_params(dataset)
        if getattr(self, "_pjit_fns", None) is None:
            with span("trainer.compile"):
                self._pjit_fns = tensor.build_pjit_epoch_fn(
                    self.model, self.loss, self.tx, self.mesh, self.metrics,
                    self.partition_rules, dropout_seed=self.seed,
                    accum_steps=self.accum_steps,
                    precision=self.precision,
                    bucket_bytes=self.bucket_bytes)
        epoch_fn, place_state, place_data = self._pjit_fns
        if positions is not None:
            data_sharding = NamedSharding(
                self.mesh, P(None, mesh_lib.WORKER_AXIS))
            mesh_workers = self.mesh.shape[mesh_lib.WORKER_AXIS]

            def place_data(data):  # noqa: F811 — host-sharded placement
                return mesh_lib.put_host_sharded(
                    data, data_sharding, mesh_workers, positions)
        state = place_state(state)
        ckpt = self._checkpointer()
        snap, start_epoch = self._maybe_resume(
            ckpt, {"state": state, "counters": np.zeros((1,), np.int64)},
            resume)
        state = snap["state"]
        self.history = []
        staged = None  # shuffle=False + whole-epoch staging: place once
        step_offset = int(np.asarray(snap["counters"])[0])
        for epoch in range(start_epoch, self.num_epoch):
            # Same single code path as DistributedTrainer.train: the
            # staging_steps=None default is the one-chunk case, cached
            # across epochs when not shuffling.
            with span("trainer.stage"):
                chunks, staged = self._epoch_chunk_stream(
                    staged,
                    lambda: ((place_data(data), steps)
                             for data, steps in tensor.stage_step_chunks(
                                 dataset.shuffle(self.seed + epoch)
                                 if shuffle else dataset,
                                 self.features_col, self.label_col,
                                 local_batch, chunk_steps=self.staging_steps,
                                 max_steps=max_steps)),
                    resident=not shuffle and self.staging_steps is None)
            with span("trainer.epoch"):
                pending = []
                for data, steps in chunks:
                    state, ms = epoch_fn(state, data, np.int32(step_offset))
                    step_offset += steps
                    pending.append((ms, steps))
                for ms, steps in pending:
                    host = device_get_batched(ms)
                    self.history.extend(
                        {k: float(v[i]) for k, v in host.items()}
                        for i in range(steps))
            if ckpt is not None:
                ckpt.save(epoch, {"state": state,
                                  "counters": np.array([step_offset],
                                                       np.int64)})
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        with span("trainer.finalize"):
            self.params = device_get_batched(state.params)
        self._stop()
        return self.params


class SingleTrainer(Trainer):
    """One replica, plain minibatch SGD — the reference's minimum slice
    (SingleTrainer: coalesce to one partition, train locally).

    ``staging_steps=None`` (default) stages the whole epoch device-resident
    once and reuses it every epoch; an int bounds device data memory to
    O(staging_steps) chunks streamed with background prefetch — use it when
    the dataset doesn't fit in HBM.
    """

    def __init__(self, *args, staging_steps: Optional[int] = None,
                 accum_steps: int = 1, **kwargs):
        super().__init__(*args, **kwargs)
        self.staging_steps = staging_steps
        # gradient-accumulation microbatching (DESIGN.md §10)
        self.accum_steps = int(accum_steps)
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if self.batch_size % self.accum_steps != 0:
            raise ValueError(
                f"accum_steps={self.accum_steps} must divide "
                f"batch_size={self.batch_size}: each step is a scan over "
                f"accum_steps equal microbatches")

    def train(self, dataset: Dataset, shuffle: bool = False,
              resume: bool = False):
        from distkeras_tpu.parallel import tensor

        self._reject_global_shards(dataset, "SingleTrainer")
        self._start()
        if shuffle:
            dataset = dataset.shuffle(self.seed)
        self._check_trainable(dataset, self.batch_size)
        if self.staging_steps is None:
            self._warn_if_large_resident(dataset, "staging_steps")
        with span("trainer.init"):
            state = self._init_params(dataset)
        ckpt = self._checkpointer()
        snap, start_epoch = self._maybe_resume(ckpt, {"state": state}, resume)
        state = snap["state"]
        # whole staged chunks scanned in ONE device call each — numerics
        # identical to the old per-batch step loop (same rng-fold of
        # state.step), but without a host dispatch per minibatch
        if getattr(self, "_epoch_fn", None) is None:
            with span("trainer.compile"):
                self._epoch_fn = engine.make_epoch_fn(
                    self.model, self.loss, self.tx, metrics=self.metrics,
                    dropout_seed=self.seed, accum_steps=self.accum_steps,
                    precision=self.precision)
        epoch_fn = self._epoch_fn
        staged = None
        device_history = []  # device arrays; fetched once at the end
        for epoch in range(start_epoch, self.num_epoch):
            with span("trainer.stage"):
                chunks, staged = self._epoch_chunk_stream(
                    staged,
                    lambda: (jax.device_put(
                        {"features": data["features"],
                         "labels": data["labels"]})
                        for data, _ in tensor.stage_step_chunks(
                            dataset, self.features_col, self.label_col,
                            self.batch_size, chunk_steps=self.staging_steps)),
                    resident=self.staging_steps is None)
            with span("trainer.epoch"):
                for data in chunks:
                    state, ms = epoch_fn(state, data)
                    device_history.append(ms)
            if ckpt is not None:
                ckpt.save(epoch, {"state": state})
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        with span("trainer.finalize"):
            self.history = []
            for ms in device_get_batched(device_history):
                steps = len(next(iter(ms.values())))
                self.history.extend({k: float(v[i]) for k, v in ms.items()}
                                    for i in range(steps))
            self.params = device_get_batched(state.params)
        self._stop()
        return self.params
