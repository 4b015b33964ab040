"""Gradient-bucket collective overlap (DESIGN.md §11).

The contract is EXACTNESS, not approximation: issuing the grad psum as
several per-bucket variadic psums performs the same per-leaf reductions
as the whole-tree psum, so every bucketed trajectory must be bitwise the
unbucketed one (f32 models) — across the dp-sync substrate, the pjit
explicit-DP mode, and their accum_steps compositions. Speed is the
benchmark's problem (a cell of BENCHMARK.json); correctness lives here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.parallel import collectives


def _max_leaf_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# -- partition layer --------------------------------------------------------

def test_partition_buckets_reversed_and_exhaustive():
    # reversed index order approximates backward completion order
    assert collectives.partition_buckets([4, 4, 4, 4], 8) == [[3, 2], [1, 0]]
    # ragged tail stays its own bucket (never merged backward)
    assert collectives.partition_buckets([4, 4, 4], 8) == [[2, 1], [0]]
    # oversized leaf closes its bucket immediately
    assert collectives.partition_buckets([4, 100, 4], 8) == [[2, 1], [0]]
    # every index appears exactly once, whatever the target
    for target in (1, 7, 64, 10**9):
        buckets = collectives.partition_buckets([3, 11, 5, 2, 8], target)
        flat = [i for b in buckets for i in b]
        assert sorted(flat) == [0, 1, 2, 3, 4], (target, buckets)


def test_partition_buckets_rejects_nonpositive():
    with pytest.raises(ValueError, match="positive"):
        collectives.partition_buckets([4, 4], 0)
    with pytest.raises(ValueError, match="positive"):
        collectives.partition_buckets([4, 4], -8)


def test_bucketed_psum_bitwise_matches_whole_tree():
    from jax.sharding import PartitionSpec as P

    from distkeras_tpu.parallel import mesh as mesh_lib
    from distkeras_tpu.utils.jax_compat import shard_map

    mesh = mesh_lib.make_mesh()
    n = mesh.shape[mesh_lib.WORKER_AXIS]
    rng = np.random.default_rng(0)
    tree = {"a": rng.standard_normal((n, 33, 7)).astype(np.float32),
            "b": rng.standard_normal((n, 128)).astype(np.float32),
            "c": {"d": rng.standard_normal((n, 5)).astype(np.float32)}}

    def reduce_with(bucket_bytes):
        fn = shard_map(
            lambda t: collectives.bucketed_psum(
                t, mesh_lib.WORKER_AXIS, bucket_bytes),
            mesh=mesh, in_specs=(P(mesh_lib.WORKER_AXIS),),
            out_specs=P(mesh_lib.WORKER_AXIS))
        return jax.jit(fn)(tree)

    ref = reduce_with(None)  # the whole-tree psum
    for bucket_bytes in (1, 64, 512, 1 << 20):
        out = reduce_with(bucket_bytes)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- end-to-end trajectory parity across substrates -------------------------

def _mlp_dataset(n=128, seed=0):
    from distkeras_tpu.data.dataset import Dataset

    rng = np.random.default_rng(seed)
    return Dataset({
        "features": rng.standard_normal((n, 784)).astype(np.float32),
        "label": rng.integers(0, 10, (n,)).astype(np.int32)})


def _train(cls, bucket_bytes, accum=1, **kw):
    from distkeras_tpu.models import mnist_mlp

    t = cls(mnist_mlp(), loss="sparse_categorical_crossentropy",
            learning_rate=0.05, batch_size=32, num_epoch=1,
            metrics=("accuracy",), accum_steps=accum,
            bucket_bytes=bucket_bytes, **kw)
    params = t.train(_mlp_dataset())
    return params, t.get_history()


@pytest.mark.parametrize("substrate", ["dp_sync", "pjit"])
@pytest.mark.parametrize("accum", [1, 2])
def test_bucketed_trajectory_bitwise_parity(substrate, accum):
    """bucket_bytes must not change a single bit of the f32 trajectory —
    tiny buckets (one leaf each), mid-size (ragged tail), and effectively
    whole-tree all reduce to the same per-leaf sums.

    One carve-out: pjit + accum_steps > 1 is ulp-level, not bitwise —
    GSPMD all-reduces inside each microbatch's backward while the
    explicit mode accumulates locally and psums once, so the summation
    ORDER differs (float associativity). Everything else is exact."""
    from distkeras_tpu import DistributedTrainer, PjitTrainer

    if substrate == "dp_sync":
        cls, kw = DistributedTrainer, dict(num_workers=2,
                                           communication_window=2)
    else:
        cls, kw = PjitTrainer, dict(num_workers=2)
    ulp_level = substrate == "pjit" and accum > 1
    p_ref, h_ref = _train(cls, None, accum=accum, **kw)
    for bucket_bytes in (64, 16384, 1 << 30):
        p, h = _train(cls, bucket_bytes, accum=accum, **kw)
        diff = _max_leaf_diff(p_ref, p)
        assert diff <= (1e-7 if ulp_level else 0.0), (bucket_bytes, diff)
        assert len(h) == len(h_ref)
        for s_ref, s in zip(h_ref, h):
            if ulp_level:
                np.testing.assert_allclose(s_ref["loss"], s["loss"],
                                           rtol=1e-6)
                np.testing.assert_allclose(s_ref["accuracy"], s["accuracy"],
                                           atol=1e-6)
            else:
                np.testing.assert_array_equal(s_ref["loss"], s["loss"])
                np.testing.assert_array_equal(s_ref["accuracy"],
                                              s["accuracy"])


def test_bucketed_with_precision_trains():
    """bucket_bytes composes with a quantized policy (shard_map step reads
    the live guard scale; smoke-level: it runs and the loss is finite)."""
    from distkeras_tpu import PjitTrainer

    p, h = _train(PjitTrainer, 16384, num_workers=2, precision="int8")
    assert np.isfinite(h[-1]["loss"])


# -- validation -------------------------------------------------------------

def test_bucket_bytes_rejected_off_the_sync_path():
    from distkeras_tpu import DistributedTrainer
    from distkeras_tpu.models import mnist_mlp

    with pytest.raises(ValueError, match="sync"):
        DistributedTrainer(mnist_mlp(), num_workers=2, batch_size=32,
                           mode="host_async", bucket_bytes=1 << 20)
    with pytest.raises(ValueError, match="positive"):
        DistributedTrainer(mnist_mlp(), num_workers=2, batch_size=32,
                           bucket_bytes=0)


def test_bucket_bytes_rejected_with_model_parallelism():
    import jax as _jax

    from distkeras_tpu import PjitTrainer
    from distkeras_tpu.models import mnist_mlp

    if len(_jax.devices()) < 4:
        pytest.skip("needs >= 4 devices for a 2x2 mesh")
    with pytest.raises(ValueError, match="data-parallel"):
        PjitTrainer(mnist_mlp(), num_workers=2, model_parallelism=2,
                    batch_size=32, bucket_bytes=1 << 20)
