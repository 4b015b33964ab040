"""Wire codecs: identity, error bounds, error feedback, negotiation,
and end-to-end convergence parity of quantized async training."""

import socket

import numpy as np
import pytest

from distkeras_tpu import comms, synthetic_mnist
from distkeras_tpu.comms.chunking import iter_chunks, leaf_buffer, send_buffers
from distkeras_tpu.models.mlp import MLP


def _model():
    return MLP(features=(32,), num_classes=10)


# -- codec unit tests -------------------------------------------------------

DTYPES = ["float32", "float16", "int32", "uint8"]


@pytest.mark.parametrize("dtype", DTYPES + ["bfloat16"])
def test_raw_codec_identity(dtype):
    import ml_dtypes

    dt = np.dtype(getattr(ml_dtypes, dtype, dtype))
    rng = np.random.default_rng(0)
    arr = rng.normal(0, 3, (4, 5)).astype(dt) \
        if dt.kind not in "iu" else rng.integers(0, 100, (4, 5)).astype(dt)
    codec = comms.get_codec("raw")
    blob = codec.encode(arr)
    out = codec.decode(bytes(blob), arr.shape, dt)
    assert out.dtype == dt
    np.testing.assert_array_equal(out.view(np.uint8), arr.view(np.uint8))


@pytest.mark.parametrize("name", ["f16", "bf16"])
def test_cast_codecs_bounded_error_and_int_passthrough(name):
    codec = comms.get_codec(name)
    rng = np.random.default_rng(1)
    arr = rng.normal(0, 1, (64,)).astype(np.float32)
    blob = codec.encode(arr)
    assert len(bytes(blob)) == arr.nbytes // 2, "cast must halve the wire"
    out = codec.decode(bytes(blob), arr.shape, arr.dtype)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, arr, atol=0, rtol=1e-2)
    ints = np.arange(7, dtype=np.int64)
    out = codec.decode(bytes(codec.encode(ints)), ints.shape, ints.dtype)
    np.testing.assert_array_equal(out, ints)  # integers are exact


def test_quant_codec_error_bound():
    codec = comms.get_codec("int8")
    rng = np.random.default_rng(2)
    arr = rng.normal(0, 0.1, (1000,)).astype(np.float32)
    blob = codec.encode(arr, kind="commit")
    assert len(blob) == 8 + arr.size, "8B scale/lo prefix + 1B per element"
    out = codec.decode(blob, arr.shape, arr.dtype, kind="commit")
    step = (arr.max() - arr.min()) / 255
    # rint quantization: error is at most half a step (+ fp slack)
    assert np.max(np.abs(out - arr)) <= step * 0.5 + 1e-7


def test_quant_codec_constant_leaf_exact():
    codec = comms.get_codec("int8")
    arr = np.full((3, 3), 0.25, np.float32)
    out = codec.decode(codec.encode(arr, kind="commit"),
                       arr.shape, arr.dtype, kind="commit")
    np.testing.assert_array_equal(out, arr)


def test_quant_codec_pulls_are_f16():
    codec = comms.get_codec("int8")
    arr = np.linspace(-1, 1, 16, dtype=np.float32)
    blob = bytes(codec.encode(arr, kind="pull"))
    assert len(blob) == arr.nbytes // 2  # f16 cast, not 8+n quantization
    out = codec.decode(blob, arr.shape, arr.dtype, kind="pull")
    np.testing.assert_allclose(out, arr, atol=1e-3)


def test_int8_codec_cuts_commit_bytes_3x_on_float32_delta_tree():
    """Bytes on the wire for one commit of an MLP-shaped float32 delta
    tree: the int8 codec sends at least 3x fewer than raw, and raw sends
    exactly the tree's bytes."""
    import jax
    import jax.numpy as jnp

    params = MLP(features=(256, 128), num_classes=10).init(
        jax.random.key(0), jnp.zeros((2, 784)))["params"]
    rng = np.random.default_rng(0)
    leaves = [rng.normal(0.0, 0.01, l.shape).astype(np.float32)
              for l in jax.tree.leaves(params)]
    raw_bytes = sum(l.nbytes for l in leaves)
    wire = {name: sum(len(comms.get_codec(name).encode(l, kind="commit"))
                      for l in leaves)
            for name in ("raw", "int8")}
    assert wire["raw"] == raw_bytes
    assert raw_bytes / wire["int8"] >= 3.0, wire


def test_quant_codec_wrong_length_raises():
    codec = comms.get_codec("int8")
    with pytest.raises(ValueError, match="does not match leaf"):
        codec.decode(b"\x00" * 12, (16,), np.float32, kind="commit")


def test_get_codec_unknown_raises():
    with pytest.raises(ValueError, match="Unknown codec"):
        comms.get_codec("zstd")


def test_negotiate_rule():
    assert comms.negotiate("int8", ("raw", "int8")) == "int8"
    assert comms.negotiate("int8", ("raw",)) == "raw"
    assert comms.negotiate("raw", ()) == "raw"  # raw is always legal


# -- error feedback ---------------------------------------------------------

def test_error_feedback_invariant():
    """Sum of decoded commits tracks the sum of true deltas to within one
    step's quantization error — the residual carries what each encode
    dropped into the next commit instead of losing it."""
    ef = comms.ErrorFeedback("int8")
    codec = comms.get_codec("int8")
    rng = np.random.default_rng(3)
    specs = [((50,), np.dtype(np.float32))]
    true_sum = np.zeros(50, np.float32)
    dec_sum = np.zeros(50, np.float32)
    for _ in range(40):
        delta = rng.normal(0, 0.01, 50).astype(np.float32)
        true_sum += delta
        (blob,) = ef.encode_leaves([delta], specs)
        dec_sum += codec.decode(bytes(blob), (50,), np.float32,
                                kind="commit")
    # without feedback the worst case is 40 half-steps of independent error;
    # with it the cumulative gap stays within ~one step
    step = 4 * 0.01 / 255  # generous bound on one encode's range/255
    assert np.max(np.abs(dec_sum - true_sum)) <= 2 * step, \
        np.max(np.abs(dec_sum - true_sum))


def test_error_feedback_integer_leaves_passthrough():
    ef = comms.ErrorFeedback("int8")
    specs = [((4,), np.dtype(np.int32))]
    arr = np.arange(4, dtype=np.int32)
    (blob,) = ef.encode_leaves([arr], specs)
    np.testing.assert_array_equal(np.frombuffer(bytes(blob), np.int32), arr)


# -- chunking ---------------------------------------------------------------

def test_leaf_buffer_is_bytes_view():
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    buf = leaf_buffer(arr)
    assert bytes(buf) == arr.tobytes()


def test_iter_chunks_covers_everything():
    data = np.arange(1000, dtype=np.uint8)
    chunks = list(iter_chunks(memoryview(data), chunk_bytes=256))
    assert sum(len(c) for c in chunks) == 1000
    assert b"".join(bytes(c) for c in chunks) == data.tobytes()


def test_send_buffers_over_socketpair():
    a, b = socket.socketpair()
    try:
        bufs = [leaf_buffer(np.arange(n, dtype=np.float32))
                for n in (3, 700)]
        total = sum(len(x) for x in bufs)
        sent = send_buffers(a, bufs, chunk_bytes=64)
        assert sent == total
        got = b""
        while len(got) < total:
            got += b.recv(65536)
        assert got == b"".join(bytes(x) for x in bufs)
    finally:
        a.close()
        b.close()


# -- EncodedParameterServer -------------------------------------------------

def test_encoded_ps_tracks_raw_center():
    from distkeras_tpu.parameter_servers import DeltaParameterServer

    rng = np.random.default_rng(4)
    params = {"w": rng.normal(0, 0.1, (20,)).astype(np.float32)}
    raw_ps = DeltaParameterServer(dict(params))
    enc_ps = comms.EncodedParameterServer(
        DeltaParameterServer(dict(params)), "int8")
    for _ in range(30):
        delta = {"w": rng.normal(0, 0.005, (20,)).astype(np.float32)}
        raw_ps.commit(delta)
        enc_ps.commit(delta)
    assert enc_ps.num_updates == raw_ps.num_updates == 30
    raw_c, _ = raw_ps.pull()
    enc_c, _ = enc_ps.ps.pull()  # unwrapped: the exact folded center
    # error feedback keeps the folded stream within ~one quantization step
    assert np.max(np.abs(np.asarray(enc_c["w"])
                         - np.asarray(raw_c["w"]))) < 1e-3


# -- end-to-end: quantized async training converges -------------------------

def test_quantized_downpour_convergence_parity():
    """DOWNPOUR through the int8 wire (EncodedParameterServer numerics)
    must converge like the raw run — the error-feedback acceptance."""
    from distkeras_tpu import DOWNPOUR

    finals = {}
    for codec in ("raw", "int8"):
        ds = synthetic_mnist(n=1024)
        t = DOWNPOUR(_model(), mode="host_async", num_workers=4,
                     worker_optimizer="sgd", learning_rate=0.05,
                     batch_size=32, communication_window=4, num_epoch=3,
                     codec=codec, seed=0)
        t.train(ds, shuffle=True)
        h = t.get_history()
        first = np.mean([x["loss"] for x in h[:10]])
        last = np.mean([x["loss"] for x in h[-10:]])
        assert last < first * 0.8, (codec, first, last)
        finals[codec] = last
    # async scheduling is nondeterministic; parity = same convergence
    # regime, not bit equality
    assert finals["int8"] < finals["raw"] * 1.5 + 0.1, finals


def test_adag_overlap_converges_and_counts_commits():
    """The double-buffered loop must neither lose nor duplicate commits,
    and must still train (ADAG here; clock bookkeeping is codec-free)."""
    from distkeras_tpu import ADAG

    ds = synthetic_mnist(n=1024)
    t = ADAG(_model(), mode="host_async", num_workers=4,
             worker_optimizer="sgd", learning_rate=0.05,
             batch_size=16, communication_window=2, num_epoch=2,
             comms_overlap=True)
    t.train(ds, shuffle=True)
    # every worker's every round committed exactly once
    assert t.num_updates == 4 * (1024 // 4 // (16 * 2)) * 2
    assert len(t.staleness_history) == t.num_updates
    assert all(s >= 0 for s in t.staleness_history)
    h = t.get_history()
    assert np.mean([x["loss"] for x in h[-10:]]) \
        < np.mean([x["loss"] for x in h[:10]])


def test_codec_is_host_async_only():
    from distkeras_tpu import DOWNPOUR

    with pytest.raises(ValueError, match="host_async"):
        DOWNPOUR(_model(), num_workers=2, codec="int8")
    with pytest.raises(ValueError, match="Unknown codec"):
        DOWNPOUR(_model(), mode="host_async", num_workers=2, codec="gzip")


# -- negotiation over a real socket ----------------------------------------

def test_service_negotiation_fallback():
    """A server built with codecs=("raw",) must refuse int8 in the hello;
    both ends drop to raw and the exchange stays exact."""
    import jax

    from distkeras_tpu.parallel import remote_ps as rps
    from distkeras_tpu.parameter_servers import DeltaParameterServer

    params = {"w": np.linspace(-1, 1, 32, dtype=np.float32)}
    service = rps.ParameterServerService(
        DeltaParameterServer(params), params, token="t",
        codecs=("raw",))
    service.start()
    client = rps.RemoteParameterServer(
        f"127.0.0.1:{service.port}", params, token="t", codec="int8")
    try:
        assert client.negotiated == "raw"
        center, clock = client.pull()
        np.testing.assert_array_equal(np.asarray(center["w"]), params["w"])
        delta = {"w": np.full(32, 0.5, np.float32)}
        client.commit(delta, last_update=clock)
        center, _ = client.pull()
        np.testing.assert_allclose(np.asarray(center["w"]),
                                   params["w"] + 0.5, rtol=1e-6)
    finally:
        client.close()
        service.stop()


def test_service_grants_requested_codec():
    from distkeras_tpu.parallel import remote_ps as rps
    from distkeras_tpu.parameter_servers import DeltaParameterServer

    rng = np.random.default_rng(5)
    params = {"w": rng.normal(0, 0.1, (64,)).astype(np.float32)}
    service = rps.ParameterServerService(
        DeltaParameterServer(dict(params)), params, token="t")
    service.start()
    client = rps.RemoteParameterServer(
        f"127.0.0.1:{service.port}", params, token="t", codec="int8")
    try:
        assert client.negotiated == "int8"
        center, clock = client.pull()  # f16-cast pull
        np.testing.assert_allclose(np.asarray(center["w"]), params["w"],
                                   atol=1e-3)
        delta = {"w": rng.normal(0, 0.01, (64,)).astype(np.float32)}
        client.commit(delta, last_update=clock)
        center, _ = client.pull()
        np.testing.assert_allclose(np.asarray(center["w"]),
                                   params["w"] + delta["w"], atol=2e-3)
    finally:
        client.close()
        service.stop()
