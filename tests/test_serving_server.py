"""Socket front-end tests: framing reuse, token auth, error taxonomy.

The wire is the remote_ps length-prefixed convention; these run the server
genuinely over loopback TCP (sibling of test_remote_ps.py).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models.mlp import MLP
from distkeras_tpu.predictors import make_forward_fn
from distkeras_tpu.serving import ServingClient, ServingEngine, ServingServer

FEATS = 64


@pytest.fixture(autouse=True)
def fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def served():
    model = MLP(features=(16,), num_classes=4)
    params = model.init(jax.random.key(0), jnp.zeros((2, FEATS)),
                        train=False)["params"]
    return model, params


def _stack(served, token=None, **engine_kw):
    model, params = served
    engine_kw.setdefault("buckets", (1, 8, 32))
    engine_kw.setdefault("max_wait_ms", 2.0)
    eng = ServingEngine(model, params, input_shape=(FEATS,), **engine_kw)
    srv = ServingServer(eng, host="127.0.0.1", token=token)
    srv.start()
    return eng, srv


def test_infer_over_the_wire_matches_local_forward(served):
    model, params = served
    eng, srv = _stack(served)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        x = np.random.default_rng(0).normal(size=(5, FEATS)) \
            .astype(np.float32)
        out = cli.infer(x)
        ref = np.asarray(jax.jit(make_forward_fn(model))(params, x))
        np.testing.assert_array_equal(out, ref)
        assert cli.ping()
        stats = cli.stats()
        assert stats["counters"]["serving.completed"] == 5
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()


def test_token_required_and_connection_dropped_on_mismatch(served):
    eng, srv = _stack(served, token="s3cret")
    try:
        good = ServingClient(f"127.0.0.1:{srv.port}", token="s3cret")
        assert good.ping()
        good.close()
        for bad_token in (None, "wrong"):
            bad = ServingClient(f"127.0.0.1:{srv.port}", token=bad_token)
            with pytest.raises(RuntimeError, match="authentication"):
                bad.ping()
            # the server hangs up after an auth failure; the retrying
            # client (PR 17) reconnects and is refused again with the
            # same typed error — a wrong token never turns into a
            # silent socket death
            with pytest.raises(RuntimeError, match="authentication"):
                bad.ping()
            # fail-fast clients (retry=None) keep the old contract: the
            # NEXT request on the hung-up connection dies at the socket
            raw = ServingClient(f"127.0.0.1:{srv.port}", token=bad_token,
                                retry=None)
            with pytest.raises(RuntimeError, match="authentication"):
                raw.ping()
            with pytest.raises((ConnectionError, OSError)):
                raw.ping()
            raw.close()
            bad.close()
        # three refused requests per bad token: two from the retrying
        # client (each reconnect re-presents the bad token), one from
        # the fail-fast client's first ping
        assert telemetry.counter("serving.server.auth_failures").value == 6
    finally:
        srv.stop()
        eng.shutdown()


def test_wrong_row_shape_is_an_error_response_not_a_crash(served):
    eng, srv = _stack(served)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        with pytest.raises(RuntimeError, match="bad_request"):
            cli.infer(np.zeros((2, FEATS + 1), np.float32))
        # the connection survives an application-level error
        assert cli.ping()
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()


def test_unknown_op_rejected(served):
    eng, srv = _stack(served)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        resp, _ = cli._roundtrip({"op": "exec"})
        assert "unknown op" in resp["error"]
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()


def test_concurrent_tcp_clients_get_their_own_rows(served):
    model, params = served
    eng, srv = _stack(served, token="t")
    fw = jax.jit(make_forward_fn(model))
    rng = np.random.default_rng(1)
    xs = [rng.normal(size=(n, FEATS)).astype(np.float32)
          for n in (1, 3, 8, 17)]
    outs: dict = {}
    try:
        def client(k):
            cli = ServingClient(f"127.0.0.1:{srv.port}", token="t")
            outs[k] = cli.infer(xs[k])
            cli.close()

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        for k, x in enumerate(xs):
            np.testing.assert_array_equal(outs[k], np.asarray(fw(params, x)))
    finally:
        srv.stop()
        eng.shutdown()


# ------------------------------------------------- generative streaming wire

@pytest.fixture(scope="module")
def lm():
    from distkeras_tpu.models.gpt import gpt_tiny

    model = gpt_tiny()
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _stack_with_generator(served, token=None, generator=None, **engine_kw):
    model, params = served
    engine_kw.setdefault("buckets", (1, 8))
    eng = ServingEngine(model, params, input_shape=(FEATS,), **engine_kw)
    srv = ServingServer(eng, host="127.0.0.1", token=token,
                        generator=generator)
    srv.start()
    return eng, srv


def test_generate_streams_and_matches_local_engine(served, lm):
    """Wire equality: the streamed frames, the final frame, and a local
    GenerationEngine run of the same prompt all agree; stream tokens
    arrive strictly before the final result lands."""
    from distkeras_tpu.serving import GenerationEngine

    model, params = lm
    gen = GenerationEngine(model, params, num_slots=2,
                           prefill_buckets=(8,))
    eng, srv = _stack_with_generator(served, generator=gen)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        prompt = np.arange(1, 7, dtype=np.int32)
        streamed = []
        res = cli.generate(prompt, max_new_tokens=9,
                           on_token=streamed.append)
        assert res.reason == "length"
        assert streamed == res.tokens.tolist()
        local = gen.generate(prompt, max_new_tokens=9).result(timeout=60)
        assert res.tokens.tolist() == local.tokens.tolist()
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()
        gen.shutdown()


def test_concurrent_streams_carry_every_token_before_the_result(served, lm):
    """Eight clients on four lanes: the engine hands a step's tokens to
    the handler threads after the next dispatch and a result behind its
    last token, so each client's frames, put together, are its final
    tokens: none is lost to the handler's done-then-empty exit."""
    from distkeras_tpu.serving import GenerationEngine

    model, params = lm
    gen = GenerationEngine(model, params, num_slots=4, queue_capacity=16,
                           prefill_buckets=(8,))
    eng, srv = _stack_with_generator(served, generator=gen)
    prompts = [np.arange(1 + k, 5 + k + k % 3, dtype=np.int32)
               for k in range(8)]
    streamed = [[] for _ in prompts]
    results = [None] * len(prompts)
    try:
        def client(k):
            cli = ServingClient(f"127.0.0.1:{srv.port}")
            results[k] = cli.generate(prompts[k], max_new_tokens=6 + 3 * k,
                                      on_token=streamed[k].append)
            cli.close()

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        for k, res in enumerate(results):
            assert res.reason == "length" and res.tokens.size == 6 + 3 * k
            assert streamed[k] == res.tokens.tolist(), k
    finally:
        srv.stop()
        eng.shutdown()
        gen.shutdown()
    assert not gen._owed


def test_generate_requires_auth(served, lm):
    from distkeras_tpu.serving import GenerationEngine

    model, params = lm
    gen = GenerationEngine(model, params, num_slots=1,
                           prefill_buckets=(8,))
    eng, srv = _stack_with_generator(served, token="s3cret", generator=gen)
    try:
        good = ServingClient(f"127.0.0.1:{srv.port}", token="s3cret")
        assert good.generate(np.arange(1, 5, dtype=np.int32),
                             max_new_tokens=2).tokens.size == 2
        good.close()
        bad = ServingClient(f"127.0.0.1:{srv.port}", token="wrong")
        with pytest.raises(RuntimeError, match="auth"):
            bad.generate(np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
        bad.close()
    finally:
        srv.stop()
        eng.shutdown()
        gen.shutdown()


def test_generate_typed_errors(served, lm):
    from distkeras_tpu.serving import GenerationEngine

    model, params = lm
    # no generator mounted -> bad_request, connection stays usable
    eng, srv = _stack_with_generator(served, generator=None)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        with pytest.raises(RuntimeError, match="bad_request"):
            cli.generate(np.arange(1, 5, dtype=np.int32))
        assert cli.ping()
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()

    gen = GenerationEngine(model, params, num_slots=1,
                           prefill_buckets=(8,))
    eng, srv = _stack_with_generator(served, generator=gen)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        # undeclared prompt shape -> bad_request (engine validation)
        with pytest.raises(RuntimeError, match="bad_request"):
            cli.generate(np.arange(1, 30, dtype=np.int32))
        # closed generator -> closed
        gen.shutdown()
        with pytest.raises(RuntimeError, match="closed"):
            cli.generate(np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
        assert cli.ping()  # the connection survived every typed error
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()


def test_status_merges_decode_state(served, lm):
    from distkeras_tpu.serving import GenerationEngine

    model, params = lm
    gen = GenerationEngine(model, params, num_slots=2, slot_ladder=(1, 2),
                           prefill_buckets=(8,))
    eng, srv = _stack_with_generator(served, generator=gen)
    try:
        cli = ServingClient(f"127.0.0.1:{srv.port}")
        resp, _ = cli._roundtrip({"op": "status"})
        assert resp["decode"]["num_slots"] == 2
        assert resp["decode"]["compiled"] == {"prefill": [8],
                                              "decode": [1, 2]}
        cli.close()
    finally:
        srv.stop()
        eng.shutdown()
        gen.shutdown()
