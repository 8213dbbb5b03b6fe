"""Network-fault chaos matrix for the HTTP serving gateway.

The robustness proof of the gateway tentpole: every hostile-wire
scenario — slow-loris body, mid-stream client disconnect, malformed/
truncated/oversized frames, a stalled backend, SIGTERM mid-stream —
terminates deterministically with the contracted wire code
(docs/lm_serving.md), leaks zero handler threads and zero decode
slots (asserted via statusz occupancy + ``threading.active_count``),
and emits exactly one wide event per request.

Driven end to end: a REAL ``TokenServer`` over a tiny TransformerLM
(the expensive fixtures are module-scoped; each scenario gets its own
throwaway ``Gateway``, so thread accounting brackets every test), and
the wire-level injectors from ``mxnet_tpu.testing.faults`` — raw
sockets only, stdlib HTTP client only, whole file runs in seconds on
CPU.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import events, generate, nd
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gateway import Gateway
from mxnet_tpu.serving_async import Cancelled
from mxnet_tpu.testing import faults

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))

from transformer_lm import TransformerLM  # noqa: E402

VOCAB, D_MODEL, N_HEADS, N_LAYERS, MAX_LEN = 48, 32, 2, 2, 24


@pytest.fixture(scope="module")
def lm():
    mx.random.seed(0)
    net = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL,
                       n_heads=N_HEADS, n_layers=N_LAYERS,
                       max_len=MAX_LEN)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 4), np.float32)))
    return net


@pytest.fixture(scope="module")
def eng(lm):
    return generate.PagedGenerationEngine(
        lm, slots=3, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        sampling=generate.SamplingConfig(greedy=True))


@pytest.fixture(scope="module")
def server(eng):
    srv = generate.TokenServer(eng, queue_depth=8)
    # warm the compiled programs off every scenario's clock
    srv.generate(np.array([1, 2, 3], np.int32), timeout=120,
                 max_new_tokens=2)
    yield srv
    srv.close(drain=False, timeout=5)


@pytest.fixture
def registry():
    tel.enable()
    tel.reset()
    events.enable(path="", sample=1.0)
    events.reset()
    yield tel
    events.reset()
    events.disable()
    tel.reset()
    tel.disable()


def _gw_events():
    return [e for e in events.recent() if e["kind"] == "gateway_request"]


def _wait(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError("timed out waiting for %s" % msg)


def _assert_no_leaks(baseline_threads, server):
    """The matrix's shared postcondition: handler threads unwound,
    zero open gateway streams, zero occupied decode slots."""
    _wait(lambda: threading.active_count() <= baseline_threads,
          msg="handler threads to unwind (baseline %d, now %d)"
          % (baseline_threads, threading.active_count()))
    _wait(lambda: tel.GATEWAY_OPEN_STREAMS.value() == 0,
          msg="gateway open_streams -> 0")
    _wait(lambda: server.stats()["active"] == 0
          and server.stats()["free_slots"] == 3,
          msg="decode slots to free")
    sub = tel.statusz()["subsystems"]
    assert sub["gateway"]["open_streams"] == 0
    assert sub["decode"]["active_slots"] == 0


# ---------------------------------------------------------------------------
# the matrix
# ---------------------------------------------------------------------------

def test_slow_loris_body_cut_408(registry, server):
    baseline = threading.active_count()
    with Gateway(port=0, read_timeout_s=0.4) as gw:
        gw.add_route("lm", server)
        body = json.dumps({"tokens": [1, 2, 3]})
        t0 = time.monotonic()
        status, raw = faults.slow_loris_post(
            "127.0.0.1", gw.port, "/v1/generate/lm", body,
            trickle_delay_s=0.15, bytes_per_trickle=1)
        took = time.monotonic() - t0
        assert status == 408, raw[:200]
        assert took < 8.0, "slow-loris held a handler %.1fs" % took
        assert tel.GATEWAY_BAD_REQUESTS.value(kind="slow_body") == 1
        evs = _gw_events()
        assert len(evs) == 1
        assert evs[0]["http_status"] == 408
        assert evs[0]["error_kind"] == "slow_body"
        _assert_no_leaks(baseline + 1, server)   # gateway thread lives
    _assert_no_leaks(baseline, server)


def test_malformed_truncated_oversized(registry, server):
    baseline = threading.active_count()
    with Gateway(port=0, max_body=4096, read_timeout_s=0.5) as gw:
        gw.add_route("lm", server)
        # broken JSON -> 400
        status, _ = faults.malformed_post(
            "127.0.0.1", gw.port, "/v1/generate/lm",
            raw_body=b'{"tokens": [1, 2')
        assert status == 400
        # lying Content-Length (body shorter than declared) -> the
        # read times out waiting for bytes that never come: 408, not a
        # pinned thread
        status, _ = faults.malformed_post(
            "127.0.0.1", gw.port, "/v1/generate/lm",
            raw_body=b'{"tokens": [1]}', content_length=400)
        assert status == 408
        # memory-bomb Content-Length -> refused 413 without reading
        status, _ = faults.oversized_post(
            "127.0.0.1", gw.port, "/v1/generate/lm",
            claim_bytes=50 * 1024 * 1024)
        assert status == 413
        assert tel.GATEWAY_BAD_REQUESTS.value(kind="malformed") == 1
        assert tel.GATEWAY_BAD_REQUESTS.value(kind="oversized") == 1
        evs = _gw_events()
        assert len(evs) == 3
        assert sorted(e["http_status"] for e in evs) == [400, 408, 413]
        assert all(e["outcome"] == "error" for e in evs)
        _assert_no_leaks(baseline + 1, server)
    _assert_no_leaks(baseline, server)


def test_midstream_disconnect_evicts_slot(registry, server, eng):
    """The leaked-lane scenario: the client reads the first SSE token
    then vanishes with a TCP RST.  The gateway's next write fails ->
    cancel -> the decode loop evicts the slot (reason cancelled); no
    stream, thread, or lane survives the client."""
    baseline = threading.active_count()
    # slow each decode step so the disconnect deterministically lands
    # mid-generation (~19 tokens to the cache cap, 60 ms each)
    real_step = eng.decode_step
    eng.decode_step = faults.LatencySpike(real_step, delay=0.06)
    try:
        with Gateway(port=0) as gw:
            gw.add_route("lm", server)
            body = json.dumps({"tokens": [1, 2, 3]})
            status, nread = faults.disconnecting_stream_post(
                "127.0.0.1", gw.port, "/v1/generate/lm", body,
                read_bytes=1, rst=True)
            assert status == 200          # the stream was live (TTFT)
            assert nread >= 1
            # cancel propagated: slot evicted, not run to completion
            _wait(lambda: tel.GATEWAY_CLIENT_DISCONNECTS.value() == 1,
                  msg="disconnect to be detected")
            _wait(lambda: tel.DECODE_EVICTIONS.value(
                reason="cancelled") == 1, msg="slot eviction")
            evs = _gw_events()
            assert len(evs) == 1
            assert evs[0]["http_status"] == 499
            assert evs[0]["outcome"] == "evicted"
            _assert_no_leaks(baseline + 1, server)
        _assert_no_leaks(baseline, server)
    finally:
        eng.decode_step = real_step


def test_stalled_handler_answers_504(registry, server):
    """A backend that admits and then never resolves (the hung-device
    stall, via faults.StallingCallable) cannot pin the request past
    its deadline: the gateway retracts it and answers the contract's
    504."""
    stall = faults.StallingCallable(lambda: None)

    class StalledBackend:
        def submit(self, tokens, deadline_ms=None, max_new_tokens=None,
                   on_token=None):
            fut = _ChaosFut()
            threading.Thread(target=lambda: (stall(), fut.set_done()),
                             daemon=True).start()
            return fut

    class _ChaosFut:
        def __init__(self):
            self._ev = threading.Event()
            self.cancelled = False

        def set_done(self):
            self._ev.set()

        def done(self):
            return self._ev.is_set()

        def cancel(self):
            self.cancelled = True
            self._ev.set()
            return True

        def result(self, timeout=None):
            raise Cancelled("retracted")

    baseline = threading.active_count()
    try:
        with Gateway(port=0) as gw:
            gw.add_route("stuck", StalledBackend())
            import http.client

            conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                              timeout=30)
            payload = json.dumps({"tokens": [1]})
            t0 = time.monotonic()
            conn.request("POST", "/v1/generate/stuck", body=payload,
                         headers={"Content-Length": str(len(payload)),
                                  "X-Deadline-Ms": "300"})
            resp = conn.getresponse()
            resp.read()
            conn.close()
            assert resp.status == 504
            assert time.monotonic() - t0 < 10.0
            evs = _gw_events()
            assert len(evs) == 1
            assert evs[0]["outcome"] == "deadline"
            assert evs[0]["http_status"] == 504
            assert stall.stalled.is_set()  # it really was stalled
    finally:
        stall.release()
    _wait(lambda: threading.active_count() <= baseline,
          msg="stalled-backend threads to unwind")
    _assert_no_leaks(baseline, server)


def test_sigterm_drains_inflight_stream(registry, server, eng):
    """SIGTERM mid-stream: /healthz flips 503 and new work sheds 503
    while the open SSE stream runs to completion — then the listener
    stops and the gateway deregisters.  No dropped in-flight request,
    no connection refused during the drain."""
    import signal

    baseline = threading.active_count()
    real_step = eng.decode_step
    eng.decode_step = faults.LatencySpike(real_step, delay=0.06)
    gw = Gateway(port=0, drain_s=30.0)
    gw.add_route("lm", server)
    prev = gw.install_signal_handler()
    inflight = {}

    def fire():
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=60)
        payload = json.dumps({"tokens": [1, 2, 3]})
        conn.request("POST", "/v1/generate/lm", body=payload,
                     headers={"Content-Length": str(len(payload))})
        resp = conn.getresponse()
        inflight["status"] = resp.status
        inflight["body"] = resp.read()
        conn.close()

    try:
        t = threading.Thread(target=fire, daemon=True)
        t.start()
        _wait(lambda: tel.GATEWAY_OPEN_STREAMS.value() == 1,
              msg="stream to open")
        faults.send_preemption(sig=signal.SIGTERM)
        _wait(lambda: not gw.is_ready(), msg="drain to start")
        # mid-drain: probes and new work shed typed, listener up
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=10)
        conn.request("GET", "/healthz")
        assert conn.getresponse().status == 503
        conn.close()
        payload = json.dumps({"tokens": [1]})
        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=10)
        conn.request("POST", "/v1/generate/lm", body=payload,
                     headers={"Content-Length": str(len(payload))})
        assert conn.getresponse().status == 503
        conn.close()
        # the in-flight stream finishes whole
        t.join(30)
        assert inflight["status"] == 200
        frames = [json.loads(p[len(b"data: "):])
                  for p in inflight["body"].split(b"\n\n")
                  if p.startswith(b"data: ")]
        assert frames[-1].get("done") is True
        _wait(lambda: gw._closed, msg="gateway to close")
        _wait(lambda: tel.readiness()[0], msg="readiness to clear")
        # one event per request: the drained stream + the shed one
        evs = _gw_events()
        assert len(evs) == 2
        assert sorted(e["http_status"] for e in evs) == [200, 503]
    finally:
        signal.signal(signal.SIGTERM, prev)
        eng.decode_step = real_step
        gw.close(drain=False)
    _assert_no_leaks(baseline, server)
