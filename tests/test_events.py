"""Wide-event request observability (mxnet_tpu/events.py) + the
introspection surface (ISSUE 15 tentpole).

Tier-1 guards:

* sampling semantics — non-ok outcomes and the tail are ALWAYS kept,
  ok traffic head-samples, disabled mode is a no-op;
* the bounded writer — JSONL stream, torn-line tolerant reads, drop
  accounting at the queue bound;
* one event per resolved request with the typed outcome taxonomy,
  for both AsyncPredictor and TokenServer (faults-driven), each
  event's span id resolving in the trace buffer;
* /statusz (schema-stable, >= 5 subsystems), /requestz, /varz, and the
  /healthz readiness flip during drained shutdown;
* trace<->metric exemplars in scrape() + the exposition parser;
* tools/events_query.py slices, top-K, --join.

Kept lean: one Dense-predictor compile and one tiny-LM engine for the
whole file (module-scoped), mirroring test_generate's budget.
"""
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import events, generate, gluon, nd, telemetry as tel
from mxnet_tpu import tracing
from mxnet_tpu.serving import Predictor
from mxnet_tpu.serving_async import (AsyncPredictor, DeadlineExceeded,
                                     Overloaded)
from mxnet_tpu.testing import faults

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))
from transformer_lm import TransformerLM  # noqa: E402

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture
def wide(tmp_path):
    """Events + telemetry + tracing on, zeroed, ring-only; all off
    after (the suite default)."""
    tel.enable()
    tel.reset()
    tracing.enable()
    tracing.reset()
    events.reset()
    events._path = None
    events.enable(sample=1.0)
    yield events
    events.disable()
    events.reset()
    events._path = None
    tracing.disable()
    tracing.reset()
    tel.reset()
    tel.disable()
    # closed predictors/servers must leave the readiness weak-sets
    # before any later /healthz 200 assertion runs
    import gc

    gc.collect()


def _evs(kind=None):
    out = events.recent()
    return [e for e in out if kind is None or e["kind"] == kind]


# ---------------------------------------------------------------------------
# emission + sampling semantics
# ---------------------------------------------------------------------------

def test_disabled_is_noop_and_off_by_default():
    assert not events.enabled()   # suite runs with MXNET_EVENTS unset
    assert events.emit("train_step", dur_s=1.0) is None
    assert events.recent() == []


def test_outcomes_always_kept_ok_head_sampled(wide):
    events.enable(sample=0.0)     # drop every ok event (head)
    for outcome, kw in (("shed", {"reason": "queue"}),
                        ("deadline", {"stage": "decode"}),
                        ("evicted", {"reason": "cancelled"}),
                        ("error", {"error_kind": "ReplicaFailed"})):
        assert events.emit("serving_request", outcome=outcome,
                           dur_s=0.001, **kw) is not None
    assert events.emit("serving_request", outcome="ok",
                       dur_s=0.001) is None
    st = events.stats()
    assert st["emitted"] == 4 and st["sampled_out"] == 1
    assert [e["outcome"] for e in events.recent()] == \
        ["shed", "deadline", "evicted", "error"]
    with pytest.raises(ValueError):
        events.emit("serving_request", outcome="weird")


def test_tail_latency_always_kept(wide):
    events.enable(sample=0.0)
    # seed the per-kind window past the minimum with fast oks
    for _ in range(events._TAIL_MIN + 40):
        events.emit("train_step", dur_s=0.001)
    assert _evs() == []           # all head-sampled out
    # a 100x outlier beats the p99 threshold -> kept despite sample=0
    assert events.emit("train_step", dur_s=0.1) is not None
    kept = _evs()
    assert len(kept) == 1 and kept[0]["dur_s"] == 0.1


def test_event_carries_trace_span_and_provenance(wide):
    with tracing.span("unit"):
        ev = events.emit("train_step", dur_s=0.5, step=7)
    assert ev["trace_id"] == tracing.TRACE_ID
    prov = ev["provenance"]
    for key in ("git_sha", "jax_version", "backend", "device_count"):
        assert key in prov
    # the span id resolves in the trace ring buffer
    spans = {e["args"]["span_id"]
             for e in tracing.chrome_trace_payload(False)["traceEvents"]
             if e.get("args", {}).get("span_id")}
    assert ev["span_id"] in spans


# ---------------------------------------------------------------------------
# bounded writer: JSONL stream, torn lines, drop accounting
# ---------------------------------------------------------------------------

def test_writer_appends_jsonl_and_read_reports_torn_lines(
        wide, tmp_path):
    path = str(tmp_path / "events.jsonl")
    events.enable(path=path, sample=1.0)
    for i in range(5):
        events.emit("checkpoint_save", dur_s=0.01 * (i + 1), step=i)
    assert events.flush() == 5
    with open(path, "a") as f:
        f.write('{"kind": "torn...')   # crash mid-append
    evs, problems = events.read_events(path)
    assert len(evs) == 5 and [e["step"] for e in evs] == list(range(5))
    assert len(problems) == 1 and problems[0][0] == 6
    st = events.stats()
    assert st["written"] == 5 and st["dropped"] == 0


def test_writer_queue_bound_drops_and_counts(wide, tmp_path,
                                             monkeypatch):
    events.enable(path=str(tmp_path / "e.jsonl"), sample=1.0)
    monkeypatch.setattr(events, "QUEUE_MAX", 2)
    # stop the writer from draining under us
    monkeypatch.setattr(events, "_ensure_writer_locked", lambda: None)
    for i in range(5):
        events.emit("train_step", outcome="error", error_kind="X",
                    step=i)
    st = events.stats()
    assert st["dropped"] == 3 and st["queue"] == 2
    # the ring still has everything: /requestz evidence survives drops
    assert len(events.recent()) == 5


# ---------------------------------------------------------------------------
# serving integration: one typed event per resolved request
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_pred():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(2))
    net.initialize()
    x = np.random.RandomState(0).rand(4, 3).astype(np.float32)
    pred, _ = Predictor.from_block(net, nd.array(x), chain=2)
    return pred, x


def test_async_predictor_event_per_request(wide, dense_pred):
    pred, x = dense_pred
    orig = pred.predict
    pred.predict = faults.LatencySpike(orig, delay=0.3, count=1)
    ap = AsyncPredictor(pred, queue_depth=4)
    try:
        f1 = ap.submit(x)                 # slow dispatch holds the replica
        time.sleep(0.05)
        f2 = ap.submit(x, deadline_ms=60)  # expires while queued
        f3 = ap.submit(x)                  # cancelled while queued
        assert f3.cancel()
        with pytest.raises(DeadlineExceeded) as ei:
            f2.result(10)
        assert ei.value.stage == "queue"
        np.asarray(f1.result(10))
    finally:
        pred.predict = orig
        ap.close()
    evs = _evs("serving_request")
    by_outcome = {}
    for e in evs:
        by_outcome.setdefault(e["outcome"], []).append(e)
    # exactly ONE deadline event, stage-tagged, span resolving
    assert len(by_outcome["deadline"]) == 1
    dl = by_outcome["deadline"][0]
    assert dl["stage"] == "queue" and dl["trace_id"] == tracing.TRACE_ID
    assert len(by_outcome["ok"]) == 1
    ok = by_outcome["ok"][0]
    assert set(ok["stages_s"]) == {"queue", "dispatch"}
    assert ok["rows"] == 4
    assert len(by_outcome["evicted"]) == 1   # the cancel
    assert by_outcome["evicted"][0]["reason"] == "cancelled"
    spans = {e["args"]["span_id"]
             for e in tracing.chrome_trace_payload(False)["traceEvents"]
             if e.get("args", {}).get("span_id")}
    for e in evs:
        assert e["span_id"] in spans, e


def test_async_predictor_shed_event_and_readiness_flip(
        wide, dense_pred):
    import threading

    pred, x = dense_pred
    ap = AsyncPredictor(pred, queue_depth=1)
    srv = tel.serve_scrape(port=0)
    base = "http://127.0.0.1:%d" % srv.port
    try:
        assert urllib.request.urlopen(base + "/healthz").status == 200
        orig = pred.predict
        pred.predict = faults.LatencySpike(orig, delay=0.25, count=2)
        try:
            futs = [ap.submit(x)]         # occupies the replica
            time.sleep(0.05)
            futs.append(ap.submit(x))     # fills the queue
            with pytest.raises(Overloaded) as ei:
                ap.submit(x)
            assert ei.value.reason == "queue"
            sheds = [e for e in _evs("serving_request")
                     if e["outcome"] == "shed"]
            assert len(sheds) == 1 and sheds[0]["reason"] == "queue"
            # drained shutdown: /healthz reads 503 WHILE close()
            # drains the in-flight work (the regression the old
            # always-200 probe hid) ...
            closer = threading.Thread(target=ap.close)
            closer.start()
            deadline = time.monotonic() + 5
            while not ap._closed:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz")
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert "serving" in body["failing"] and not body["ready"]
            closer.join(timeout=30)
            for f in futs:
                f.result(10)
        finally:
            pred.predict = orig
        # ... and recovers once shutdown completed: a fully closed
        # predictor stops counting even while still referenced
        assert urllib.request.urlopen(base + "/healthz").status == 200
    finally:
        tel.stop_scrape()
    ok, _checks = tel.readiness()
    assert ok


# ---------------------------------------------------------------------------
# TokenServer integration (faults-driven, mirrors test_generate)
# ---------------------------------------------------------------------------

VOCAB = 48


@pytest.fixture(scope="module")
def eng():
    mx.random.seed(0)
    lm = TransformerLM(vocab_size=VOCAB, d_model=32, n_heads=2,
                       n_layers=2, max_len=24)
    lm.initialize(mx.init.Xavier())
    lm(nd.array(np.zeros((1, 4), np.float32)))
    return generate.PagedGenerationEngine(
        lm, slots=2, cache_len=24, page_size=4, prefill_chunk=8,
        sampling=generate.SamplingConfig(greedy=True))


def _prompt(n=5, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, n) \
        .astype(np.int32)


def test_token_server_ok_event_with_stage_split(wide, eng):
    srv = generate.TokenServer(eng, queue_depth=8, max_new_tokens=3)
    try:
        r = srv.generate(_prompt(5), timeout=60)
        assert r.finish_reason == "length"
    finally:
        srv.close()
    oks = [e for e in _evs("token_request") if e["outcome"] == "ok"]
    assert len(oks) == 1
    ev = oks[0]
    assert ev["reason"] == "length" and ev["tokens"] == 3
    assert ev["prompt_tokens"] == 5
    assert set(ev["stages_s"]) == {"queue", "prefill", "decode"}
    # the split covers the whole duration (prefill+decode+queue ~ dur)
    assert sum(ev["stages_s"].values()) == pytest.approx(
        ev["dur_s"], rel=0.05)
    spans = {e["args"]["span_id"]
             for e in tracing.chrome_trace_payload(False)["traceEvents"]
             if e.get("args", {}).get("span_id")}
    assert ev["span_id"] in spans


def test_token_server_deadline_and_evicted_events(wide, eng):
    """Faults-driven: a slow decode_step burns a mid-generation
    deadline (stage=decode, evicted), a queued request expires
    (stage=prefill), a cancel evicts — each EXACTLY one event."""
    srv = generate.TokenServer(eng, queue_depth=8, max_new_tokens=64)
    orig = eng.decode_step
    eng.decode_step = faults.LatencySpike(orig, delay=0.05)
    try:
        fut = srv.submit(_prompt(4), deadline_ms=200)
        with pytest.raises(DeadlineExceeded) as ei:
            fut.result(60)
        assert ei.value.stage == "decode"
        # fill both slots, then queue one whose deadline expires first
        longs = [srv.submit(_prompt(4, seed=i), max_new_tokens=30)
                 for i in range(eng.slots)]
        time.sleep(0.1)
        fut2 = srv.submit(_prompt(4, seed=50), deadline_ms=60)
        with pytest.raises(DeadlineExceeded) as ei:
            fut2.result(60)
        assert ei.value.stage == "prefill"
        for f in longs:
            f.cancel()
    finally:
        eng.decode_step = orig
        srv.close()
    evs = _evs("token_request")
    dl = [e for e in evs if e["outcome"] == "deadline"]
    assert sorted(e["stage"] for e in dl) == ["decode", "prefill"]
    decode_dl = next(e for e in dl if e["stage"] == "decode")
    assert decode_dl["evicted"] is True and decode_dl["tokens"] >= 1
    evicted = [e for e in evs if e["outcome"] == "evicted"]
    assert len(evicted) == len(longs)
    assert {e["reason"] for e in evicted} == {"cancelled"}
    # exactly one event per resolved request, each span-resolvable
    assert len(evs) == 2 + len(longs)
    spans = {e["args"]["span_id"]
             for e in tracing.chrome_trace_payload(False)["traceEvents"]
             if e.get("args", {}).get("span_id")}
    for e in evs:
        assert e["span_id"] in spans, e
    # the decode tier flipped the heartbeat's TTFT fields on
    from mxnet_tpu import monitor

    line = monitor.TelemetryHeartbeat().line()
    assert "ttft_p99_ms" in line and "slots" in line


# ---------------------------------------------------------------------------
# /statusz, /requestz, /varz
# ---------------------------------------------------------------------------

def test_statusz_schema_stable_and_served(wide, eng):
    srv = generate.TokenServer(eng, queue_depth=4, max_new_tokens=2)
    http = tel.serve_scrape(port=0)
    base = "http://127.0.0.1:%d" % http.port
    try:
        srv.generate(_prompt(4), timeout=60)
        sz = json.loads(urllib.request.urlopen(base + "/statusz").read())
        assert sz["format_version"] == 1
        subs = sz["subsystems"]
        # schema-stable core: these keys exist on EVERY snapshot
        for key in ("aot", "fusion", "serving", "decode", "checkpoint",
                    "events", "process"):
            assert key in subs, key
        assert sz["trace_id"] == tracing.TRACE_ID
        assert sz["ready"] is True and "decode" in sz["readiness"]
        assert subs["decode"]["ttft_p99_ms"] is not None
        assert any(s["occupancy"]["slots"] == 2
                   for s in subs["decode"]["servers"])
        assert subs["events"]["enabled"] is True
        assert subs["events"]["emitted"] >= 1
        assert "fallbacks" in subs["aot"]
        rq = json.loads(
            urllib.request.urlopen(base + "/requestz?n=2").read())
        assert len(rq["events"]) >= 1
        assert rq["events"][-1]["kind"] == "token_request"
        vz = json.loads(urllib.request.urlopen(base + "/varz").read())
        assert vz["MXNET_EVENTS_SAMPLE"] == 1.0
        assert "MXNET_DECODE_SLOTS" in vz
    finally:
        tel.stop_scrape()
        srv.close()


# ---------------------------------------------------------------------------
# exemplars: observe -> scrape -> parse
# ---------------------------------------------------------------------------

def test_histogram_exemplars_in_scrape_and_parser(wide, tmp_path):
    with tracing.span("slow-req") as sp:
        tel.SERVING_REQUEST_SECONDS.observe(0.8)
        span_id = sp._span.span_id
    # exemplars are OpenMetrics-only syntax: the classic 0.0.4 body
    # must stay clean for old Prometheus parsers, the negotiated one
    # carries them and terminates with # EOF
    assert " # {" not in tel.scrape()
    text = tel.scrape(openmetrics=True)
    assert text.rstrip().endswith("# EOF")
    needle = None
    for line in text.splitlines():
        if line.startswith("mxnet_tpu_serving_request_seconds_bucket") \
                and " # {" in line:
            needle = line
    assert needle is not None, "no exemplar emitted"
    assert 'trace_id="%s"' % tracing.TRACE_ID in needle
    assert 'span_id="%s"' % span_id in needle
    # explicit exemplar wins over the contextvar lookup
    tel.DECODE_TTFT_SECONDS.observe(
        0.2, exemplar={"trace_id": "T", "span_id": "S"})
    assert tel.DECODE_TTFT_SECONDS.exemplars()[0.25][1] == \
        {"trace_id": "T", "span_id": "S"}
    # the dump CLI parses exemplar-bearing expositions + diffs them
    a, b = str(tmp_path / "a.txt"), str(tmp_path / "b.txt")
    open(a, "w").write(text)
    tel.SERVING_REQUEST_SECONDS.observe(1.5)
    open(b, "w").write(tel.scrape(openmetrics=True))
    sys.path.insert(0, TOOLS)
    try:
        import importlib
        import telemetry_dump

        importlib.reload(telemetry_dump)
        data = telemetry_dump._load(a)
        fam = data["metrics"]["mxnet_tpu_serving_request_seconds"]
        assert fam["type"] == "histogram"
        assert fam["series"][0]["count"] == 1
        assert telemetry_dump.main([a, "--top", "3"]) == 0
        assert telemetry_dump.main(["--diff", a, b]) == 0
    finally:
        sys.path.remove(TOOLS)


def test_openmetrics_body_parses_under_strict_parser(wide):
    """The negotiated exposition must satisfy a REAL OpenMetrics
    parser (counter families named without _total, # EOF terminator,
    exemplar syntax) — the exact clients the negotiation targets."""
    parser = pytest.importorskip(
        "prometheus_client.openmetrics.parser")
    with tracing.span("r"):
        tel.SERVING_REQUEST_SECONDS.observe(0.8)
    tel.TRAIN_STEPS.inc(loop="sharded")
    fams = list(parser.text_string_to_metric_families(
        tel.scrape(openmetrics=True)))
    names = {f.name for f in fams}
    assert "mxnet_tpu_train_steps" in names          # counter, bare
    assert "mxnet_tpu_serving_request_seconds" in names
    ex = [s.exemplar for f in fams for s in f.samples if s.exemplar]
    assert ex and ex[0].labels["trace_id"] == tracing.TRACE_ID


def test_train_step_events_without_telemetry(wide):
    """MXNET_EVENTS is independent of MXNET_TELEMETRY: train_step
    evidence rows must appear with telemetry off (regression: the
    emit used to hide inside the telemetry-only accounting block)."""
    from mxnet_tpu import parallel

    tel.disable()
    try:
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(3))
        net.initialize()
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        tr = parallel.ShardedTrainer(net, lambda o, l: loss_fn(o, l),
                                     mesh=None)
        x = nd.array(np.random.RandomState(0)
                     .rand(4, 5).astype(np.float32))
        y = nd.array(np.zeros(4, np.float32))
        tr.step([x], y)
        tr.drain()
    finally:
        tel.enable()
    evs = _evs("train_step")
    assert len(evs) == 1 and evs[0]["dur_s"] > 0
    assert evs[0]["steps"] == 1 and evs[0]["batch_rows"] == 4


def test_no_exemplars_when_tracing_off(wide):
    tracing.disable()
    tel.SERVING_REQUEST_SECONDS.observe(0.8)
    assert tel.SERVING_REQUEST_SECONDS.exemplars() == {}
    assert " # {" not in tel.scrape(openmetrics=True)


def test_metrics_endpoint_negotiates_openmetrics(wide):
    """A classic Prometheus scrape (no Accept negotiation) must get a
    0.0.4 body WITHOUT exemplar suffixes — the classic parser rejects
    them; only an OpenMetrics Accept header earns them."""
    with tracing.span("req"):
        tel.SERVING_REQUEST_SECONDS.observe(0.8)
    srv = tel.serve_scrape(port=0)
    base = "http://127.0.0.1:%d" % srv.port
    try:
        plain = urllib.request.urlopen(base + "/metrics")
        assert "0.0.4" in plain.headers["Content-Type"]
        assert " # {" not in plain.read().decode()
        req = urllib.request.Request(
            base + "/metrics",
            headers={"Accept": "application/openmetrics-text"})
        om = urllib.request.urlopen(req)
        assert "openmetrics-text" in om.headers["Content-Type"]
        body = om.read().decode()
        assert " # {" in body and body.rstrip().endswith("# EOF")
    finally:
        tel.stop_scrape()


# ---------------------------------------------------------------------------
# events_query CLI
# ---------------------------------------------------------------------------

def test_events_query_slices_top_and_join(wide, tmp_path, capsys):
    path = str(tmp_path / "events.jsonl")
    events.enable(path=path, sample=1.0)
    for i in range(10):
        with tracing.span("req%d" % i):
            events.emit("serving_request", dur_s=0.01 * (i + 1), rows=2)
    with tracing.span("the-slow-one"):
        events.emit("token_request", outcome="deadline", stage="decode",
                    dur_s=0.9, tokens=3)
    events.flush()
    trace = str(tmp_path / "trace.json")
    tracing.export_trace(trace)
    sys.path.insert(0, TOOLS)
    try:
        import importlib
        import events_query

        importlib.reload(events_query)
        rc = events_query.main([path, "--by", "kind,outcome", "--top",
                                "2", "--join", trace])
        assert rc == 0
        out = capsys.readouterr().out
        assert "token_request/deadline" in out
        assert "p999_ms" in out
        assert "900.000" in out              # the slow one leads top-K
        assert "trace: span 'the-slow-one'" in out
        assert "stage=decode" in out
        # filters + unusable input
        assert events_query.main([path, "--kind", "nope"]) == 2
    finally:
        sys.path.remove(TOOLS)


# ---------------------------------------------------------------------------
# flight-recorder bundles gain the events ring
# ---------------------------------------------------------------------------

def test_flight_bundle_contains_events_ring(wide, tmp_path):
    events.emit("token_request", outcome="error", error_kind="boom")
    tracing.enable_flight_recorder(str(tmp_path))
    try:
        tracing.rearm_flight_recorder()
        bundle = tracing.record_crash("test-events")
        assert bundle is not None
        payload = json.load(open(os.path.join(bundle, "events.json")))
        assert payload["stats"]["emitted"] >= 1
        assert payload["events"][-1]["error_kind"] == "boom"
    finally:
        tracing.disable_flight_recorder()
