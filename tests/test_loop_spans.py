"""The hot loops' step-level spans (ISSUE 26): kept in the ring whether or
not tracing is enabled, mirrored into a running profiler trace as
``mx:<name>`` on the thread that opened them, with ``gc`` and
``compile:*`` beside them; and the benchmark's readers of both (the ring
in-process, the ``.xplane.pb``), on hand-made records and planes.

One trainer compile and one paged-engine compile for the file.
"""
import gc
import glob
import os
import sys
import threading
import types

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import generate, gluon, nd, parallel, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "examples")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from transformer_lm import TransformerLM  # noqa: E402

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.reducers import (idle_by_program_span,  # noqa: E402
                                    program_spans, setup_spans)

TICK_CHILDREN = {"serve.admit", "engine.prefill", "engine.decode",
                 "serve.deliver"}
DECODE_PHASES = ["engine.decode:prep", "engine.decode:launch",
                 "engine.decode:readback", "engine.decode:post"]


@pytest.fixture
def untraced():
    """``MXNET_TRACE`` unset: collection disabled, clean ring."""
    tracing.disable()
    tracing.reset()
    yield tracing
    tracing.reset()


@pytest.fixture(scope="module")
def trainer():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4))
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = parallel.ShardedTrainer(net, lambda o, l: loss_fn(o, l), mesh=None)
    x = nd.array(np.random.rand(8, 6).astype(np.float32))
    y = nd.array(np.random.randint(0, 4, 8).astype(np.float32))
    tr.step([x], y)
    return tr, x, y


@pytest.fixture(scope="module")
def engine():
    mx.random.seed(0)
    net = TransformerLM(vocab_size=48, d_model=32, n_heads=2, n_layers=2,
                        max_len=24)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 4), np.float32)))
    return generate.PagedGenerationEngine(
        net, slots=2, cache_len=24, page_size=4, prefill_chunk=8,
        sampling=generate.SamplingConfig(greedy=True))


def _serve(engine, prompts=((3, 1, 4, 1, 5), (9, 2, 6)), new=4):
    """Requests through a TokenServer; returns after its worker stopped."""
    with generate.TokenServer(engine, max_new_tokens=new) as srv:
        futs = [srv.submit(np.asarray(p, np.int32)) for p in prompts]
        out = [f.result(timeout=60) for f in futs]
    assert all(len(o["tokens"]) == new for o in out)


def _by_id(recs):
    return {r["span_id"]: r for r in recs}


def _kids(recs, parent):
    """The loop's own spans under ``parent`` in start order (a ``gc`` or
    ``compile:*`` span may land under any of them)."""
    return sorted((r for r in recs if r["parent_id"] == parent["span_id"]
                   and r["name"].startswith(("serve.", "engine."))),
                  key=lambda r: r["t0"])


def _end(r):
    return r["t0"] + r["dur"]


# ---------------------------------------------------------------------------
# the ring, with tracing disabled
# ---------------------------------------------------------------------------

def test_trainer_step_tree_is_kept_with_tracing_off(untraced, trainer):
    tr, x, y = trainer
    assert not tracing.enabled()
    tr.step([x], y)
    recs = tracing.records()
    step = [r for r in recs if r["name"] == "ShardedTrainer.step"]
    assert len(step) == 1 and step[0]["parent_id"] is None
    assert step[0]["args"]["step"] == tr.global_step
    # thread CPU time of the calling thread, never above the wall time
    assert 0 <= step[0]["args"]["cpu_ms"] <= 1e3 * step[0]["dur"] + 1.0
    for name in ("step:dispatch", "step:fetch"):
        kid = [r for r in recs if r["name"] == name]
        assert len(kid) == 1, name
        assert kid[0]["parent_id"] == step[0]["span_id"]
        assert kid[0]["tid"] == step[0]["tid"]
        assert step[0]["t0"] <= kid[0]["t0"] and _end(kid[0]) <= _end(step[0])
    # nothing but the always-kept names got in: the other layers' spans
    # still ask tracing.enabled()
    assert {r["name"] for r in recs} <= {
        "ShardedTrainer.step", "step:dispatch", "step:fetch", "gc",
        "compile:trace", "compile:lower", "compile:executable"}


def test_server_tick_tree_is_kept_with_tracing_off(untraced, engine):
    _serve(engine)
    recs = tracing.records()
    ids = _by_id(recs)
    ticks = [r for r in recs if r["name"] == "serve.tick"]
    assert ticks and all(t["parent_id"] is None for t in ticks)
    assert len({t["tid"] for t in ticks}) == 1
    assert all("cpu_ms" in t["args"] and "slots" in t["args"]
               and "queue" in t["args"] for t in ticks)
    for r in recs:
        if r["name"] in TICK_CHILDREN:
            assert ids[r["parent_id"]]["name"] == "serve.tick", r["name"]
        elif r["name"].startswith("engine.decode:"):
            assert ids[r["parent_id"]]["name"] == "engine.decode"
        elif r["name"].startswith("engine.prefill:"):
            assert ids[r["parent_id"]]["name"] == "engine.prefill"
    # a decode call launches a step (prep, launch), then reads the
    # oldest step still unread (readback, post) and leaves `steps_ahead`
    # queued: the first call has nothing to read yet, the last ones
    # nothing to launch.  Every slot stepped took its token on the
    # device (`fed`), and `unread` is what the call left in flight
    ahead, unread, both = engine.steps_ahead, 0, 0
    decs = sorted((r for r in recs if r["name"] == "engine.decode"),
                  key=lambda r: r["t0"])
    for dec in decs:
        kids = [k["name"] for k in _kids(recs, dec)]
        launched = kids[:2] == DECODE_PHASES[:2]
        read = kids[-2:] == DECODE_PHASES[2:]
        assert kids and kids == DECODE_PHASES[:2] * launched \
            + DECODE_PHASES[2:] * read
        assert dec["args"]["fed"] == dec["args"]["slots"]
        assert (dec["args"]["slots"] >= 1) == launched
        assert launched == (dec["args"]["live"] >= 1)
        # the step a call reads is one an EARLIER call launched: when
        # the call has launched its own, two are in flight, and the
        # older one is read
        unread += launched
        assert read == (unread > (ahead if launched else 0))
        both += launched and read
        unread -= read
        assert dec["args"]["unread"] == unread
    assert both >= 2 and unread == 0
    # ... by the ring's own order of the two phases too: no read-back
    # before `steps_ahead` + 1 launches
    phases = sorted((r for r in recs if r["name"] in (
        "engine.decode:launch", "engine.decode:readback")),
        key=lambda r: r["t0"])
    flight = 0
    for r in phases:
        if r["name"].endswith(":launch"):
            flight += 1
            continue
        own = any(k["name"] == "engine.decode:launch"
                  for k in _kids(recs, ids[r["parent_id"]]))
        assert flight == ahead + 1 if own else flight >= 1
        flight -= 1
    # no chunk reads a token back, a prompt's last one neither: the
    # first token stays on the device and comes with a decode step's
    pres = [r for r in recs if r["name"] == "engine.prefill"]
    for pre in pres:
        assert [k["name"] for k in _kids(recs, pre)] == [
            "engine.prefill:launch"]
        assert pre["args"].get("fed", 0) == int(pre["args"]["final"])
    assert sum(p["args"]["final"] for p in pres) == 2
    assert not any(r["name"] == "engine.prefill:readback" for r in recs)
    assert any(r["args"]["admitted"] for r in recs
               if r["name"] == "serve.admit")
    assert sum(r["args"]["tokens"] for r in recs
               if r["name"] == "serve.deliver") == 8
    # a tick's children lie inside it and cover it but for its self time
    selfs = []
    for t in ticks:
        kids = _kids(recs, t)
        assert all(t["t0"] <= k["t0"] and _end(k) <= _end(t) for k in kids)
        selfs.append((t["dur"] - sum(k["dur"] for k in kids)) / t["dur"])
    assert min(selfs) >= 0.0
    assert sorted(selfs)[len(selfs) // 2] < 0.5
    # no request-level span without MXNET_TRACE
    assert not any(r["name"] == "decode.request" for r in recs)


def test_decode_step_histogram_reads_the_span(untraced, engine):
    from mxnet_tpu import telemetry

    telemetry.enable()
    try:
        telemetry.reset()
        _serve(engine, prompts=((7, 7, 7),), new=3)
        steps = [r for r in tracing.records() if r["name"] == "engine.decode"]
        hist = telemetry.DECODE_STEP_SECONDS
        assert hist.count() == len(steps) >= 2
        assert hist.sum() == pytest.approx(sum(r["dur"] for r in steps))
    finally:
        telemetry.reset()
        telemetry.disable()


def test_long_gc_pause_is_a_span(untraced, monkeypatch):
    monkeypatch.setattr(tracing, "RARE_SPAN_MIN_SECONDS", 1e-4)
    heap = []
    for _ in range(200000):
        a, b = [], []
        a.append(b), b.append(a)
        heap.append(a)
    del heap, a, b
    collected = gc.collect()
    # (passes over the growing heap that found nothing may be there too)
    spans = [r for r in tracing.records() if r["name"] == "gc"
             and r["args"]["collected"] >= 400000]
    assert len(spans) == 1, "collecting 400000 cyclic lists left no span"
    assert spans[0]["args"] == {"generation": 2, "collected": collected}
    assert spans[0]["dur"] >= 1e-4 and spans[0]["status"] == "ok"
    assert spans[0]["tid"] == threading.get_ident()
    # a quick collection stays out of the ring
    monkeypatch.setattr(tracing, "RARE_SPAN_MIN_SECONDS", 3600.0)
    tracing.reset()
    gc.collect()
    assert not tracing.records()


def test_jit_of_a_new_function_leaves_compile_spans(untraced, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(tracing, "RARE_SPAN_MIN_SECONDS", 0.0)

    def loop_spans_probe(x):
        return jnp.tanh(x) * 3.0 + 26.0

    with tracing.begin("outer") as outer:
        jax.jit(loop_spans_probe)(jnp.ones((3, 5))).block_until_ready()
    recs = {r["name"]: r for r in tracing.records()}
    for name in ("compile:trace", "compile:lower", "compile:executable"):
        assert name in recs, name
        assert recs[name]["dur"] > 0 and recs[name]["tid"] == outer.tid
        assert recs[name]["parent_id"] == outer.span_id
        assert outer.t0 <= recs[name]["t0"] + 1e-3
    assert "loop_spans_probe" in recs["compile:trace"]["args"]["fun_name"]


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------

def test_spans_lie_in_the_profiler_trace_on_their_threads(
        untraced, trainer, engine, tmp_path):
    import jax
    from jax.profiler import ProfileData

    tr, x, y = trainer
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tr.step([x], y)
        _serve(engine, prompts=((5, 4, 3),), new=3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    lines = []          # one set of mx:* names for every host thread
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = set()
            for ev in line.events:
                if ev.name.startswith("mx:"):
                    names.add(ev.name)
                    stats.setdefault(ev.name, dict(ev.stats))
            if names:
                lines.append(names)
    caller = [n for n in lines if "mx:ShardedTrainer.step" in n]
    worker = [n for n in lines if "mx:engine.decode" in n]
    assert len(caller) == 1 and len(worker) == 1
    assert {"mx:step:dispatch", "mx:step:fetch"} <= caller[0]
    assert {"mx:serve.tick", "mx:serve.admit", "mx:serve.deliver",
            "mx:engine.prefill", "mx:engine.prefill:launch"} <= worker[0]
    assert "mx:engine.prefill:readback" not in worker[0]
    assert {"mx:" + n for n in DECODE_PHASES} <= worker[0]
    assert "mx:serve.tick" not in caller[0]
    assert "mx:ShardedTrainer.step" not in worker[0]
    # the span's args ride along, those set at its end too
    assert "step" in stats["mx:ShardedTrainer.step"]
    assert "cpu_ms" in stats["mx:serve.tick"]
    # the ring holds the same spans on its own clock
    assert {"mx:" + r["name"] for r in tracing.records()} >= caller[0]


# ---------------------------------------------------------------------------
# the benchmark's readers, on hand-made records and planes
# ---------------------------------------------------------------------------

MS = 1e6  # ns


def _rec(name, t0, dur, tid=1, **args):
    return {"name": name, "span_id": "%s@%s" % (name, t0), "parent_id": None,
            "tid": tid, "t0": t0, "dur": dur, "status": "ok",
            "args": args or None}


def _train_records():
    """Four steps of 100, 100, 300, 100 ms from t = 10 s; each waits in
    ``step:fetch`` for all but 4 ms of its period, the third for all but
    104 ms: it stalled on the host.  A collection of 90 ms inside it."""
    recs, t = [], 10.0
    for i, (period, host) in enumerate([(.1, .004), (.1, .004), (.3, .104),
                                        (.1, .004)]):
        recs.append(_rec("ShardedTrainer.step", t, period - .001, step=i,
                         cpu_ms=3.0))
        recs.append(_rec("step:dispatch", t + .0005, .002))
        recs.append(_rec("step:fetch", t + .003, period - host))
        recs.append(_rec("step:fetch", t + .003, .05, tid=2))  # fetch thread
        t += period
    recs.append(_rec("gc", 10.21, .09, generation=2, collected=5))
    recs.append(_rec("gc", 9.0, .5, generation=2))            # during set-up
    recs.append(_rec("compile:trace", 2.0, 3.0))
    recs.append(_rec("compile:trace", 2.5, 1.0))              # nested: once
    recs.append(_rec("compile:lower", 5.0, 1.0))
    recs.append(_rec("compile:executable", 6.0, 2.5))
    recs.append(_rec("compile:executable", 10.05, .01))       # in the window
    return recs


@pytest.fixture
def ctx(monkeypatch):
    """A run whose window opens at 10 s on the program's clock and lasts
    0.65 s; the ring is handed to the readers in place of the program's."""
    ring = {"records": _train_records(), "evicted": 0}
    monkeypatch.setattr(program_spans, "ring",
                        lambda: (ring["records"], ring["evicted"]))
    monkeypatch.setitem(sys.modules, "__main__",
                        types.SimpleNamespace(T_START=4.0))
    return {"end_to_end": {"setup_s": 6.0}, "window": {"seconds": 0.65},
            "ring": ring}


STEP_ARGS = {"spans": ["ShardedTrainer.step"], "step": "ShardedTrainer.step",
             "measure": "period", "less": ["step:fetch"]}


@pytest.mark.parametrize("stat,want", [("p50", 4.0), ("max", 104.0),
                                       ("mean", (4 + 4 + 104) / 3.0)])
def test_ring_reader_host_time_of_a_step(ctx, capsys, stat, want):
    # the fourth step has no next one to end its period: three values
    got = program_spans.reduce(ctx, stat=stat, **STEP_ARGS)
    assert got == pytest.approx(want)
    err = capsys.readouterr().err
    # the slowest step's line: its phases, cpu_ms and the gc inside it
    assert "slowest of 3" in err and "ShardedTrainer.step 300.00 ms" in err
    assert "step:fetch 196.00" in err and "cpu_ms 3.00" in err
    assert "gen2 90.00 ms" in err
    # printed once a run
    program_spans.reduce(ctx, stat=stat, **STEP_ARGS)
    assert "slowest" not in capsys.readouterr().err


def test_ring_reader_durations_gc_and_windows(ctx):
    tick = {"spans": ["ShardedTrainer.step"], "step": "ShardedTrainer.step"}
    # all four steps end in the window: 99, 99, 299, 99 ms
    assert program_spans.reduce(ctx, stat="p95", **tick) == pytest.approx(299)
    assert program_spans.reduce(ctx, stat="mean", less=["step:fetch"],
                                **tick) == pytest.approx((3 + 3 + 103 + 3) / 4)
    gcs = {"spans": ["gc"], "stat": "max", "step": "ShardedTrainer.step"}
    assert program_spans.reduce(ctx, **gcs) == pytest.approx(90.0)
    # a window that saw steps and no collection reads 0, not nothing
    ctx["ring"]["records"] = [r for r in ctx["ring"]["records"]
                              if r["name"] != "gc"]
    assert program_spans.reduce(ctx, **gcs) == 0.0


def test_setup_reader_sums_compile_phases_before_the_window(ctx):
    assert setup_spans.reduce(ctx, spans=["compile:trace", "compile:lower"]) \
        == pytest.approx(4.0)
    assert setup_spans.reduce(ctx, spans=["compile:executable"]) \
        == pytest.approx(2.5)


def test_readers_find_nothing_and_say_nothing(ctx, capsys, monkeypatch):
    gcs = {"spans": ["gc"], "stat": "max", "step": "ShardedTrainer.step"}
    # a ring that lost records of the window is not reduced, and says so
    ctx["ring"]["evicted"] = 7
    ctx["ring"]["records"] = [r for r in ctx["ring"]["records"]
                              if r["t0"] > 10.05]
    assert program_spans.reduce(ctx, stat="p50", **STEP_ARGS) is None
    assert "evicted 7" in capsys.readouterr().err
    assert setup_spans.reduce(ctx, spans=["compile:executable"]) is None
    # evictions that ended before the window opened take nothing from it
    ctx["ring"]["records"] = _train_records()
    assert program_spans.reduce(ctx, stat="p50", **STEP_ARGS) == \
        pytest.approx(4.0)
    assert setup_spans.reduce(ctx, spans=["compile:trace"]) is None
    # a program that records no step span (the parent commit): nothing,
    # never 0
    ctx["ring"].update(evicted=0, records=[
        r for r in _train_records() if r["name"].startswith("compile")
        and r["t0"] > 10])
    assert program_spans.reduce(ctx, stat="p50", **STEP_ARGS) is None
    assert program_spans.reduce(ctx, **gcs) is None
    assert setup_spans.reduce(ctx, spans=["compile:trace"]) is None
    # no window, or a program without the ring's reader
    assert program_spans.reduce({"window": {}}, stat="p50", **STEP_ARGS) is None
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "mxnet_tpu.tracing",
                        types.SimpleNamespace())
    assert program_spans.ring() is None
    assert program_spans.reduce(ctx, stat="p50", **STEP_ARGS) is None
    assert setup_spans.reduce(ctx, spans=["compile:trace"]) is None


def test_the_real_ring_is_read_through_tracing_records(untraced, monkeypatch):
    with tracing.begin("serve.tick"):
        pass
    t0 = tracing.records()[0]["t0"]
    monkeypatch.setitem(sys.modules, "__main__",
                        types.SimpleNamespace(T_START=t0 - 2.0))
    ctx = {"end_to_end": {"setup_s": 1.0}, "window": {"seconds": 2.0}}
    assert program_spans.ring() == (tracing.records(), 0)
    got = program_spans.reduce(ctx, spans=["serve.tick"], stat="max",
                               step="serve.tick")
    assert got == pytest.approx(1e3 * tracing.records()[0]["dur"])


@pytest.mark.parametrize("spec_k,want", [(0, 100.0), (2, 0.0)],
                         ids=["launched_ahead", "speculating"])
def test_fed_on_device_share_reads_the_served_ring(untraced, monkeypatch,
                                                   engine, spec_k, want):
    """``decode_fed_on_device_share`` (ISSUE 34) over the spans a server
    really wrote: every slot-step took its token on the device where
    steps are launched ahead, none where speculation reads each step
    first; a program that writes no ``fed`` (the parent) gives the
    reader nothing."""
    from benchmark.lib.reducers import span_args

    spec = manifest.layer_metric("decode_fed_on_device_share")
    assert spec["reducer"] == "span_args"
    eng = engine if not spec_k else generate.PagedGenerationEngine(
        engine._net, slots=2, cache_len=24, page_size=4, prefill_chunk=8,
        spec_k=spec_k, sampling=generate.SamplingConfig(greedy=True))
    _serve(eng)
    t0 = min(r["t0"] for r in tracing.records())
    monkeypatch.setitem(sys.modules, "__main__",
                        types.SimpleNamespace(T_START=t0 - 2.0))
    ctx = {"end_to_end": {"setup_s": 1.0}, "window": {"seconds": 3600.0}}
    assert span_args.reduce(ctx, **spec["args"]) == want
    steps = [r for r in tracing.records() if r["name"] == "engine.decode"]
    assert all(r["args"]["unread"] == 0 for r in steps) == bool(spec_k)
    monkeypatch.setattr(program_spans, "ring", lambda: (
        [dict(r, args={k: v for k, v in r["args"].items() if k != "fed"})
         for r in steps], 0))
    assert span_args.reduce(ctx, **spec["args"]) is None


def _planes():
    """A device busy [0,10) [14,20) [23,30) [32,40) ms; on the host two
    steps with a dispatch each, and the fetch thread's span on another
    line."""
    dev = [("fusion.1", 0 * MS, 10 * MS), ("fusion.2", 14 * MS, 6 * MS),
           ("fusion.3", 23 * MS, 7 * MS), ("copy.4", 32 * MS, 8 * MS)]
    main = [("mx:ShardedTrainer.step", 1 * MS, 20 * MS),
            ("mx:step:dispatch", 11 * MS, 2.5 * MS),
            ("mx:step:fetch", 14 * MS, 6 * MS),
            ("mx:ShardedTrainer.step", 22 * MS, 17 * MS),
            ("mx:step:dispatch", 30.5 * MS, 1 * MS),
            ("bench:trainer.step", 0, 40 * MS), ("other", 0, 50 * MS)]
    return {"/device:TPU:0": {"XLA Ops": dev},
            "/host:CPU": {"main": main,
                          "fetch": [("mx:step:fetch", 0, 50 * MS)]}}


def test_idle_reader_splits_gaps_among_the_innermost_program_spans(capsys):
    ctx = {"planes": _planes()}
    # gap [10,14): step to 11, dispatch to 13.5, step again; gap [20,23):
    # the first step to 21, then only the fetch thread's span, from 22 the
    # second step; gap [30,32): step, dispatch from 30.5 to 31.5, step
    found = idle_by_program_span.table(ctx["planes"])
    assert found["idle"] == {"mx:step:dispatch": pytest.approx(0.0035),
                             "mx:ShardedTrainer.step": pytest.approx(0.0045),
                             "mx:step:fetch": pytest.approx(0.001)}
    # the rule of xplane.idle_gaps: each gap whole to the innermost span
    # at its middle (12 -> dispatch, 21.5 -> fetch thread, 31 -> dispatch)
    assert found["by_middle"] == {"mx:step:dispatch": pytest.approx(0.006),
                                  "mx:step:fetch": pytest.approx(0.003)}
    assert found["counts"]["mx:ShardedTrainer.step"] == 2
    assert found["window_s"] == pytest.approx(0.040)
    assert found["lead_ns"] == 0.0
    got = idle_by_program_span.reduce(
        ctx, spans=["mx:step:dispatch"], per="mx:ShardedTrainer.step")
    assert got == pytest.approx(1.75)
    err = capsys.readouterr().err
    assert "idle 0.009000 s of 0.040000 s; 100.0 %" in err
    assert "thread /host:CPU fetch holds mx:step:fetch" in err
    idle_by_program_span.reduce(ctx, spans=["mx:step:fetch"],
                                per="mx:ShardedTrainer.step")
    assert "idle" not in capsys.readouterr().err      # the table once
    # a gap under no program span at all
    assert idle_by_program_span.charge([(0, 4)], [("mx:a", 5, 9)]) == \
        {idle_by_program_span.NO_SPAN: pytest.approx(4e-9)}


def test_idle_reader_puts_the_device_on_the_hosts_clock():
    from benchmark.lib import xplane

    # the device's clock reads 2 ms ahead: of two runs, the one that
    # started soonest after the host handed it over says so
    planes = _planes()
    planes[xplane.RUNS] = {
        "enqueued": [("7", 12 * MS, 1 * MS), ("8", 19 * MS, 1 * MS)],
        "device:0": [("7", 16 * MS, 6 * MS), ("8", 21 * MS, 7 * MS)]}
    found = idle_by_program_span.table(planes)
    assert found["lead_ns"] == pytest.approx(2 * MS)
    # gaps now [8,12) [18,21) [28,30): step 3 + dispatch 1; fetch 2 +
    # step 1; step 2
    assert found["idle"] == {"mx:ShardedTrainer.step": pytest.approx(0.006),
                             "mx:step:dispatch": pytest.approx(0.001),
                             "mx:step:fetch": pytest.approx(0.002)}


def test_idle_reader_finds_nothing():
    planes = _planes()
    assert idle_by_program_span.reduce({"planes": None}, spans=[], per="x") \
        is None
    # the parent commit's trace: bench:* spans only
    host = planes["/host:CPU"]
    planes["/host:CPU"] = {"main": [e for e in host["main"]
                                    if not e[0].startswith("mx:")]}
    assert idle_by_program_span.reduce(
        {"planes": planes}, spans=["mx:step:dispatch"],
        per="mx:ShardedTrainer.step") is None
    # spans, but none of the kind a step is counted by
    assert idle_by_program_span.reduce(
        {"planes": _planes()}, spans=["mx:step:dispatch"],
        per="mx:serve.tick") is None


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

NEW_METRICS = {
    "train_host_ms_per_step_p50": "program_spans",
    "train_host_ms_per_step_max": "program_spans",
    "train_gc_pause_ms_max": "program_spans",
    "train_idle_in_dispatch_ms_per_step": "idle_by_program_span",
    "serve_tick_ms_p95": "program_spans",
    "serve_tick_host_ms_mean": "program_spans",
    "serve_gc_pause_ms_max": "program_spans",
    "serve_idle_in_host_ms_per_tick": "idle_by_program_span",
    "setup_trace_lower_s": "setup_spans",
    "setup_executable_load_s": "setup_spans",
}


def test_manifest_checks_and_cells_report_the_new_metrics():
    man = manifest.manifest()
    assert manifest.check(man)
    names = [m["name"] for m in man["per_layer"]]
    # PR 26's ten follow the 17 of PR 24; PRs 28 and 33 appended four
    # each, PR 34 the share of slot-steps fed on the device, PR 35 three
    # of self-drafting, PR 36 the share of rows the held experts
    # multiply, PR 37 eleven parts of the device's time by named scope
    # (and PR 38 one, PR 39 four: the list only grows at its end)
    assert names[17:27] == list(NEW_METRICS) and len(names) >= 52
    assert names[35] == "decode_fed_on_device_share"
    train = {m["name"] for m in
             manifest.metrics_of(man, "per_layer", "resnet50_train_b256")}
    serve = {m["name"] for m in
             manifest.metrics_of(man, "per_layer", "opt1.3b_serve_chat")}
    assert {n for n in NEW_METRICS if n.startswith(("train_", "setup_"))} \
        <= train
    assert {n for n in NEW_METRICS if n.startswith(("serve_", "setup_"))} \
        <= serve
    assert not any(n.startswith("serve_") for n in train & set(NEW_METRICS))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_file_names_a_reader_that_takes_its_args(name):
    import importlib
    import inspect

    spec = manifest.layer_metric(name)
    assert spec["name"] == name and spec["reducer"] == NEW_METRICS[name]
    reader = importlib.import_module(
        "benchmark.lib.reducers." + spec["reducer"])
    inspect.signature(reader.reduce).bind({}, **spec["args"])
    # with no trace and no window the reader finds nothing and is silent
    assert reader.reduce({"window": {}, "planes": None, "end_to_end": {}},
                         **spec["args"]) is None
