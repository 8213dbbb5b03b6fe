"""Perf observatory (ISSUE 12): the BENCH record schema + run ledger
(mxnet_tpu/perf_ledger.py), the step-time attribution breakdown, the
noise-aware regression gate (tools/perf_gate.py), the ledger reporter /
legacy backfill (tools/perf_report.py), the Prometheus scrape endpoint,
and the heartbeat attribution fields.

Kept lean per the tier-1 budget: ONE tiny trainer compile for the whole
file; the gate/report/backfill tests are pure-stdlib on synthetic
ledgers.
"""
import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import nd, monitor, parallel
from mxnet_tpu import gluon
from mxnet_tpu import perf_ledger as pl
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon import nn
from mxnet_tpu.io.prefetch import DevicePrefetcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture
def registry():
    tel.enable()
    tel.reset()
    yield tel
    tel.reset()
    tel.disable()


# ---------------------------------------------------------------------------
# record schema + ledger
# ---------------------------------------------------------------------------

def test_record_schema_roundtrip():
    rec = pl.make_record("m", 1.5, "x",
                         prov={"mesh_shape": {"dp": 2}, "layout": "fsdp",
                               "dtype_policy": "bf16_mixed",
                               "steps_per_call": 4},
                         extra_field=7)
    assert pl.validate_record(rec) == []
    assert rec["schema_version"] == pl.SCHEMA_VERSION
    assert rec["provenance"]["layout"] == "fsdp"
    assert rec["provenance"]["git_sha"]  # resolved from the checkout
    assert rec["extra_field"] == 7
    # every provenance key is present on every record
    assert set(pl.PROVENANCE_KEYS) <= set(rec["provenance"])


def test_validate_record_catches_malformed():
    good = pl.make_record("m", 1.0, "x")
    for breakage, expect in (
            ({"metric": ""}, "metric"),
            ({"value": float("nan")}, "non-finite"),
            ({"value": None}, "value"),
            ({"schema_version": 99}, "schema_version"),
            ({"provenance": {"git_sha": "x"}}, "provenance."),
            ({"attribution": {"nope": 1}}, "attribution")):
        bad = dict(good)
        bad.update(breakage)
        problems = pl.validate_record(bad)
        assert problems and any(expect in p for p in problems), \
            (breakage, problems)
    with pytest.raises(ValueError):
        pl.check_record({"metric": "m"})
    with pytest.raises(ValueError):
        pl.make_record("m", 1.0, "x", provenance_collision=1,
                       prov={"not_a_field": 1})


def test_ledger_append_read_and_torn_line(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    r1 = pl.make_record("a", 1.0, "x")
    r2 = pl.make_record("b", 2.0, "x")
    assert pl.append([r1, r2], path=path) == path
    # a torn final line (crash mid-write) is reported, not fatal
    with open(path, "a") as f:
        f.write('{"schema_version": 1, "metr')
    recs, problems = pl.read_ledger(path)
    assert [r["metric"] for r in recs] == ["a", "b"]
    assert len(problems) == 1 and problems[0][0] == 3
    # append validates: malformed records never reach the file
    with pytest.raises(ValueError):
        pl.append({"metric": "m"}, path=path)


def test_emit_marker_line_and_ledger(tmp_path, capsys):
    path = str(tmp_path / "ledger.jsonl")
    rec = pl.make_record("m", 3.0, "x")
    pl.emit(rec, path=path)
    out = capsys.readouterr().out.strip()
    assert out.startswith(pl.BENCH_MARKER)
    assert json.loads(out[len(pl.BENCH_MARKER):]) == rec
    recs, problems = pl.read_ledger(path)
    assert recs == [rec] and not problems


def test_parse_bench_lines_marker_and_legacy():
    text = "\n".join([
        "[bench   1.2s] warmup step 0 done (loss=7.5312)",
        'BENCH {"metric": "a", "value": 1, "unit": "x"}',
        '{"metric": "legacy", "value": 2, "unit": "x"}',
        '{"not_a_metric": true}',
        "BENCH not-json",
    ])
    got = pl.parse_bench_lines(text)
    assert [r["metric"] for r in got] == ["a", "legacy"]
    # strict mode: only the marker counts
    got = pl.parse_bench_lines(text, legacy=False)
    assert [r["metric"] for r in got] == ["a"]


# ---------------------------------------------------------------------------
# every bench emitter produces schema-valid rows (the tier-1 guard of
# the acceptance criteria; canned results — the heavy benches are not
# run here)
# ---------------------------------------------------------------------------

_BENCH_RESULT = {
    "metric": "resnet50_train_images_per_sec_per_chip", "value": 2183.1,
    "unit": "images/sec", "vs_baseline": 6.0, "warmup_seconds": 120.0,
    "warmup_step_seconds": [118.0, 0.4], "mesh_shape": {},
    "layout": None, "images_per_sec_sync": 2100.0,
    "images_per_sec_async": 2183.1, "async_speedup": 1.04,
    "steps_per_call": 4, "async_metrics": True,
    "host_gap_seconds": {"sync": 0.001, "async": 0.0005},
    "dtype_policy": "bf16_mixed", "loss_scale": 65536.0,
    "loss_scale_backoffs": 0,
    "attribution": {"loop": "sharded", "steps": 40,
                    "wall_ms_per_step": 117.0, "span_ms_per_step": 110.0,
                    "gap_ms_per_step": 7.0,
                    "buckets_ms_per_step": {
                        "device_compute": 110.0, "compile": 0.0,
                        "aot_load": 0.0, "data_wait": 1.0,
                        "host_other": 6.0},
                    "collective_bytes_per_step": {}},
}
_LM_RESULT = {
    "metric": "transformer_lm_train_tokens_per_sec", "value": 51200.0,
    "unit": "tokens/sec", "tokens_per_sec": 51200.0,
    "tokens_per_sec_sync": 48000.0, "tokens_per_sec_async": 51200.0,
    "async_speedup": 1.067, "steps_per_call": 4, "async_metrics": True,
    "host_gap_seconds": {"sync": 0.001, "async": 0.0004}, "mfu": 0.41,
    "model_flops_per_step": 1e12, "mesh_shape": {"dp": 2, "tp": 4},
    "layout": "fsdp_tp", "batch": 32, "seq_len": 512,
    "warmup_step_seconds": [90.0, 0.2], "dtype_policy": "bf16_mixed",
    "loss_scale": 65536.0, "loss_scale_backoffs": 0,
}
_SERVING_RESULT = {
    "batch": 32, "n_batches": 32, "chain": 8, "dtype": "bfloat16",
    "link_MBps": 12.1, "link_ceiling_img_s": 80.5,
    "host_uint8_img_s": 71.2, "link_efficiency": 0.884,
    "device_resident_img_s": 2100.5, "device_top5_img_s": 6100.0,
    "anchor_v100_img_s": 2086.0, "device_vs_anchor": 1.007,
}
_SERVING_LOAD_RESULT = {
    "mode": "open-loop-poisson", "duration_s": 5.0,
    "rows_per_request": 1, "batch_rows": 8, "chain": 8, "replicas": 1,
    "devices": 8, "deadline_ms": 200.0,
    "sweep": [{"target_qps": 50.0, "offered": 250, "offered_qps": 50.0,
               "completed": 248, "goodput_qps": 49.6, "shed": 2,
               "shed_rate": 0.008, "timeouts": 0, "timeout_rate": 0.0,
               "errors": 0, "p50_ms": 4.2, "p99_ms": 11.0,
               "p999_ms": 15.0}],
}
_FUSION_ROWS = [
    {"metric": "fusion_layer_norm_fast_32x128x512_train_speedup",
     "value": 1.38, "unit": "x", "fused_ms": 1.1, "unfused_ms": 1.52,
     "infer_speedup": 1.6, "key": "layer_norm_fast|f32|-1x128x512"},
    {"metric": "fusion_best_speedup", "value": 1.38, "unit": "x",
     "pattern": "layer_norm_fast", "mode": "train",
     "shape": "32x128x512"},
]
_CHECKPOINT_RESULT = {
    "params_mb": 8.0, "hidden": 707, "n_layers": 4, "steps": 30,
    "period": 1, "platform": "cpu", "baseline_ms": 11.2,
    "blocking_ms": 14.9, "async_ms": 11.9,
    "blocking_overhead_ms_per_save": 3.7,
    "async_overhead_ms_per_save": 0.7,
    # a --sharded run's fields (both headline seconds are down-good)
    "gather_save_s": 0.041, "gather_restore_s": 0.022,
    "sharded_save_s": 0.027, "sharded_restore_s": 0.019,
}


def _records_bench():
    import bench

    return bench.ledger_records(_BENCH_RESULT)


def _records_bench_lm():
    import bench_lm

    return bench_lm.ledger_records(_LM_RESULT)


def _records_bench_serving():
    import bench_serving

    return bench_serving.ledger_records(_SERVING_RESULT) + \
        bench_serving.ledger_records(_SERVING_LOAD_RESULT)


def _records_bench_fusion():
    import bench_fusion

    return bench_fusion.ledger_records(_FUSION_ROWS)


def _records_bench_checkpoint():
    import bench_checkpoint

    recs = bench_checkpoint.ledger_records(_CHECKPOINT_RESULT)
    assert {"checkpoint_async_overhead_ms_per_save",
            "checkpoint_sharded_save_seconds",
            "checkpoint_sharded_restore_seconds"} <= \
        {r["metric"] for r in recs}
    return recs


def _records_bench_io():
    import bench_io

    return bench_io.ledger_records(312.0, 81.5, 2048, 4)


def _records_bench_decode():
    # every bench_decode mode: the default two-phase bench plus the
    # three lever modes (--prefix-share / --chunked-prefill / --spec),
    # each with its own canned result and headline metric
    import bench_decode

    recs = []
    for mode, canned in sorted(bench_decode.CANNED_MODE_RESULTS.items()):
        recs += bench_decode.ledger_records(canned)
    metrics = {r["metric"] for r in recs}
    assert {"lm_decode_tokens_per_sec_per_user",
            "lm_decode_prefix_share_tokens_per_sec",
            "lm_decode_prefix_hit_rate",
            "lm_decode_ttft_interference_p99_ms",
            "lm_decode_spec_accepted_per_step"} <= metrics
    return recs


# a merged /goodputz payload the goodput emitter prices into ledger
# records (canned — the real kill/resume drill lives in
# tests/test_goodput.py)
_GOODPUT_PAYLOAD = {
    "active": True, "dir": "/tmp/goodput-job", "wall_s": 120.0,
    "goodput_pct": 81.25, "goodput_s": 97.5, "badput_s": 22.5,
    "buckets_s": {"goodput": 97.5, "lost_work": 6.0, "compile": 4.0,
                  "ckpt_save": 2.0, "ckpt_restore": 1.0,
                  "data_wait": 3.0, "startup": 2.5, "drain": 0.5,
                  "other": 3.0},
    "steps": 3200, "lost_steps": 200, "kills": 1,
    "n_incarnations": 2, "n_ranks": 1,
    "mttr": {"events": [{"rank": 0, "killed": 100.0,
                         "resumed": 142.0, "mttr_s": 42.0}],
             "mean_s": 42.0},
}


def _records_goodput():
    from mxnet_tpu import goodput

    recs = goodput.ledger_records(_GOODPUT_PAYLOAD)
    assert {r["metric"] for r in recs} == {
        "goodput_pct", "goodput_lost_work_s", "goodput_mttr_s"}
    # inactive or wall-less payloads emit nothing rather than zeros
    assert goodput.ledger_records({"active": False}) == []
    assert goodput.ledger_records(
        dict(_GOODPUT_PAYLOAD, wall_s=0.0)) == []
    return recs


def test_goodput_ledger_records_reject_malformed():
    from mxnet_tpu import goodput

    rec = goodput.ledger_records(_GOODPUT_PAYLOAD)[0]
    for breakage in ({"unit": ""}, {"value": None},
                     {"value": float("nan")}):
        bad = dict(rec)
        bad.update(breakage)
        assert pl.validate_record(bad), breakage


@pytest.mark.parametrize("builder", [
    _records_bench, _records_bench_lm, _records_bench_serving,
    _records_bench_fusion, _records_bench_checkpoint, _records_bench_io,
    _records_bench_decode, _records_goodput,
], ids=["bench", "bench_lm", "bench_serving", "bench_fusion",
        "bench_checkpoint", "bench_io", "bench_decode", "goodput"])
def test_every_emitter_builds_schema_valid_records(builder):
    recs = builder()
    assert recs, "emitter produced no records"
    for rec in recs:
        assert pl.validate_record(rec) == [], rec["metric"]
        assert set(pl.PROVENANCE_KEYS) <= set(rec["provenance"])
    # topology/precision provenance actually lands where stamped
    for rec in recs:
        if rec["metric"] == "transformer_lm_train_tokens_per_sec":
            assert rec["provenance"]["layout"] == "fsdp_tp"
            assert rec["provenance"]["dtype_policy"] == "bf16_mixed"
            assert rec["provenance"]["steps_per_call"] == 4


# ---------------------------------------------------------------------------
# step-time attribution
# ---------------------------------------------------------------------------

def test_step_breakdown_sums_to_measured_wall(registry):
    import jax

    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    trainer = parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), optimizer="sgd",
        optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(8, 8).astype(np.float32))
    y = nd.array(rng.rand(8, 4).astype(np.float32))
    loss = trainer.step([x], y)  # warm/compile off the measured window
    jax.block_until_ready(loss)
    tel.reset()
    steps = 30
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step([x], y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    bd = trainer.step_breakdown()
    assert bd is not None and bd.steps == steps
    buckets = bd.buckets()
    assert set(buckets) == set(pl.BREAKDOWN_BUCKETS)
    # the accounting identity: buckets sum to span+gap exactly
    assert sum(buckets.values()) == pytest.approx(bd.wall_s, rel=1e-9)
    # ... and the wall it decomposes matches the externally measured
    # loop wall within the 5% acceptance bound (the first step of the
    # window observes no gap, so the breakdown slightly undercounts)
    assert bd.wall_s * steps == pytest.approx(dt, rel=0.05)
    # steady state on a warm executable: no compile/aot in the window
    assert buckets["compile"] == 0.0 and buckets["aot_load"] == 0.0
    assert buckets["device_compute"] > 0
    assert "device_compute" in bd.describe()
    # the record embedding the gate consumes
    rec = pl.make_record("m", 1.0, "x", attribution=bd)
    assert rec["attribution"]["buckets_ms_per_step"]["device_compute"] > 0
    assert pl.validate_record(rec) == []


def test_step_breakdown_none_without_telemetry_window(registry):
    tel.reset()
    assert pl.StepBreakdown.from_telemetry(loop="sharded") is None


def test_prefetch_wait_feeds_data_wait_bucket(registry):
    def slow_source():
        for i in range(3):
            time.sleep(0.01)
            yield i

    got = list(DevicePrefetcher(slow_source(), put=lambda b: b, depth=1))
    assert got == [0, 1, 2]
    assert tel.PREFETCH_STALLS.value() >= 1
    assert tel.PREFETCH_WAIT_SECONDS.count() >= 1
    assert tel.PREFETCH_WAIT_SECONDS.sum() > 0


def test_heartbeat_line_has_attribution_fields(registry):
    tel.TRAIN_STEPS.inc(4, loop="sharded")
    tel.TRAIN_STEP_SECONDS.observe(0.01, loop="sharded")
    tel.HOST_GAP_SECONDS.observe(0.002, loop="sharded")
    tel.PREFETCH_WAIT_SECONDS.observe(0.004)
    line = monitor.TelemetryHeartbeat().line()
    # p50 is bucket-interpolated (a single 2 ms sample reads ~1.8)
    assert "host_gap_ms p50 1." in line, line
    assert "data_wait_ms 1.0" in line, line  # 4 ms over 4 steps


# ---------------------------------------------------------------------------
# scrape endpoint
# ---------------------------------------------------------------------------

def test_serve_scrape_metrics_and_healthz(registry):
    srv = tel.serve_scrape(port=0)
    try:
        assert tel.serve_scrape(port=0) is srv  # one per process
        base = "http://127.0.0.1:%d" % srv.port
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "# TYPE mxnet_tpu_train_steps_total counter" in body
        hz = urllib.request.urlopen(base + "/healthz")
        assert hz.status == 200 and hz.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")
    finally:
        tel.stop_scrape()
    assert tel.scrape_server() is None


def test_healthz_readiness_flips_to_503(registry):
    """The probe answers 503 while any registered readiness check
    fails — e.g. a serving tier that has not brought its first
    replica up yet — and recovers when it passes (regression: the old
    probe answered 200 for process lifetime regardless of serving
    state; the drained-shutdown flip is driven end-to-end in
    tests/test_events.py)."""
    srv = tel.serve_scrape(port=0)
    base = "http://127.0.0.1:%d" % srv.port
    replica_up = []
    tel.register_readiness("gateway", lambda: bool(replica_up))
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz")
        assert ei.value.code == 503
        payload = json.loads(ei.value.read())
        assert payload["failing"] == ["gateway"]
        replica_up.append(True)          # first replica ready
        hz = urllib.request.urlopen(base + "/healthz")
        assert hz.status == 200 and hz.read() == b"ok\n"
        # a RAISING check fails closed, it does not read as ready
        tel.register_readiness("broken", lambda: 1 / 0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz")
        assert ei.value.code == 503
    finally:
        tel.unregister_readiness("gateway")
        tel.unregister_readiness("broken")
        tel.stop_scrape()


# ---------------------------------------------------------------------------
# the regression gate (synthetic ledgers; pure stdlib)
# ---------------------------------------------------------------------------

def _attr(host_other_ms):
    return {"loop": "sharded", "steps": 40,
            "wall_ms_per_step": 111.0 + host_other_ms,
            "span_ms_per_step": 110.0,
            "gap_ms_per_step": 1.0 + host_other_ms,
            "buckets_ms_per_step": {
                "device_compute": 110.0, "compile": 0.0, "aot_load": 0.0,
                "data_wait": 1.0, "host_other": host_other_ms}}


def _gate_rec(run, t, value, host_other_ms, metric="m_img_s",
              unit="images/sec"):
    return {"schema_version": pl.SCHEMA_VERSION, "run_id": run,
            "time": t, "metric": metric, "value": value, "unit": unit,
            "provenance": {k: "unknown" for k in pl.PROVENANCE_KEYS},
            "attribution": _attr(host_other_ms)}


def _write_jsonl(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_gate_flags_injected_regression_naming_bucket(tmp_path, capsys):
    import perf_gate

    base = _write_jsonl(tmp_path / "base.jsonl", [
        _gate_rec("r%d" % i, 100.0 + i, v, 6.0)
        for i, v in enumerate([2183.12, 2190.1, 2179.38, 2180.72])])
    # injected 10% throughput regression, host_other bucket grown
    cand = _write_jsonl(tmp_path / "cand.jsonl", [
        _gate_rec("cand", 200.0, 2183.0 * 0.9, 19.0)])
    rc = perf_gate.main(["--baseline", base, "--candidate", cand])
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL m_img_s" in out
    assert "largest-moving attribution bucket: host_other" in out


def test_gate_passes_identical_rerun_within_band(tmp_path, capsys):
    import perf_gate

    base = _write_jsonl(tmp_path / "base.jsonl", [
        _gate_rec("r%d" % i, 100.0 + i, v, 6.0)
        for i, v in enumerate([2183.12, 2190.1, 2179.38, 2180.72])])
    cand = _write_jsonl(tmp_path / "cand.jsonl", [
        _gate_rec("cand", 200.0, 2180.72, 6.0)])
    rc = perf_gate.main(["--baseline", base, "--candidate", cand])
    out = capsys.readouterr().out
    assert rc == 0 and "PASS m_img_s" in out


def test_gate_min_of_blocks_and_direction(tmp_path, capsys):
    import perf_gate

    # latency metric (lower-better): within-run blocks reduce to min,
    # so one noisy block cannot fail the run...
    base = _write_jsonl(tmp_path / "base.jsonl", [
        _gate_rec("r0", 100.0, 10.0, 6.0, metric="m_lat_seconds",
                  unit="seconds"),
        _gate_rec("r1", 101.0, 10.2, 6.0, metric="m_lat_seconds",
                  unit="seconds")])
    cand = _write_jsonl(tmp_path / "cand.jsonl", [
        _gate_rec("cand", 200.0, 25.0, 6.0, metric="m_lat_seconds",
                  unit="seconds"),
        _gate_rec("cand", 201.0, 10.1, 6.0, metric="m_lat_seconds",
                  unit="seconds")])
    assert perf_gate.main(["--baseline", base, "--candidate", cand]) == 0
    capsys.readouterr()
    # ...but a genuinely slower candidate (every block) fails upward
    cand_bad = _write_jsonl(tmp_path / "cand_bad.jsonl", [
        _gate_rec("cand", 200.0, 12.0, 6.0, metric="m_lat_seconds",
                  unit="seconds")])
    rc = perf_gate.main(["--baseline", base, "--candidate", cand_bad])
    out = capsys.readouterr().out
    assert rc == 1 and "FAIL m_lat_seconds" in out


def test_gate_band_seeded_from_baseline_spread(tmp_path, capsys):
    import perf_gate

    # noisy baseline (+-10%): a -12% candidate sits INSIDE the seeded
    # band (2 x 20% spread) even though it is far past the 2% floor
    base = _write_jsonl(tmp_path / "base.jsonl", [
        _gate_rec("r%d" % i, 100.0 + i, v, 6.0)
        for i, v in enumerate([900.0, 1000.0, 1100.0])])
    cand = _write_jsonl(tmp_path / "cand.jsonl", [
        _gate_rec("cand", 200.0, 880.0, 6.0)])
    rc = perf_gate.main(["--baseline", base, "--candidate", cand])
    capsys.readouterr()
    assert rc == 0
    # an explicit per-metric tolerance overrides the seeding
    rc = perf_gate.main(["--baseline", base, "--candidate", cand,
                         "--tolerance", "m_img_s=0.05"])
    capsys.readouterr()
    assert rc == 1


def test_gate_single_ledger_latest_vs_history(tmp_path, capsys):
    import perf_gate

    recs = [_gate_rec("r%d" % i, 100.0 + i, v, 6.0)
            for i, v in enumerate([2183.12, 2190.1, 2179.38])]
    recs.append(_gate_rec("new", 200.0, 1900.0, 21.0))
    ledger = _write_jsonl(tmp_path / "ledger.jsonl", recs)
    rc = perf_gate.main(["--ledger", ledger])
    out = capsys.readouterr().out
    assert rc == 1 and "host_other" in out


def test_gate_unusable_input_is_rc2(tmp_path, capsys):
    import perf_gate

    only = _write_jsonl(tmp_path / "one.jsonl",
                        [_gate_rec("r0", 100.0, 1.0, 6.0)])
    assert perf_gate.main(["--ledger", only]) == 2
    capsys.readouterr()
    # a multi-line ledger under a non-.jsonl name (or any unreadable
    # file) must be exit 2, never exit 1: CI reads 1 as a regression
    misnamed = str(tmp_path / "perf.ledger")
    with open(misnamed, "w") as f:
        for r in [_gate_rec("r0", 100.0, 1.0, 6.0),
                  _gate_rec("r1", 101.0, 1.0, 6.0)]:
            f.write(json.dumps(r) + "\n")
    assert perf_gate.main(["--baseline", misnamed,
                           "--candidate", misnamed]) == 2
    capsys.readouterr()
    assert perf_gate.main(["--baseline", str(tmp_path / "absent.jsonl"),
                           "--candidate", misnamed]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# perf_report: backfill + single-run + diff
# ---------------------------------------------------------------------------

def _legacy_capture(tmp_path, name, value):
    """A pre-schema driver bench capture (the shape --backfill reads);
    the value is synthetic."""
    row = {"metric": "resnet50_train_images_per_sec_per_chip",
           "value": value, "unit": "images/sec"}
    path = tmp_path / (name + ".json")
    path.write_text(json.dumps({
        "n": 1, "cmd": "python bench.py", "rc": 0,
        "tail": "[bench] 40 steps\n%s\n" % json.dumps(row),
        "parsed": row}))
    return str(path)


def test_backfill_ingests_legacy_run_files(tmp_path, capsys):
    import perf_report

    ledger = str(tmp_path / "hist.jsonl")
    files = [_legacy_capture(tmp_path, "BENCH_r0%d" % i, 100.0 + i)
             for i in (2, 3, 4, 5)]
    multichip = tmp_path / "MULTICHIP_r01.json"
    multichip.write_text(json.dumps(
        {"n_devices": 8, "rc": 1, "ok": False, "skipped": False,
         "tail": "AssertionError: need 8 devices\n"}))
    files += [str(multichip), os.path.join(REPO, "MULTIHOST_r04.json")]
    assert perf_report.main(["--ledger", ledger, "--backfill"]
                            + files) == 0
    capsys.readouterr()
    recs, problems = pl.read_ledger(ledger)
    assert not problems and len(recs) == 6
    heads = [r for r in recs
             if r["metric"] == "resnet50_train_images_per_sec_per_chip"]
    assert len(heads) == 4
    assert all(r["provenance"]["git_sha"] == "unknown" for r in recs)
    assert all(r["backfill"] for r in recs)
    assert {r["run_id"] for r in heads} == \
        {"BENCH_r02", "BENCH_r03", "BENCH_r04", "BENCH_r05"}
    # the legacy runs are now queryable history the report renders
    assert perf_report.main(["--ledger", ledger]) == 0
    out = capsys.readouterr().out
    assert "resnet50_train_images_per_sec_per_chip" in out
    assert "multihost_dryrun_ok" in out


def test_report_single_run_and_attributed_diff(tmp_path, capsys):
    import perf_report

    ledger = _write_jsonl(tmp_path / "ledger.jsonl", [
        _gate_rec("runA", 100.0, 2183.0, 6.0),
        _gate_rec("runB", 200.0, 2100.0, 12.0)])
    assert perf_report.main(["--ledger", ledger, "--run", "runA"]) == 0
    out = capsys.readouterr().out
    assert "where did the milliseconds go" in out
    assert "device_compute" in out and "host_other" in out
    assert perf_report.main(["--ledger", ledger, "--diff", "prev",
                             "latest"]) == 0
    out = capsys.readouterr().out
    assert "m_img_s" in out and "-3.8%" in out
    assert "host_other" in out and "+100.0%" in out
    assert "story:" in out
    # unknown run ids are a clean rc=2, not a traceback
    assert perf_report.main(["--ledger", ledger, "--run", "nope"]) == 2
    capsys.readouterr()
    # 'prev' on a one-run ledger is an error, not a self-diff
    single = _write_jsonl(tmp_path / "one.jsonl",
                          [_gate_rec("only", 100.0, 2183.0, 6.0)])
    assert perf_report.main(["--ledger", single, "--diff", "latest",
                             "prev"]) == 2
    capsys.readouterr()


def test_diff_against_backfilled_baseline_zero_fills_attribution(
        tmp_path, capsys):
    """--diff where one side is pre-schema backfilled history: the
    baseline run carries NO attribution (and the schema'd side may
    carry bucket names the other lacks) — missing buckets read as
    zero and the story still renders, instead of raising or silently
    dropping the section."""
    import perf_report

    ledger = str(tmp_path / "hist.jsonl")
    # a real backfilled baseline (provenance unknown, no attribution)
    assert perf_report.main(
        ["--ledger", ledger, "--backfill",
         _legacy_capture(tmp_path, "BENCH_r05", 105.0)]) == 0
    capsys.readouterr()
    # a modern run whose attribution has an extra custom bucket
    rec = _gate_rec("runNew", 300.0, 2100.0, 12.0,
                    metric="resnet50_train_images_per_sec_per_chip",
                    unit="images/sec")
    rec["attribution"]["buckets_ms_per_step"]["custom_wait"] = 3.0
    with open(ledger, "a") as f:
        f.write(json.dumps(rec) + "\n")
    assert perf_report.main(
        ["--ledger", ledger, "--diff", "prev", "latest"]) == 0
    out = capsys.readouterr().out
    assert "read as zero" in out
    assert "device_compute" in out and "custom_wait" in out
    assert "story:" in out
    # the reverse direction (attribution -> none) renders too
    assert perf_report.main(
        ["--ledger", ledger, "--diff", "latest", "prev"]) == 0
    capsys.readouterr()
