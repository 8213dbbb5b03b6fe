"""The reduction from a trace to numbers: its arithmetic on hand-made
events, and its reading of the small recorded trace kept beside it."""
import os

import pytest

from benchmark.lib import xplane
from benchmark.lib.reducers import device_busy_per_step

MS = 1e6  # ns


def planes():
    dev0 = [("fusion.1", 0 * MS, 10 * MS), ("fusion.2", 5 * MS, 10 * MS),
            ("all-reduce.3", 20 * MS, 5 * MS), ("copy.4", 40 * MS, 10 * MS)]
    dev1 = [("fusion.1", 0 * MS, 10 * MS), ("all-gather-start.1", 30 * MS, 20 * MS)]
    host = [("bench:trainer.step", 0 * MS, 18 * MS), ("bench:await", 16 * MS, 3 * MS),
            ("bench:trainer.step", 24 * MS, 30 * MS), ("other", 0, 100 * MS)]
    return {"/device:TPU:0": {"XLA Ops": dev0, "XLA Modules": [("jit_step", 0, 50 * MS)]},
            "/device:TPU:1": {"XLA Ops": dev1},
            "/host:CPU": {"python3": host}}


def test_busy_union_and_window():
    busy, window, busiest = xplane.busy_and_window(planes())
    # dev0: [0,15) + [20,25) + [40,50) = 30 ms; dev1: 10 + 20 = 30 ms
    assert window == pytest.approx(0.050)
    assert busy == pytest.approx(0.030)
    assert busiest == pytest.approx(0.030)
    assert 1 - busy / window == pytest.approx(0.4)


def test_per_op_sums_group_by_kind():
    top = dict(xplane.top_ops(planes()))
    assert top["fusion__x2"] == pytest.approx(0.020)
    assert top["copy__x1"] == pytest.approx(0.010)
    assert xplane.op_group("fusion.123") == "fusion"
    hlo = ("%multiply_reduce_fusion.6 = (bf16[256]{0:T(256)}, "
           "f32[256,64,56,56]{1,0,3,2:T(8,128)}) fusion(bf16[256]{0} "
           "%all-reduce.1, f32[8]{0} %p), kind=kOutput")
    assert xplane.op_name(hlo) == "multiply_reduce_fusion.6"
    assert xplane.op_group(hlo) == "multiply_reduce_fusion_f32_256_64_56_56"
    assert xplane.op_group("%copy.7 = bf16[24,513]{1,0} copy(bf16[24,513] %x)") \
        == "copy_bf16_24_513"
    # an operand named for a collective does not make a fusion one
    assert not xplane.COLLECTIVE.search(xplane.op_name(hlo))


def test_collectives_are_classified():
    # busiest of dev0 (all-reduce, 5 ms) and dev1 (all-gather-start, 20 ms)
    assert xplane.collective_seconds(planes()) == pytest.approx(0.020)
    assert not xplane.COLLECTIVE.search("fusion.7")
    for name in ("all-reduce.1", "reduce-scatter.2", "collective-permute-done",
                 "all-to-all.3", "all-gather-start"):
        assert xplane.COLLECTIVE.search(name)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(xplane.idle_gaps(planes()))
    # dev0 idle [15,20): middle 17.5 lies in trainer.step and await -> await
    assert gaps["bench:await"] == pytest.approx(0.005)
    # idle [25,40): middle 32.5 lies in the second trainer.step
    assert gaps["bench:trainer.step"] == pytest.approx(0.015)


def test_busy_per_counted_step():
    ctx = {"planes": planes(), "window": {"traced_steps": 2}}
    assert device_busy_per_step.reduce(ctx, "traced_steps") == pytest.approx(15.0)
    assert device_busy_per_step.reduce({"planes": None, "window": {}},
                                       "traced_steps") is None


def serving_trace(handed_over_ms_late):
    """decode, prefill chunk, decode, decode.  The first decode step
    launches a second program (a write-back) that runs on after the host
    has its tokens; the prefill chunk's span closes as soon as the call
    returns.  The runtime hands each program to the device some
    milliseconds after the call, and the device's clock is 7 ms off."""
    off, late = 7 * MS, handed_over_ms_late * MS
    call = xplane.LAUNCH
    launches = [(1, 1), (2, 58), (3, 82.5), (4, 84.5), (5, 199)]  # run, ms
    runs = {"device:0": [("0", -30 * MS - off, 25 * MS),    # before the trace
                         ("1", 0 * MS - off, 60 * MS), ("2", 60 * MS - off, 20 * MS),
                         ("3", 85 * MS - off, 40 * MS), ("4", 130 * MS - off, 60 * MS),
                         ("5", 200 * MS - off, 60 * MS)],
            "enqueued": [(str(r), at * MS + late, 0) for r, at in launches]
            + [("1", 1 * MS + late + 1, 0)]}
    host = [("bench:engine.decode", 0 * MS, 62 * MS),
            ("bench:engine.prefill", 82 * MS, 2 * MS),
            ("bench:engine.decode", 84.2 * MS, 110 * MS),
            ("bench:engine.decode", 198 * MS, 70 * MS),
            ("bench:loadgen.wait", 62 * MS, 20 * MS)] \
        + [(call, at * MS, 0.3 * MS) for _r, at in launches]
    return {"/device:TPU:0": {"XLA Ops": [("op", 0, 1)]},
            "/host:CPU": {"server": host}, xplane.RUNS: runs}


@pytest.mark.parametrize("handed_over_ms_late", [0.1, 1.4, 6.0])
def test_a_program_is_charged_to_the_step_that_launched_it(handed_over_ms_late):
    """Whenever the runtime hands it over (the prefill chunk's, at 1.4 ms,
    after its span has closed and the next has opened; the write-back's,
    at 6 ms, after the host has left the decode step) and wherever on the
    device's time line it runs: moving work past the read-back lowers
    nothing."""
    pl = serving_trace(handed_over_ms_late)
    by_span = xplane.program_seconds_by_span(pl, "bench:engine.")
    assert by_span == [("bench:engine.decode", pytest.approx(0.080)),
                       ("bench:engine.prefill", pytest.approx(0.040)),
                       ("bench:engine.decode", pytest.approx(0.060)),
                       ("bench:engine.decode", pytest.approx(0.060))]
    # the last span is left out: (80 + 60) / 2
    sec, n = device_busy_per_step.seconds_charged_to(
        pl, "bench:engine.decode", "bench:engine.")
    assert (n, sec) == (2, pytest.approx(0.140))
    assert device_busy_per_step.reduce(
        {"planes": pl, "window": {}}, span="bench:engine.decode",
        among="bench:engine.") == pytest.approx(70.0)
    ctx = {"planes": pl, "window": {"live": 1000},
           "cfg": {"family": "opt", "num_hidden_layers": 2,
                   "hidden_size": 8, "ffn_dim": 32, "vocab_size": 16},
           "peaks": {"hbm_bytes_per_s": 1e6}}
    from benchmark.lib.reducers import decode_hbm_roofline
    share = decode_hbm_roofline.reduce(
        ctx, "bench:engine.decode", "bench:engine.", "live")
    assert share == pytest.approx(100.0 * (
        2 * (2 * (4 * 64 + 2 * 8 * 32) + 16 * 8)
        + 2 * 2 * 8 * 2 * 1000) / 1e6 / 0.070)
    assert decode_hbm_roofline.reduce(
        dict(ctx, window={}), "bench:engine.decode", "bench:engine.",
        "live") is None
    # a trace with no program runs in it gives nothing, not 0
    assert device_busy_per_step.seconds_charged_to(
        planes(), "bench:trainer.step", "bench:") is None


RECORDED = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_reads():
    """A trace of a few training steps recorded on a TPU v5 lite (PR 24):
    device plane, operations line, the benchmark's host spans."""
    pl = xplane.load(RECORDED)
    ops = xplane.device_ops(pl)
    assert list(ops) == [0]
    busy, window, busiest = xplane.busy_and_window(pl)
    assert 0 < busy <= window
    assert busy == pytest.approx(busiest)
    assert busy == pytest.approx(44.286e-6, rel=1e-6)
    assert xplane.top_ops(pl, 1)[0][0] == "fusion_bf16_1024_1024__x3"
    assert xplane.collective_seconds(pl) == 0.0
    assert len(xplane.host_spans(pl, "bench:trainer.step")) == 3
    assert dict(xplane.idle_gaps(pl))["bench:trainer.step"] > 0
    # each step's one program, tied to it by the run_id of its enqueue
    by_span = xplane.program_seconds_by_span(pl, "bench:trainer.step")
    assert [n for n, _s in by_span] == ["bench:trainer.step"] * 3
    assert [s for _n, s in by_span] == pytest.approx(
        [12.643e-6, 15.931e-6, 15.731e-6])
