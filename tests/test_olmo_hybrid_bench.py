"""The benchmark's part of the Olmo-Hybrid-7B cell, tested from
``tests/`` because ``benchmark/tests`` is not in the tier-1 command: the
cell's entries in ``BENCHMARK.json``, the traffic file, the family's
counts against a hand count at the published widths, the cell's
rehearsal through ``benchmark/run.py --rehearse 1``, the faults
``tools/gdn_limits.py`` plants, the readers of the four new metrics on
hand-made rings, and the chip's readings judged by the committed
limits."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark"),
           os.path.join(ROOT, "benchmark", "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402

from benchmark import programs  # noqa: E402
from benchmark.drivers import serve_gdn  # noqa: E402
from benchmark.lib import manifest, weights  # noqa: E402
from benchmark.lib.reducers import (chunk_scan_roofline,  # noqa: E402
                                    device_busy_per_step, device_by_scope,
                                    state_hbm_roofline)
from test_blockgen_bench import ctx  # noqa: E402,F401 (a fixture)

CELL, CONFIG = "olmohybrid7b_serve_doc4k", "olmo-hybrid-7b"
NEW_METRICS = {
    "decode_gdn_scan_device_ms_per_step": ("ms", "lower"),
    "prefill_gdn_scan_device_ms_per_chunk": ("ms", "lower"),
    "gdn_chunk_scan_roofline": ("%", "higher"),
    "gdn_decode_hbm_roofline": ("%", "higher")}
# the accepted serving metrics the cell reports besides its own
LISTED = {
    "serve_prefill_share", "serve_tick_ms_p95",
    "hybrid_prefill_device_ms_per_chunk", "decode_state_slots_share",
    "decode_cache_gather_device_ms_per_step",
    "decode_attn_core_device_ms_per_step", "decode_ffn_device_ms_per_step",
    "decode_head_device_ms_per_step", "decode_device_unscoped_share",
    "prefill_attn_core_device_ms_per_chunk", "prefill_attended_rows_share",
    "decode_attended_rows_share"}


@pytest.fixture(scope="module")
def cfg():
    return manifest.config(manifest.manifest(), CONFIG)


# -- the manifest -------------------------------------------------------------

def test_manifest_gains_one_configuration_and_one_cell():
    man = manifest.manifest()
    assert manifest.check(man)
    assert man["configs"][-1]["name"] == CONFIG
    assert man["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert man["workloads"][-1]["name"] == CELL
    cell = manifest.workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "serve_doc4k", 1)
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"serve_itl_p95_ms", "setup_s"}
    layer = {m["name"]: m for m in manifest.metrics_of(man, "per_layer", CELL)}
    names = [m["name"] for m in man["per_layer"]]
    assert names[-4:] == list(NEW_METRICS)
    for name, (unit, better) in NEW_METRICS.items():
        assert (layer[name]["unit"], layer[name]["better"],
                layer[name]["layer"], layer[name]["moves"],
                layer[name]["workloads"]) == (
            unit, better, "kernels", "serve_itl_p95_ms", [CELL])
    # the accepted metrics: the cell appended to their lists, and nothing
    # else of them changed
    for name in LISTED:
        assert layer[name]["workloads"][-1] == CELL, name
    assert {n for n in layer if "workloads" in layer[n]} == \
        LISTED | set(NEW_METRICS)
    assert {m["moves"] for m in layer.values()} <= e2e


def test_traffic_is_as_the_cell_states():
    t, chat = manifest.traffic("serve_doc4k"), manifest.traffic("serve_chat")
    assert t["driver"] == "serve_gdn"
    assert {k: t[k] for k in (
        "clients", "slots", "cache_len", "page_size", "num_pages",
        "prefill_chunk", "prefix_share", "strata", "check_requests",
        "check_slots", "trace_seconds")} == {
        "clients": 64, "slots": 32, "cache_len": 5120, "page_size": 16,
        "num_pages": 10241, "prefill_chunk": 512, "prefix_share": False,
        "strata": 64, "check_requests": 6, "check_slots": 2,
        "trace_seconds": 3.0}
    assert t["prompt_len"] == {"median": 1024, "sigma": 0.8, "lo": 256,
                               "hi": 4096}
    assert t["answer_len"] == {"median": 384, "sigma": 0.6, "lo": 64,
                               "hi": 1024}
    assert t["pairing"] == chat["pairing"] and t["order"] == chat["order"]
    from benchmark.lib import lengths

    shapes = lengths.request_shapes(t)
    assert max(p + a for p, a in shapes) <= t["cache_len"]
    assert t["num_pages"] == t["slots"] * t["cache_len"] // t["page_size"] + 1


# -- the counts, by hand at the published widths ------------------------------

def test_family_counts_against_a_hand_count(cfg):
    fam = weights.family(cfg)
    D, H, F, V = 3840, 30, 11008, 100352
    wide = 30 * (96 + 96 + 192)
    gdn = D * wide + 2 * D * H + D * H * 192 + H * 192 * D       # 88.7 M
    full = 4 * D * D                                              # 59.0 M
    mlp = 3 * D * F                                               # 126.8 M
    assert (gdn, full, mlp) == (88704000, 58982400, 126812160)
    assert fam.mixer_params(cfg, "gdn") == gdn
    assert fam.mixer_params(cfg, "gqa") == full
    assert fam.mlp_params(cfg) == mlp
    assert fam.layer_kinds(cfg) == ["gdn"] * 3 + ["gqa"] + ["gdn"] * 3 + \
        ["gqa"]
    state = 4 * 30 * 96 * 192 + 4 * wide        # a GDN layer's state update
    per_token = V * D + 6 * (gdn + mlp) + 2 * (full + mlp) + 6 * state
    assert fam.serve_flops_per_token(cfg, 1000) == \
        2 * per_token + 2 * 4 * 30 * 128 * 1000
    # every weight once as stored (the head float32), the active slots'
    # state read and written, the full layers' rows of the live positions
    weights_b = 4 * V * D + 2 * (6 * (gdn + mlp) + 2 * (full + mlp)) \
        + 6 * 2 * 4 * wide
    slot = 6 * 2 * (4 * 30 * 96 * 192 + 2 * 3 * wide)
    rows = 2 * 2 * 2 * 30 * 128
    assert fam.forward_min_bytes(cfg, 48000, 31.5) == \
        weights_b + 31.5 * slot + 48000 * rows
    # a (32, 1) step at a mean live length of 1500: about 7.2 GB, 8.8 ms
    # at 819 GB/s
    assert 7.1e9 < fam.forward_min_bytes(cfg, 32 * 1500, 32) < 7.3e9
    with pytest.raises(ValueError):
        fam.forward_min_bytes(cfg, 0, 0, 3)
    # the chunk form's least work, six GDN layers: per head and token in
    # sub-chunks of 64, 2 dk C + C^2 / 3 + C (dk + dv) + 6 dk dv + C dv
    per = 2 * 96 * 64 + 64 * 64 / 3 + 64 * 288 + 6 * 96 * 192 + 64 * 192
    assert fam.gdn_chunk_flops(cfg, 512) == pytest.approx(6 * 30 * per * 512)
    assert fam.gdn_chunk_bytes(cfg, 512) == pytest.approx(
        6 * (4 * 512 * 30 * (96 + 96 + 192 + 2 + 192) + 8 * 30 * 96 * 192))
    # bound by the bytes on a v5e: 0.24 GB of inputs and state, 0.29 ms
    assert fam.gdn_chunk_bytes(cfg, 512) / 819e9 > \
        fam.gdn_chunk_flops(cfg, 512) / 197e12
    specs = fam.param_specs(cfg)
    assert len(specs) == 1 + 6 * 16 + 2 * 11 + 2
    stored = sum(int(np.prod(s)) * (4 if k in ("gamma", "q_gamma", "head",
                                               "a_log", "dt_bias") else 2)
                 for _n, s, k in specs)
    assert 5.63e9 < stored < 5.65e9            # 5.64 GB of weights


# -- the rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_carries_no_rate(capsys, trace):
    bench_run.main(["--workload", CELL, "--seed", str(2**31 + 41),
                    "--seconds", "1", "--trace", trace, "--rehearse", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(manifest.limits(CELL)) == set(line["compared"]) == {
        "logit_gap_mean", "state_gap_max", "rows_gap_max", "wrong_length"}
    for value, limit in line["compared"].values():
        assert value <= limit


def test_the_driver_names_the_rows_for_what_they_are():
    got = serve_gdn.renamed({"latent_rows_gap_max": 0.5, "state_gap_max": 1})
    assert got == {"rows_gap_max": 0.5, "state_gap_max": 1}
    assert serve_gdn.renamed({"state_gap_max": None}) == \
        {"state_gap_max": None}


# -- the faults tools/gdn_limits.py plants -----------------------------------

def test_planted_faults_move_one_layer_of_the_program_alone():
    """Each fault changes the program's output, in its one layer, and
    leaves nothing behind: the weights the reference keeps are the
    sound ones, and once the fault is taken out the network computes
    what it computed before to the bit."""
    import gdn_limits
    from mxnet_tpu import nd

    small = manifest.config(manifest.manifest(), CONFIG, rehearse=True)
    fam = weights.family(small)
    specs = fam.param_specs(small)
    arrays = weights.make_params(small, 3)
    net = programs.program(small).build_net(small)
    programs.set_weights(net, specs, arrays)
    kinds = fam.layer_kinds(small)
    assert (kinds[gdn_limits.GDN_LAYER], kinds[gdn_limits.PRE_NORM_LAYER],
            kinds[gdn_limits.FULL_LAYER]) == ("gdn", "gdn", "gqa")
    toks = nd.array(np.random.default_rng(0).integers(
        0, small["vocab_size"], (1, 40)))
    sound = net(toks).asnumpy()
    for kind in gdn_limits.FAULTS:
        out = gdn_limits.planted(specs, arrays, kind)
        changed = [n for (n, _s, _k), a, b in zip(specs, arrays, out)
                   if a is not b]
        if kind == "gdn_no_decay":
            h = "h%d_" % gdn_limits.GDN_LAYER
            assert changed == [h + "decay_weight", h + "decay_dt_bias"]
        else:
            assert changed == []
        undo = gdn_limits.plant(net, kind)
        programs.set_weights(net, specs, out)
        faulty = net(toks).asnumpy()
        for fn in undo:
            fn()
        programs.set_weights(net, specs, arrays)
        assert np.abs(faulty - sound).max() > 1e-3, kind
        np.testing.assert_array_equal(net(toks).asnumpy(), sound)
    with pytest.raises(SystemExit):
        gdn_limits.plant(net, "nothing")


# -- the readers of the new metrics -------------------------------------------

def _span(name, t0, **args):
    return {"name": name, "t0": t0, "dur": 0.01, "tid": 1, "args": args}


def test_state_roofline_reads_the_slots_of_the_window(cfg, ctx, monkeypatch):
    """``gdn_decode_hbm_roofline``: the family's least bytes of a step
    at the traced steps' live positions and the window's mean active
    slots, over the device time of a decode step's programs; nothing
    without a trace or where the spans say nothing of the slots."""
    spec = manifest.layer_metric("gdn_decode_hbm_roofline")
    assert spec["reducer"] == "state_hbm_roofline"
    args = spec["args"]
    ctx["cfg"] = cfg
    ctx["window"]["traced_decode_live_positions_mean"] = 48000.0
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    ctx["ring"]["records"] += [_span("engine.decode", 9.5, slots=1),
                               _span("engine.decode", 10.2, slots=32),
                               _span("engine.decode", 10.4, slots=31)]
    assert state_hbm_roofline.reduce(dict(ctx, planes=None), **args) is None
    monkeypatch.setattr(device_busy_per_step, "seconds_charged_to",
                        lambda planes, span, among: (1.2, 100))
    least = weights.family(cfg).forward_min_bytes(cfg, 48000.0, 31.5) / 819e9
    share = state_hbm_roofline.reduce(dict(ctx, planes=object()), **args)
    assert share == pytest.approx(100 * least / 0.012) and share < 100
    ctx["ring"]["records"][:] = [_span("engine.decode", 10.2)]
    assert state_hbm_roofline.reduce(dict(ctx, planes=object()),
                                     **args) is None


def test_chunk_roofline_reads_the_scope_and_the_chunks(cfg, ctx, monkeypatch):
    """``gdn_chunk_scan_roofline``: the chunk form's least time at the
    window's mean counted positions a chunk over ``gdn.scan``'s device
    time a chunk; the two scan metrics are ``device_by_scope``'s; a
    program that opens no such scope leaves all three out."""
    for name, span in (("decode_gdn_scan_device_ms_per_step", "decode"),
                       ("prefill_gdn_scan_device_ms_per_chunk", "prefill")):
        assert manifest.layer_metric(name) == {
            "name": name, "reducer": "device_by_scope",
            "args": {"scope": "gdn.scan", "span": "mx:engine." + span}}
    spec = manifest.layer_metric("gdn_chunk_scan_roofline")
    assert spec["reducer"] == "chunk_scan_roofline"
    args = spec["args"]
    ctx["cfg"] = cfg
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx["ring"]["records"] += [
        _span("engine.prefill", 9.5, count=7),
        _span("engine.prefill", 10.2, count=512),
        _span("engine.prefill", 10.4, count=300)]
    assert chunk_scan_roofline.reduce(dict(ctx, planes=None), **args) is None
    scopes = {"gdn.scan": 2.0}
    monkeypatch.setattr(
        device_by_scope, "reduce",
        lambda ctx, scope=None, span=None, **kw: scopes.get(scope))
    fam = weights.family(cfg)
    least = max(fam.gdn_chunk_flops(cfg, 406) / 197e12,
                fam.gdn_chunk_bytes(cfg, 406) / 819e9)
    share = chunk_scan_roofline.reduce(dict(ctx, planes=object()), **args)
    assert share == pytest.approx(100 * least / 2e-3) and share < 100
    scopes.clear()                       # the parent: no gdn.scan
    assert chunk_scan_roofline.reduce(dict(ctx, planes=object()),
                                      **args) is None


# -- the chip's readings ------------------------------------------------------

def _readings():
    path = os.path.join(manifest.BENCH, "limits", CELL + ".readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("who, correct, at_least", [
    ("program", True, 4), ("witness_bf16", True, 1),
    ("control_fp8", False, 2),
    ("fault_gdn_no_neg_eigval", False, 1), ("fault_gdn_no_decay", False, 1),
    ("fault_pre_norm_block", False, 1), ("fault_qk_norm_per_head", False, 1)])
def test_chip_readings_judged_by_the_committed_limits(who, correct,
                                                      at_least):
    """What ``tools/gdn_limits.py`` and the cell's own runs read on the
    chip at the cell's size, judged here as a run judges (the lines
    carry no verdict of their own): the program and the bfloat16 witness
    are correct on every seed; the fp8 control and each fault planted in
    ONE layer on none."""
    from benchmark.lib import compare

    limits = manifest.limits(CELL)
    mine = [r for r in _readings() if r["who"] == who]
    assert len(mine) >= at_least
    for row in mine:
        assert "correct" not in row
        have = {k: v for k, v in limits.items() if k in row}
        assert have and compare.judge(row, have)[0] is correct, row["seed"]


@pytest.mark.parametrize("name", ["logit_gap_mean", "state_gap_max",
                                  "rows_gap_max"])
def test_limits_lie_between_the_programs_and_the_faults_readings(name):
    """Each limit at least 1.9 x over the program's largest reading and
    at least 1.9 x under the smallest reading of the fp8 control and of
    every planted fault: each of them fails every limit, the weakest
    (the per-head QK-norm in one full layer) included."""
    rows, limits = _readings(), manifest.limits(CELL)
    assert set(limits) == {"logit_gap_mean", "state_gap_max",
                           "rows_gap_max", "wrong_length"}
    assert limits["wrong_length"] == 0
    program = [r[name] for r in rows if r["who"] == "program"]
    others = [r[name] for r in rows
              if r["who"] == "control_fp8" or r["who"].startswith("fault_")]
    assert len(program) >= 4 and len(others) >= 6
    assert 1.9 * max(program) < limits[name] < min(others) / 1.9
