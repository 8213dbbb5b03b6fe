"""The benchmark's part of ISSUE 28, tested from ``tests/`` because
``benchmark/tests`` is not in the tier-1 command (``benchmark/tests/
test_blockgen.py`` runs this very file there): the new cell's entries in
``BENCHMARK.json``, its rehearsal through ``benchmark/run.py --rehearse
1``, the family's counts against a hand count at the published widths,
and the new reducers on hand-made rings and on the recorded small trace
(a number, or ``None`` where the program writes nothing to read)."""
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402

from benchmark.lib import manifest, weights, xplane  # noqa: E402
from benchmark.lib.reducers import (blockgen_hbm_roofline,  # noqa: E402
                                    program_spans, span_args)

CELL, CONFIG = "sdar30b_serve_blockgen", "sdar-30b-a3b-chat"
NEW_METRICS = {
    "blockgen_tokens_per_slot_pass": ("tokens/pass", "decode engine"),
    "blockgen_commit_share": ("%", "decode engine"),
    "moe_expert_load_max_over_mean": ("ratio", "experts"),
    "blockgen_hbm_roofline": ("%", "kernels")}


# -- the manifest -------------------------------------------------------------

def test_manifest_gains_one_configuration_and_one_cell():
    man = manifest.manifest()
    assert manifest.check(man)
    assert [c["name"] for c in man["configs"]][2] == CONFIG
    assert [w["name"] for w in man["workloads"]][2] == CELL
    cell = manifest.workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "serve_blockgen", 1)
    entry = [c for c in man["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert {"serve_output_tok_s", "setup_s"} <= e2e
    layer = {m["name"]: m for m in manifest.metrics_of(man, "per_layer", CELL)}
    for name, (unit, where) in NEW_METRICS.items():
        # (a later expert model's cell may follow on the routing metric)
        assert (layer[name]["unit"], layer[name]["layer"],
                layer[name]["moves"], layer[name]["workloads"][:1]) == \
            (unit, where, "serve_output_tok_s", [CELL])
    # its lists of bytes count full-width K and V: not this model's
    assert "decode_hbm_roofline" not in layer
    # every metric this cell lists moves an end-to-end metric it reports
    assert {m["moves"] for m in layer.values()} <= e2e


def test_configuration_keeps_every_published_width():
    cfg = manifest.config(manifest.manifest(), CONFIG)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 6 and cfg["reduced"] == \
        ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 48}
    assert {"qk_norm", "block_length", "mask_token_id", "remasking",
            "commit_pass", "shift", "weights"} <= set(cfg["assumed"])
    assert (cfg["weights_dtype"], cfg["dtype_policy"]) == \
        ("bfloat16", "bf16_mixed")


def test_traffic_is_serve_chats_requests_under_another_model():
    t, chat = manifest.traffic("serve_blockgen"), manifest.traffic("serve_chat")
    assert t["driver"] == "serve_blockgen"
    for key in ("prompt_len", "answer_len", "strata", "pairing", "order"):
        assert t[key] == chat[key]
    assert {k: t[k] for k in (
        "clients", "slots", "cache_len", "page_size", "num_pages",
        "prefill_chunk", "block_length", "denoise_steps", "trace_seconds")} \
        == {"clients": 64, "slots": 32, "cache_len": 1024, "page_size": 16,
            "num_pages": 2049, "prefill_chunk": 256, "block_length": 4,
            "denoise_steps": 2, "trace_seconds": 3.0}


# -- the counts, by hand at the published widths --------------------------------

def test_family_counts_against_a_hand_count():
    cfg = manifest.config(manifest.manifest(), CONFIG)
    fam = weights.family(cfg)
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128   # + router
    assert attention == 19136512
    expert = 3 * 2048 * 768
    assert fam.layer_matmul_params(cfg, 128) == attention + 128 * expert \
        == 623116288
    head = 151936 * 2048
    # active parameters only: 8 experts a token; attention 2 x 2 x 32 x 128
    assert fam.serve_flops_per_token(cfg, 500) == \
        2 * (6 * (attention + 8 * expert) + head) + 4 * 6 * 32 * 128 * 500
    # every multiplied weight once as stored (bf16; the head f32), K and V
    # of the live positions at 2 x 6 x 4 x 128 x 2 B
    assert fam.forward_min_bytes(cfg, 10000) == \
        2 * 6 * 623116288 + 4 * head + 12288 * 10000
    assert sum(1 for _ in fam.param_specs(cfg)) == 1 + 6 * 12 + 2
    n = sum(int(np.prod(s)) for _n, s, _k in fam.param_specs(cfg))
    assert n == 2 * head + 6 * (623116288 + 2 * 2048 + 2 * 128) + 2048


# -- the rehearsal -------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_carries_no_rate(capsys, trace):
    bench_run.main(["--workload", CELL, "--seed", str(2**31 + 28),
                    "--seconds", "1", "--trace", trace, "--rehearse", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(manifest.limits(CELL)) == set(line["compared"])
    for value, limit in line["compared"].values():
        assert value <= limit


@pytest.mark.parametrize("who, correct", [
    ("program", True), ("witness_bf16", True), ("control_fp8", False),
    ("fault_commit_unwritten", False), ("fault_denoise_written", False),
    ("fault_expert_left_out", False)])
def test_chip_readings_judged_by_the_committed_limits(who, correct):
    """What ``tools/blockgen_limits.py`` and the builder's runs read on
    the chip at the cell's own size, judged as a run judges: the program
    and the bfloat16 witness are correct on every seed, the fp8 control
    and each planted fault on none (each on the numbers it has)."""
    from benchmark.lib import compare

    limits = manifest.limits(CELL)
    path = os.path.join(manifest.BENCH, "limits", CELL + ".readings.jsonl")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    mine = [r for r in rows if r["who"] == who]
    assert len(mine) >= (12 if who == "program" else 2 if "fault" not in who
                         else 1)
    for row in mine:
        have = {k: v for k, v in limits.items() if k in row}
        assert have and compare.judge(row, have)[0] is correct, row["seed"]
    # one expert of 128 in one layer of six reads like one of bfloat16's
    # own routing flips: recorded, and not the planted fault
    floor = [r for r in rows if r["who"] == "fault_one_expert_of_one_layer"]
    assert floor and all(r["logprob_gap_mean"] < limits["logprob_gap_mean"]
                         for r in floor)


# -- the reducers --------------------------------------------------------------

def _decode(t0, **args):
    return {"name": "engine.decode", "t0": t0, "dur": 0.01, "tid": 1,
            "args": dict({"slots": 3, "live": 100}, **args)}


@pytest.fixture
def ctx(monkeypatch):
    """A window from 10 s to 11 s on the program's clock over a
    hand-made ring."""
    ring = {"records": [], "evicted": 0}
    monkeypatch.setattr(program_spans, "ring",
                        lambda: (ring["records"], ring["evicted"]))
    monkeypatch.setitem(sys.modules, "__main__",
                        types.SimpleNamespace(T_START=4.0))
    return {"end_to_end": {"setup_s": 6.0}, "window": {"seconds": 1.0},
            "ring": ring}


def _args(name):
    spec = manifest.layer_metric(name)
    assert spec["reducer"] == "span_args"
    return spec["args"]


def test_span_args_reader_sums_passes_and_tokens(ctx):
    ctx["ring"]["records"] += [
        _decode(9.5, denoise=9, commit=9, emitted=99, expert_load_max=50,
                expert_load_mean=8.0),                # before the window
        _decode(10.1, denoise=2, commit=1, emitted=4, expert_load_max=20,
                expert_load_mean=8.0),
        _decode(10.2, denoise=3, commit=0, emitted=0, expert_load_max=12,
                expert_load_mean=8.0),
        _decode(10.3, denoise=1, commit=2, emitted=7, expert_load_max=32,
                expert_load_mean=8.0),
        _decode(10.4)]                                # another kind of step
    assert span_args.reduce(ctx, **_args("blockgen_tokens_per_slot_pass")) \
        == pytest.approx(11 / 9)
    assert span_args.reduce(ctx, **_args("blockgen_commit_share")) \
        == pytest.approx(100 * 3 / 9)
    assert span_args.reduce(ctx, **_args("moe_expert_load_max_over_mean")) \
        == pytest.approx((20 + 12 + 32) / 8.0 / 3)


@pytest.mark.parametrize("name", ["blockgen_tokens_per_slot_pass",
                                  "blockgen_commit_share",
                                  "moe_expert_load_max_over_mean"])
def test_span_args_reader_finds_nothing_in_a_program_without_them(
        ctx, monkeypatch, name):
    ctx["ring"]["records"] += [_decode(10.1), _decode(10.2)]
    assert span_args.reduce(ctx, **_args(name)) is None
    ctx["ring"]["records"].clear()
    assert span_args.reduce(ctx, **_args(name)) is None
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert span_args.reduce(ctx, **_args(name)) is None


def test_roofline_reader_on_the_recorded_trace_and_without_one():
    man = manifest.manifest()
    cfg = manifest.config(man, CONFIG)
    args = manifest.layer_metric("blockgen_hbm_roofline")["args"]
    ctx = {"window": {"traced_decode_live_positions_mean": 9000.0},
           "cfg": cfg, "peaks": {"hbm_bytes_per_s": 819e9}, "planes": None}
    assert blockgen_hbm_roofline.reduce(ctx, **args) is None
    small = os.path.join(manifest.BENCH, "tests", "data", "small.xplane.pb")
    ctx["planes"] = xplane.load(small)
    got = blockgen_hbm_roofline.reduce(ctx, **args)
    assert got is None or 0 < got          # no decode span in that trace
    # a family without the byte count (the parent's) gives nothing
    ctx["cfg"] = manifest.config(man, "opt-1.3b")
    assert blockgen_hbm_roofline.reduce(ctx, **args) is None


def test_roofline_reader_divides_least_time_by_device_time(monkeypatch):
    from benchmark.lib.reducers import device_busy_per_step

    cfg = manifest.config(manifest.manifest(), CONFIG)
    monkeypatch.setattr(device_busy_per_step, "seconds_charged_to",
                        lambda planes, span, among: (0.2, 10))
    ctx = {"window": {"traced_decode_live_positions_mean": 10000.0},
           "cfg": cfg, "peaks": {"hbm_bytes_per_s": 819e9}, "planes": {"x": 1}}
    least = weights.family(cfg).forward_min_bytes(cfg, 10000.0) / 819e9
    got = blockgen_hbm_roofline.reduce(
        ctx, **manifest.layer_metric("blockgen_hbm_roofline")["args"])
    assert got == pytest.approx(100 * least / 0.02)
    assert 50 < got < 60          # 10.7 ms of weights in a 20 ms pass
