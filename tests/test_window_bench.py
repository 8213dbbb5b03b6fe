"""The benchmark's part of ISSUE 39, tested from ``tests/`` because
``benchmark/tests`` is not in the tier-1 command: the new cell's entries
in ``BENCHMARK.json``, the configuration against the catalog's row, the
traffic file, the family's counts against a hand count at the published
widths and at a small size, the cell's rehearsal through
``benchmark/run.py --rehearse 1``, the faults ``tools/window_limits.py``
plants, what the cell's driver (``drivers/serve_window.py``) reads of a
ring, the readers of the four new metrics on hand-made rings, and the
chip's readings judged by the committed limits."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402

from benchmark.lib import manifest, weights  # noqa: E402
from benchmark.drivers import serve_window  # noqa: E402
from benchmark.lib.reducers import (device_busy_per_step,  # noqa: E402
                                    device_by_scope, hybrid_hbm_roofline,
                                    span_args)
from test_blockgen_bench import _args, ctx  # noqa: E402,F401 (a fixture)

CELL, CONFIG = "kexaone_serve_mixedlen", "k-exaone-236b-a23b"
SOURCE = ("https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/"
          "config.json")
NEW_METRICS = {
    "decode_window_attn_device_ms_per_step": ("ms", "device_trace"),
    "prefill_window_attn_device_ms_per_chunk": ("ms", "device_trace"),
    "decode_attended_rows_share": ("%", "program_counter"),
    "window_verify_hbm_roofline": ("%", "device_trace")}
JOINED = (
    "serve_prefill_share", "serve_tick_ms_p95",
    "hybrid_prefill_device_ms_per_chunk", "moe_held_pairs_share",
    "moe_expert_rows_share", "mtp_accept_share", "mtp_tokens_per_slot_step",
    "prefill_attended_rows_share", "decode_cache_gather_device_ms_per_step",
    "decode_attn_core_device_ms_per_step",
    "decode_experts_ffn_device_ms_per_step", "decode_ffn_device_ms_per_step",
    "decode_head_device_ms_per_step", "decode_device_unscoped_share",
    "prefill_attn_core_device_ms_per_chunk",
    "prefill_experts_ffn_device_ms_per_chunk")


@pytest.fixture(scope="module")
def cfg():
    return manifest.config(manifest.manifest(), CONFIG)


@pytest.fixture(scope="module")
def small(cfg):
    return dict(cfg, **cfg["rehearsal"])


# -- the manifest -------------------------------------------------------------

def test_manifest_gains_one_configuration_and_one_cell():
    man = manifest.manifest()
    assert manifest.check(man)
    # (the sixth of each; later PRs add theirs after them)
    assert [c["name"] for c in man["configs"]][5] == CONFIG
    assert [w["name"] for w in man["workloads"]][5] == CELL
    cell = manifest.workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "serve_mixedlen", 1)
    assert "144-row rings" in cell["why"] and len(cell["why"]) <= 200
    entry = man["configs"][5]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == SOURCE and len(SOURCE) <= 200
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"serve_itl_p95_ms", "setup_s"}
    layer = {m["name"]: m for m in manifest.metrics_of(man, "per_layer", CELL)}
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("decode_window_attn_device_ms_per_step")
    assert at == 52 and names[at:at + 4] == list(NEW_METRICS)
    for name, (unit, source) in NEW_METRICS.items():
        m = layer[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["workloads"][0]) == (unit, source, "kernels",
                                       "serve_itl_p95_ms", CELL)
    # the accepted metrics whose spans and scopes the cell's program
    # writes list it after the cells they had
    for name in JOINED:
        assert layer[name]["workloads"][-1] == CELL or \
            CELL in layer[name]["workloads"]
    assert set(NEW_METRICS) | set(JOINED) | {
        "setup_build_s", "setup_compile_s", "setup_trace_lower_s",
        "setup_executable_load_s"} == set(layer)
    assert {m["moves"] for m in layer.values()} <= e2e


def test_configuration_is_the_catalog_row_cut_three_ways(cfg):
    """Every key of the catalog's row under its own name, the three cut
    keys apart (listed in ``reduced`` with the published numbers beside
    them); no width differs, the lists a layer and the
    ``rope_parameters`` group are whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "K-EXAONE-236B-A23B"]
    published = row[0]["config"]
    assert row[0]["source_url"] == cfg["source"] == SOURCE
    cut = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200}
    for key, value in published.items():
        assert cfg[key] == cut.get(key, value), key
    assert cfg["reduced"] == list(cut)
    assert cfg["published"] == {k: published[k] for k in cut} == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    assert cfg["family"] == "exaone_moe"
    assert "8 chips" in cfg["deployment"] and "36 and 12" in cfg["distorts"]
    for reading in ("block", "attention", "rope", "ffn", "mtp", "cache",
                    "weights"):
        assert reading in cfg["assumed"], reading
    assert "q_norm_gamma" in cfg["assumed"]["weights"]
    assert "a reading" in cfg["assumed"]["block"]
    # the floors of a cut: the leading dense layer counted once and one
    # whole period of four expert layers (sliding, sliding, sliding,
    # full), 8 routed experts, an eighth of the vocabulary
    fam = weights.family(cfg)
    assert cfg["layers_held"] == [0, 4, 5, 6, 7]
    assert [cfg["layer_types"][l][0] for l in cfg["layers_held"]] == \
        list("ssssf")
    assert fam.layer_kinds(cfg) == [(128, "dense")] + [(128, "moe")] * 3 \
        + [(0, "moe")]
    assert fam.blocks(cfg) == fam.layer_kinds(cfg) + [(0, "moe")]
    assert cfg["num_nextn_predict_layers"] == 1
    assert cfg["vocab_size"] * 8 == 153600
    assert (cfg["experts_first"], cfg["num_experts"]) == (0, 128 // 8)
    assert (cfg["weights_dtype"], cfg["dtype_policy"]) == \
        ("bfloat16", "bf16_mixed")
    # the toy sizes keep the pattern: a window under the prompts'
    # lengths and under a chunk
    small = cfg["rehearsal"]
    assert small["sliding_windows"][:4] == [16, 16, 16, 0]
    assert small["sliding_window"] == 16 < manifest.traffic(
        "serve_mixedlen", rehearse=True)["prefill_chunk"]


def test_traffic_is_the_issues_table():
    t, chat = manifest.traffic("serve_mixedlen"), manifest.traffic(
        "serve_chat")
    assert t["driver"] == "serve_window"
    assert {k: t[k] for k in (
        "clients", "slots", "cache_len", "page_size", "num_pages",
        "prefill_chunk", "prefix_share", "spec_k", "strata",
        "check_requests", "check_slots", "trace_seconds")} == {
        "clients": 64, "slots": 32, "cache_len": 9216, "page_size": 16,
        "num_pages": 18433, "prefill_chunk": 512, "prefix_share": False,
        "spec_k": 1, "strata": 64, "check_requests": 6, "check_slots": 2,
        "trace_seconds": 8.0}
    assert t["prompt_len"] == {"median": 1024, "sigma": 1.2, "lo": 64,
                               "hi": 8192}
    assert t["answer_len"] == {"median": 256, "sigma": 0.7, "lo": 32,
                               "hi": 1024}
    assert t["pairing"] == chat["pairing"] and t["order"] == chat["order"]
    from benchmark.lib import lengths

    shapes = lengths.request_shapes(t)
    prompts = [p for p, _a in shapes]
    assert (min(prompts), max(prompts)) == (64, 8192)
    assert sum(prompts) / 64 == pytest.approx(1820, abs=1)
    assert sum(p <= 256 for p in prompts) == 8          # one in eight
    assert sum(p >= 2048 for p in prompts) == 18        # 28 %
    assert sum(p > 128 for p in prompts) == 61          # 95 % over the window
    assert sum(a for _p, a in shapes) / 64 == pytest.approx(319, abs=0.5)
    assert max(p + a for p, a in shapes) == 8977 <= t["cache_len"]
    assert t["num_pages"] == t["slots"] * t["cache_len"] // t["page_size"] + 1
    # a request costs 4.05 chunks and 319 verify steps
    assert sum(-(-p // 512) for p in prompts) / 64 == pytest.approx(4.05,
                                                                    abs=0.01)


# -- the counts, by hand ------------------------------------------------------

def test_family_counts_against_a_hand_count(cfg):
    fam = weights.family(cfg)
    D, H, Hkv, dh = 6144, 64, 8, 128
    attn = 2 * D * H * dh + 2 * D * Hkv * dh
    assert attn == 113_246_208 and fam.mixer_params(cfg) == attn  # 113.25 M
    expert = 3 * D * 2048                                         # 37.75 M
    dense = 3 * D * 18432                                         # 339.74 M
    router = D * 128
    assert (expert, dense, 16 * expert) == (37_748_736, 339_738_624,
                                            603_979_776)
    assert fam.ffn_params(cfg, "dense", 0) == dense
    assert fam.ffn_params(cfg, "moe", 16) == router + expert + 16 * expert
    head, w_eh = 19200 * D, 2 * D * D
    held = 6 * attn + dense + 5 * (router + 17 * expert) + w_eh + head
    assert fam.matmul_params(cfg) == held
    assert fam.kv_row(cfg) == 2048
    # a token: six blocks' attention matrices, the dense layer, five
    # routers and shared experts and of its 8 routed experts the one
    # that falls on the held eighth; the draft module's projection; the
    # head twice; scores and values over the cached positions of the
    # two full blocks and over at most the window's of the four windowed
    per_token = 6 * attn + dense + 5 * (router + expert + 1.0 * expert) \
        + w_eh + 2 * head
    for context, attended in ((1000, 2 * 1000 + 4 * 128),
                              (50, 6 * 50)):
        assert fam.serve_flops_per_token(cfg, context) == \
            2 * per_token + 4 * H * dh * attended
    assert 3.4e9 < fam.serve_flops_per_token(cfg, 0) < 3.6e9
    # every weight a step multiplies once as stored: of the routed
    # experts the (block, expert) pairs some row chose; [K | V] of the
    # live positions in the two full blocks and of at most 128 a slot in
    # the four windowed ones
    fixed = 2 * (6 * attn + dense + 5 * (router + expert) + w_eh) + 4 * head
    assert fam.forward_min_bytes(cfg, 70000, 31.5, 60.25) == \
        fixed + 2 * expert * 60.25 \
        + 2 * 2048 * (2 * 70000 + 4 * 128 * 31.5)
    assert fam.forward_min_bytes(cfg, 900, 30, 0) == \
        fixed + 2 * 2048 * 6 * 900      # every slot under the window
    assert 3.0e9 < fixed < 3.1e9
    # with every held expert touched: what the chip holds but the
    # embedding and the norms, 9.09 GB
    assert 9.0e9 < fam.forward_min_bytes(cfg, 0, 0, 5 * 16) < 9.1e9
    specs = fam.param_specs(cfg)
    assert len(specs) == 1 + 11 + 4 * 16 + 2 + 4 + 16
    assert fam.draft_leaves(cfg) == 20
    stored = sum(int(np.prod(s)) * (2 if k == "matrix" else 4)
                 for _n, s, k in specs)
    assert 9.31e9 < stored < 9.33e9            # the issue's 9.32 GB
    draft = sum(int(np.prod(s)) * (2 if k == "matrix" else 4)
                for _n, s, k in specs[-20:])
    assert 1.66e9 < draft < 1.67e9             # 0.151 + 1.512 GB
    # the pool and the rings as the engine builds them (ISSUE 39)
    assert 2 * 18433 * 16 * 2048 * 2 == 2_416_050_176      # 2.42 GB
    assert 4 * 32 * 144 * 2048 * 2 == 75_497_472           # 0.08 GB


def test_counts_at_a_small_size_follow_the_parameters(small):
    fam = weights.family(small)
    specs = fam.param_specs(small)
    matrices = sum(int(np.prod(s)) for n, s, k in specs
                   if k in ("matrix", "head") and n != "embed_weight")
    assert fam.matmul_params(small) == matrices
    stored = sum(int(np.prod(s)) * (4 if k == "head" else 2)
                 for n, s, k in specs
                 if k in ("matrix", "head") and n != "embed_weight")
    assert fam.forward_min_bytes(small, 0, 3, 5 * 4) == stored
    # 2 slots of 40 positions: both full blocks read them all, the four
    # windowed ones 16 a slot
    assert fam.forward_min_bytes(small, 80, 2, 5 * 4) - stored == \
        2 * 64 * (2 * 80 + 4 * 16 * 2)


# -- the rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_carries_no_rate(capsys, trace):
    bench_run.main(["--workload", CELL, "--seed", str(2**31 + 39),
                    "--seconds", "2", "--trace", trace, "--rehearse", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(manifest.limits(CELL)) == set(line["compared"]) == {
        "logit_gap_mean", "cache_rows_gap_max", "draft_logit_gap_mean",
        "wrong_length"}
    for value, limit in line["compared"].values():
        assert value <= limit


def test_planted_faults_do_what_their_names_say(small):
    """Each fault of ``tools/window_limits.py`` changes what its name
    says, in the program's network or in the rule the engine sizes a
    ring by, and what it returns takes it out again."""
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    import window_limits

    from benchmark import programs
    from mxnet_tpu.ops import attention_rows

    fam = weights.family(small)
    specs = fam.param_specs(small)
    arrays = weights.make_params(small, 3)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, small["vocab_size"], (1, 60)).astype(np.int32))
    zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), 60, jnp.int32)

    def rows_of(kind):
        net = programs.program(small).build_net(small)
        programs.set_weights(net, specs, arrays)
        undo = window_limits.plant(net, kind) if kind else []
        try:
            logits, kept, extras = net.chunk_forward(toks, None, zero, full)
            kept = list(kept) + [net.draft_forward(
                extras["hidden"], toks, None, zero, full)[1]]
            return net, [np.asarray(r)[0] for r in kept]
        finally:
            for fn in undo:
                fn()

    _net, sound = rows_of(None)
    for kind, window in (("window_127", 127), ("window_129", 129)):
        net, rows = rows_of(kind)
        assert net._sizes["window"] == window
        assert net.config["layer_caches"][0] == {"window": (16, 64)}
        np.testing.assert_array_equal(rows[0], sound[0])   # the first
        # block's rows come before any attention; a wider band moves
        # the next block's
        assert np.abs(rows[1] - sound[1]).max() > 1e-4
    net, rows = rows_of("rope_on_full")
    assert net._rotary == ("swa", "gqa")
    for li in range(4):
        np.testing.assert_array_equal(rows[li], sound[li])
    for li in (4, 5):       # the keys turn (position 0's stands still)
        assert np.abs(rows[li][1:, :32] - sound[li][1:, :32]).max() > 1e-2
    # the values of the trunk's full block do not (the draft module's
    # follow what that block's attention then gave)
    np.testing.assert_array_equal(rows[4][:, 32:], sound[4][:, 32:])
    net, rows = rows_of("no_qk_norm")
    assert net._qk_norm is False
    assert np.abs(rows[0][:, :32] - sound[0][:, :32]).max() > 1e-2
    np.testing.assert_array_equal(rows[0][:, 32:], sound[0][:, 32:])
    was = attention_rows.ring_rows
    undo = window_limits.plant(_net, "ring_one_row_short")
    assert attention_rows.ring_rows(128, 1, 2) == 127
    undo[0]()
    assert attention_rows.ring_rows is was and was(128, 1, 2) == 144
    with pytest.raises(SystemExit):
        window_limits.plant(_net, "nothing")
    assert window_limits.FAULTS == (
        "window_127", "window_129", "rope_on_full", "no_qk_norm",
        "ring_one_row_short")


# -- what the cell's driver reads of a ring -----------------------------------

def test_cache_numbers_cut_the_reference_to_what_a_ring_holds(small,
                                                              monkeypatch):
    """``cache_rows_gap_max``: the largest ``|rows - ref| / |ref|`` over
    a snapshot's layers, a windowed layer's rows against the reference's
    from the ring's first position on, the draft module's (the last)
    with the id after the cached ones handed over."""
    import jax.numpy as jnp

    fam = weights.family(small)

    def caches(_cfg, params, tokens, upto, quant=None):
        base = jnp.arange(8.0)[None, :, None] + jnp.ones((1, 8, 3))
        base = base * (1.0 if quant is None else 1.5)
        # (the last layer's row 4 says which id stood at position 5)
        return [base, base, base.at[0, 4].set(tokens[0, 5] - 4.0)]

    monkeypatch.setattr(fam, "caches", caches)
    run = type("R", (), {"cfg": small, "traffic": {"cache_len": 8},
                         "log": staticmethod(lambda msg: None)})
    want = np.arange(8.0)[:, None] + np.ones((8, 3))
    snap = {"position": 5, "tokens": [1, 2, 3, 4, 5], "next_token": 9,
            "layers": [{"first": 2, "rows": want[2:5] * 1.1},
                       want[:5] * 0.95,
                       np.concatenate([want[:4], np.full((1, 3), 5.0)])]}
    got = serve_window.cache_numbers(run, None, [snap])
    assert got["window_rows_gap_max"] == pytest.approx(0.1)
    assert got["paged_rows_gap_max"] == pytest.approx(0.05)
    assert got["cache_rows_gap_max"] == pytest.approx(0.1)
    # the control: the reference in its precision, cut to the same rows
    got = serve_window.cache_numbers(run, None, [snap], quant="q")
    assert got["window_rows_gap_max"] == pytest.approx(0.5)
    assert serve_window.cache_numbers(run, None, [{"position": 0}]) == {
        "cache_rows_gap_max": None, "paged_rows_gap_max": None,
        "window_rows_gap_max": None}


# -- the readers of the new metrics -------------------------------------------

def _decode(t0, **args):
    return {"name": "engine.decode", "t0": t0, "dur": 0.01, "tid": 1,
            "args": dict({"slots": 30, "live": 100}, **args)}


def test_span_args_reader_of_the_rows_a_step_attends(ctx):
    assert manifest.layer_metric("decode_attended_rows_share") == {
        "name": "decode_attended_rows_share", "reducer": "span_args",
        "args": {"span": "engine.decode", "num": ["cache_rows_attended"],
                 "den": ["cache_rows_held"], "scale": 100.0}}
    held = 6 * 9216
    ctx["ring"]["records"] += [
        _decode(9.5, cache_rows_attended=1, cache_rows_held=1),   # set-up
        _decode(10.2, cache_rows_attended=4 * 144 + 2 * 9216,
                cache_rows_held=held),
        _decode(10.4, cache_rows_attended=4 * 144 + 2 * 9216,
                cache_rows_held=held)]
    assert span_args.reduce(ctx, **_args("decode_attended_rows_share")) == \
        pytest.approx(100.0 * 19008 / 55296)          # 34.4 %
    # a program that writes neither (the parent) leaves it out
    ctx["ring"]["records"][:] = [_decode(10.2), _decode(10.4)]
    assert span_args.reduce(
        ctx, **_args("decode_attended_rows_share")) is None


def test_scope_readers_name_the_window_scope_and_stay_silent_untraced():
    import inspect

    from mxnet_tpu import profiler

    for name, span in (
            ("decode_window_attn_device_ms_per_step", "mx:engine.decode"),
            ("prefill_window_attn_device_ms_per_chunk", "mx:engine.prefill")):
        spec = manifest.layer_metric(name)
        assert spec["reducer"] == "device_by_scope"
        assert spec["args"] == {"scope": "attn.window", "span": span}
        inspect.signature(device_by_scope.reduce).bind({}, **spec["args"])
        assert device_by_scope.reduce({"window": {}, "planes": None},
                                      **spec["args"]) is None
    assert "attn.window" in profiler.PART_SCOPES
    assert profiler.part_scope(("draft", "attn.window")) == "attn.window"


def test_roofline_reader(cfg, ctx, monkeypatch):
    """``window_verify_hbm_roofline`` is the family's least bytes, at
    what the window's ``engine.decode`` spans say a step touched, over
    the device time of a verify step's programs; under 100 % at the
    cell's own numbers."""
    spec = manifest.layer_metric("window_verify_hbm_roofline")
    assert spec["reducer"] == "hybrid_hbm_roofline"
    assert spec["args"] == manifest.layer_metric(
        "latent_verify_hbm_roofline")["args"]
    ctx["cfg"] = cfg
    ctx["window"]["traced_decode_live_positions_mean"] = 69000.0
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    ctx["ring"]["records"] += [
        _decode(9.5, slots=1, experts_held_touched=1),      # set-up
        _decode(10.2, slots=32, experts_held_touched=62),
        _decode(10.4, slots=31, experts_held_touched=58)]
    assert hybrid_hbm_roofline.reduce(dict(ctx, planes=None),
                                      **spec["args"]) is None
    monkeypatch.setattr(device_busy_per_step, "seconds_charged_to",
                        lambda planes, span, among: (3.0, 100))
    least = weights.family(cfg).forward_min_bytes(
        cfg, 69000.0, 31.5, 60.0) / 819e9
    assert 0.0095 < least < 0.0105           # 8.2 GB at 819 GB/s
    share = hybrid_hbm_roofline.reduce(dict(ctx, planes=object()),
                                       **spec["args"])
    assert share == pytest.approx(100 * least / 0.030) and share < 100
    ctx["ring"]["records"][:] = [_decode(10.2), _decode(10.4)]
    assert hybrid_hbm_roofline.reduce(dict(ctx, planes=object()),
                                      **spec["args"]) is None


# -- the chip's readings ------------------------------------------------------

def _readings():
    path = os.path.join(manifest.BENCH, "limits", CELL + ".readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("who, correct, at_least", [
    ("program", True, 6), ("witness_bf16", True, 1),
    ("control_fp8", False, 2),
    ("fault_window_127", False, 1), ("fault_window_129", False, 1),
    ("fault_rope_on_full", False, 1), ("fault_no_qk_norm", False, 1),
    ("fault_ring_one_row_short", False, 1)])
def test_chip_readings_judged_by_the_committed_limits(who, correct,
                                                      at_least):
    """What ``tools/window_limits.py`` and the cell's own runs read on
    the chip at the cell's size, judged here as a run judges (the lines
    carry no verdict of their own): the program and the bfloat16 witness
    are correct on every seed; the fp8 control and each planted fault on
    none."""
    from benchmark.lib import compare

    limits = manifest.limits(CELL)
    mine = [r for r in _readings() if r["who"] == who]
    assert len(mine) >= at_least
    assert len({r["seed"] for r in mine}) == len(mine)
    for row in mine:
        assert "correct" not in row
        have = {k: v for k, v in limits.items() if k in row}
        assert len(have) >= 3      # (a control reads no length)
        assert compare.judge(row, have)[0] is correct, row["seed"]
