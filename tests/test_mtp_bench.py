"""The benchmark's part of ISSUE 35, tested from ``tests/`` because
``benchmark/tests`` is not in the tier-1 command: the new cell's entries
in ``BENCHMARK.json``, the configuration against the catalog's row, the
traffic file, the family's counts against a hand count at the published
widths and at a small size, the cell's rehearsal through
``benchmark/run.py --rehearse 1``, the faults ``tools/mtp_limits.py``
plants, what the cell's driver (``drivers/serve_mtp.py``) adds to the
comparison, the readers of the three new metrics on hand-made rings, and
the chip's readings judged by the committed limits."""
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
from benchmark.drivers import serve_mtp  # noqa: E402
from benchmark.lib.reducers import (device_busy_per_step,  # noqa: E402
                                    hybrid_hbm_roofline, span_args)
from test_blockgen_bench import _args, ctx  # noqa: E402,F401 (a fixture)

CELL, CONFIG = "gigachat3_serve_reason", "gigachat3.1-702b-a36b"
SOURCE = ("https://huggingface.co/ai-sage/GigaChat3.1-702B-A36B/blob/main/"
          "config.json")
NEW_METRICS = {
    "mtp_accept_share": ("%", "decode engine"),
    "mtp_tokens_per_slot_step": ("tokens/step", "decode engine"),
    "latent_verify_hbm_roofline": ("%", "kernels")}


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
    # (the fifth of each; later PRs add theirs after them)
    assert [c["name"] for c in man["configs"]][4] == CONFIG
    assert [w["name"] for w in man["workloads"]][4] == CELL
    assert len(man["configs"]) == len(man["workloads"]) >= 5
    cell = manifest.workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "serve_reason", 1)
    assert "cost side of self-drafting" in cell["why"]
    entry = man["configs"][4]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == SOURCE and len(SOURCE) <= 200
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert {"serve_itl_p95_ms", "setup_s"} <= e2e
    layer = {m["name"]: m for m in manifest.metrics_of(man, "per_layer", CELL)}
    for name, (unit, where) in NEW_METRICS.items():
        assert (layer[name]["unit"], layer[name]["layer"],
                layer[name]["moves"], layer[name]["workloads"][0]) == \
            (unit, where, "serve_itl_p95_ms", CELL)
    # (later PRs appended theirs after them, and their cells after this
    # one on the lists of the metrics they report too)
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("mtp_accept_share")
    assert at == 36 and names[at:at + 3] == list(NEW_METRICS)
    assert set(NEW_METRICS) | {
        "serve_prefill_share", "serve_tick_ms_p95",
        "hybrid_prefill_device_ms_per_chunk", "moe_held_pairs_share",
        "setup_build_s", "setup_compile_s", "setup_trace_lower_s",
        "setup_executable_load_s"} <= set(layer)
    # every metric the cell's line carries moves one the cell reports
    assert {m["moves"] for m in layer.values()} <= e2e
    # the rate and what moves it come together or not at all
    rate = {"decode_step_ms_mean", "decode_step_ms_max",
            "decode_slot_occupancy", "decode_device_busy_ms_per_step",
            "serve_step_mfu", "serve_tick_host_ms_mean",
            "serve_gc_pause_ms_max", "serve_idle_in_host_ms_per_tick",
            "moe_expert_load_max_over_mean"}
    assert (rate <= set(layer)) == ("serve_output_tok_s" in e2e)
    assert not rate & set(layer) or rate <= set(layer)


def test_configuration_is_the_catalog_row_cut_three_ways(cfg):
    """Every key of the catalog's row under its own name, the three cut
    keys apart (listed in ``reduced`` with the published numbers beside
    them); no width differs, the ``rope_scaling`` group is whole."""
    # the row as the model-configs catalog gave it, kept in the
    # repository (tests/data/catalog_rows.json): nothing outside it is read
    with open(os.path.join(ROOT, "tests", "data", "catalog_rows.json")) as f:
        row = [json.load(f)["GigaChat3.1-702B-A36B"]]
    published = row[0]["config"]
    assert row[0]["source_url"] == cfg["source"] == SOURCE
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 16032}
    for key, value in published.items():
        assert cfg[key] == cut.get(key, value), key
    assert cfg["reduced"] == list(cut)
    assert cfg["published"] == {k: published[k] for k in cut} == {
        "num_hidden_layers": 64, "n_routed_experts": 256,
        "vocab_size": 128256}
    assert cfg["family"] == "deepseek_v3"
    assert "16 chips" in cfg["deployment"] and "ten times" in cfg["distorts"]
    for reading in ("block", "mla", "rope_interleave", "yarn", "score_scale",
                    "router", "experts", "mtp", "cache", "weights"):
        assert reading in cfg["assumed"], reading
    assert "q_norm_gamma" in cfg["assumed"]["weights"]
    # the floors of a cut: a leading dense layer counted once and four
    # expert layers, 8 routed experts, an eighth of the vocabulary
    fam = weights.family(cfg)
    assert fam.layer_kinds(cfg) == ["dense"] + ["moe"] * 4
    assert fam.blocks(cfg) == ["dense"] + ["moe"] * 5
    assert cfg["layers_held"] == [2, 3, 4, 5, 6]
    assert cfg["num_nextn_predict_layers"] == 1
    assert cfg["vocab_size"] * 8 == 128256
    # experts 0-15 are half of group 0 of the router's 8
    assert (cfg["experts_first"], cfg["n_routed_experts"]) == \
        (0, 256 // 8 // 2)
    assert (cfg["weights_dtype"], cfg["dtype_policy"]) == \
        ("bfloat16", "bf16_mixed")


def test_traffic_is_the_issues_table():
    t, chat = manifest.traffic("serve_reason"), manifest.traffic("serve_chat")
    assert t["driver"] == "serve_mtp"
    assert {k: t[k] for k in (
        "clients", "slots", "cache_len", "page_size", "num_pages",
        "prefill_chunk", "prefix_share", "spec_k", "strata",
        "check_requests", "check_slots", "trace_seconds")} == {
        "clients": 64, "slots": 32, "cache_len": 6144, "page_size": 16,
        "num_pages": 12289, "prefill_chunk": 512, "prefix_share": False,
        "spec_k": 1, "strata": 64, "check_requests": 6, "check_slots": 2,
        "trace_seconds": 8.0}
    assert t["prompt_len"] == {"median": 1024, "sigma": 0.8, "lo": 128,
                               "hi": 4096}
    assert t["answer_len"] == {"median": 768, "sigma": 0.6, "lo": 128,
                               "hi": 2048}
    # pairing and order generated as serve_chat.json's were
    assert t["pairing"] == chat["pairing"] and t["order"] == chat["order"]
    from benchmark.lib import lengths

    shapes = lengths.request_shapes(t)
    assert max(p + a for p, a in shapes) <= t["cache_len"]
    assert t["num_pages"] == t["slots"] * t["cache_len"] // t["page_size"] + 1
    # a request costs 2-3 chunks and about 770 verify steps
    chunks = sum(-(-p // 512) for p, _a in shapes) / 64
    steps = sum(a for _p, a in shapes) / 64
    assert 2 < chunks < 3.2 and 740 < steps < 900


# -- the counts, by hand ------------------------------------------------------

def test_family_counts_against_a_hand_count(cfg):
    fam = weights.family(cfg)
    D, H = 7168, 64
    mla = D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 320 + H * 192 * D
    assert mla == 132_579_328 and fam.mixer_params(cfg) == mla   # 132.6 M
    expert = 3 * D * 2048                                        # 44.04 M
    dense = 3 * D * 18432                                        # 396.4 M
    router = D * 256
    assert (expert, dense) == (44_040_192, 396_361_728)
    assert fam.ffn_params(cfg, "dense", 0) == dense
    assert fam.ffn_params(cfg, "moe", 16) == router + expert + 16 * expert
    head, w_eh = 16032 * D, 2 * D * D
    held = 6 * mla + dense + 5 * (router + 17 * expert) + w_eh + head
    assert fam.matmul_params(cfg) == held
    # a token: six blocks' mixers, the dense layer, five routers and
    # shared experts and of its 8 routed experts the half an expert that
    # falls on the held sixteenth; the draft module's projection; the
    # head twice; six blocks of attention over the cached positions
    per_token = 6 * mla + dense + 5 * (router + expert + 0.5 * expert) \
        + w_eh + 2 * head
    assert fam.serve_flops_per_token(cfg, 1000) == \
        2 * per_token + 6 * 2 * H * (192 + 192) * 1000
    # every weight a step multiplies once as stored: of the routed
    # experts the (block, expert) pairs some row chose; the live latent
    # rows of all six blocks
    fixed = 2 * (6 * mla + dense + 5 * (router + expert) + w_eh) + 4 * head
    assert fam.forward_min_bytes(cfg, 80000, 31.5, 70.25) == \
        fixed + 2 * expert * 70.25 + 6 * 2 * 576 * 80000
    assert 3.5e9 < fixed < 3.7e9
    # with every held expert touched: what the chip holds but the
    # embedding and the norms, 10.5 GB
    assert 10.4e9 < fam.forward_min_bytes(cfg, 0, 0, 5 * 16) < 10.7e9
    specs = fam.param_specs(cfg)
    assert len(specs) == 1 + 12 + 4 * 17 + 2 + 4 + 17
    assert fam.draft_leaves(cfg) == 21
    stored = sum(int(np.prod(s)) * (2 if k == "matrix" else 4)
                 for _n, s, k in specs)
    assert 10.77e9 < stored < 10.80e9          # the issue's 10.8 GB
    draft = sum(int(np.prod(s)) * (2 if k == "matrix" else 4)
                for _n, s, k in specs[-21:])
    assert 1.96e9 < draft < 1.98e9             # the module's 1.97 GB


def test_counts_at_a_small_size_follow_the_parameters(small):
    """At the rehearsal's sizes: what ``matmul_params`` counts is every
    matrix of ``param_specs`` but the embedding (which is looked up,
    not multiplied), and ``forward_min_bytes`` with every held expert
    touched is those matrices as stored."""
    fam = weights.family(small)
    specs = fam.param_specs(small)
    matrices = sum(int(np.prod(s)) for n, s, k in specs
                   if k in ("matrix", "head") and n != "embed_weight")
    assert fam.matmul_params(small) == matrices
    stored = sum(int(np.prod(s)) * (4 if k == "head" else 2)
                 for n, s, k in specs
                 if k in ("matrix", "head") and n != "embed_weight")
    assert fam.forward_min_bytes(small, 0, 3, 5 * 4) == stored
    assert fam.forward_min_bytes(small, 10, 3, 5 * 4) - stored == \
        10 * 6 * 2 * (32 + 8)


# -- the rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_carries_no_rate(capsys, trace):
    bench_run.main(["--workload", CELL, "--seed", str(2**31 + 35),
                    "--seconds", "2", "--trace", trace, "--rehearse", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(manifest.limits(CELL)) == set(line["compared"]) == {
        "logit_gap_mean", "latent_rows_gap_max", "draft_logit_gap_mean",
        "wrong_length"}
    for value, limit in line["compared"].values():
        assert value <= limit


def test_planted_faults_do_what_their_names_say(small):
    """Each fault of ``tools/mtp_limits.py`` changes what its name says
    and nothing else: three in the weights the program is handed, one in
    the model's rotation of one block's ``k_r``."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    import mtp_limits

    from benchmark import programs

    fam = weights.family(small)
    specs = fam.param_specs(small)
    names = [n for n, _s, _k in specs]
    arrays = weights.make_params(small, 3)
    layer = "h%d_" % mtp_limits.FAULT_LAYER
    assert fam.layer_kinds(small)[mtp_limits.FAULT_LAYER] == "moe"

    def changed(kind):
        out = mtp_limits.planted(small, specs, arrays, kind)
        return out, [n for n, a, b in zip(names, arrays, out) if a is not b]

    D = small["hidden_size"]
    out, diff = changed("mtp_no_hidden")
    assert diff == ["mtp_proj_weight"]
    w = np.asarray(out[names.index(diff[0])])
    assert np.abs(w[:, :D]).max() == 0.0 and np.abs(w[:, D:]).max() > 0
    out, diff = changed("no_shared_expert")
    assert diff == [layer + "shared_down_weight"]
    assert float(jnp.abs(out[names.index(diff[0])]).max()) == 0.0
    out, diff = changed("no_mscale_queries")
    assert diff == [layer + "proj_q_weight"]
    i = names.index(diff[0])
    m2 = (0.1 * np.log(64) + 1) ** 2
    # (the leaves are bfloat16: the quotient is rounded once more)
    np.testing.assert_allclose(
        np.asarray(out[i].astype(jnp.float32)) * m2,
        np.asarray(arrays[i].astype(jnp.float32)), rtol=1e-2)
    _out, diff = changed("no_yarn_in_cached_k_r")
    assert diff == []
    with pytest.raises(SystemExit):
        mtp_limits.planted(small, specs, arrays, "nothing")
    # the one planted in the code: block 3's cached rope part is turned
    # at the plain frequencies, every other block's row and block 3's
    # latent part are the sound model's
    net = programs.program(small).build_net(small)
    programs.set_weights(net, specs, arrays)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, small["vocab_size"], (1, 90)).astype(np.int32))
    zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), 90, jnp.int32)
    sound = [np.asarray(r) for r in net.chunk_forward(toks, None, zero,
                                                      full)[1]]
    undo = mtp_limits.plain_k_r(net)
    try:
        faulty = [np.asarray(r) for r in net.chunk_forward(toks, None, zero,
                                                           full)[1]]
    finally:
        undo()
    dl = small["kv_lora_rank"]
    for li, (a, b) in enumerate(zip(sound, faulty)):
        if li < mtp_limits.FAULT_LAYER:
            np.testing.assert_array_equal(a, b)
    a, b = sound[3], faulty[3]
    np.testing.assert_array_equal(a[..., :dl], b[..., :dl])
    # the first pair turns at 1 under YaRN too; the ramp's pairs differ
    np.testing.assert_allclose(a[..., dl:dl + 2], b[..., dl:dl + 2],
                               atol=1e-6)
    assert np.abs(a[..., dl + 2:] - b[..., dl + 2:]).max() > 1e-3
    again = [np.asarray(r) for r in net.chunk_forward(toks, None, zero,
                                                      full)[1]]
    for a, b in zip(sound, again):
        np.testing.assert_array_equal(a, b)
    assert mtp_limits.FAULTS == (
        "mtp_no_hidden", "no_yarn_in_cached_k_r", "no_mscale_queries",
        "no_shared_expert")
    del jax


# -- what the cell's driver adds to the comparison ----------------------------

def test_draft_numbers_read_every_draft():
    gaps = [np.array([0.0, 0.0, 0.3, 0.0]), np.array([0.1, 0.0])]
    got = serve_mtp.draft_numbers(gaps)
    assert got["draft_logit_gap_mean"] == pytest.approx(0.4 / 6)
    assert got["draft_logit_gap_max"] == pytest.approx(0.3)
    assert got["draft_off_first_choice_share"] == pytest.approx(2 / 6)
    assert serve_mtp.draft_numbers([]) == {"draft_logit_gap_mean": None}
    assert serve_mtp.draft_numbers([np.array([])]) == {
        "draft_logit_gap_mean": None}


def test_cache_numbers_hand_the_reference_the_next_token(small, monkeypatch):
    """``latent_rows_gap_max``: the largest ``|rows - ref| / |ref|`` over
    a snapshot's layers, the reference's caches computed from the
    snapshot's ids and the id after them (the draft module's last row
    was fed it); a slot filled to its end has no such id, and that one
    row of the draft module's is left out."""
    import jax.numpy as jnp

    fam = weights.family(small)
    seen = []

    def caches(_cfg, params, tokens, upto, quant=None):
        seen.append(quant)
        rows = jnp.ones((1, 8, 3)) * (1.0 if quant is None else 1.5)
        # (the second layer's row 4 says which id stood at position 5)
        return [rows, rows.at[0, 4].set(tokens[0, 5] - 2.0)]

    monkeypatch.setattr(fam, "caches", caches)
    run = type("R", (), {"cfg": small, "traffic": {"cache_len": 8},
                         "log": staticmethod(lambda msg: None)})
    snap = {"position": 5, "tokens": [1, 2, 3, 4, 5], "next_token": 9,
            "layers": [np.full((5, 3), 0.9),
                       np.concatenate([np.ones((4, 3)),
                                       np.full((1, 3), 7.0)])]}
    got = serve_mtp.cache_numbers(run, None, [snap])
    assert got["latent_rows_gap_max"] == pytest.approx(0.1)
    assert seen == [None]
    # a slot filled to its end: the draft module's last row is left out
    full = {"position": 8, "tokens": list(range(8)), "next_token": None,
            "layers": [np.ones((8, 3)),
                       np.concatenate([np.ones((4, 3)), np.full((1, 3), 3.0),
                                       np.ones((2, 3)),
                                       np.full((1, 3), 100.0)])]}
    assert serve_mtp.cache_numbers(run, None, [full])[
        "latent_rows_gap_max"] == 0.0
    # the control: the reference in its precision in the snapshot's place
    got = serve_mtp.cache_numbers(run, None, [snap], quant="q")
    assert got["latent_rows_gap_max"] == pytest.approx(0.5)
    assert serve_mtp.cache_numbers(run, None, [{"position": 0}]) == {
        "latent_rows_gap_max": None}


# -- the readers of the new metrics -------------------------------------------

def _decode(t0, **args):
    return {"name": "engine.decode", "t0": t0, "dur": 0.01, "tid": 1,
            "args": dict({"slots": 30, "live": 100}, **args)}


def test_span_args_readers_of_acceptance_and_tokens_a_step(ctx):
    ctx["ring"]["records"] += [
        _decode(9.5, drafted=1, accepted=1, emitted=2),      # set-up
        _decode(10.2, drafted=30, accepted=0, emitted=30),
        _decode(10.4, slots=32, drafted=32, accepted=31, emitted=63)]
    assert span_args.reduce(ctx, **_args("mtp_accept_share")) == \
        pytest.approx(100.0 * 31 / 62)
    assert span_args.reduce(ctx, **_args("mtp_tokens_per_slot_step")) == \
        pytest.approx(93 / 62)
    # a program that writes neither (the parent; the token-at-a-time
    # path) leaves both out
    ctx["ring"]["records"][:] = [_decode(10.2), _decode(10.4)]
    assert span_args.reduce(ctx, **_args("mtp_accept_share")) is None
    assert span_args.reduce(ctx, **_args("mtp_tokens_per_slot_step")) is None


def test_roofline_reader(cfg, ctx, monkeypatch):
    """``latent_verify_hbm_roofline`` is the family's least bytes, at
    what the window's ``engine.decode`` spans say a step touched, over
    the device time of a verify step's programs; without a trace, and
    for a program whose spans do not say what a step touched, it is left
    out."""
    spec = manifest.layer_metric("latent_verify_hbm_roofline")
    assert spec["reducer"] == "hybrid_hbm_roofline"
    assert spec["args"] == manifest.layer_metric(
        "hybrid_decode_hbm_roofline")["args"]
    ctx["cfg"] = cfg
    ctx["window"]["traced_decode_live_positions_mean"] = 80000.0
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    ctx["ring"]["records"] += [
        _decode(9.5, slots=1, experts_held_touched=1),      # set-up
        _decode(10.2, slots=32, experts_held_touched=72),
        _decode(10.4, slots=31, experts_held_touched=68)]
    assert hybrid_hbm_roofline.reduce(dict(ctx, planes=None),
                                      **spec["args"]) is None
    monkeypatch.setattr(device_busy_per_step, "seconds_charged_to",
                        lambda planes, span, among: (2.8, 100))
    least = weights.family(cfg).forward_min_bytes(
        cfg, 80000.0, 31.5, 70.0) / 819e9
    assert 0.0120 < least < 0.0130           # 10.2 GB at 819 GB/s
    share = hybrid_hbm_roofline.reduce(dict(ctx, planes=object()),
                                       **spec["args"])
    assert share == pytest.approx(100 * least / 0.028) and share < 100
    ctx["ring"]["records"][:] = [_decode(10.2), _decode(10.4)]
    assert hybrid_hbm_roofline.reduce(dict(ctx, planes=object()),
                                      **spec["args"]) is None


# -- the chip's readings ------------------------------------------------------

def _readings():
    path = os.path.join(manifest.BENCH, "limits", CELL + ".readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("who, correct, at_least", [
    ("program", True, 10), ("witness_bf16", True, 2),
    ("control_fp8", False, 3),
    ("fault_mtp_no_hidden", False, 2),
    ("fault_no_yarn_in_cached_k_r", False, 2),
    ("fault_no_mscale_queries", False, 2),
    ("fault_no_shared_expert", False, 2)])
def test_chip_readings_judged_by_the_committed_limits(who, correct,
                                                      at_least):
    """What ``tools/mtp_limits.py`` and the cell's own runs read on the
    chip at the cell's size, judged here as a run judges (the lines
    carry no verdict of their own): the program and the bfloat16 witness
    (the program's own precision) are correct on every seed; the fp8
    control and each fault planted in ONE block on none: the draft
    module's ``h`` half left out of its projection, YaRN's ramp left out
    of one layer's cached ``k_r``, ``m^2`` left out of one layer's
    scores on the query's side, one layer's shared expert left out."""
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


@pytest.mark.parametrize("name, room, catches", [
    ("logit_gap_mean", 1.45, ("no_yarn_in_cached_k_r", "no_mscale_queries",
                              "no_shared_expert")),
    ("latent_rows_gap_max", 1.3, ("mtp_no_hidden", "no_yarn_in_cached_k_r",
                                  "no_mscale_queries", "no_shared_expert")),
    ("draft_logit_gap_mean", 1.45, ("mtp_no_hidden", "no_yarn_in_cached_k_r",
                                    "no_mscale_queries",
                                    "no_shared_expert"))])
def test_limits_lie_between_the_programs_and_the_faults_readings(
        name, room, catches):
    """Each limit above the program's largest reading of at least ten
    seeds and under the fp8 control's smallest, and under the smallest
    reading of every fault it is there to catch, with ``room`` on both
    sides (the query-side fault, ``m^2`` left out of one layer of six,
    reads nearest: 1.3-1.5 limits).  The draft module's fault leaves the
    trunk's logits as they are: only the module's own numbers see it.
    The widest gaps of thousands of tokens have no such limit (the
    program's largest, 1.15, against the control's smallest, 2.76, with
    one routing flip's worth of tail) and are logged."""
    rows, limits = _readings(), manifest.limits(CELL)
    assert set(limits) == {"logit_gap_mean", "latent_rows_gap_max",
                           "draft_logit_gap_mean", "wrong_length"}
    program = [r[name] for r in rows if r["who"] == "program"]
    control = [r[name] for r in rows if r["who"] == "control_fp8"]
    assert len(program) >= 10 and len(control) >= 3
    assert room * max(program) < limits[name] < min(control) / room
    for fault in catches:
        read = [r[name] for r in rows if r["who"] == "fault_" + fault]
        assert len(read) >= 2 and room * limits[name] < min(read), fault
    unseen = [r["logit_gap_mean"] for r in rows
              if r["who"] == "fault_mtp_no_hidden"]
    assert max(unseen) < limits["logit_gap_mean"]
