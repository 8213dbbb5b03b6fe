"""The benchmark's part of ISSUE 33, tested from ``tests/`` because
``benchmark/tests`` is not in the tier-1 command: the new cell's entries
in ``BENCHMARK.json``, the configuration against the catalog's row, the
traffic file, the family's counts against a hand count at the published
widths, the cell's rehearsal through ``benchmark/run.py --rehearse 1``,
the faults ``tools/hybrid_limits.py`` plants, what the cell's driver
(``drivers/serve_hybrid.py``) adds to ``serve_lm``'s comparison, the
readers of the four new metrics on hand-made rings, and the chip's
readings judged by the committed limits."""
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
from benchmark.drivers import serve_hybrid  # noqa: E402
from benchmark.lib.reducers import (device_busy_per_step,  # noqa: E402
                                    hybrid_hbm_roofline, span_args)
from test_blockgen_bench import _args, ctx  # noqa: E402,F401 (a fixture)

CELL, CONFIG = "ling3flash_serve_longdoc", "ling-3.0-flash"
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/"
          "config.json")
NEW_METRICS = {
    "hybrid_decode_hbm_roofline": ("%", "kernels"),
    "hybrid_prefill_device_ms_per_chunk": ("ms", "device"),
    "moe_held_pairs_share": ("%", "experts"),
    "decode_state_slots_share": ("%", "decode engine")}


@pytest.fixture(scope="module")
def cfg():
    return manifest.config(manifest.manifest(), CONFIG)


# -- the manifest -------------------------------------------------------------

def test_manifest_gains_one_configuration_and_one_cell():
    man = manifest.manifest()
    assert manifest.check(man)
    assert [c["name"] for c in man["configs"]][3] == CONFIG
    assert [w["name"] for w in man["workloads"]][3] == CELL
    cell = manifest.workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "serve_longdoc", 1)
    entry = [c for c in man["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == SOURCE
    # the tail is the cell's metric: `serve_output_tok_s` spread by 0.9 %
    # over six runs on the chip when the cell was added (machine stalls
    # of 100-170 ms in a loop that read every step before it launched
    # the next; PERF.md), where a new cell is admitted under 0.5 %, so
    # the cell does not report it, nor the per-layer metrics that move
    # it.  Steps are launched ahead since PR 34 and the rate repeats; a
    # `benchmark` PR may list the cell under it
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"serve_itl_p95_ms", "setup_s"}
    layer = {m["name"]: m for m in manifest.metrics_of(man, "per_layer", CELL)}
    for name, (unit, where) in NEW_METRICS.items():
        # (later cells may be appended to a metric's list: PR 35's is)
        assert (layer[name]["unit"], layer[name]["layer"],
                layer[name]["moves"], layer[name]["workloads"][0]) == \
            (unit, where, "serve_itl_p95_ms", CELL)
    # (PR 36 added the share of rows the held experts multiply, PR 37
    # the device's time by named scope: `tests/test_device_scopes.py`,
    # PR 38 the share of a slot's cache a chunk's attention multiplies)
    assert {n for n in layer if manifest.layer_metric(n)["reducer"]
            != "device_by_scope"} == set(NEW_METRICS) | {
        "moe_expert_rows_share", "prefill_attended_rows_share",
        "serve_prefill_share", "serve_tick_ms_p95", "setup_build_s",
        "setup_compile_s", "setup_trace_lower_s", "setup_executable_load_s"}
    assert {m["moves"] for m in layer.values()} <= e2e


def test_configuration_is_the_catalog_row_cut_three_ways(cfg):
    """Every key of the catalog's row under its own name, the three cut
    keys apart (listed in ``reduced`` with the published numbers beside
    them); no width differs."""
    # the row as the model-configs catalog gave it, kept in the
    # repository (tests/data/catalog_rows.json): nothing outside it is read
    with open(os.path.join(ROOT, "tests", "data", "catalog_rows.json")) as f:
        row = [json.load(f)["Ling-3.0-flash"]]
    published = row[0]["config"]
    assert row[0]["source_url"] == cfg["source"] == SOURCE
    cut = {"num_hidden_layers": 7, "num_experts": 128, "vocab_size": 39296}
    for key, value in published.items():
        assert cfg[key] == cut.get(key, value), key
    assert cfg["reduced"] == list(cut)
    assert cfg["published"] == {k: published[k] for k in cut} == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184}
    assert "4 chips" in cfg["deployment"]
    assert "num_nextn_predict_layers" in cfg["not_served"]
    assert "swiglu_limit" in cfg["unused_here"]
    # the floors of a cut: a whole period and four layers after the
    # leading dense one, 8 routed experts, an eighth of the vocabulary
    fam = weights.family(cfg)
    kinds = fam.layer_kinds(cfg)
    assert kinds == [("kda", "dense")] + [("kda", "moe")] * 3 + \
        [("mla", "moe")] + [("kda", "moe")] * 2
    assert cfg["layers_held"] == [1, 2, 3, 4, 5, 6, 7]
    assert cfg["vocab_size"] * 8 >= 157184 and cfg["vocab_size"] * 4 == 157184
    # experts 0-127 are groups 0 and 1 of the router's 8, whole
    assert (cfg["experts_first"], cfg["num_experts"]) == (0, 2 * 512 // 8)
    assert (cfg["weights_dtype"], cfg["dtype_policy"]) == \
        ("bfloat16", "bf16_mixed")


def test_traffic_is_the_issues_table():
    t, chat = manifest.traffic("serve_longdoc"), manifest.traffic("serve_chat")
    # serve_lm's run whole, under a comparison that can see one layer
    assert t["driver"] == "serve_hybrid"
    assert {k: t[k] for k in (
        "clients", "slots", "cache_len", "page_size", "num_pages",
        "prefill_chunk", "prefix_share", "strata", "check_requests",
        "check_slots", "trace_seconds")} == {
        "clients": 64, "slots": 32, "cache_len": 9216, "page_size": 16,
        "num_pages": 18433, "prefill_chunk": 512, "prefix_share": False,
        "strata": 64, "check_requests": 6, "check_slots": 2,
        "trace_seconds": 3.0}
    assert t["prompt_len"] == {"median": 2048, "sigma": 0.8, "lo": 256,
                               "hi": 8192}
    assert t["answer_len"] == {"median": 384, "sigma": 0.6, "lo": 64,
                               "hi": 1024}
    # pairing and order generated as serve_chat.json's were
    assert t["pairing"] == chat["pairing"] and t["order"] == chat["order"]
    from benchmark.lib import lengths

    shapes = lengths.request_shapes(t)
    assert max(p + a for p, a in shapes) <= t["cache_len"]
    assert t["num_pages"] == t["slots"] * t["cache_len"] // t["page_size"] + 1


# -- the counts, by hand at the published widths ------------------------------

def test_family_counts_against_a_hand_count(cfg):
    fam = weights.family(cfg)
    D, H = 2560, 32
    kda = D * 12288 + D * 4096 + 2 * D * H + 4096 * D          # 52.6 M
    mla = D * 6144 + D * 576 + 512 * 8192 + D * H + 4096 * D   # 31.9 M
    assert (kda, mla) == (52592640, 31965184)
    assert fam.mixer_params(cfg, "kda") == kda
    assert fam.mixer_params(cfg, "mla") == mla
    expert = 3 * D * 768
    dense = 3 * D * 6144
    router = D * 512
    assert fam.ffn_params(cfg, "dense", 0) == dense
    assert fam.ffn_params(cfg, "moe", 128) == router + expert + 128 * expert
    head = 39296 * D
    # a token: every mixer and the dense layer, and of its 8 routed
    # experts the 2 that fall on the held quarter, beside the shared one;
    # a KDA layer's state update (4 passes over 32 x 128 x 128) and its
    # convolution; MLA over the cached positions
    per_token = 6 * kda + mla + dense + 6 * (router + expert + 2 * expert) \
        + head + 6 * (4 * H * 128 * 128 + 4 * 12288)
    assert fam.serve_flops_per_token(cfg, 1000) == \
        2 * per_token + 2 * H * (192 + 128) * 1000
    # every weight a step multiplies once as stored: of the routed
    # experts the (layer, expert) pairs some row chose, not all 768
    # held; the active slots' state read and written; the latent rows of
    # the live positions
    fixed = 2 * (6 * kda + mla + dense + 6 * (router + expert)
                 + 6 * 4 * 12288) + 4 * head
    state = 6 * 2 * (4 * H * 128 * 128 + 2 * 3 * 12288)
    assert fam.forward_min_bytes(cfg, 50000, 31.5, 300.25) == \
        fixed + 2 * expert * 300.25 + 31.5 * state + 2 * 576 * 50000
    # with every held expert touched: what the chip holds, 10.4 GB
    assert 10.3e9 < fam.forward_min_bytes(cfg, 0, 0, 6 * 128) < 10.5e9
    assert 1.27e9 < fixed < 1.29e9 and 0.82e9 < 32 * state < 0.85e9
    specs = fam.param_specs(cfg)
    assert len(specs) == 1 + 14 + 5 * 19 + 16 + 2
    stored = sum(int(np.prod(s)) * (4 if k in ("gamma", "head", "a_log",
                                               "dt_bias", "bias") else 2)
                 for _n, s, k in specs)
    assert 10.53e9 < stored < 10.55e9          # the issue's 10.54 GB


# -- the rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_carries_no_rate(capsys, trace):
    bench_run.main(["--workload", CELL, "--seed", str(2**31 + 33),
                    "--seconds", "1", "--trace", trace, "--rehearse", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(manifest.limits(CELL)) == set(line["compared"])
    for value, limit in line["compared"].values():
        assert value <= limit


def test_planted_faults_are_in_the_programs_weights_alone(cfg):
    """Each fault of ``tools/hybrid_limits.py`` does what its name says
    to the weights the program is handed, and to nothing else: the decay
    gate reads an exact 0, the shared expert adds an exact 0, the rotary
    part of every query is an exact 0."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    import hybrid_limits

    small = dict(cfg, **cfg["rehearsal"])
    fam = weights.family(small)
    specs = fam.param_specs(small)
    names = [n for n, _s, _k in specs]
    arrays = weights.make_params(small, 3)
    layer = "h%d_" % hybrid_limits.FAULT_LAYER
    assert fam.layer_kinds(small)[hybrid_limits.FAULT_LAYER] == ("kda", "moe")

    def changed(kind):
        out = hybrid_limits.planted(small, specs, arrays, kind)
        diff = [n for n, a, b in zip(names, arrays, out) if a is not b]
        return out, diff

    out, diff = changed("kda_no_decay")
    assert diff == [layer + "decay_weight", layer + "decay_dt_bias"]
    at = names.index(layer + "attn_norm_gamma")
    n = jax.random.normal(jax.random.key(0), (1, 5, small["hidden_size"]))
    g = fam.kda_inputs(small, n, out[at + 1:at + 10])[3]
    assert float(jnp.abs(g).max()) == 0.0
    assert float(jnp.abs(fam.kda_inputs(
        small, n, arrays[at + 1:at + 10])[3]).max()) > 0.01
    out, diff = changed("no_shared_expert")
    assert diff == [layer + "shared_down_weight"]
    assert float(jnp.abs(out[names.index(diff[0])].astype(
        jnp.float32)).max()) == 0.0
    out, diff = changed("mla_no_rope_scores")
    assert diff == ["h4_kv_down_weight"]
    dn, dr = small["qk_nope_head_dim"], small["qk_rope_head_dim"]
    dl = small["kv_lora_rank"]
    wd = np.asarray(out[names.index(diff[0])].astype(jnp.float32))
    assert np.abs(wd[dl:]).max() == 0.0 and np.abs(wd[:dl]).max() > 0
    # what the latent pages then hold of a position: no rotary part
    at = names.index("h4_attn_norm_gamma")
    rows = fam.mla(small, n, out[at + 1:at + 7], upto=5)[1]
    assert float(jnp.abs(rows[..., dl:]).max()) == 0.0
    assert float(jnp.abs(rows[..., :dl]).max()) > 0.1
    out, diff = changed("mla_no_rope_queries")
    assert diff == ["h4_proj_q_weight"]
    wq = np.asarray(out[names.index(diff[0])].astype(jnp.float32)).reshape(
        small["num_attention_heads"], dn + dr, -1)
    assert np.abs(wq[:, dn:]).max() == 0.0 and np.abs(wq[:, :dn]).max() > 0
    assert hybrid_limits.FAULTS == (
        "kda_no_decay", "no_shared_expert", "mla_no_rope_scores",
        "mla_no_rope_queries")
    with pytest.raises(SystemExit):
        hybrid_limits.planted(small, specs, arrays, "nothing")


# -- what the cell's driver adds to serve_lm's comparison ---------------------

def test_sample_numbers_read_every_token():
    gaps = [np.array([0.0, 0.0, 0.3, 0.0]), np.array([0.1, 0.0])]
    got = serve_hybrid.sample_numbers(gaps)
    assert got["logit_gap_mean"] == pytest.approx(0.4 / 6)
    assert got["off_first_choice_share"] == pytest.approx(2 / 6)
    assert got["logit_gap_p90"] == pytest.approx(0.2)
    assert serve_hybrid.sample_numbers([]) == {"logit_gap_mean": None}


class _Engine:
    """What ``tap_caches`` asks of an engine."""

    def __init__(self, positions):
        self.positions, self.read, self.evicted = positions, [], []

    def active_slots(self):
        return sorted(self.positions)

    def position(self, slot):
        return self.positions[slot]

    def cached(self, slots):
        self.read.append(list(slots))
        return [{"position": self.positions[s]} for s in slots]

    def evict(self, slot, reason):
        self.evicted.append((slot, reason))


def test_caches_are_read_once_when_the_cut_requests_are_let_go():
    """The first eviction with reason ``drain`` (``TokenServer.close``,
    its worker gone) reads ``check_slots`` slots, the longest among
    them; evictions during the window read nothing, and every eviction
    goes through."""
    run = type("R", (), {"seed": 2**31 + 5, "traffic": {"check_slots": 2}})
    eng, taken = _Engine({0: 40, 1: 900, 2: 7, 3: 300}), []
    serve_hybrid.tap_caches(run, eng, taken)
    eng.evict(2, "length")
    assert taken == [] and eng.read == []
    eng.evict(0, "drain")
    eng.evict(1, "drain")
    assert len(eng.read) == 1 and eng.read[0][0] == 1
    assert len(set(eng.read[0])) == 2
    assert [s["position"] for s in taken][0] == 900
    assert eng.evicted == [(2, "length"), (0, "drain"), (1, "drain")]
    assert "evict" not in vars(eng)          # the engine's own again
    # no slot decoding (a tiny rehearsal): the one being let go
    idle, got = _Engine({}), []
    serve_hybrid.tap_caches(run, idle, got)
    idle.positions[5] = 0
    idle.active_slots = lambda: []
    idle.evict(5, "drain")
    assert idle.read == [[5]] and got == [{"position": 0}]
    again = []
    eng2 = _Engine({0: 40, 1: 900, 2: 7, 3: 300})
    serve_hybrid.tap_caches(run, eng2, again)
    eng2.evict(3, "drain")
    assert eng2.read == eng.read             # the seed draws the others


def test_cache_numbers_are_relative_gaps_layer_by_layer(cfg, monkeypatch):
    """``state_gap_max`` and ``latent_rows_gap_max``: the largest
    ``|x - ref| / |ref|`` over layers and slots, the reference's caches
    computed from the snapshot's own ids."""
    import jax.numpy as jnp

    small = dict(cfg, **cfg["rehearsal"])
    fam = weights.family(small)
    seen = []

    def caches(_cfg, params, tokens, upto, quant=None):
        seen.append(quant)
        rows = jnp.ones((1, 16, 3)) * (1.0 if quant is None else 1.5)
        return [(jnp.full((1, 2, 2), 2.0), jnp.ones((1, 3, 4))), rows]

    monkeypatch.setattr(fam, "caches", caches)
    run = type("R", (), {"cfg": small, "traffic": {"cache_len": 16},
                         "log": staticmethod(lambda msg: None)})
    snap = {"position": 5, "tokens": [1, 2, 3, 4, 5], "layers": [
        (np.full((2, 2), 2.2), np.ones((3, 4))), np.full((5, 3), 0.9)]}
    got = serve_hybrid.cache_numbers(run, None, [snap])
    assert got["state_gap_max"] == pytest.approx(0.1)
    assert got["latent_rows_gap_max"] == pytest.approx(0.1)
    assert got["conv_tail_gap_max"] == 0.0
    # the control: the reference in its precision in the snapshot's place
    got = serve_hybrid.cache_numbers(run, None, [snap], quant="q")
    assert got["latent_rows_gap_max"] == pytest.approx(0.5)
    assert got["state_gap_max"] == 0.0 and "q" in seen
    assert serve_hybrid.cache_numbers(run, None, [{"position": 0}]) == {
        "state_gap_max": None, "latent_rows_gap_max": None,
        "conv_tail_gap_max": None}


# -- the readers of the new metrics -------------------------------------------

def _decode(t0, **args):
    return {"name": "engine.decode", "t0": t0, "dur": 0.01, "tid": 1,
            "args": dict({"slots": 30, "live": 100}, **args)}


def test_span_args_readers_of_state_and_held_pairs(ctx):
    ctx["ring"]["records"] += [
        _decode(9.5, state_slots=1, expert_rows_held=1, expert_rows_all=1),
        _decode(10.2, state_slots=30, expert_rows_held=300,
                expert_rows_all=1536),
        _decode(10.4, slots=32, state_slots=32, expert_rows_held=468,
                expert_rows_all=1536)]
    assert span_args.reduce(ctx, **_args("decode_state_slots_share")) == \
        pytest.approx(100.0)
    assert span_args.reduce(ctx, **_args("moe_held_pairs_share")) == \
        pytest.approx(100.0 * 768 / 3072)
    # a program that writes neither (the parent) leaves both out
    ctx["ring"]["records"][:] = [_decode(10.2), _decode(10.4)]
    assert span_args.reduce(ctx, **_args("decode_state_slots_share")) is None
    assert span_args.reduce(ctx, **_args("moe_held_pairs_share")) is None


def test_span_args_reader_of_the_rows_the_experts_multiply(ctx):
    """``moe_expert_rows_share`` (PR 36): the rows the held experts
    multiplied, each one's padded up to whole tiles, over what every
    held expert multiplying every row would be, of the window's
    ``engine.decode`` spans; data only, the manifest's entry before PR
    37's eleven (cells that later PRs add follow the two it was in)."""
    man = manifest.manifest()
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("moe_expert_rows_share")
    assert names[at + 1] == "decode_cache_gather_device_ms_per_step"
    entry = dict(man["per_layer"][at])
    assert entry.pop("workloads")[:2] == [CELL, "gigachat3_serve_reason"]
    assert entry == {
        "name": "moe_expert_rows_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "experts",
        "moves": "serve_itl_p95_ms"}
    ctx["ring"]["records"] += [
        _decode(9.5, expert_rows_multiplied=1, expert_rows_dense=1),
        _decode(10.2, expert_rows_multiplied=4608,
                expert_rows_dense=6 * 32 * 128),
        _decode(10.4, expert_rows_multiplied=4480,
                expert_rows_dense=6 * 32 * 128)]
    assert span_args.reduce(ctx, **_args("moe_expert_rows_share")) == \
        pytest.approx(100.0 * (4608 + 4480) / (2 * 6 * 32 * 128))
    # the parent writes neither argument: the metric is left out
    ctx["ring"]["records"][:] = [_decode(10.2, expert_rows_held=3,
                                         expert_rows_all=9)]
    assert span_args.reduce(ctx, **_args("moe_expert_rows_share")) is None


def test_span_args_reader_of_the_rows_a_chunk_attends(ctx):
    """``prefill_attended_rows_share`` (PR 38): the cached rows a
    chunk's attention multiplies, whole blocks up to what its sequence
    has written, over the rows a slot holds, of the window's
    ``engine.prefill`` spans; data only, the manifest's entry after PR
    37's eleven, in the two cells whose model attended its own rows
    then (cells that later PRs add follow them)."""
    man = manifest.manifest()
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("prefill_attended_rows_share")
    assert names[at - 1] == "train_norm_device_ms_per_step"
    entry = dict(man["per_layer"][at])
    assert entry.pop("workloads")[:2] == [CELL, "gigachat3_serve_reason"]
    assert entry == {
        "name": "prefill_attended_rows_share", "unit": "%",
        "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "serve_itl_p95_ms"}
    assert manifest.layer_metric("prefill_attended_rows_share") == {
        "name": "prefill_attended_rows_share", "reducer": "span_args",
        "args": {"span": "engine.prefill", "num": ["cache_rows_attended"],
                 "den": ["cache_rows_held"], "scale": 100.0}}

    def chunk(t0, **args):
        return {"name": "engine.prefill", "t0": t0, "dur": 0.01, "tid": 1,
                "args": dict({"slot": 0, "filled": 0, "count": 512}, **args)}

    ctx["ring"]["records"] += [
        chunk(9.5, cache_rows_attended=9216, cache_rows_held=9216),
        chunk(10.2, cache_rows_attended=0, cache_rows_held=9216),
        chunk(10.3, cache_rows_attended=1536, cache_rows_held=9216),
        chunk(10.4, cache_rows_attended=4096, cache_rows_held=9216)]
    assert span_args.reduce(
        ctx, **_args("prefill_attended_rows_share")) == pytest.approx(
            100.0 * (1536 + 4096) / (3 * 9216))
    # the parent writes neither argument: the metric is left out
    ctx["ring"]["records"][:] = [chunk(10.2), chunk(10.4)]
    assert span_args.reduce(
        ctx, **_args("prefill_attended_rows_share")) is None


def test_roofline_and_chunk_readers(cfg, ctx, monkeypatch):
    """``hybrid_decode_hbm_roofline`` is the family's least bytes, at
    what the window's ``engine.decode`` spans say a step touched, over
    the device time of a decode step's programs;
    ``hybrid_prefill_device_ms_per_chunk`` the device time charged to the
    chunks' spans; without a trace both are left out, and a program that
    does not say what a step touched leaves the share out."""
    spec = manifest.layer_metric("hybrid_decode_hbm_roofline")
    assert spec["reducer"] == "hybrid_hbm_roofline"
    assert {k: spec["args"][k] for k in ("span", "among", "live_key")} == \
        manifest.layer_metric("blockgen_hbm_roofline")["args"]
    chunk = manifest.layer_metric("hybrid_prefill_device_ms_per_chunk")
    assert (chunk["reducer"], chunk["args"]["span"]) == \
        ("device_busy_per_step", "bench:engine.prefill")
    ctx["cfg"] = cfg
    ctx["window"]["traced_decode_live_positions_mean"] = 80000.0
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    ctx["ring"]["records"] += [
        _decode(9.5, slots=1, experts_held_touched=1),      # set-up
        _decode(10.2, slots=32, experts_held_touched=290),
        _decode(10.4, slots=31, experts_held_touched=270)]
    assert hybrid_hbm_roofline.reduce(dict(ctx, planes=None),
                                      **spec["args"]) is None
    assert device_busy_per_step.reduce(dict(ctx, planes=None),
                                       **chunk["args"]) is None
    monkeypatch.setattr(
        device_busy_per_step, "seconds_charged_to",
        lambda planes, span, among: (1.6, 100) if span.endswith("decode")
        else (2.5, 50))
    least = weights.family(cfg).forward_min_bytes(
        cfg, 80000.0, 31.5, 280.0) / 819e9
    assert 0.0065 < least < 0.0072           # 5.3-5.9 GB at 819 GB/s
    share = hybrid_hbm_roofline.reduce(dict(ctx, planes=object()),
                                       **spec["args"])
    assert share == pytest.approx(100 * least / 0.016) and share < 100
    assert device_busy_per_step.reduce(dict(ctx, planes=object()),
                                       **chunk["args"]) == pytest.approx(50.0)
    # a program whose spans do not say what a step touched (the parent)
    ctx["ring"]["records"][:] = [_decode(10.2), _decode(10.4)]
    assert hybrid_hbm_roofline.reduce(dict(ctx, planes=object()),
                                      **spec["args"]) is None


# -- the chip's readings ------------------------------------------------------

def _readings():
    path = os.path.join(manifest.BENCH, "limits", CELL + ".readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("who, correct, at_least", [
    ("program", True, 8), ("witness_bf16", True, 2),
    ("control_fp8", False, 4),
    ("fault_kda_no_decay", False, 2),
    ("fault_no_shared_expert", False, 2),
    ("fault_mla_no_rope_scores", False, 2),
    ("fault_mla_no_rope_queries", True, 1)])
def test_chip_readings_judged_by_the_committed_limits(who, correct,
                                                      at_least):
    """What ``tools/hybrid_limits.py`` and the cell's own runs read on
    the chip at the cell's size, judged here as a run judges (the lines
    carry no verdict of their own): the program and the bfloat16 witness
    (the program's own precision) are correct on every seed; the fp8
    control and each fault planted in ONE layer on none: a KDA layer's
    decay left out, a layer's shared expert left out, the rotary part of
    MLA's scores left out of the cached rows.  The same part left out on
    the query's side passes, and is recorded as what the comparison
    cannot see."""
    from benchmark.lib import compare

    limits = manifest.limits(CELL)
    mine = [r for r in _readings() if r["who"] == who]
    assert len(mine) >= at_least
    for row in mine:
        assert "correct" not in row
        have = {k: v for k, v in limits.items() if k in row}
        assert have and compare.judge(row, have)[0] is correct, row["seed"]


@pytest.mark.parametrize("name, room, least_readings", [
    ("logit_gap_mean", 1.5, 8), ("state_gap_max", 1.5, 8),
    ("latent_rows_gap_max", 1.5, 8)])
def test_limits_lie_between_the_programs_and_the_controls_readings(
        name, room, least_readings):
    """Each limit above the program's largest reading and under the fp8
    control's smallest, with ``room`` on both sides, and under the
    smallest reading of every fault it is there to catch.  The widest
    gap of 3000 tokens has no such limit (the control's smallest, 1.64,
    against the program's largest), so it is logged and not compared."""
    rows, limits = _readings(), manifest.limits(CELL)
    assert set(limits) == {"logit_gap_mean", "state_gap_max",
                           "latent_rows_gap_max", "wrong_length"}
    assert min(r["logit_gap_max"] for r in rows
               if r["who"] == "control_fp8") < 1.7
    program = [r[name] for r in rows if r["who"] == "program"]
    control = [r[name] for r in rows if r["who"] == "control_fp8"
               and name in r]
    assert len(program) >= least_readings and len(control) >= 4
    assert room * max(program) < limits[name] < min(control) / room
    catches = {"logit_gap_mean": ("kda_no_decay", "no_shared_expert"),
               "state_gap_max": ("kda_no_decay", "no_shared_expert"),
               "latent_rows_gap_max": ("kda_no_decay", "no_shared_expert",
                                       "mla_no_rope_scores")}
    for fault in catches.get(name, ()):
        read = [r[name] for r in rows if r["who"] == "fault_" + fault]
        assert read and limits[name] < min(read), fault
