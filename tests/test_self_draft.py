"""Self-drafting (ISSUE 35): a DeepSeek-V3 stack in the zoo's
``HybridDecoderLM`` (every mixer an MLA layer with a query latent, a
value width of its own and no gate, under YaRN; a dense and four expert
feed-forwards) whose multi-token-prediction module drafts for the paged
engine's verify step.

Everything runs on the CPU at the configuration's ``rehearsal`` sizes,
float32, seeded random weights:

* the package's model against the plain reference
  (``benchmark/lib/reference/deepseek_v3.py``), trunk and draft logits;
* YaRN's frequency table and the score scale against numbers worked out
  by hand at the published sizes;
* absorbed MLA against expanded, with a query latent and values wider
  than the keys' no-rope part;
* chunks of several sizes, then verify steps, through the engine
  against the reference's full forward, logits and cached rows;
* greedy tokens with drafting on are the tokens with it off, with every
  draft accepted and with none, the rejected rows rolled back in the
  trunk's and the draft block's layers of the pool;
* prefix attachment is offered to a model that keeps rows alone, the
  draft block's among them; n-gram speculation is as it was; a model
  with per-slot state refuses ``spec_k`` 1 as it refuses 2;
* (ISSUE 39) the same under a model whose windowed layers keep their
  rows in rings (``tests/test_window_cache.py``'s model and helpers):
  with every draft accepted, with none and with every other one the
  tokens are the plain engine's and the rings hold the reference's
  rows; a ring one row short of what a verify step needs fails that.
"""
import json
import math
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import generate, nd, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import programs  # noqa: E402
from benchmark.lib import weights  # noqa: E402
from benchmark.lib.reference import deepseek_v3 as ref  # noqa: E402
import test_window_cache as wc  # noqa: E402

# float32 on both sides, the same weights: what is left is the order of
# the sums (the absorbed products, XLA's own fusions)
TOL = 2e-5
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "gigachat3.1-702b-a36b.json")


@pytest.fixture(scope="module")
def published():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg(published):
    return dict(published, **published["rehearsal"])


@pytest.fixture(scope="module")
def model(cfg):
    net = programs.program(cfg).build_net(cfg)
    arrays = weights.make_params(cfg, 5)
    programs.set_weights(net, ref.param_specs(cfg), arrays)
    return net, arrays


def _engine(net, spec_k=1, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("prefix_share", False)
    kw.setdefault("prefill_chunk", 24)
    return generate.PagedGenerationEngine(
        net, cache_len=128, page_size=8, spec_k=spec_k,
        sampling=generate.SamplingConfig(greedy=True), **kw)


def _ids(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], n).astype(np.int32)


_REF = {}


def _ref_logits(cfg, arrays, seq):
    """(the trunk's logits, the draft module's) of the reference at
    every position of ``seq``: one compiled forward over 128 positions
    serves every length (the model is causal; the draft module's last
    position is fed the padding and means nothing)."""
    import jax
    import jax.numpy as jnp

    if "both" not in _REF:
        _REF["both"] = jax.jit(lambda params, toks: ref.both_logits_at(
            cfg, params, toks, jnp.arange(128)))
    toks = np.zeros((1, 128), np.int32)
    toks[0, :len(seq)] = seq
    trunk, draft = _REF["both"](arrays, toks)
    return np.asarray(trunk)[0, :len(seq)], np.asarray(draft)[0, :len(seq)]


def _ref_rows(cfg, arrays, seq):
    import jax

    if "rows" not in _REF:
        _REF["rows"] = jax.jit(lambda params, toks: ref.caches(
            cfg, params, toks, 0))
    toks = np.zeros((1, 128), np.int32)
    toks[0, :len(seq)] = seq
    return [np.asarray(r)[0] for r in _REF["rows"](arrays, toks)]


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("length", [40, 100])
def test_trunk_and_draft_logits_match_reference(cfg, model, length):
    """The whole model, uncached: five MLA blocks absorbed against the
    reference's expanded attention, the expert layers' share against the
    reference's, and the draft block on the trunk's states and each
    position's following token against the reference's module."""
    import jax.numpy as jnp

    net, arrays = model
    toks = np.stack([_ids(cfg, length, 1), _ids(cfg, length, 2)])
    got = net(nd.array(toks)).asnumpy()
    zero = jnp.zeros((2,), jnp.int32)
    full = jnp.full((2,), length, jnp.int32)
    _logits, kept, extras = net.chunk_forward(jnp.asarray(toks), None, zero,
                                              full)
    follow = np.concatenate([toks[:, 1:], np.zeros((2, 1), np.int32)], 1)
    drafted, rows, more = net.draft_forward(
        extras["hidden"], jnp.asarray(follow), None, zero, full)
    assert extras["expert_load"].shape == (4, 16)
    assert more["expert_load"].shape == (1, 16)
    assert len(kept) == 5 and rows.shape == kept[0].shape == (2, length, 40)
    for b in range(2):
        trunk, draft = _ref_logits(cfg, arrays, toks[b])
        assert np.abs(got[b] - trunk).max() < TOL
        assert np.abs(np.asarray(drafted._data)[b, :-1]
                      - draft[:-1]).max() < TOL
        assert np.abs(trunk).max() > 0.1 and np.abs(draft).max() > 0.1
        # the two heads are not each other's: a draft is no echo
        assert np.abs(trunk[:-1] - draft[:-1]).max() > 0.1


def test_yarn_table_and_score_scale_by_hand(published):
    """At the published sizes (theta 1e5, 32 pairs, factor 64, original
    context 4096, beta 32 and 1): d(32) = 64 ln(4096 / 64 pi) / (2 ln
    1e5) = 8.378 and d(1) = 18.01, so the ramp rises from pair 8 to pair
    19.  Pair 0 turns at 1, pair 8 at 1e5^-0.25 = 0.0562341 (ramp 0),
    pair 13 at 1e5^(-26/64) = 0.00930572 times (6/11 + 5/11/64) =
    0.00514194, pair 19 at 1e5^(-38/64) / 64 = 1.67908e-5, pair 31 at
    1e5^(-62/64) / 64 = 2.23908e-7.  m = 0.1 ln 64 + 1 = 1.4158883 and
    the scores are scaled by 192^-1/2 m^2 = 0.144681.  The package's
    rotation and the reference's table both say so."""
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.language import hybrid_decoder as hd

    sc = published["rope_scaling"]
    lo, hi = hd.yarn_corners(1e5, 64, 4096, 32, 1)
    assert (lo, hi) == (8, 19)
    by_hand = {0: 1.0, 8: 0.0562341, 13: 0.00514194, 19: 1.67908e-5,
               31: 2.23908e-7}
    inv, gain = ref.yarn_inv_freq(published)
    assert gain == 1.0 and inv.shape == (32,)
    # the package rotates (1, 0) pairs at position 1 by the frequencies
    x = jnp.tile(jnp.asarray([1.0, 0.0]), 32).reshape(1, 1, 64)
    turned = np.asarray(hd._rope_pairs(
        x, jnp.ones((1, 1), jnp.int32), 1e5,
        (float(sc["factor"]), lo, hi, 1.0)))[0, 0]
    mine = np.arctan2(turned[1::2], turned[0::2])
    plain = np.asarray(hd._rope_pairs(x, jnp.ones((1, 1), jnp.int32),
                                      1e5))[0, 0]
    for i, want in by_hand.items():
        assert float(inv[i]) == pytest.approx(want, rel=2e-5)
        # (a float32 sine of 2e-7 beside a cosine of 1: absolute there)
        assert mine[i] == pytest.approx(want, rel=1e-4, abs=1e-7)
    np.testing.assert_allclose(mine, np.asarray(inv), rtol=1e-4, atol=1e-7)
    # below the ramp YaRN is the plain table, above it 1/64 of it
    np.testing.assert_allclose(turned[:18], plain[:18], atol=1e-7)
    assert np.arctan2(plain[39], plain[38]) == pytest.approx(
        64 * mine[19], rel=1e-4)
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.4158883, rel=1e-7)
    assert hd.yarn_mscale(64, 1) == pytest.approx(m)
    assert ref.score_scale(published) == pytest.approx(0.144681, rel=1e-5)
    assert ref.score_scale(dict(published, rope_scaling=None)) == \
        pytest.approx(192 ** -0.5)
    net = hd.HybridDecoderLM(
        vocab_size=8, d_model=8, mixers=["mla"], ffns=["dense"], n_heads=1,
        d_nope=128, d_rope=64, d_latent=8, d_v_mla=8, d_ff=8,
        rope_theta=1e5, rope_scaling=sc)
    assert net._scale == pytest.approx(0.144681, rel=1e-5)
    assert net._yarn == (64.0, 8, 19, 1.0)
    with pytest.raises(ValueError, match="not built"):
        hd.HybridDecoderLM(
            vocab_size=8, d_model=8, mixers=["mla"], ffns=["dense"],
            n_heads=1, d_nope=8, d_rope=8, d_latent=8, d_v_mla=8, d_ff=8,
            rope_scaling={"rope_type": "linear", "factor": 2})


def test_absorbed_mla_with_query_latent_matches_expanded(cfg, model):
    """One MLA layer alone, a query latent of 24 under its own norm
    (weight 2), keys of 16 | 8 and values of 24, no gate, YaRN's
    frequencies and m^2: 48 positions from nothing absorbed against the
    reference's attention over keys and values expanded a head; and a
    chunk of 8 against 40 cached rows, zero lanes after them as the pool
    keeps them."""
    import jax
    import jax.numpy as jnp

    net, arrays = model
    assert (cfg["v_head_dim"], cfg["qk_nope_head_dim"]) == (24, 16)
    p = [q.data()._data for q in net._layers[1][1]]
    assert len(p) == 7 and float(p[1][0]) == ref.Q_GAIN
    names = [n for n, _s, _k in ref.param_specs(cfg)]
    at = names.index("h1_q_down_weight")
    D, w = cfg["hidden_size"], cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    n = jax.random.normal(jax.random.key(0), (2, 48, D))
    want, want_rows = ref.mla(cfg, n, arrays[at:at + 7])
    want = np.asarray(want)
    zero = jnp.zeros((2,), jnp.int32)
    pos = jnp.arange(48)[None, :] + zero[:, None]
    got, rows = net._mla(n, p, None, zero, pos)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(want).max() > 1e-3 and rows.shape == (2, 48, w)
    assert np.abs(np.asarray(rows) - np.asarray(want_rows)).max() < 1e-5
    cached = jnp.pad(rows, ((0, 0), (0, 16), (0, 128 - w)))  # 64 rows
    start = jnp.full((2,), 40, jnp.int32)
    got, new = net._mla(n[:, 40:], p, cached, start, pos[:, 40:])
    assert np.abs(np.asarray(got) - want[:, 40:]).max() < 1e-5
    assert np.abs(np.asarray(new) - np.asarray(rows[:, 40:])).max() < 1e-5
    # the score scale carries m^2: without it the layer says otherwise
    was, net._scale = net._scale, net._scale / ref.yarn_mscale(64, 1) ** 2
    try:
        off, _rows = net._mla(n, p, None, zero, pos)
    finally:
        net._scale = was
    assert np.abs(np.asarray(off) - want).max() > 1e-4


# -- through the engine -------------------------------------------------------

@pytest.mark.parametrize("chunk", [24, 16, 64])
def test_chunks_then_verify_steps_match_reference(cfg, model, chunk):
    """Three sequences side by side: prompts of 50, 7 and 61 tokens in
    chunks of ``chunk`` (the last chunk of each padded), then 10 verify
    steps of all slots.  The trunk's logits of every position served
    are the reference's full forward over the same tokens; every draft
    is the reference's draft module's first choice at its row; and what
    the pool holds of a slot, five trunk layers' rows and the draft
    block's, is what the reference keeps."""
    net, arrays = model
    eng = _engine(net, prefill_chunk=chunk)
    assert eng.dispatch_shapes() == [(1, chunk), (3, 1), (3, 2)]
    seqs, slots, logits = [], [], []
    for n, seed in ((50, 3), (7, 4), (61, 5)):
        p = _ids(cfg, n, seed)
        slot, tok = eng.admit(p)
        logits.append([eng.last_logits[0, (n - 1) % chunk]])
        seqs.append(list(p) + [tok])
        slots.append(slot)
    for _ in range(10):
        out = eng.decode_step()
        step = eng.last_logits                     # (slots, 2, V)
        for sl, seq, lg in zip(slots, seqs, logits):
            lg.extend(step[sl, :len(out[sl])])
            seq.extend(out[sl])
    for sl, seq, lg, n in zip(slots, seqs, logits, (50, 7, 61)):
        trunk, draft = _ref_logits(cfg, arrays, seq)
        got = np.stack(lg)
        assert len(seq) - n >= 11 and len(got) == len(seq) - n
        assert np.abs(got - trunk[n - 1:-1]).max() < TOL
        assert seq[n:] == list(trunk[n - 1:-1].argmax(-1))
        # the drafts: one an emitted token, the module's choice at the
        # row the token was chosen at
        drafts = eng.drafted(sl)
        assert drafts == list(draft[n - 1:-1].argmax(-1))
        snap = eng.cached([sl])[0]
        assert snap["position"] == len(seq) - 1 == len(snap["tokens"])
        assert snap["tokens"] == seq[:-1] and snap["next_token"] == seq[-1]
        assert snap["drafts"] == drafts and len(snap["layers"]) == 6
        for mine, theirs in zip(snap["layers"], _ref_rows(cfg, arrays, seq)):
            assert mine.shape == (len(seq) - 1, 40)
            assert np.abs(mine - theirs[:len(seq) - 1]).max() < TOL
        eng.evict(sl, "length")
    assert eng.drafted(slots[0]) == []


def _serve(eng, prompts, steps, drafts=None):
    """Every prompt admitted, ``steps`` decode calls, what is in flight
    drained: ``{slot: tokens}``.  ``drafts(slot, emitted so far)``, if
    given, plants the next step's draft in the draft block's place."""
    outs = {}
    for p in prompts:
        slot, tok = eng.admit(p)
        outs[slot] = [] if tok is None else [tok]
    for _ in range(steps):
        if drafts is not None:
            for slot, toks in outs.items():
                eng._draft_tok[slot] = drafts(slot, toks)
        for slot, toks in eng.decode_step().items():
            outs[slot].extend(toks)
    for more in eng.drain():
        for slot, toks in more.items():
            outs[slot].extend(toks)
    return outs


@pytest.mark.parametrize("accept", ["all", "none", "own"])
def test_tokens_with_drafting_on_are_the_tokens_with_it_off(cfg, model,
                                                            accept):
    """Greedy output under self-drafting, token for token the output of
    the plain engine: with every draft accepted (the drafts planted from
    the plain run's tokens: two tokens a step), with none (planted one
    off: one token a step, the second row's rows of all six layers
    written and then overwritten) and with the draft block's own (at
    seeded weights nearly none).  What the pool holds afterwards is the
    reference's rows of the tokens served: nothing of a rejected row is
    left under a position that counts."""
    net, arrays = model
    prompts = [_ids(cfg, n, seed) for n, seed in ((50, 3), (7, 4), (33, 6))]
    plain = _serve(_engine(net, spec_k=0), prompts, 25)
    assert all(len(t) == 26 for t in plain.values())
    eng = _engine(net)
    t0 = len(tracing.records())
    V = cfg["vocab_size"]
    plant = {"all": lambda s, toks: plain[s][len(toks)],
             "none": lambda s, toks: (plain[s][len(toks)] + 1) % V,
             "own": None}[accept]
    got = _serve(eng, prompts, 11, plant)
    spans = [r["args"] for r in tracing.records()[t0:]
             if r["name"] == "engine.decode"]
    assert len(spans) == 11 and all(s["draft"] == "model" for s in spans)
    assert all(s["drafted"] == 3 and s["slots"] == 3 for s in spans)
    if accept == "all":
        assert all(len(t) == 23 for t in got.values())
        assert all(s["accepted"] == 3 and s["emitted"] == 6 for s in spans)
        assert eng.spec_accept_rate() == 1.0
    elif accept == "none":
        assert all(len(t) == 12 for t in got.values())
        assert all(s["accepted"] == 0 and s["emitted"] == 3 for s in spans)
        assert eng.spec_accept_rate() == 0.0
    for slot, toks in got.items():
        assert toks == plain[slot][:len(toks)]
        seq = list(prompts[slot]) + toks
        snap = eng.cached([slot])[0]
        assert snap["position"] == len(seq) - 1
        for mine, theirs in zip(snap["layers"], _ref_rows(cfg, arrays, seq)):
            assert np.abs(mine - theirs[:len(seq) - 1]).max() < TOL
    if accept != "own":
        # a planted draft is not the block's: its own choices are still
        # recorded, one an emitted token
        assert all(len(eng.drafted(s)) == len(t) for s, t in got.items())


def test_spans_and_counters_of_self_drafting(cfg, model):
    """``engine.weights`` says what the draft block holds,
    ``engine.pool`` what its rows take, a prompt's last chunk that it
    drafted; the verify step's span carries the routing of the trunk's
    four expert layers and the draft block's one; the counters are
    labelled by where the drafts come from."""
    from mxnet_tpu import telemetry

    net, _arrays = model
    t0 = len(tracing.records())
    was_on = telemetry.enabled()
    telemetry.enable()
    before = {s: (telemetry.DECODE_SPEC_DRAFTED.value(source=s),
                  telemetry.DECODE_SPEC_ACCEPTED.value(source=s))
              for s in ("model", "ngram")}
    eng = _engine(net)
    for n, seed in ((30, 1), (9, 2)):
        eng.admit(_ids(cfg, n, seed))
    eng.decode_step()
    after = {s: (telemetry.DECODE_SPEC_DRAFTED.value(source=s),
                 telemetry.DECODE_SPEC_ACCEPTED.value(source=s))
             for s in ("model", "ngram")}
    if not was_on:
        telemetry.disable()
    assert after["model"][0] - before["model"][0] == 2
    assert after["ngram"] == before["ngram"]
    recs = tracing.records()[t0:]
    held = [r for r in recs if r["name"] == "engine.weights"][-1]["args"]
    params = list(net.collect_params().values())
    n_draft = net.config["draft_params"]
    assert n_draft == 21 and all("mtp_" in p.name for p in params[-21:])
    assert held["draft_bytes"] == sum(
        4 * int(np.prod(p.shape)) for p in params[-21:])
    pool = [r for r in recs if r["name"] == "engine.pool"][-1]["args"]
    assert pool["shape"] == [6 * 49 * 8, 128]
    assert pool["draft_rows_bytes"] * 6 == pool["latent_rows_bytes"]
    chunks = [r["args"] for r in recs if r["name"] == "engine.prefill"]
    assert [c["drafted"] for c in chunks] == [0, 1, 1]
    step = [r for r in recs if r["name"] == "engine.decode"][-1]["args"]
    assert (step["draft"], step["drafted"], step["slots"]) == ("model", 2, 2)
    assert step["emitted"] == 2 + step["accepted"] and step["attn"] == "rows"
    # 3 slots x 2 rows x 4 choices in each of 5 expert layers
    assert step["expert_rows_all"] == 5 * 6 * cfg["num_experts_per_tok"]
    assert 0 < step["experts_held_touched"] <= 5 * cfg["n_routed_experts"]
    # with drafting off the block is left alone: no rows, no draft
    t1 = len(tracing.records())
    off = _engine(net, spec_k=0)
    pool = [r for r in tracing.records()[t1:]
            if r["name"] == "engine.pool"][-1]["args"]
    assert pool["shape"] == [5 * 49 * 8, 128]
    assert "draft_rows_bytes" not in pool and off.drafted(0) is None


def test_token_server_serves_with_self_drafting(cfg, model):
    """``TokenServer`` over the self-drafting engine: two requests at
    once, each the reference's greedy continuation, and the result
    carries the drafts each was served with."""
    net, arrays = model
    eng = _engine(net, slots=2)
    with generate.TokenServer(eng, max_new_tokens=7) as server:
        prompts = [_ids(cfg, 35, 11), _ids(cfg, 12, 12)]
        futures = [server.submit(p) for p in prompts]
        for p, f in zip(prompts, futures):
            res = f.result(120)
            out = res["tokens"]
            trunk, draft = _ref_logits(cfg, arrays, list(p) + out)
            assert len(out) == 7
            assert out == list(trunk[len(p) - 1:-1].argmax(-1))
            assert res["drafts"] == list(draft[len(p) - 1:-1].argmax(-1))
    plain = _engine(net, slots=2, spec_k=0)
    with generate.TokenServer(plain, max_new_tokens=3) as server:
        assert "drafts" not in server.submit(prompts[1]).result(120)


# -- prefix attachment, n-gram speculation, the refusal -----------------------

def test_prefix_attachment_is_offered_to_rows_and_serves_the_same(cfg,
                                                                  model):
    """A model that keeps rows alone takes prefix attachment, the draft
    block's rows with the trunk's: a second prompt that shares 40 tokens
    (5 pages) attaches them and is served the tokens, and the drafts, of
    an engine that shares nothing.  A page is the same only with the
    token after it: the draft block's row of a page's last position was
    fed that token, so a prompt that parts ways exactly at a page's
    boundary does not attach that page."""
    net, _arrays = model
    first = _ids(cfg, 61, 31)
    second = np.concatenate([first[:44], _ids(cfg, 9, 32)])
    third = np.concatenate([first[:40], _ids(cfg, 9, 33)])
    assert third[40] != first[40]

    def run(share):
        eng = _engine(net, prefix_share=share)
        hits, toks, drafts = [], [], []
        for p in (first, second, third):
            slot, tok = eng.admit(p)
            hits.append(eng.last_prefix_hit_tokens)
            out = [tok]
            for _ in range(6):
                out.extend(eng.decode_step()[slot])
            toks.append(out)
            drafts.append(eng.drafted(slot))
        return hits, toks, drafts

    hits, toks, drafts = run(True)
    assert hits == [0, 40, 32]
    assert (toks, drafts) == run(False)[1:]


def test_ngram_speculation_on_a_model_that_declares_rows(cfg, model):
    """``spec_k`` 2 on the same model drafts from the host's n-gram
    history, as for any model (the draft block is left alone and keeps
    no rows): a prompt that repeats itself has drafts accepted, and the
    tokens are the plain engine's."""
    net, _arrays = model
    prompt = np.tile(_ids(cfg, 8, 41), 5)
    plain = _serve(_engine(net, spec_k=0), [prompt], 12)[0]
    eng = _engine(net, spec_k=2, spec_ngram=2)
    assert eng.pool_shape == (5 * 49 * 8, 128) and eng.drafted(0) is None
    t0 = len(tracing.records())
    got = _serve(eng, [prompt], 8)[0]
    assert got == plain[:len(got)] and len(got) >= 9
    spans = [r["args"] for r in tracing.records()[t0:]
             if r["name"] == "engine.decode"]
    assert all(s["draft"] == "ngram" for s in spans)
    assert sum(s["drafted"] for s in spans) > 0


@pytest.mark.parametrize("spec_k", [1, 2])
def test_a_model_with_state_still_refuses_speculation(spec_k):
    """Per-slot recurrent state cannot be rolled back past a rejected
    draft, whoever drafted it."""
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    net = HybridDecoderLM(
        vocab_size=64, d_model=32, mixers=["kda", "mla"],
        ffns=["dense", "dense"], n_heads=2, d_k=8, d_v=8, conv_kernel=4,
        kda_lower_bound=-5, d_nope=8, d_rope=4, d_latent=16, d_ff=32,
        max_len=64, draft_layers=1)
    net.initialize(mx.init.Normal(0.02))
    assert net.config["draft_layers"] == 1
    with pytest.raises(mx.MXNetError, match="roll"):
        generate.PagedGenerationEngine(
            net, slots=2, cache_len=64, page_size=8, prefill_chunk=16,
            spec_k=spec_k, sampling=generate.SamplingConfig(greedy=True))
    with pytest.raises(ValueError, match="draft_layers"):
        HybridDecoderLM(
            vocab_size=64, d_model=32, mixers=["mla", "kda"],
            ffns=["dense", "dense"], n_heads=2, d_k=8, d_v=8, conv_kernel=4,
            kda_lower_bound=-5, d_nope=8, d_rope=4, d_latent=16, d_ff=32,
            draft_layers=1)
    with pytest.raises(ValueError, match="needs"):
        HybridDecoderLM(vocab_size=64, d_model=32, mixers=["kda"],
                        ffns=["dense"], n_heads=2, d_ff=32)


# -- windowed layers' rings under self-drafting (ISSUE 39) -------------------

@pytest.fixture(scope="module")
def window_cfg():
    return wc.small_config()


@pytest.fixture(scope="module")
def window_model(window_cfg):
    return wc.build_model(window_cfg)


def _drafting_against_plain(cfg, model, accept, steps=14):
    """Greedy output and ring rows under self-drafting against the plain
    engine's: ``(tokens equal, rings equal)`` over three slots."""
    net, arrays = model
    prompts = [wc._ids(cfg, n, seed)
               for n, seed in ((50, 3), (7, 4), (33, 6))]
    if "plain" not in wc._REF:     # (the plain engine's ring is the window)
        wc._REF["plain"] = wc._serve(wc._engine(net, spec_k=0), prompts,
                                     2 * steps + 3)
    plain = wc._REF["plain"]
    eng = wc._engine(net, spec_k=1)
    V = cfg["vocab_size"]
    plant = {"all": lambda s, toks: plain[s][len(toks)],
             "none": lambda s, toks: (plain[s][len(toks)] + 1) % V,
             "mixed": lambda s, toks: (plain[s][len(toks)]
                                       + (len(toks) + s) % 2) % V}[accept]
    got = wc._serve(eng, prompts, steps, plant)
    tokens_equal, rings_equal = True, True
    for slot, toks in got.items():
        tokens_equal &= toks == plain[slot][:len(toks)]
        seq = list(prompts[slot]) + plain[slot]
        snap = eng.cached([slot])[0]
        n = snap["position"]
        theirs = wc._ref_rows(cfg, arrays, seq)
        for mine, want in zip(snap["layers"][:4], theirs):
            first = mine["first"]
            rings_equal &= bool(
                np.abs(mine["rows"] - want[first:n]).max() < wc.TOL)
    return got, eng, tokens_equal, rings_equal


@pytest.mark.parametrize("accept", ["all", "none", "mixed"])
def test_drafting_on_serves_the_tokens_and_keeps_the_rings_of_off(
        window_cfg, window_model, accept):
    """With every draft accepted (planted from the plain run's tokens:
    two rows a step written and kept), with none (planted one off: the
    second row written at ``p + 1`` and then overwritten) and with every
    other one: token for token the plain engine's output, and ring row
    for ring row the reference's rows of the tokens served.  Nothing is
    copied to roll a rejected row back."""
    got, eng, tokens_equal, rings_equal = _drafting_against_plain(
        window_cfg, window_model, accept)
    assert tokens_equal and rings_equal
    lens = {"all": 29, "none": 15}
    if accept in lens:
        assert all(len(t) == lens[accept] for t in got.values())
    assert eng.spec_accept_rate() == {"all": 1.0, "none": 0.0}.get(
        accept, eng.spec_accept_rate())


@pytest.mark.parametrize("rows, fails", [(wc.WINDOW, False),
                                         (wc.WINDOW - 1, True)])
def test_a_ring_one_row_short_fails_under_the_verify_step(
        window_cfg, window_model, monkeypatch, rows, fails):
    """The engine attends before it writes, so a query needs the
    ``window - 1`` positions before it in the ring, and the verify step
    before it may have left ``spec_k`` rejected rows on the rows before
    those: ``window - 1 + spec_k`` rows are the least
    (``ops.attention_rows.ring_rows`` gives one more, in whole tiles;
    an engine that wrote before it attended would need that one more,
    and a ring of exactly ``window`` rows would fail there).  A ring of
    ``window - 1`` rows serves plain decoding right
    (``test_plain_decoding_stands_the_least_ring``) and fails this
    test under the verify step: the rejected draft's row at ``p + 1``
    displaces position ``p + 2 - window``, which the query at ``p + 1``
    still attends."""
    monkeypatch.setattr(
        wc.attention_rows, "ring_rows",
        lambda window, spec_k=0, itemsize=2: rows if spec_k else window)
    _got, eng, tokens_equal, rings_equal = _drafting_against_plain(
        window_cfg, window_model, "none")
    assert eng._ring_rows == [rows] * 4
    assert (not (tokens_equal and rings_equal)) == fails


def test_plain_decoding_stands_the_least_ring(window_cfg, window_model,
                                              monkeypatch):
    """Without a draft to reject, ``window - 1`` rows are enough."""
    monkeypatch.setattr(wc.attention_rows, "ring_rows",
                        lambda window, spec_k=0, itemsize=2: window - 1)
    net, arrays = window_model
    eng = wc._engine(net, spec_k=0)
    assert eng._ring_rows == [wc.WINDOW - 1] * 4
    p = wc._ids(window_cfg, 61, 5)
    got = wc._serve(eng, [p], 12)[0]
    trunk, _draft = wc._ref_logits(window_cfg, arrays, list(p) + got)
    assert got == list(trunk[60:60 + len(got)].argmax(-1))
