"""Sliding-window layers kept in rings beside full-attention layers kept
in pages (ISSUE 39): the zoo's ``HybridDecoderLM`` with ``"swa"`` and
``"gqa"`` mixers under QK-norm, rotary positions on the windowed layers
alone, a dense and four expert feed-forwards behind a sigmoid router of
one group, and a draft block of the ``"gqa"`` kind, served by the paged
engine.

Everything runs on the CPU at the configuration's ``rehearsal`` sizes
(a window of 16, chunks of 24), float32, seeded random weights:

* ``ops.attention_rows``' ring: positions by ``start`` alone, a chunk
  and a step against a full score matrix under the band, a chunk longer
  than the ring, rows that do not count left out;
* the model, uncached, against the plain reference
  (``benchmark/lib/reference/exaone_moe.py``), trunk and draft logits;
* prefill chunks then decode or verify steps through the engine
  against the reference's full forward, LOGITS compared, with prompts
  shorter than, equal to and several times the window, the rings and
  the pages read back row for row;
* (greedy tokens and ring rows with drafting on are those with it off,
  and a ring one row short of what the verify step needs fails exactly
  that: ``tests/test_self_draft.py``, with this file's helpers;)
* what the engine refuses and what it warns of; the spans' arguments
  and the counting rules against hand counts.
"""
import json
import os
import sys

import numpy as np
import pytest

from mxnet_tpu import generate, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import attention_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import programs  # noqa: E402
from benchmark.lib import weights  # noqa: E402
from benchmark.lib.reference import exaone_moe as ref  # noqa: E402

# float32 on both sides, the same weights: what is left is the order of
# the sums (a softmax over a ring and a chunk apart, XLA's own fusions)
TOL = 2e-5
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "k-exaone-236b-a23b.json")
WINDOW = 16


def small_config():
    with open(CONFIG) as f:
        published = json.load(f)
    small = dict(published, **published["rehearsal"])
    assert small["sliding_window"] == WINDOW
    return small


def build_model(cfg):
    net = programs.program(cfg).build_net(cfg)
    arrays = weights.make_params(cfg, 5)
    programs.set_weights(net, ref.param_specs(cfg), arrays)
    return net, arrays


@pytest.fixture(scope="module")
def cfg():
    return small_config()


@pytest.fixture(scope="module")
def model(cfg):
    return build_model(cfg)


def _engine(net, spec_k=0, **kw):
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
    serves every length (the model is causal)."""
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


def _rows_match(snap, theirs, n, ring=None):
    """A slot's snapshot against the reference's rows of ``n``
    positions: a paged layer whole, a windowed layer from the first
    position its ring still holds."""
    for mine, want in zip(snap["layers"], theirs):
        if isinstance(mine, dict):
            first = mine["first"]
            if ring is not None:
                assert first == max(0, n - ring)
            assert mine["rows"].shape == (n - first, want.shape[1])
            assert np.abs(mine["rows"] - want[first:n]).max() < TOL
        else:
            assert np.abs(mine - want[:n]).max() < TOL


# -- the ring, by itself ------------------------------------------------------

def test_ring_rows_and_positions_by_hand():
    # window + spec_k up to whole sublane tiles: 16 rows of bfloat16, 8
    # of float32
    assert attention_rows.ring_rows(128, 0, 2) == 128
    assert attention_rows.ring_rows(128, 1, 2) == 144
    assert attention_rows.ring_rows(128, 1, 4) == 136
    assert attention_rows.ring_rows(16, 1, 4) == 24
    import jax.numpy as jnp

    held = np.asarray(attention_rows.ring_positions(
        jnp.asarray([0, 3, 4, 5, 11], jnp.int32), 4))
    # before anything is written no row holds a position; at start 5 the
    # rows hold 4, 1, 2, 3: the largest position under 5 of each residue
    assert (held[0] < 0).all()
    assert list(held[1]) == [0, 1, 2, -1]
    assert list(held[2]) == [0, 1, 2, 3]
    assert list(held[3]) == [4, 1, 2, 3]
    assert list(held[4]) == [8, 9, 10, 7]


def _band_reference(q, k, v, window, H, Hkv):
    """Full score matrix under the band, float64, a head at a time."""
    T, dh = q.shape[0], q.shape[2]
    i = np.arange(T)
    ok = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < window)
    out = np.zeros((T, H, dh))
    for h in range(H):
        s = q[:, h].astype(np.float64) @ k[:, h // (H // Hkv)].T * dh ** -0.5
        s = np.where(ok, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = p / p.sum(-1, keepdims=True) @ v[:, h // (H // Hkv)]
    return out.reshape(T, H * dh)


@pytest.mark.parametrize("rows, chunks", [
    (8, [26]), (8, [3, 10, 3, 10]), (8, [1] * 26), (16, [13, 13]),
    (5, [10, 3, 3, 10])],
    ids=["one-chunk", "mixed", "steps", "ring-over-window", "ring-least"])
def test_window_attention_over_a_ring_is_the_band(rows, chunks):
    """A sequence of 26 positions in chunks of several sizes (longer than
    the ring, a step of one, padded by two rows that do not count),
    each attending the ring as the chunks before left it and then
    written to it: a window of 6 over the whole sequence.  The ring
    starts full of another sequence's rows, which positions mask.  5
    rows are the fewest a window of 6 can do with."""
    import jax.numpy as jnp

    H, Hkv, dh, window, T = 8, 2, 16, 6, 26
    w = Hkv * dh
    rng = np.random.default_rng(0)
    q = rng.normal(size=(T, H, dh)).astype(np.float32)
    k = rng.normal(size=(T, Hkv, dh)).astype(np.float32)
    v = rng.normal(size=(T, Hkv, dh)).astype(np.float32)
    want = _band_reference(q, k, v, window, H, Hkv)
    ring = jnp.asarray(rng.normal(size=(1, rows, 2 * w + 5)), jnp.float32)
    got, at = [], 0
    for C in chunks:
        def padded(a, width, fill):
            out = np.full((1, C + 2, width), fill, np.float32)
            out[0, :C] = a[at:at + C].reshape(C, width)
            return jnp.asarray(out)

        qc, kc, vc = padded(q, H * dh, 0), padded(k, w, 99), padded(v, w, 99)
        start = jnp.asarray([at], jnp.int32)
        out = attention_rows.window_attention_rows(
            qc, kc, vc, ring, start, window, H, Hkv)
        got.append(np.asarray(out)[0, :C])
        ring = attention_rows.ring_write(
            ring, jnp.concatenate([kc, vc, jnp.zeros((1, C + 2, 5))], -1),
            start, jnp.asarray([C], jnp.int32))
        at += C
    assert np.abs(np.concatenate(got) - want).max() < 1e-5
    # the ring holds the sequence's last rows, each where its position
    # says, and nothing of the padding
    held = np.asarray(attention_rows.ring_positions(
        jnp.asarray([T], jnp.int32), rows))[0]
    assert sorted(held) == list(range(T - rows, T))
    assert np.abs(np.asarray(ring)[0, :, :w]
                  - k[held].reshape(rows, w)).max() == 0


def test_a_ring_too_short_for_the_window_loses_a_row():
    """4 rows under a window of 6: the fifth position back is attended
    by the reference and gone from the ring."""
    with pytest.raises(AssertionError):
        test_window_attention_over_a_ring_is_the_band(4, [1] * 26)


# -- the model against the reference ------------------------------------------

def test_the_model_declares_a_layer_at_a_time_what_it_caches(cfg, model):
    net, _arrays = model
    c = net.config
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    assert net._mixers == ["swa", "swa", "swa", "swa", "gqa"]
    assert c["layer_caches"] == [{"window": (WINDOW, kv)}] * 4 + [
        {"rows": kv, "attended": "whole"}] * 2
    assert c["draft_layers"] == 1 and c["experts_held"] == (0, 4)


@pytest.mark.parametrize("length", [100])
def test_trunk_and_draft_logits_match_reference(cfg, model, length):
    """The whole model, uncached (every layer attends its own rows under
    its mask and a ring of one row that no query reaches), against the
    reference's blocks of query rows over a full score matrix."""
    import jax.numpy as jnp

    from mxnet_tpu.ndarray import NDArray

    net, arrays = model
    seq = _ids(cfg, length, 11)
    trunk, draft = _ref_logits(cfg, arrays, seq)
    toks = jnp.asarray(seq[None])
    zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), length, jnp.int32)
    logits, _new, extras = net.chunk_forward(toks, None, zero, full)
    assert np.abs(np.asarray(logits._data)[0] - trunk).max() < TOL
    follow = jnp.asarray(np.append(seq[1:], 0)[None])
    dlogits, rows, _ = net.draft_forward(extras["hidden"], follow, None,
                                         zero, full)
    assert np.abs(np.asarray(dlogits._data)[0, :-1] - draft[:-1]).max() < TOL
    assert rows.shape == (1, length, 64)
    assert isinstance(net(NDArray(toks.astype(jnp.float32))), NDArray)


def test_rotation_is_on_the_windowed_layers_alone(cfg, model):
    """A full layer carries no positions: its cached keys of a token are
    the same wherever the token stands, a windowed layer's are not.
    Read off the reference's rows and the engine's alike."""
    net, arrays = model
    seq = np.asarray([5, 9, 5, 9, 5, 9, 5, 9], np.int32)
    rows = _ref_rows(cfg, arrays, seq)
    kw = cfg["num_key_value_heads"] * cfg["head_dim"]
    # layer 0's input is the embedding alone: the same token, the same
    # normed key before rotation
    assert np.abs(rows[0][0, kw:] - rows[0][2, kw:]).max() < 1e-6   # values
    assert np.abs(rows[0][0, :kw] - rows[0][2, :kw]).max() > 1e-2   # keys
    eng = _engine(net)
    slot, _tok = eng.admit(seq)
    snap = eng.cached([slot])[0]
    _rows_match(snap, rows, len(seq))


# -- through the engine -------------------------------------------------------

@pytest.mark.parametrize("spec_k", [0, 1])
def test_chunks_then_steps_match_reference(cfg, model, spec_k):
    """Three sequences side by side, prompts of 7 (under the window), 16
    (the window) and 61 tokens (several windows; three chunks of 24,
    each longer than the window and than the ring at ``spec_k`` 0),
    then 12 decode calls of all slots: token-at-a-time steps launched
    ahead of their results, or verify steps of the model's own drafts.
    The trunk's LOGITS of every position served are the reference's
    full forward over the same tokens, and what the rings and the pages
    hold afterwards is what the reference keeps of those positions."""
    net, arrays = model
    eng = _engine(net, spec_k=spec_k)
    ring = attention_rows.ring_rows(WINDOW, spec_k, 4)
    assert ring == (24 if spec_k else 16) and eng._ring_rows == [ring] * 4
    assert eng.dispatch_shapes() == [(1, 24), (3, 1)] + [(3, 2)] * spec_k
    seqs, slots, logits = [], [], []
    for n, seed in ((7, 3), (16, 4), (61, 5)):
        p = _ids(cfg, n, seed)
        slot, tok = eng.admit(p)
        logits.append([eng.last_logits[0, (n - 1) % 24]])
        seqs.append(list(p) + [tok])
        slots.append(slot)
    for _ in range(12):
        out = eng.decode_step()
        step = eng.last_logits
        for sl, seq, lg in zip(slots, seqs, logits):
            if out[sl]:     # (the first call launches and reads nothing)
                lg.extend(step[sl, :len(out[sl])])
                seq.extend(out[sl])
    for sl, seq, lg, n in zip(slots, seqs, logits, (7, 16, 61)):
        trunk, draft = _ref_logits(cfg, arrays, seq)
        got = np.stack(lg)
        assert len(seq) - n >= 12 and len(got) == len(seq) - n
        assert np.abs(got - trunk[n - 1:len(seq) - 1]).max() < TOL
        assert seq[n:] == list(trunk[n - 1:len(seq) - 1].argmax(-1))
        snap = eng.cached([sl])[0]
        # (without speculation one step is still in flight: its token
        # is cached and not yet handed out)
        cached = snap["position"]
        assert cached == len(seq) - 1 + (0 if spec_k else 1)
        full = snap["tokens"] + ([snap["next_token"]] if spec_k else [])
        assert full[:len(seq)] == seq
        assert len(snap["layers"]) == (6 if spec_k else 5)
        _rows_match(snap, _ref_rows(cfg, arrays, full), cached,
                    ring=ring - spec_k)
        if spec_k:
            assert eng.drafted(sl) == list(
                draft[n - 1:len(seq) - 1].argmax(-1))
        eng.evict(sl, "length")


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


# -- what the engine refuses, warns of and writes on its spans ----------------

def test_layer_caches_validation_names_the_window(model, monkeypatch):
    net, _arrays = model
    sound = net.config["layer_caches"]
    for bad in ([{"ring": (16, 64)}] + sound[1:],
                [{"rows": 64, "attended": "blocks"}] + sound[1:],
                sound[:5] + [{"window": (16, 64)}]):   # a draft block's
        monkeypatch.setitem(net._cfg, "layer_caches", bad)
        with pytest.raises(MXNetError, match=r"'window': \(positions"):
            _engine(net, spec_k=1)


def test_prefix_sharing_is_switched_off_with_a_warning(model, caplog):
    net, _arrays = model
    with caplog.at_level("WARNING", logger="mxnet_tpu.generate"):
        eng = _engine(net, prefix_share=True)
    assert "windowed layers (layers [0, 1, 2, 3])" in caplog.text
    assert "rings" in caplog.text
    assert eng._prefix_share is False
    p = np.arange(40, dtype=np.int32)
    for _ in range(2):
        eng.admit(p)
        assert eng.last_prefix_hit_tokens == 0
    assert eng.prefix_hit_rate() is None
    assert eng.occupancy()["prefix_cached_pages"] == 0


def test_spans_and_counting_rules_by_hand(cfg, model):
    """``engine.pool``'s ``window_rows_bytes``, and ``cache_rows_attended``
    / ``cache_rows_held`` on ``engine.prefill`` and ``engine.decode``:
    the rows a slot that a dispatch's cached layers multiply, a windowed
    layer counted at its ring's rows, against what they would multiply
    were every one paged at a slot's capacity."""
    net, _arrays = model
    t0 = len(tracing.records())
    eng = _engine(net, spec_k=1)
    eng.admit(_ids(cfg, 30, 1))
    eng.decode_step()
    recs = tracing.records()[t0:]
    pool = [r for r in recs if r["name"] == "engine.pool"][-1]["args"]
    # four rings of 3 slots x 24 rows x 128 lanes of float32; one pool
    # of two layers x 49 pages x 8 rows x 128 lanes
    assert pool["window_rows"] == [24] * 4
    assert pool["window_rows_bytes"] == 4 * 3 * 24 * 128 * 4
    assert pool["latent_rows_bytes"] == 2 * 49 * 8 * 128 * 4
    assert pool["bytes"] == pool["window_rows_bytes"] \
        + pool["latent_rows_bytes"]
    want = {"cache_rows_attended": 4 * 24 + 2 * 128,
            "cache_rows_held": 6 * 128}
    chunks = [r["args"] for r in recs if r["name"] == "engine.prefill"]
    steps = [r["args"] for r in recs if r["name"] == "engine.decode"]
    assert len(chunks) == 2 and len(steps) == 1
    for args in chunks + steps:
        assert {k: args[k] for k in want} == want
    # without the draft block's layer (spec_k 0) and its extra ring row
    plain = _engine(net, spec_k=0)
    assert plain._cache_rows_attended(1, 50) == 4 * 16 + 128
    assert plain._cache_rows_held() == 5 * 128
    # the rule itself: a windowed layer multiplies its ring in every shape
    assert attention_rows.attended_cache_rows(512, 0, 144, "whole") == 144
    assert attention_rows.attended_cache_rows(512, 600, 9216) == 1024
    assert attention_rows.attended_cache_rows(512, 600, 9216, "whole") == 9216


def test_a_last_chunk_leaves_its_first_token_to_the_next_steps_read(cfg,
                                                                   model):
    """On the served path (``prefill_step``, not ``admit``) a speculating
    engine no longer waits for a prompt's first token where its last
    chunk is launched: the tick's verify step is queued behind the chunk
    without the new slot, one read-back brings both, and the slot joins
    the next step.  Token for token what ``admit`` by hand serves."""
    net, _arrays = model
    p, q = _ids(cfg, 40, 8), _ids(cfg, 9, 9)
    by_hand = _serve(_engine(net, spec_k=1), [p, q], 8)
    eng = _engine(net, spec_k=1)
    t0 = len(tracing.records())
    first = eng.admit_incremental(p)
    assert eng.prefill_step() is None               # 24 of 40
    assert eng.prefill_step() == (first, None)      # no token read here
    assert eng.active_slots() == [first] and eng.drafted(first) == []
    got = {first: []}
    out = eng.decode_step()         # nothing to launch: the first token
    assert list(out) == [first] and len(out[first]) == 1
    got[first] += out[first]
    second = eng.admit_incremental(q)
    assert eng.prefill_step() == (second, None)
    out = eng.decode_step()         # a step for `first`, `second`'s first
    assert len(out[first]) == 1 and len(out[second]) == 1
    got[first] += out[first]
    got[second] = list(out[second])
    for _ in range(6):
        for slot, toks in eng.decode_step().items():
            got[slot] += toks
    assert got[first] == by_hand[0][:len(got[first])]
    assert got[second] == by_hand[1][:len(got[second])]
    assert len(eng.drafted(second)) == len(got[second])
    steps = [r["args"] for r in tracing.records()[t0:]
             if r["name"] == "engine.decode"]
    assert [(a["slots"], a.get("firsts", 0)) for a in steps[:3]] == [
        (0, 1), (1, 1), (2, 0)]
    # a slot let go before its first token was read leaves nothing behind
    third = eng.admit_incremental(_ids(cfg, 5, 10))
    assert eng.prefill_step() == (third, None)
    eng.evict(third, "cancelled")
    assert third not in eng.decode_step()


def test_token_server_serves_the_model(cfg, model):
    net, arrays = model
    eng = _engine(net, spec_k=1, slots=2)
    prompts = [_ids(cfg, n, s) for n, s in ((40, 1), (9, 2), (25, 3))]
    with generate.TokenServer(eng, queue_depth=8, deadline_ms=0,
                              max_new_tokens=16) as server:
        futures = [server.submit(p, max_new_tokens=10) for p in prompts]
        results = [f.result(120) for f in futures]
    for p, res in zip(prompts, results):
        trunk, _draft = _ref_logits(cfg, arrays, list(p) + res["tokens"])
        assert len(res["tokens"]) == 10 and res["finish_reason"] == "length"
        assert res["tokens"] == list(trunk[len(p) - 1:-1].argmax(-1))


def test_block_diffusion_and_deeper_speculation(model, monkeypatch):
    net, _arrays = model
    with monkeypatch.context() as m:
        m.setitem(net._cfg, "block_length", 4)
        m.setitem(net._cfg, "mask_token_id", 1)
        with pytest.raises(MXNetError, match="windowed layers"):
            _engine(net)
    # n-gram speculation at a depth over 1: the ring takes spec_k rows more
    eng = _engine(net, spec_k=2)
    assert eng._ring_rows == [24] * 4 and eng.drafted(0) is None
