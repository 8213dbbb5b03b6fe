"""The hybrid decoder (ISSUE 33): KDA linear-attention layers with
per-slot recurrent state beside a paged latent (MLA) cache in one
``PagedGenerationEngine``, a sigmoid group-limited router with a shared
expert, held as one chip's share.

Everything runs on the CPU at the configuration's ``rehearsal`` sizes
(7 layers: six KDA, one MLA; one dense and six expert feed-forwards),
float32, seeded random weights, and compares logits:

* the package's model against the plain reference
  (``benchmark/lib/reference/bailing_hybrid.py``) on a full forward;
* prefill in chunks whose length is no multiple of the delta rule's
  sub-chunk, then token-by-token decode through the engine, against the
  reference's full forward;
* the chunkwise gated delta rule against the recurrence, outputs and
  state; absorbed MLA against expanded;
* the share test: the parts of the four shares, the shared expert
  counted once, add up to the uncut layer of the reference;
* the group-limited selection against a hand-worked case;
* the life cycle of per-slot state, and the two refusals.
"""
import json
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
from benchmark.lib.reference import bailing_hybrid as ref  # noqa: E402

# float32 on both sides, the same weights: what is left is the order of
# the sums (the chunkwise delta rule, the absorbed products, XLA's own
# fusions), a few units in the last place of logits of size 1
TOL = 2e-5


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash.json")) as f:
        c = json.load(f)
    c.update(c["rehearsal"])
    return c


@pytest.fixture(scope="module")
def model(cfg):
    net = programs.program(cfg).build_net(cfg)
    arrays = weights.make_params(cfg, 5)
    programs.set_weights(net, ref.param_specs(cfg), arrays)
    return net, arrays


def _engine(net, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("prefix_share", False)
    return generate.PagedGenerationEngine(
        net, cache_len=128, page_size=8, prefill_chunk=24, spec_k=0,
        sampling=generate.SamplingConfig(greedy=True), **kw)


@pytest.fixture(scope="module")
def engine(model):
    return _engine(model[0])


def _step(eng):
    """One decode step launched and read at once, by hand: a call of
    ``decode_step`` hands over the tokens of a step launched a call
    before, ``drain`` reads what is still in flight."""
    out = eng.decode_step()
    for more in eng.drain():
        for slot, toks in more.items():
            out[slot] = out.get(slot, []) + toks
    return out


def _ids(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], n).astype(np.int32)


_REF = {}


def _ref_logits(cfg, arrays, seq):
    """The reference's logits at every position of ``seq``: one
    compiled forward over 128 positions serves every length (the model
    is causal, so the padding after ``seq`` changes nothing before it)."""
    import jax

    if "forward" not in _REF:
        _REF["forward"] = jax.jit(lambda params, toks: ref.forward(
            cfg, params, toks))
    toks = np.zeros((1, 128), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(_REF["forward"](arrays, toks))[0, :len(seq)]


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("length", [40, 100])
def test_full_forward_matches_reference(cfg, model, length):
    """The whole model, uncached: KDA by the chunkwise rule (one
    sub-chunk, and two with padding) against the reference's
    token-by-token scan, MLA absorbed against the reference's expanded
    attention, the expert layer's share against the reference's."""
    net, arrays = model
    toks = np.stack([_ids(cfg, length, 1), _ids(cfg, length, 2)])
    got = net(nd.array(toks)).asnumpy()
    for b in range(2):
        want = _ref_logits(cfg, arrays, toks[b])
        assert np.abs(got[b] - want).max() < TOL
        assert np.abs(want).max() > 0.1


def test_chunked_prefill_then_decode_matches_reference(cfg, model, engine):
    """Three sequences side by side: prompts of 7, 50 and 61 tokens in
    chunks of 24 (no multiple of the delta rule's 64; the last chunk of
    each is padded), then 12 decode steps of all slots: every position's
    logits are the reference's full forward over the same tokens."""
    net, arrays = model
    eng = engine
    seqs, slots, logits = [], [], []
    for n, seed in ((50, 3), (7, 4), (61, 5)):
        p = _ids(cfg, n, seed)
        slot, tok = eng.admit(p)
        logits.append([eng.last_logits[0, (n - 1) % 24]])
        seqs.append(list(p) + [tok])
        slots.append(slot)
    for _ in range(12):
        out = _step(eng)
        step = eng.last_logits             # of the step read
        for sl, seq, lg in zip(slots, seqs, logits):
            lg.append(step[sl, 0])
            seq.extend(out[sl])
    for sl, seq, lg in zip(slots, seqs, logits):
        want = _ref_logits(cfg, arrays, seq[:-1])
        n = len(seq) - 1 - 12
        got = np.stack(lg)
        assert np.abs(got - want[n - 1:]).max() < TOL
        assert seq[n:] == list(want[n - 1:].argmax(-1))
        eng.evict(sl, "length")


# -- the parts --------------------------------------------------------------

def _delta_inputs(B, T, H, dk, dv, seed):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    # decays over the model's whole range, [-5, 0] a channel a token
    g = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (B, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    s0 = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("T,valid", [(128, None), (150, None), (24, None),
                                     (128, (70, 0))])
def test_chunkwise_delta_rule_matches_recurrence(T, valid):
    """Outputs and final state of the chunkwise form (sub-chunks of 64,
    the triangular system solved once) against the recurrence a token,
    both float32: 1e-5 of values of size 1 is the order of the sums.
    With ``valid`` the positions past it leave the state alone."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import (gated_delta_chunk,
                                           gated_delta_step)

    q, k, v, g, beta, s0 = _delta_inputs(2, T, 3, 16, 8, T)
    count = None if valid is None else jnp.asarray(valid, jnp.int32)
    o_c, s_c = gated_delta_chunk(q, k, v, g, beta, s0, count)
    s, outs = s0, []
    for t in range(T):
        o, s_t = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], s)
        if count is not None:
            s_t = jnp.where((t < count)[:, None, None, None], s_t, s)
        s = s_t
        outs.append(o)
    o_r = np.asarray(jnp.stack(outs, 1))
    o_c = np.asarray(o_c)
    if valid is not None:
        live = np.arange(T)[None, :] < np.asarray(valid)[:, None]
        o_r, o_c = o_r * live[..., None, None], o_c * live[..., None, None]
        # a row with nothing valid keeps its state to the bit
        np.testing.assert_array_equal(np.asarray(s_c)[1], np.asarray(s0)[1])
    assert np.abs(o_c - o_r).max() < 1e-5
    assert np.abs(np.asarray(s_c) - np.asarray(s)).max() < 1e-5
    assert np.abs(o_r).max() > 0.05


@pytest.mark.parametrize("cos, beta", [(0.5, 0.5), (0.7, 0.8),
                                       (0.95, 0.99)])
def test_chunkwise_delta_rule_stands_keys_that_resemble_each_other(cos,
                                                                   beta):
    """Keys that share a component (a stream with a common part under a
    SiLU), written strongly and never forgotten: the triangular system
    of a sub-chunk is then far from the identity.  Its inverse by blocks
    stays exact to float32; the Neumann series it replaced read 3e-3 at
    the first of these cases and infinity at the second, and on the chip
    a KDA layer's state came out wrong by its own size on two seeds of
    six (PERF.md, PR 33)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_chunk

    rng = np.random.default_rng(3)
    B, T, H, dk, dv = 1, 200, 2, 32, 16
    k = np.sqrt(cos / (1 - cos)) * rng.normal(size=(1, 1, H, dk)) \
        + rng.normal(size=(B, T, H, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(B, T, H, dk)) / dk
    v = rng.normal(size=(B, T, H, dv))
    b = np.clip(beta + 0.05 * rng.normal(size=(B, T, H)), 0, 1)
    args = [jnp.asarray(a, jnp.float32)
            for a in (q, k, v, np.zeros_like(k), b)]
    o_ref, s_ref = ref.delta_rule(*args)
    o, s = gated_delta_chunk(*args, jnp.zeros((B, H, dk, dv), jnp.float32))
    assert np.abs(np.asarray(s) - np.asarray(s_ref)).max() < 1e-5
    assert np.abs(np.asarray(o) - np.asarray(o_ref)).max() < 1e-5
    assert np.abs(np.asarray(s_ref)).max() > 0.1


def test_delta_rule_matches_the_reference_recurrence():
    """The package's one-token form against the reference's scan (which
    imports nothing of the program)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_step

    q, k, v, g, beta, _s0 = _delta_inputs(2, 20, 3, 16, 8, 9)
    want_o, want_s = ref.delta_rule(q, k, v, g, beta)
    s = jnp.zeros_like(want_s)
    for t in range(20):
        o, s = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], s)
        assert np.abs(np.asarray(o) - np.asarray(want_o[:, t])).max() < 1e-6
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 1e-6


def test_absorbed_mla_matches_expanded(cfg, model):
    """The MLA layer alone, 24 positions from nothing: the program's
    absorbed products (queries taken to the latent space, the rows read
    as they lie) against the reference's attention over keys and values
    expanded a head; and a chunk of 8 against 40 cached rows, zero lanes
    after them as the pool keeps them, against the same 48 positions
    from nothing."""
    import jax
    import jax.numpy as jnp

    net, arrays = model
    li = net._mixers.index("mla")
    p = [q.data()._data for q in net._layers[li][1]]
    names = [n for n, _s, _k in ref.param_specs(cfg)]
    at = names.index("h%d_proj_q_weight" % li)
    D, w = cfg["hidden_size"], cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    n = jax.random.normal(jax.random.key(0), (2, 48, D))
    want = np.asarray(ref.mla(cfg, n, arrays[at:at + 6])[0])
    zero = jnp.zeros((2,), jnp.int32)
    pos = jnp.arange(48)[None, :] + zero[:, None]
    got, rows = net._mla(n, p, None, zero, pos)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(want).max() > 1e-3 and rows.shape == (2, 48, w)
    cached = jnp.pad(rows, ((0, 0), (0, 16), (0, 128 - w)))  # 64 rows
    start = jnp.full((2,), 40, jnp.int32)
    got, new = net._mla(n[:, 40:], p, cached, start, pos[:, 40:])
    assert np.abs(np.asarray(got) - want[:, 40:]).max() < 1e-5
    assert np.abs(np.asarray(new) - np.asarray(rows[:, 40:])).max() < 1e-5


# the blocked form of a chunk's cached attention (ISSUE 38): 64 cached
# rows in blocks of 16, chunks of 16 queries
BLOCK, HELD, CHUNK = 16, 64, 16


@pytest.fixture
def mla_layer(cfg, model, monkeypatch):
    """The rehearsal model's MLA layer alone with 80 positions' rows
    made from nothing, and ``attend(start, cached, blocks)``: a chunk of
    16 queries a slot at ``start`` (2,) against ``cached`` (2, 64, 128),
    in blocks of 16 rows or over all 64."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention_rows

    net, _arrays = model
    li = net._mixers.index("mla")
    p = [q.data()._data for q in net._layers[li][1]]
    w = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    n = jax.random.normal(jax.random.key(3), (2, HELD + CHUNK,
                                              cfg["hidden_size"]))
    zero = jnp.zeros((2,), jnp.int32)
    _out, rows = net._mla(n, p, None, zero,
                          jnp.arange(HELD + CHUNK)[None, :] + zero[:, None])
    cached = jnp.pad(rows[:, :HELD], ((0, 0), (0, 0), (0, 128 - w)))
    monkeypatch.setattr(attention_rows, "CACHE_BLOCK_ROWS", BLOCK)

    def attend(start, cached, blocks):
        monkeypatch.setattr(
            attention_rows, "BLOCKED_CACHE_MIN_QUERY_POSITIONS",
            CHUNK if blocks else CHUNK + 1)
        assert attention_rows.cached_rows_in(CHUNK) == \
            ("blocks" if blocks else "whole")
        start = jnp.asarray(start, jnp.int32)
        pos = start[:, None] + jnp.arange(CHUNK, dtype=jnp.int32)[None, :]
        # (the queries of positions 0..15 wherever the chunk is placed:
        # the two forms are held to each other, not to a sequence)
        return np.asarray(net._mla(n[:, :CHUNK], p, cached, start, pos)[0])

    return attend, cached


@pytest.mark.parametrize("start", [
    (0, 0), (1, 1), (BLOCK - 1, BLOCK - 1), (BLOCK, BLOCK),
    (BLOCK + 1, BLOCK + 1), (HELD - CHUNK, HELD - CHUNK),
    (BLOCK + 1, 3), (0, HELD - CHUNK), (HELD, 2 * BLOCK)],
    ids=lambda s: "start_%d_%d" % s)
def test_a_chunk_attends_in_blocks_what_it_attends_whole(mla_layer, start):
    """``_mla`` over blocks of cached rows up to the longest ``start``
    of the dispatch (ISSUE 38) against ``_mla`` over all the rows a slot
    holds, float32: one mathematics in another order of summation.  Both
    slots at one ``start`` (nothing cached; one row; a block less one, a
    whole block, a block and one; all a slot holds less the chunk) and
    two slots at different ones (the dispatch is bound by its longest,
    the shorter one masked in every block).  The rows from the end of
    the bound's last block on are never read: filled with NaN they leave
    the result finite and the same."""
    import jax.numpy as jnp

    attend, cached = mla_layer
    whole = attend(start, cached, blocks=False)
    blocked = attend(start, cached, blocks=True)
    assert np.abs(whole).max() > 1e-3
    assert np.abs(blocked - whole).max() < 1e-6
    bound = -(-max(start) // BLOCK) * BLOCK
    poisoned = attend(start, cached.at[:, bound:].set(jnp.nan),
                      blocks=True)
    assert np.isfinite(poisoned).all()
    assert np.array_equal(poisoned, blocked)
    if bound < HELD:    # (the whole form reads them, and masks too late)
        assert np.isnan(attend(start, cached.at[:, bound:].set(jnp.nan),
                               blocks=False)).any()


@pytest.mark.parametrize("held,block", [(40, 16), (24, 512)],
                         ids=["ragged_last_block", "cache_under_a_block"])
def test_blocks_of_a_cache_that_is_not_whole_blocks(mla_layer, monkeypatch,
                                                    held, block):
    """A cache of 40 rows in blocks of 16 (the last block is read ending
    at row 40, its rows under 32 masked: no row counts twice) and one of
    24 rows under a block of 512 (one block of all 24)."""
    from mxnet_tpu.ops import attention_rows

    attend, cached = mla_layer
    monkeypatch.setattr(attention_rows, "CACHE_BLOCK_ROWS", block)
    assert attention_rows.cache_block_rows(held) == min(block, held)
    for start in ((held, 5), (held - 3, held - 9), (17, 0)):
        whole = attend(start, cached[:, :held], blocks=False)
        blocked = attend(start, cached[:, :held], blocks=True)
        assert np.abs(blocked - whole).max() < 1e-6


def test_only_a_chunk_shape_lowers_to_a_loop():
    """Which dispatches attend in blocks is decided by the static shape
    alone (ISSUE 38): of the three programs of an engine over a stack of
    five MLA layers and a draft block of one more, the chunk's ``(1,
    24)`` holds one ``while`` an MLA layer and the decode step's ``(3,
    1)`` and the verify step's ``(3, 2)`` hold none; the engine's count
    of the rows a chunk multiplies follows the same rule."""
    from mxnet_tpu.ops import attention_rows

    _fam, small = _deepseek_small()
    net = programs.program(small).build_net(small)
    net.initialize()
    eng = generate.PagedGenerationEngine(
        net, slots=3, cache_len=128, page_size=8, prefill_chunk=24,
        spec_k=1, prefix_share=False,
        sampling=generate.SamplingConfig(greedy=True))
    loops = {
        shape: eng._jit_chunk.lower(
            *eng._dispatch_args(shape)).as_text().count("stablehlo.while")
        for shape in eng.dispatch_shapes()}
    assert loops == {(1, 24): len(net._mixers) + 1, (3, 1): 0, (3, 2): 0}
    assert [attention_rows.cached_rows_in(c) for _b, c in loops] == \
        ["blocks", "whole", "whole"]
    # a slot holds 128 rows, under one block of 512: a chunk that has
    # rows to attend multiplies them all, a step always does; counted
    # over the six layers that cache rows (ISSUE 39)
    assert eng._cache_rows_attended(24, 0) == 0
    assert eng._cache_rows_attended(24, 48) == 6 * 128
    assert eng._cache_rows_attended(2, 0) == 6 * 128
    assert eng._cache_rows_held() == 6 * 128


def _deepseek_small():
    """The ``deepseek_v3`` reference and its configuration at the
    rehearsal's widths, its router as published (256 experts, 8 groups
    of which 4, 8 a token, 2.5)."""
    from benchmark.lib.reference import deepseek_v3

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gigachat3.1-702b-a36b.json")) as f:
        c = json.load(f)
    small = dict(c, **c["rehearsal"])
    small.update({k: c[k] for k in ("n_group", "topk_group",
                                    "num_experts_per_tok")})
    return deepseek_v3, small


def _exaone_small():
    """The ``exaone_moe`` reference and its configuration at the
    rehearsal's widths, its router as published (128 experts in one
    group, 8 a token, 2.5)."""
    from benchmark.lib.reference import exaone_moe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        c = json.load(f)
    small = dict(c, **c["rehearsal"])
    small.update({k: c[k] for k in ("n_group", "topk_group",
                                    "num_experts_per_tok")})
    return exaone_moe, small


@pytest.mark.parametrize("family, E, share", [
    ("bailing_hybrid", 16, 4), ("deepseek_v3", 256, 16),
    ("exaone_moe", 128, 16)])
def test_the_shares_add_up_to_the_uncut_layer(cfg, family, E, share):
    """The share test.  An expert layer of ``E`` routed experts held as
    ``E / share`` shares of ``share`` (Ling's 16 as 4 of 4 at the
    rehearsal's router; GigaChat's 256 as the 16 chips' 16 of 16 at the
    published router, ISSUE 35; K-EXAONE's 128 as the 8 chips' 16 of 16
    at the published router of one group, ISSUE 39): each share's part
    through
    ``routed_experts`` (the router over all of them every time), summed,
    plus the shared expert ONCE, is the uncut layer of the family's
    reference; and one share's part is the reference's for the same
    ``held``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.moe import routed_experts, sigmoid_group_select

    if family == "bailing_hybrid":
        fam, whole = ref, dict(cfg, num_experts=E, experts_first=0)
    elif family == "exaone_moe":
        fam, cfg = _exaone_small()
        whole = dict(cfg, num_experts=E, experts_first=0,
                     published={"num_experts": E})
    else:
        fam, cfg = _deepseek_small()
        whole = dict(cfg, n_routed_experts=E, experts_first=0,
                     published={"n_routed_experts": E})
    F, D = cfg["moe_intermediate_size"], cfg["hidden_size"]
    ks = jax.random.split(jax.random.key(2), 9)
    wr = 0.5 * jax.random.normal(ks[0], (E, D))
    bias = 0.1 * jax.random.normal(ks[1], (E,))
    wg, wu = (0.1 * jax.random.normal(k, (D, E * F)) for k in ks[2:4])
    wd = 0.1 * jax.random.normal(ks[4], (E * F, D))
    sg, su = (0.1 * jax.random.normal(k, (F, D)) for k in ks[5:7])
    sd = 0.1 * jax.random.normal(ks[7], (D, F))
    x = jax.random.normal(ks[8], (64, D))
    want = fam.experts(whole, x, (wr, bias, wg, wu, wd, sg, su, sd))
    select = sigmoid_group_select(
        bias, cfg["n_group"], cfg["topk_group"],
        cfg["routed_scaling_factor"], cfg["norm_topk_prob"])
    total = fam._gated_mlp(x, sg, su, sd, None)     # counted once
    seen = 0
    for first in range(0, E, share):
        here = slice(first * F, (first + share) * F)
        part, counts = routed_experts(
            x, wr.T, wg[:, here], wu[:, here], wd[here],
            cfg["num_experts_per_tok"], F, first=first, select=select)
        assert int(counts.sum()) == 64 * cfg["num_experts_per_tok"]
        seen += int(counts[first:first + share].sum())
        one = fam.experts(whole, x, (wr, bias, wg[:, here], wu[:, here],
                                     wd[here], sg, su, sd),
                          held=(first, share), shared=False)
        assert np.abs(np.asarray(part) - np.asarray(one)).max() < 1e-5
        total = total + part
    assert seen == 64 * cfg["num_experts_per_tok"]
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.1
    del jnp


def test_group_limited_selection_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts a token,
    scaling 2.5.  Scores s = (.9 .15 | .8 .2 | .6 .5 | .3 .3): the
    groups score 1.05, 1.0, 1.1, 0.6, so groups 2 and 0 are kept and
    experts 0 and 4 chosen, weighted .9/1.5 and .6/1.5.  A bias of +0.2
    on experts 2 and 3 lifts group 1 to 1.4: groups 1 and 2 are kept,
    experts 2 (1.0 biased) and 4 chosen, and their weights are their
    UNBIASED scores': .8/1.4 and .6/1.4, not 1.0/1.6."""
    import jax.numpy as jnp

    from mxnet_tpu.parallel.moe import sigmoid_group_select

    s = np.asarray([[.9, .15, .8, .2, .6, .5, .3, .3]], np.float32)
    logits = jnp.asarray(np.log(s / (1 - s)))

    def chosen(bias):
        w, i = sigmoid_group_select(jnp.asarray(bias, jnp.float32), 4, 2,
                                    2.5)(logits, 2)
        order = np.argsort(np.asarray(i)[0])
        return np.asarray(i)[0][order].tolist(), np.asarray(w)[0][order]

    who, w = chosen(np.zeros(8))
    assert who == [0, 4]
    np.testing.assert_allclose(w, [2.5 * .9 / 1.5, 2.5 * .6 / 1.5], 1e-5)
    who, w = chosen([0, 0, .2, .2, 0, 0, 0, 0])
    assert who == [2, 4]
    np.testing.assert_allclose(w, [2.5 * .8 / 1.4, 2.5 * .6 / 1.4], 1e-5)
    # a bias inside the kept groups moves who is chosen and nothing else
    who, w = chosen([0, .8, 0, 0, 0, 0, 0, 0])
    assert who == [0, 1]
    np.testing.assert_allclose(w, [2.5 * .9 / 1.05, 2.5 * .15 / 1.05], 1e-5)
    # the reference's router says the same
    cfg = {"n_group": 4, "topk_group": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "num_experts_per_tok": 2,
           "num_attention_heads": 1, "head_dim": 1, "hidden_size": 1,
           "short_conv_kernel_size": 1, "qk_nope_head_dim": 1,
           "qk_rope_head_dim": 1, "kv_lora_rank": 1, "v_head_dim": 1,
           "intermediate_size": 1, "moe_intermediate_size": 1,
           "moe_shared_expert_intermediate_size": 1, "num_experts": 8,
           "experts_first": 0, "vocab_size": 1,
           "published": {"num_experts": 8}}
    weight, picks = ref.route(cfg, logits,
                              jnp.asarray([0, 0, .2, .2, 0, 0, 0, 0.]))
    assert sorted(np.asarray(picks)[0].tolist()) == [2, 4]
    np.testing.assert_allclose(np.asarray(weight)[0, [2, 4]],
                               [2.5 * .8 / 1.4, 2.5 * .6 / 1.4], 1e-5)


def test_one_group_selects_a_plain_top_k():
    """``n_group`` 1 and ``topk_group`` 1 (K-EXAONE's router, ISSUE
    39): the one group is always kept, so group limiting selects every
    expert and the choice is the 8 highest ``s + bias`` of all 128,
    weighted by their unbiased scores over their sum, times 2.5."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.moe import sigmoid_group_select

    k1, k2 = jax.random.split(jax.random.key(4))
    logits = 2.0 * jax.random.normal(k1, (64, 128))
    bias = 0.3 * jax.random.normal(k2, (128,))
    w, i = sigmoid_group_select(bias, 1, 1, 2.5)(logits, 8)
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    plain = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :8]
    assert (np.sort(np.asarray(i), 1) == np.sort(plain, 1)).all()
    picked = np.take_along_axis(s, np.asarray(i), 1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(1, keepdims=True), 1e-5)
    # the reference's router says the same
    fam, small = _exaone_small()
    weight, picks = fam.route(small, logits, bias)
    assert (np.sort(np.asarray(picks), 1) == np.sort(plain, 1)).all()
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weight), np.asarray(i), 1),
        np.asarray(w), 1e-5)
    del jnp


# -- the life cycle of per-slot state ----------------------------------------

def _state(eng):
    return [np.asarray(a) for a in eng._state]


def test_state_life_cycle(cfg, model):
    """A chunk for one slot leaves the others' state bit-identical; a
    decode step leaves an idle slot's state as it was and advances every
    active one's; a reused slot starts from zero: its answer is that of
    a fresh engine, whatever the slot held before."""
    net, _arrays = model
    eng = _engine(net)
    # 6 KDA layers of (S, tail) a slot; the MLA layer has one pool
    assert len(eng._state) == 12 and eng._pool_v is None
    assert eng._state[0].shape == (3, 4, 16, 16)
    assert eng._state[0].dtype == np.float32
    assert eng._state[1].shape == (3, 3, 3 * 4 * 16)
    # 49 pages of 8 rows; 32 + 8 values a row, in 128 lanes
    assert eng.pool_shape == (49 * 8, 128)
    a, tok_a = eng.admit(_ids(cfg, 30, 6))
    before = _state(eng)
    assert any(np.abs(s[a]).max() > 0 for s in before)
    b = eng.admit_incremental(_ids(cfg, 40, 7))
    assert eng.prefill_step(slot=b) is None           # 24 of 40
    after = _state(eng)
    idle = ({0, 1, 2} - {a, b}).pop()
    for x, y in zip(before, after):
        np.testing.assert_array_equal(x[a], y[a])
        np.testing.assert_array_equal(x[idle], y[idle])
        assert np.all(y[idle] == 0)
    assert any(np.abs(y[b]).max() > 0 for y in after)
    # b is mid-prefill, so a decode step advances a alone
    eng.decode_step()
    stepped = _state(eng)
    for x, y in zip(after, stepped):
        np.testing.assert_array_equal(x[b], y[b])
        np.testing.assert_array_equal(x[idle], y[idle])
    assert any(np.abs(x[a] - y[a]).max() > 0
               for x, y in zip(after, stepped))
    # the slot's next occupant starts from zero (a's step is still in
    # flight: its token is thrown away with its occupant)
    assert len(eng._inflight) == eng.steps_ahead
    eng.evict(a, "length")
    eng.evict(b, "length")
    prompt = _ids(cfg, 33, 8)
    again, tok = eng.admit(prompt)
    assert again == b                      # LIFO: the slot b left dirty
    reused = [_step(eng)[again][0] for _ in range(6)]
    fresh_eng = _engine(net, slots=1)
    slot, tok_f = fresh_eng.admit(prompt)
    fresh = [_step(fresh_eng)[slot][0] for _ in range(6)]
    assert (tok, reused) == (tok_f, fresh)


def test_cached_reads_what_the_reference_keeps(cfg, model):
    """``PagedGenerationEngine.cached``: after prompts of 50 and 9
    tokens in chunks of 24 and five decode steps, the last of them
    still in flight (its ids are read for the answer and stay for
    ``decode_step`` to hand out), a slot's caches (every
    KDA layer's ``S`` and convolution tail, the MLA layer's latent rows
    as they lie in its pages) are what the plain reference's ``caches``
    keeps of the same ids, a token a step.  A slot mid-prefill reads as
    the positions its chunks have filled."""
    import jax

    net, arrays = model
    eng = _engine(net)
    slots = [eng.admit(_ids(cfg, n, seed))[0] for n, seed in
             ((50, 21), (9, 22))]
    for _ in range(5):
        eng.decode_step()
    assert len(eng._inflight) == eng.steps_ahead
    late = eng.admit_incremental(_ids(cfg, 70, 23))
    eng.prefill_step(slot=late)
    kept = jax.jit(lambda params, toks, n: ref.caches(cfg, params, toks, n))
    for snap, n in zip(eng.cached(slots + [late]), (55, 14, 24)):
        assert snap["position"] == n == len(snap["tokens"])
        toks = np.zeros((1, 128), np.int32)
        toks[0, :n] = snap["tokens"]
        want = jax.device_get(kept(arrays, toks, np.int32(n)))
        kinds = [isinstance(c, tuple) for c in snap["layers"]]
        assert kinds == [True] * 4 + [False] + [True] * 2
        for mine, theirs in zip(snap["layers"], want):
            if isinstance(mine, tuple):
                for a, b in zip(mine, theirs):
                    assert a.shape == b.shape[1:]
                    assert np.abs(a - b[0]).max() < TOL
                assert np.abs(mine[0]).max() > 1e-3
            else:
                assert mine.shape == (n, cfg["kv_lora_rank"]
                                      + cfg["qk_rope_head_dim"])
                assert np.abs(mine - theirs[0, :n]).max() < TOL
    # the step in flight is still there to be read, with its tokens
    assert len(eng._inflight) == eng.steps_ahead
    (last,) = eng.drain()
    assert sorted(last) == sorted(slots) and all(
        len(t) == 1 for t in last.values())


@pytest.mark.parametrize("depth", [1, 3])
def test_launched_ahead_leaves_the_caches_of_read_then_launch(cfg, model,
                                                             depth):
    """Steps launched ahead of their results (ISSUE 34; ``depth`` left
    unread a call) give the tokens, and leave every layer's state and
    the latent rows, that reading each step before the next launch
    (depth 0) gives: two prompts, the second joining after three steps
    with its first token fed on the device, nine steps in all, and
    ``cached`` asked with ``depth`` steps still in flight."""
    net, _arrays = model

    def run(ahead):
        eng = _engine(net)
        eng.steps_ahead = ahead
        slots, got = [], {}
        for call in range(9):
            if call in (0, 3):
                slot = eng.admit_incremental(_ids(cfg, 31 + call, 40 + call))
                while eng.pending_prefill():
                    eng.prefill_step()
                slots.append(slot)
                got[slot] = []
            for slot, toks in eng.decode_step().items():
                got[slot].extend(toks)
        assert len(eng._inflight) == ahead
        snaps = eng.cached(slots)
        assert len(eng._inflight) == ahead
        for more in eng.drain():
            for slot, toks in more.items():
                got[slot].extend(toks)
        return [got[s] for s in slots], snaps

    want_toks, want = run(0)
    got_toks, got = run(depth)
    assert got_toks == want_toks and [len(t) for t in got_toks] == [10, 7]
    for mine, theirs, n in zip(got, want, (31 + 9, 34 + 6)):
        assert mine["position"] == theirs["position"] == n
        assert mine["tokens"] == theirs["tokens"] and len(mine["tokens"]) == n
        for a, b in zip(mine["layers"], theirs["layers"]):
            for x, y in zip(a if isinstance(a, tuple) else (a,),
                            b if isinstance(b, tuple) else (b,)):
                np.testing.assert_array_equal(x, y)


def test_the_two_refusals(model, caplog):
    """Speculation would have to roll state back: an error.  Prefix
    attachment is not offered: asked for, it is turned off with a
    warning, no page is registered and the hit rate stays unset."""
    net, _arrays = model
    with pytest.raises(mx.MXNetError, match="roll"):
        generate.PagedGenerationEngine(
            net, slots=2, cache_len=64, page_size=8, prefill_chunk=16,
            spec_k=2, sampling=generate.SamplingConfig(greedy=True))
    with caplog.at_level("WARNING", logger="mxnet_tpu.generate"):
        eng = _engine(net, slots=2, prefix_share=True)
    assert "prefix sharing is not offered" in caplog.text
    prompt = np.arange(40, dtype=np.int32)
    for _ in range(2):
        slot, _tok = eng.admit(prompt)
        assert eng.last_prefix_hit_tokens == 0
        eng.evict(slot, "length")
    assert eng.prefix_hit_rate() is None
    assert eng.occupancy()["prefix_cached_pages"] == 0


def test_spans_and_counter_of_state_and_experts(cfg, model):
    """``engine.pool`` names the kinds allocated; ``engine.prefill`` and
    ``engine.decode`` say how many rows of state the program advanced;
    the decode span carries the routing of the step it read; every
    sequence's first chunk counts one state reset."""
    from mxnet_tpu import telemetry

    net, _arrays = model
    t0 = len(tracing.records())
    was_on = telemetry.enabled()
    telemetry.enable()
    resets = telemetry.DECODE_STATE_RESETS.value()
    eng = _engine(net)
    for n, seed in ((30, 1), (9, 2)):
        eng.admit(_ids(cfg, n, seed))
    eng.decode_step()
    eng.decode_step()           # reads the first step, and its routing
    if not was_on:
        telemetry.disable()
    recs = tracing.records()[t0:]
    pool = [r for r in recs if r["name"] == "engine.pool"][-1]["args"]
    assert pool["latent_rows_bytes"] == 49 * 8 * 128 * 4
    assert pool["state_bytes"] == 6 * 3 * (4 * 16 * 16 + 3 * 192) * 4
    assert pool["bytes"] == pool["latent_rows_bytes"] + pool["state_bytes"]
    chunks = [r["args"] for r in recs if r["name"] == "engine.prefill"]
    assert len(chunks) == 3 and all(c["state_slots"] == 1 for c in chunks)
    # how much of a slot's cache a chunk's attention multiplies (ISSUE
    # 38): whole blocks up to the rows its sequence has written, here one
    # block of all a slot holds (128 rows under a block of 512) for the
    # second chunk of the prompt of 30 and none for a first chunk
    assert [(c["filled"], c["cache_rows_attended"]) for c in chunks] == [
        (0, 0), (24, 128), (0, 0)]
    assert all(c["cache_rows_held"] == 128 for c in chunks)
    assert telemetry.DECODE_STATE_RESETS.value() - resets == 2
    step = [r for r in recs if r["name"] == "engine.decode"][-1]["args"]
    assert step["slots"] == step["state_slots"] == 2
    assert step["attn"] == "rows"
    # 3 rows (one of them idle) x 4 choices in each of 6 expert layers,
    # of which the 4 held experts of 16 take their share
    assert step["expert_rows_all"] == 6 * 3 * cfg["num_experts_per_tok"]
    assert 0 <= step["expert_rows_held"] <= step["expert_rows_all"]
    # the (layer, held expert) pairs some row chose: at most the 6 x 4
    # held, at most one a pair that fell on them
    assert 0 < step["experts_held_touched"] <= min(
        6 * cfg["num_experts"], step["expert_rows_held"])
    assert step["expert_load_max"] >= 1
    assert step["expert_load_mean"] == pytest.approx(
        3 * cfg["num_experts_per_tok"] / 16)
    # how far the grouped product engages (ISSUE 36): every touched
    # (layer, held expert) pair is one row tile of 16 at these counts,
    # where the dense product multiplied 3 rows by 4 held in 6 layers
    assert step["expert_rows_multiplied"] == \
        16 * step["experts_held_touched"]
    assert step["expert_rows_dense"] == 6 * 3 * 4


def test_token_server_serves_the_hybrid_model(cfg, model):
    """``TokenServer`` over the engine: two requests at once, each the
    reference's greedy continuation."""
    net, arrays = model
    eng = _engine(net, slots=2)
    with generate.TokenServer(eng, max_new_tokens=6) as server:
        prompts = [_ids(cfg, 35, 11), _ids(cfg, 12, 12)]
        futures = [server.submit(p) for p in prompts]
        for p, f in zip(prompts, futures):
            out = f.result(120)["tokens"]
            seq = list(p) + out
            want = _ref_logits(cfg, arrays, seq[:-1])
            assert out == list(want[len(p) - 1:].argmax(-1))
