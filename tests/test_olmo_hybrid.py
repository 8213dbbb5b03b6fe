"""Olmo-Hybrid-7B's layers in ``HybridDecoderLM``: Gated DeltaNet
(``"gdn"``: a decay a head, ``beta`` in [0, 2]) beside full multi-head
attention under a QK-norm over the whole projection, in OLMo 2's
post-norm blocks, served by ``PagedGenerationEngine`` with per-slot
state and paged ``[K | V]`` rows side by side.

Everything runs on the CPU at the configuration's ``rehearsal`` sizes
(d_model 64, 4 heads, keys of 12 and values of 24, two periods of three
GDN layers and one full one, vocabulary 512), float32, seeded random
weights, against the plain reference
(``benchmark/lib/reference/olmo_hybrid.py``):

* the model's full forward, and prefill in chunks then decode through
  the engine, logits against the reference's full forward; the
  engine's cached GDN states and ``[K | V]`` rows against the
  reference's ``caches``;
* the chunkwise delta rule with a decay a head against its one-token
  form and against the reference's recurrence, at ``beta`` up to 2, keys
  that resemble each other, ``d_k != d_v`` and lengths that are no
  multiple of the sub-chunk;
* the kernel at the cell's own head widths (96 and 192);
* the per-channel (KDA) path to the bit;
* the configuration file against the published row.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

from mxnet_tpu import generate, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import programs  # noqa: E402
from benchmark.lib import manifest, weights  # noqa: E402
from benchmark.lib.reference import olmo_hybrid as ref  # noqa: E402

# float32 on both sides, the same weights: what is left is the order of
# the sums (the chunkwise delta rule, XLA's own fusions), a few units in
# the last place of logits of size 1, over eight blocks whose post-norm
# brings each sub-layer's output, rounding and all, to the stream's size
TOL = 5e-5
CONFIG = "olmo-hybrid-7b"


@pytest.fixture(scope="module")
def cfg():
    return manifest.config(manifest.manifest(), CONFIG, rehearse=True)


@pytest.fixture(scope="module")
def model(cfg):
    net = programs.program(cfg).build_net(cfg)
    arrays = weights.make_params(cfg, 7)
    programs.set_weights(net, ref.param_specs(cfg), arrays)
    return net, arrays


@pytest.fixture(scope="module")
def engine(model):
    return generate.PagedGenerationEngine(
        model[0], slots=3, cache_len=128, page_size=8, prefill_chunk=24,
        spec_k=0, prefix_share=False,
        sampling=generate.SamplingConfig(greedy=True))


def _ids(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], n).astype(np.int32)


_REF = {}


def _ref_logits(cfg, arrays, seq):
    """The reference's logits at every position of ``seq``, one compiled
    forward over 128 positions (the model is causal)."""
    import jax

    if "forward" not in _REF:
        _REF["forward"] = jax.jit(lambda params, toks: ref.forward(
            cfg, params, toks))
    toks = np.zeros((1, 128), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(_REF["forward"](arrays, toks))[0, :len(seq)]


def _step(eng):
    """One decode step launched and read at once, by hand."""
    out = eng.decode_step()
    for more in eng.drain():
        for slot, toks in more.items():
            out[slot] = out.get(slot, []) + toks
    return out


# -- the model against the reference ----------------------------------------

def test_the_cut_builds_two_periods_of_the_published_kinds(cfg, model):
    net, _arrays = model
    assert net._mixers == ["gdn", "gdn", "gdn", "gqa"] * 2
    assert net._post_norm and net._neg_eigval and net._qk_norm == "whole"
    kinds = net.config["layer_caches"]
    z = ref.sizes(cfg)
    state = {"state": [((4, 12, 24), "float32"), ((3, z["wide"]), None)]}
    rows = {"rows": 2 * 4 * 16, "attended": "pages"}
    assert kinds == [state, state, state, rows] * 2
    assert z["wide"] == 4 * (2 * 12 + 24)


@pytest.mark.parametrize("length", [40, 100])
def test_full_forward_matches_reference(cfg, model, length):
    """The whole model, uncached: GDN by the chunkwise rule with a decay
    a head (one sub-chunk, and two with padding) against the reference's
    token-by-token recurrence as the paper writes it, full attention
    under the whole-width QK-norm, every block post-norm."""
    net, arrays = model
    toks = np.stack([_ids(cfg, length, 1), _ids(cfg, length, 2)])
    got = net(nd.array(toks)).asnumpy()
    for b in range(2):
        want = _ref_logits(cfg, arrays, toks[b])
        assert np.abs(got[b] - want).max() < TOL
        assert np.abs(want).max() > 0.1


def test_beta_in_zero_one_without_negative_eigenvalues(cfg):
    """``linear_allow_neg_eigval`` false (FLA's Gated DeltaNet as the
    paper has it, ``beta`` in [0, 1]): program and reference follow the
    key, and the two models differ."""
    import jax

    plain = dict(cfg, linear_allow_neg_eigval=False)
    net = programs.program(plain).build_net(plain)
    arrays = weights.make_params(plain, 7)
    programs.set_weights(net, ref.param_specs(plain), arrays)
    assert not net._neg_eigval
    toks = _ids(plain, 40, 1)[None]
    got = net(nd.array(toks)).asnumpy()[0]
    want = np.asarray(jax.jit(lambda p, t: ref.forward(plain, p, t))(
        arrays, toks))[0]
    assert np.abs(got - want).max() < TOL
    other = np.asarray(jax.jit(lambda p, t: ref.forward(cfg, p, t))(
        arrays, toks))[0]
    assert np.abs(other - want).max() > 1e-2


def test_prefill_then_decode_and_the_caches_match_reference(cfg, model,
                                                            engine):
    """Three sequences side by side: prompts of 50, 7 and 61 tokens in
    chunks of 24 (no multiple of the delta rule's 64), then 12 decode
    steps of all slots: every position's logits are the reference's full
    forward over the same tokens, and what the engine holds of each
    sequence (every GDN layer's state and convolution tail, every full
    layer's ``[K | V]`` rows in its pages) is the reference's
    ``caches``."""
    import jax

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
        for sl, seq, lg in zip(slots, seqs, logits):
            lg.append(eng.last_logits[sl, 0])
            seq.extend(out[sl])
    for sl, seq, lg in zip(slots, seqs, logits):
        want = _ref_logits(cfg, arrays, seq[:-1])
        n = len(seq) - 1 - 12
        assert np.abs(np.stack(lg) - want[n - 1:]).max() < TOL
        assert seq[n:] == list(want[n - 1:].argmax(-1))
    caches = jax.jit(lambda p, t, n: ref.caches(cfg, p, t, n))
    for snap in eng.cached(slots):
        n = snap["position"]
        toks = np.zeros((1, 128), np.int32)
        toks[0, :n] = snap["tokens"]
        want = jax.device_get(caches(arrays, toks, np.int32(n)))
        kinds = ref.layer_kinds(cfg)
        assert len(snap["layers"]) == len(want) == len(kinds)
        for mix, got, ref_c in zip(kinds, snap["layers"], want):
            if mix == "gdn":
                for mine, theirs in zip(got, ref_c):
                    # (the first layer's tail is its input's projection:
                    # embeddings of std 0.02 give values of a few 1e-3)
                    scale = np.abs(theirs).max()
                    assert scale > 1e-3
                    assert np.abs(mine - theirs[0]).max() < TOL * scale
            else:
                # (K normed: values of a few units)
                assert got.shape == (n, 2 * 4 * 16)
                assert np.abs(got - ref_c[0, :n]).max() \
                    < TOL * np.abs(ref_c[0, :n]).max()
    for sl in slots:
        eng.evict(sl, "length")


def test_full_layers_chunk_in_blocks_serves_the_gathered_tokens(
        cfg, model, monkeypatch):
    """The two full layers (one query head a key/value head) attend a
    chunk's cached rows in blocks of 16 up to ``start`` (prompts of 61,
    7 and 40 tokens in chunks of 24: no block, two and three): the
    engine serves token for token what the engine that gathers and
    attends all a slot holds serves, and holds the same GDN states and
    ``[K | V]`` rows; every chunk calls the blocked form."""
    from mxnet_tpu.ops import attention_rows

    import test_window_cache as wc

    net, _arrays = model
    prompts = [_ids(cfg, n, seed) for n, seed in ((61, 5), (7, 4), (40, 8))]

    def serve():
        eng = generate.PagedGenerationEngine(
            net, slots=3, cache_len=128, page_size=8, prefill_chunk=24,
            spec_k=0, prefix_share=False,
            sampling=generate.SamplingConfig(greedy=True))
        return eng, wc._serve(eng, prompts, 8)

    monkeypatch.setattr(attention_rows, "CACHE_BLOCK_ROWS", 16)
    with monkeypatch.context() as m:
        seen = wc._spy_attention(m)
        blocked, got = serve()
    assert set(seen["blocks"]) == {24} and 24 not in seen["gathered"]
    assert set(seen["pages"]) == {1}
    with monkeypatch.context() as m:
        wc.gathered_whole(net, m)
        seen = wc._spy_attention(m)
        whole, want = serve()
    assert seen["blocks"] == seen["pages"] == []
    assert got == want and all(len(t) == 9 for t in got.values())
    wc.assert_same_caches(blocked, whole, sorted(got), tol=TOL)


def test_options_are_checked():
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    with pytest.raises(ValueError, match="qk_norm"):
        HybridDecoderLM(64, 16, ["gqa"], ["dense"], 2, n_kv_heads=2,
                        d_head=8, d_ff=32, qk_norm="heads")
    with pytest.raises(ValueError, match="gdn"):
        HybridDecoderLM(64, 16, ["gdn"], ["dense"], 2, d_ff=32)


# -- the delta rule with a decay a head ---------------------------------------

def _per_head_inputs(B, T, H, dk, dv, seed, cos=0.0, beta_hi=2.0):
    """Unit keys that share a component of cosine ``cos`` (a stream
    with a common part under a SiLU), decays a head from none to
    ``exp(-12)`` a token, ``beta`` drawn in [0, beta_hi] with a quarter
    of the positions near its top."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(B, T, H, dk))
    if cos:
        k = k + np.sqrt(cos / (1 - cos)) * rng.normal(size=(1, 1, H, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(B, T, H, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    v = rng.normal(size=(B, T, H, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(12.0), size=(B, T, H)))
    beta = rng.uniform(0, beta_hi, size=(B, T, H))
    top = rng.random(size=(B, T, H)) < 0.25
    beta = np.where(top, beta_hi - 1e-3 * rng.random(size=beta.shape), beta)
    s0 = rng.normal(size=(B, H, dk, dv))
    return [np.asarray(a, np.float32) for a in (q, k, v, g, beta, s0)]


@pytest.mark.parametrize("T, valid, cos", [
    (128, None, 0.0), (150, None, 0.0), (24, None, 0.0), (200, (150, 0), 0.0),
    (150, None, 0.7), (130, None, 0.95)])
def test_per_head_chunkwise_rule_matches_its_one_token_form(T, valid, cos):
    """Outputs and final state of the chunkwise form with one decay a
    head (``(K K^T) * exp(G_t - G_i)``, a matrix product and a mask a
    head) against the one-token form, both float32, ``beta`` up to 2
    (eigenvalues of the transition down to -1), ``d_k`` 12 against
    ``d_v`` 24, lengths that are no multiple of the sub-chunk of 64,
    keys that resemble each other (the triangular system far from the
    identity): the block inverse stays exact to float32 with ``beta``
    past 1.  With ``valid`` the positions past it leave the state
    alone, to the bit where nothing is valid."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step

    q, k, v, g, beta, s0 = _per_head_inputs(2, T, 3, 12, 24, T, cos)
    count = None if valid is None else jnp.asarray(valid, jnp.int32)
    o_c, s_c = gated_delta_chunk(q, k, v, g, beta, s0, count, "gdn.scan")
    s, outs = jnp.asarray(s0), []
    for t in range(T):
        o, s_t = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], s, "gdn.scan")
        if count is not None:
            s_t = jnp.where((t < count)[:, None, None, None], s_t, s)
        s = s_t
        outs.append(o)
    o_r, o_c = np.asarray(jnp.stack(outs, 1)), np.asarray(o_c)
    if valid is not None:
        live = np.arange(T)[None, :] < np.asarray(valid)[:, None]
        o_r, o_c = o_r * live[..., None, None], o_c * live[..., None, None]
        np.testing.assert_array_equal(np.asarray(s_c)[1], s0[1])
    scale = max(np.abs(o_r).max(), 1.0)
    assert np.abs(o_c - o_r).max() < 2e-5 * scale
    assert np.abs(np.asarray(s_c) - np.asarray(s)).max() < 2e-5 * max(
        np.abs(np.asarray(s)).max(), 1.0)
    assert np.abs(o_r).max() > 0.05 and (beta > 1.9).any()


def test_per_head_rule_matches_the_reference_recurrence():
    """The package's chunkwise form against the reference's scan, which
    writes the step as the paper does (``exp(g) (I - beta k k^T) S +
    beta k v^T``) and imports nothing of the program."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_chunk

    q, k, v, g, beta, _s0 = _per_head_inputs(1, 150, 2, 12, 24, 9, 0.5)
    want_o, want_s = ref.delta_rule(*map(jnp.asarray, (q, k, v, g, beta)))
    o, s = gated_delta_chunk(q, k, v, g, beta,
                             jnp.zeros((1, 2, 12, 24), jnp.float32))
    assert np.abs(np.asarray(o) - np.asarray(want_o)).max() < 2e-5
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 2e-5
    assert np.abs(np.asarray(want_s)).max() > 0.1


@pytest.mark.parametrize("T, valid, cos", [
    (150, None, 0.0), (100, (70, 0), 0.0), (130, None, 0.9),
    (600, (600, 530), 0.0)])
def test_kernel_with_a_decay_a_head_at_the_cells_widths(T, valid, cos):
    """The chunkwise kernel at Olmo-Hybrid's head widths (keys of 96,
    values of 192, each zero-padded to whole lane tiles inside the op),
    ``beta`` up to 2, against the one-token form (outputs and final
    state) and against the reference's recurrence from a zero state;
    lengths that are no multiple of the sub-chunk of 64, a row with
    nothing valid keeping its state to the bit, keys that resemble each
    other, and a chunk longer than the kernel's segment of 512 (two
    grid steps a row, the state carried between them)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step

    q, k, v, g, beta, s0 = _per_head_inputs(2, T, 2, 96, 192, T, cos)
    count = None if valid is None else jnp.asarray(valid, jnp.int32)
    o_c, s_c = gated_delta_chunk(q, k, v, g, beta, s0, count, "gdn.scan")
    live = np.ones((2, T), bool) if valid is None else \
        np.arange(T)[None, :] < np.asarray(valid)[:, None]

    def token(s, x):
        *x, on = x
        o, s_t = gated_delta_step(*x, s, "gdn.scan")
        return jnp.where(on[:, None, None, None], s_t, s), o

    s_r, o_r = jax.lax.scan(token, jnp.asarray(s0), tuple(
        jnp.moveaxis(jnp.asarray(a), 1, 0) for a in (q, k, v, g, beta, live)))
    o_r = np.asarray(jnp.moveaxis(o_r, 0, 1)) * live[..., None, None]
    o_c = np.asarray(o_c) * live[..., None, None]
    if valid is not None and valid[1] == 0:
        np.testing.assert_array_equal(np.asarray(s_c)[1], s0[1])
    scale = max(np.abs(o_r).max(), 1.0)
    assert np.abs(o_c - o_r).max() < 2e-5 * scale
    assert np.abs(np.asarray(s_c) - np.asarray(s_r)).max() < 2e-5 * max(
        np.abs(np.asarray(s_r)).max(), 1.0)
    assert np.abs(o_r).max() > 0.05 and (beta > 1.9).any()
    want_o, want_s = ref.delta_rule(*map(jnp.asarray, (q, k, v, g, beta)))
    o, s = gated_delta_chunk(q, k, v, g, beta, np.zeros_like(s0))
    assert np.abs(np.asarray(o) - np.asarray(want_o)).max() < 2e-5
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 2e-5


# the per-channel (KDA) rule's outputs on the inputs below, as the
# chunkwise kernel computes them in the TPU interpreter of Pallas on the
# CPU under this JAX: a change to the per-channel path's arithmetic
# shows here
KDA_JAX = "0.9.0"
KDA_SHA256 = "60df8395784dd977e2e3912626e685a629acc38067d9845fb053664e51c9a859"


def test_per_channel_rule_is_unchanged_to_the_bit():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step

    if jax.__version__ != KDA_JAX:
        pytest.skip("the digest was taken under JAX %s" % KDA_JAX)
    ks = jax.random.split(jax.random.key(41), 6)
    B, T, H, dk, dv = 2, 150, 3, 16, 8
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (B, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    s0 = jax.random.normal(ks[5], (B, H, dk, dv))
    h = hashlib.sha256()
    for a in gated_delta_chunk(q, k, v, g, beta, s0,
                               jnp.asarray([150, 70], jnp.int32)):
        h.update(np.asarray(a, np.float32).tobytes())
    for a in gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], s0):
        h.update(np.asarray(a, np.float32).tobytes())
    assert h.hexdigest() == KDA_SHA256


# -- the configuration -------------------------------------------------------

def test_configuration_is_the_published_row_cut_in_depth():
    """Every key of the published row (kept in ``tests/data``, as the
    model-configs catalog gives it) under its own name at its own value,
    ``num_hidden_layers`` apart (listed in ``reduced``, the published
    number beside it); the layers held are two whole periods of the 3:1
    pattern in the published order."""
    with open(os.path.join(ROOT, "tests", "data", "catalog_rows.json")) as f:
        row = json.load(f)["Olmo-Hybrid-7B"]
    c = manifest.config(manifest.manifest(), CONFIG)
    assert row["source_url"] == c["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    published = row["config"]
    for key, value in published.items():
        assert c[key] == ({"num_hidden_layers": 8}.get(key, value)), key
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 32}
    assert c["layers_held"] == list(range(8))
    held = [published["layer_types"][l] for l in c["layers_held"]]
    assert held == (["linear_attention"] * 3 + ["full_attention"]) * 2
    assert held.count("linear_attention") == 3 * held.count("full_attention")
    assert (c["family"], c["weights_dtype"], c["dtype_policy"]) == (
        "olmo_hybrid", "bfloat16", "bf16_mixed")
    assert "4 pipeline stages" in c["deployment"]
    for reading in ("block", "gdn", "neg_eigval", "attention", "rope",
                    "cache", "weights"):
        assert reading in c["assumed"], reading
    assert "q_norm_gamma 2.0" in c["assumed"]["weights"]
    # every width as published: 30 heads of 128 (the config gives no
    # head_dim), keys of 96 and values of 192
    z = ref.sizes(c)
    assert (z["D"], z["H"], z["Hkv"], z["dh"], z["Hl"], z["dk"], z["dv"],
            z["K"], z["Fd"], z["V"]) == (
        3840, 30, 30, 128, 30, 96, 192, 4, 11008, 100352)
