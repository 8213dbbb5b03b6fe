"""LM generation engine (mxnet_tpu/generate.py): KV-cache decode
correctness, sampling determinism, and continuous-batching serving.

Tier-1 guards:
* the model protocol: ``chunk_forward`` against a linear cache gives
  the full-context forward's logits in float32, however the sequence is
  cut into chunks (one chunk, chunks of 3, a token at a time), for both
  served models (``TransformerLM`` and the zoo's ``MoEDecoderLM``);
* the engine's greedy tokens are those of the full re-forward (the
  independent reference every engine test is held to), on one device,
  under a dp=2,tp=2 mesh, and to bf16 rounding under ``bf16_mixed``;
* greedy decode is deterministic, and sampling decode is reproducible
  under the framework PRNG discipline (``mx.random.seed``);
* a slot's cache ends a sequence: ``cache_len - prompt + 1`` tokens,
  finish reason ``length``;
* ``decode_step`` returns a list of tokens a slot in every mode (plain,
  speculative, block-diffusion), which is what ``TokenServer`` relies
  on;
* the TokenServer applies the serving_async typed-error taxonomy
  per-token: Overloaded at admission, DeadlineExceeded tagged
  ``prefill`` vs ``decode`` (driven via ``testing/faults`` latency
  injection), eviction counters by reason, drained close().

Kept lean for the tier-1 budget: one module-scoped model + engine
serves most tests, the engine programs are tiny (d_model 32), and the
continuous-batching soak is marked ``slow``.
"""
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import generate, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM
from mxnet_tpu.testing import faults

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from transformer_lm import TransformerLM  # noqa: E402

VOCAB, D_MODEL, N_HEADS, N_LAYERS, MAX_LEN = 48, 32, 2, 2, 24


@pytest.fixture(scope="module", autouse=True)
def _telemetry_off_afterwards():
    """The server tests turn telemetry on (and count on its staying on
    from one to the next); turn it off when the file is done, so that no
    later file in the same process inherits it."""
    yield
    telemetry.disable()


@pytest.fixture(scope="module")
def lm():
    mx.random.seed(0)
    net = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL,
                        n_heads=N_HEADS, n_layers=N_LAYERS,
                        max_len=MAX_LEN)
    net.initialize(mx.init.Xavier())
    # one eager forward finishes deferred init so every test sees
    # concrete shapes
    net(nd.array(np.zeros((1, 4), np.float32)))
    return net


def _moe(block_length=1, mask_token_id=None):
    """The zoo's sparse GQA/RoPE decoder, tiny: 4 query and 2 key/value
    heads of 8, 4 experts top-2 of width 16."""
    mx.random.seed(0)
    net = MoEDecoderLM(VOCAB, D_MODEL, N_LAYERS, 4, 2, 8, 4, 2, 16,
                       block_length=block_length,
                       mask_token_id=mask_token_id, max_len=MAX_LEN)
    net.initialize(mx.init.Xavier())
    return net


@pytest.fixture(scope="module")
def moe():
    return _moe()


@pytest.fixture(scope="module")
def models(lm, moe):
    return {"transformer_lm": lm, "moe_decoder_lm": moe}


def _engine(net, slots=3, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
            sampling=None, **kw):
    return generate.PagedGenerationEngine(
        net, slots=slots, cache_len=cache_len, page_size=page_size,
        prefill_chunk=prefill_chunk,
        sampling=sampling or generate.SamplingConfig(greedy=True), **kw)


@pytest.fixture(scope="module")
def eng(lm):
    return _engine(lm)


def _prompt(n=5, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, n) \
        .astype(np.int32)


def _full_logits(net, token_ids):
    """Reference: full-context forward over the whole sequence."""
    toks = nd.array(np.asarray(token_ids, np.float32)[None])
    return np.asarray(net(toks)._data)[0]


def _greedy_reference(net, prompt, n):
    """``n`` greedy tokens by full re-forward, float32: the reference
    the engine is held to, independent of any cache.  Every forward
    runs at the one shape (1, MAX_LEN), zeros after the sequence: under
    a causal mask they reach no position before them."""
    seq = np.zeros(MAX_LEN, np.int32)
    seq[:len(prompt)] = prompt
    out = []
    for at in range(len(prompt), len(prompt) + n):
        out.append(int(_full_logits(net, seq)[at - 1].argmax()))
        if at < MAX_LEN:
            seq[at] = out[-1]
    return out


def _generate(e, prompt, n):
    """``n`` tokens of ``prompt`` from the engine driven by hand (a
    call of ``decode_step`` hands over the tokens of a step launched a
    call before; what is still in flight at the end is thrown away)."""
    slot, tok = e.admit(prompt)
    out = [tok]
    while len(out) < n:
        out.extend(e.decode_step()[slot])
    e.evict(slot, "length")
    return out[:n]


def _step_and_read(e):
    """One step launched and read at once, by hand: ``decode_step``,
    then ``drain`` for what it left in flight."""
    out = e.decode_step()
    for more in e.drain():
        for slot, toks in more.items():
            out[slot] = out.get(slot, []) + toks
    return out


# ---------------------------------------------------------------------------
# the model protocol: chunk_forward == the full forward, however cut
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [9, 3, 1],
                         ids=["one_chunk", "chunks_of_3", "token_at_a_time"])
@pytest.mark.parametrize("model", ["transformer_lm", "moe_decoder_lm"])
def test_chunk_forward_matches_full_forward_f32(models, model, chunk):
    """Eager, no engine: feed 9 tokens through ``chunk_forward`` in
    chunks against a linear cache that holds what the chunks before
    wrote, and compare every position's logits with one full-context
    forward."""
    import jax.numpy as jnp

    net = models[model]
    cfg = net.config
    seq = _prompt(9, seed=2)
    ref = _full_logits(net, seq)
    H = cfg.get("n_kv_heads", cfg["n_heads"])
    dh = cfg.get("d_head", cfg["d_model"] // cfg["n_heads"])
    S = 16
    # the cache form the model's config declares: rows, one a position
    # with every head side by side, or a head-split view
    rows = bool(cfg.get("cache_rows"))
    shape = (lambda n: (1, n, H * dh)) if rows \
        else (lambda n: (1, H, n, dh))
    caches = [(jnp.zeros(shape(S), jnp.float32),) * 2
              for _ in range(cfg["n_layers"])]
    assert rows == (model == "transformer_lm")
    for start in range(0, len(seq), chunk):
        res = net.chunk_forward(
            jnp.asarray(seq[None, start:start + chunk]), caches,
            jnp.asarray([start], jnp.int32))
        got = np.asarray(res[0]._data)[0]
        np.testing.assert_allclose(got, ref[start:start + chunk],
                                   atol=2e-5, rtol=1e-5)
        assert (got.argmax(-1) == ref[start:start + chunk].argmax(-1)).all()
        assert len(res[1]) == cfg["n_layers"]
        assert res[1][0][0].shape == shape(chunk)
        at = (slice(None), slice(start, start + chunk)) if rows \
            else (slice(None), slice(None), slice(start, start + chunk))
        caches = [(k.at[at].set(kc), v.at[at].set(vc))
                  for (k, v), (kc, vc) in zip(caches, res[1])]


# ---------------------------------------------------------------------------
# decode correctness: the engine's greedy tokens == full re-forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["transformer_lm", "moe_decoder_lm"])
def test_engine_greedy_decode_matches_full_forward(models, eng, model):
    """Engine-level (jitted): greedy generation equals full-context
    greedy re-forward, token for token."""
    net = models[model]
    e = eng if model == "transformer_lm" else _engine(net, slots=2)
    prompt = _prompt(5, seed=3)
    assert _generate(e, prompt, 7) == _greedy_reference(net, prompt, 7)


def test_engine_decode_matches_bf16_mixed(lm):
    """bf16_mixed engine: decode-step logits track the SAME policy's
    one-chunk prefill of the sequence so far (the full-context forward
    under that policy) to bf16 rounding; cache dtype follows the policy
    compute dtype."""
    e = _engine(lm, slots=2, cache_len=16, prefill_chunk=16,
                prefix_share=False, dtype_policy="bf16_mixed")
    assert e.cache_dtype == np.dtype("bfloat16")
    assert e.dtype_policy_tag == "bf16_mixed"
    prompt = _prompt(5, seed=4)
    slot, tok = e.admit(prompt)
    seq = list(prompt) + [tok]
    for _ in range(4):
        step_toks = _step_and_read(e)
        got = e.last_logits[slot, 0]      # of the step read
        # reference: prefill of the full sequence so far in the OTHER
        # slot, one chunk from an empty cache (head stays f32 per the
        # norm/head rules)
        ref_slot, _rt = e.admit(np.asarray(seq, np.int32))
        ref = e.last_logits[0, len(seq) - 1]
        e.evict(ref_slot, "length")
        np.testing.assert_allclose(got, ref, atol=0.12, rtol=0.05)
        assert int(got.argmax()) == int(ref.argmax())
        seq.extend(step_toks[slot])


# ---------------------------------------------------------------------------
# sampling / PRNG discipline
# ---------------------------------------------------------------------------

def test_greedy_deterministic_and_sampling_reproducible(lm):
    e = _engine(lm, slots=2, cache_len=16,
                sampling=generate.SamplingConfig(greedy=False, top_k=8,
                                                 temperature=0.9))
    prompt = _prompt(4, seed=5)
    mx.random.seed(7)
    a = _generate(e, prompt, 6)
    mx.random.seed(7)
    b = _generate(e, prompt, 6)
    assert a == b, "sampled decode must be reproducible under seed"
    assert all(0 <= t < VOCAB for t in a)


def test_sample_logits_top_k_top_p():
    import jax

    logits = np.full((1, 8), -10.0, np.float32)
    logits[0, 2] = 5.0
    logits[0, 5] = 4.0
    key = jax.random.PRNGKey(0)
    cfg = generate.SamplingConfig(greedy=False, top_k=1)
    assert int(generate.sample_logits(logits, key, cfg)[0]) == 2
    cfg = generate.SamplingConfig(greedy=False, top_p=0.5)
    assert int(generate.sample_logits(logits, key, cfg)[0]) == 2
    cfg = generate.SamplingConfig(greedy=True)
    assert int(generate.sample_logits(logits, key, cfg)[0]) == 2


# ---------------------------------------------------------------------------
# engine admission / a slot's capacity
# ---------------------------------------------------------------------------

def test_engine_slot_exhaustion_and_reuse(eng):
    slots = []
    for i in range(eng.slots):
        slot, _tok = eng.admit(_prompt(4, seed=i))
        slots.append(slot)
    with pytest.raises(generate.Overloaded) as ei:
        eng.admit(_prompt(4))
    assert ei.value.reason == "slots"
    eng.evict(slots[1], "eos")
    slot, _tok = eng.admit(_prompt(4, seed=9))
    assert slot == slots[1], "evicted slot must be reused"
    for s in slots:
        eng.evict(s, "length")
    assert eng.free_slots() == eng.slots


def test_engine_prompt_too_long_and_occupancy(eng):
    with pytest.raises(MXNetError, match="paged cache capacity"):
        eng.admit(np.zeros(MAX_LEN + 1, np.int32))
    occ = eng.occupancy()
    assert occ["active_slots"] == 0 and occ["cache_tokens"] == 0
    slot, _ = eng.admit(_prompt(6))
    occ = eng.occupancy()
    assert occ["active_slots"] == 1
    assert occ["cache_tokens"] == 6
    assert 0 < occ["occupancy"] <= 1
    eng.evict(slot, "length")


@pytest.fixture(scope="module")
def short_cache(lm):
    """One slot of 8 positions under a model of 24."""
    return _engine(lm, slots=1, cache_len=8, prefill_chunk=4)


def test_cache_shorter_than_max_len_ends_the_sequence(lm, short_cache):
    """cache_len < max_len: a slot holds ``cache_len`` positions and no
    window slides.  A prompt of 6 in a cache of 8 yields the token its
    prefill samples and one for each of the two positions left, the
    full re-forward's tokens, and is then at capacity."""
    e = short_cache
    assert e.cache_len == 8 < MAX_LEN
    prompt = _prompt(6, seed=6)
    slot, tok = e.admit(prompt)
    produced = [tok]
    while not e.at_capacity(slot):
        produced.extend(e.decode_step()[slot])
    assert e.position(slot) == e.cache_len
    e.evict(slot, "length")
    assert len(produced) == e.cache_len - len(prompt) + 1
    assert produced == _greedy_reference(lm, prompt, len(produced))


@pytest.mark.parametrize("n_prompt", [6, 8],
                         ids=["two_positions_left", "prompt_fills_it"])
def test_server_finishes_by_length_at_the_cache_end(lm, short_cache,
                                                    n_prompt):
    """Through ``TokenServer`` such a sequence resolves with
    ``finish_reason`` ``length`` well under ``max_new_tokens``; a prompt
    that fills the cache yields the one token its prefill samples."""
    prompt = _prompt(n_prompt, seed=6)
    with generate.TokenServer(short_cache, max_new_tokens=64) as srv:
        r = srv.generate(prompt, timeout=60)
    assert r.finish_reason == "length"
    assert r.tokens == _greedy_reference(lm, prompt, 8 - n_prompt + 1)
    assert short_cache.free_slots() == 1


def test_engine_refuses_a_model_without_the_protocol(lm):
    """One protocol: a net without ``chunk_forward`` (or ``config``) is
    named as such when the engine is built, not at its first trace."""
    class NoProtocol:
        config = lm.config

    with pytest.raises(MXNetError, match="chunk_forward / config"):
        generate.PagedGenerationEngine(NoProtocol())


@pytest.mark.parametrize("mode", ["plain", "speculative", "block"])
def test_decode_step_returns_a_list_a_slot(lm, mode):
    """What ``TokenServer`` relies on: in every mode ``decode_step``
    maps each active slot to a list (one token; the verified drafts and
    one; none while a block is open, then a committed block)."""
    if mode == "block":
        e = _engine(_moe(block_length=4, mask_token_id=VOCAB - 1), slots=2,
                    prefill_chunk=8, spec_k=0, denoise_steps=2)
    else:
        e = _engine(lm, slots=2, spec_k=2 if mode == "speculative" else 0,
                    spec_ngram=2)
    prompt = np.tile(_prompt(3, seed=7), 3)[:8].astype(np.int32)
    slot, _tok = e.admit(prompt)
    bursts = []
    for _ in range(8):
        out = e.decode_step()
        assert set(out) == {slot}
        assert isinstance(out[slot], list), type(out[slot])
        bursts.append(len(out[slot]))
    e.evict(slot, "length")
    assert sum(bursts) > 0
    if mode == "plain":       # the first call has nothing to read yet
        assert bursts == [0] * e.steps_ahead + [1] * (8 - e.steps_ahead)
    elif mode == "block":
        assert set(bursts) == {0, 4}, bursts


# ---------------------------------------------------------------------------
# a tp-meshed engine
# ---------------------------------------------------------------------------

def test_engine_tp_mesh_matches_full_forward(lm):
    """tp serving composes with the training mesh: a dp=2,tp=2 engine
    produces the greedy tokens of the full re-forward."""
    e = _engine(lm, slots=2, cache_len=16, mesh="dp=2,tp=2")
    assert e.layout_name == "fsdp_tp"
    assert e.mesh_shape == {"dp": 2, "tp": 2}
    prompt = _prompt(5, seed=3)
    assert _generate(e, prompt, 5) == _greedy_reference(lm, prompt, 5)


# ---------------------------------------------------------------------------
# TokenServer: typed admission / deadlines / eviction / drain
# ---------------------------------------------------------------------------

def _counter_val(counter, **labels):
    telemetry.enable()
    return counter.value(**labels)


def test_server_generates_and_finishes_by_reason(lm, eng):
    telemetry.enable()
    srv = generate.TokenServer(eng, queue_depth=8, max_new_tokens=4)
    try:
        r = srv.generate(_prompt(5), timeout=60)
        assert r.finish_reason == "length"
        assert len(r.tokens) == 4
        assert r.ttft_s is not None and r.ttft_s >= 0
        # eos finish: replay and make the 2nd generated token the EOS
        eos = r.tokens[1]
        eng.sampling.eos_id = eos
        try:
            r2 = srv.generate(_prompt(5), max_new_tokens=10, timeout=60)
            assert r2.finish_reason == "eos"
            assert r2.tokens == r.tokens[:2]
        finally:
            eng.sampling.eos_id = None
        assert _counter_val(telemetry.DECODE_EVICTIONS, reason="eos") >= 1
    finally:
        srv.close()
    assert eng.free_slots() == eng.slots


def test_statusz_names_the_pool_of_a_live_server(eng):
    """The ``decode`` subsystem of ``/statusz`` reads every live
    server's engine without asking what kind it is: its pool's shape
    and the page counters of its occupancy."""
    with generate.TokenServer(eng, queue_depth=4) as srv:
        (st,) = [s for s in generate._decode_statusz()["servers"]
                 if s["pool_shape"] == list(eng.pool_shape)
                 and not s["closed"]]
        assert st["free_slots"] == eng.slots
        assert st["occupancy"]["pages_total"] == eng.num_pages - 1
        assert srv.stats()["closed"] is False


def test_server_overload_queue_and_shutdown(lm, eng):
    srv = generate.TokenServer(eng, queue_depth=1, max_new_tokens=8)
    # stall decode so work piles up: every slot busy + queue full
    orig = eng.decode_step
    eng.decode_step = faults.LatencySpike(orig, delay=0.05)
    try:
        futs = [srv.submit(_prompt(4, seed=i), block=True, timeout=30)
                for i in range(eng.slots)]
        # wait until every slot is occupied (the queue is then empty)
        deadline = time.monotonic() + 10
        while eng.free_slots() > 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        fq = srv.submit(_prompt(4, seed=90))      # fills the queue
        with pytest.raises(generate.Overloaded) as ei:
            srv.submit(_prompt(4, seed=91))
        assert ei.value.reason == "queue"
        for f in futs + [fq]:
            assert f.result(timeout=60).finish_reason == "length"
    finally:
        eng.decode_step = orig
        srv.close()
    with pytest.raises(generate.Overloaded) as ei:
        srv.submit(_prompt(4))
    assert ei.value.reason == "shutdown"


def test_server_deadline_stages_prefill_vs_decode(lm, eng):
    """Injected latency (testing/faults) drives both deadline stages
    deterministically: a queued request expires with stage='prefill',
    a mid-generation one with stage='decode' + a 'deadline' eviction."""
    telemetry.enable()
    srv = generate.TokenServer(eng, queue_depth=8, max_new_tokens=64)
    orig = eng.decode_step
    eng.decode_step = faults.LatencySpike(orig, delay=0.05)
    try:
        # decode-stage: first token lands (prefill is fast), then the
        # 50 ms/step decode burns the 200 ms budget mid-generation
        before = _counter_val(telemetry.DECODE_EVICTIONS,
                              reason="deadline")
        fut = srv.submit(_prompt(4), deadline_ms=200)
        with pytest.raises(generate.DeadlineExceeded) as ei:
            fut.result(timeout=60)
        assert ei.value.stage == "decode"
        assert _counter_val(telemetry.DECODE_EVICTIONS,
                            reason="deadline") == before + 1

        # prefill-stage: fill every slot with slow long-runners, then
        # queue a request whose deadline expires before a slot frees
        longs = [srv.submit(_prompt(4, seed=i), max_new_tokens=30)
                 for i in range(eng.slots)]
        time.sleep(0.1)
        fut2 = srv.submit(_prompt(4, seed=50), deadline_ms=60)
        with pytest.raises(generate.DeadlineExceeded) as ei:
            fut2.result(timeout=60)
        assert ei.value.stage == "prefill"
        for f in longs:
            f.cancel()
    finally:
        eng.decode_step = orig
        srv.close()


def test_server_cancel_and_drain(lm, eng):
    telemetry.enable()
    srv = generate.TokenServer(eng, queue_depth=8, max_new_tokens=50)
    orig = eng.decode_step
    eng.decode_step = faults.LatencySpike(orig, delay=0.02)
    try:
        fut = srv.submit(_prompt(4))
        time.sleep(0.08)          # active in a slot by now
        assert fut.cancel()
        with pytest.raises(generate.Cancelled):
            fut.result(timeout=60)
        deadline = time.monotonic() + 30
        while eng.free_slots() != eng.slots:
            assert time.monotonic() < deadline, "cancelled slot leaked"
            time.sleep(0.01)
        # drained close: a short request finishes, the queue survivor
        # is Cancelled
        fut2 = srv.submit(_prompt(4), max_new_tokens=2)
    finally:
        eng.decode_step = orig
    srv.close(drain=True, timeout=30)
    assert fut2.result(timeout=1).finish_reason == "length"
    assert eng.free_slots() == eng.slots


@pytest.mark.slow
def test_server_continuous_batching_soak(lm):
    """Churn: more requests than slots x few, mixed lengths/deadlines,
    every future resolves, no slot/queue leaks."""
    e = _engine(lm, cache_len=16)
    srv = generate.TokenServer(e, queue_depth=32, max_new_tokens=6)
    rng = np.random.RandomState(0)
    futs = []
    try:
        for i in range(30):
            futs.append(srv.submit(
                rng.randint(0, VOCAB, int(rng.randint(1, 8))),
                max_new_tokens=int(rng.randint(1, 7)), block=True,
                timeout=60))
        done = 0
        for f in futs:
            try:
                r = f.result(timeout=120)
                assert r.finish_reason in ("eos", "length")
                done += 1
            except generate.ServingError:
                pass
        assert done == len(futs)
    finally:
        srv.close()
    assert e.free_slots() == e.slots
    st = srv.stats()
    assert st["queue_depth"] == 0 and st["active"] == 0


# ---------------------------------------------------------------------------
# bench_decode ledger records + perf_gate latency direction
# ---------------------------------------------------------------------------

def test_bench_decode_ledger_records_schema():
    import bench_decode

    from mxnet_tpu import perf_ledger

    recs = bench_decode.ledger_records(bench_decode.CANNED_RESULT)
    assert [r["metric"] for r in recs] == [
        "lm_decode_tokens_per_sec_per_user", "lm_decode_ttft_p99_ms"]
    for rec in recs:
        assert perf_ledger.validate_record(rec) == []
    assert recs[0]["unit"] == "tokens/sec/user"
    assert recs[1]["unit"] == "ms"
    assert recs[0]["cache_speedup"] == \
        bench_decode.CANNED_RESULT["cache_speedup"]


def test_perf_gate_latency_units_regress_upward():
    import perf_gate

    from mxnet_tpu import perf_ledger

    assert perf_gate.higher_is_better("lm_decode_tokens_per_sec_per_user",
                                      "tokens/sec/user")
    assert not perf_gate.higher_is_better("lm_decode_ttft_p99_ms", "ms")

    def rec(run, metric, value, unit, t):
        r = perf_ledger.make_record(metric, value, unit, run_id=run,
                                    prov={"mesh_shape": None})
        r["time"] = t
        return r

    baseline = [rec("r1", "lm_decode_ttft_p99_ms", 10.0, "ms", 1.0),
                rec("r1", "lm_decode_tokens_per_sec_per_user", 200.0,
                    "tokens/sec/user", 1.0)]
    # TTFT UP 50% + throughput DOWN 50% must both fail the gate
    cand = [rec("r2", "lm_decode_ttft_p99_ms", 15.0, "ms", 2.0),
            rec("r2", "lm_decode_tokens_per_sec_per_user", 100.0,
                "tokens/sec/user", 2.0)]
    failures, results = perf_gate.gate(baseline, cand)
    assert {f["metric"] for f in failures} == {
        "lm_decode_ttft_p99_ms", "lm_decode_tokens_per_sec_per_user"}
    # and an IMPROVEMENT in latency (down) passes
    cand2 = [rec("r3", "lm_decode_ttft_p99_ms", 5.0, "ms", 3.0)]
    failures2, _ = perf_gate.gate(baseline, cand2)
    assert failures2 == []
