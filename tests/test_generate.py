"""LM generation engine (mxnet_tpu/generate.py): KV-cache decode
correctness, sampling determinism, and continuous-batching serving.

Tier-1 guards for the ISSUE 13 tentpole:
* prefill logits are EXACTLY the full-context forward (same children,
  same op sequence), and KV-cache decode logits match the full-context
  forward to dtype rounding across f32 and bf16_mixed — prefill N then
  decode 1 ≡ forward N+1;
* greedy decode is deterministic, and sampling decode is reproducible
  under the framework PRNG discipline (``mx.random.seed``);
* the TokenServer applies the serving_async typed-error taxonomy
  per-token: Overloaded at admission, DeadlineExceeded tagged
  ``prefill`` vs ``decode`` (driven via ``testing/faults`` latency
  injection), eviction counters by reason, drained close();
* the KV-cache lanes resolve to the fsdp_tp layout's kv_cache rule
  (slots over data axes, heads over tp) and a tp-meshed engine decodes
  the same greedy tokens as the single-device one.

Kept lean for the tier-1 budget (suite runs ~680 s of the 870 s kill
window): one module-scoped model + engine serves most tests, the
engine programs are tiny (d_model 32), and the continuous-batching
soak is marked ``slow``.
"""
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import generate, nd, telemetry
from mxnet_tpu.base import MXNetError
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


@pytest.fixture(scope="module")
def eng(lm):
    return generate.GenerationEngine(
        lm, slots=3, cache_len=MAX_LEN, buckets=[8, MAX_LEN],
        sampling=generate.SamplingConfig(greedy=True))


def _prompt(n=5, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, n) \
        .astype(np.int32)


def _full_logits(lm, token_ids):
    """Reference: full-context forward over the whole sequence."""
    toks = nd.array(np.asarray(token_ids, np.float32)[None])
    return np.asarray(lm(toks)._data)[0]


# ---------------------------------------------------------------------------
# decode correctness: prefill N + decode 1 == forward N+1
# ---------------------------------------------------------------------------

def test_prefill_logits_bitmatch_full_forward(lm):
    prompt = _prompt(6)
    ref = _full_logits(lm, prompt)
    logits_nd, caches = lm.prefill_forward(
        nd.array(prompt[None].astype(np.float32)))
    got = np.asarray(logits_nd._data)[0]
    np.testing.assert_array_equal(got, ref)
    assert len(caches) == N_LAYERS
    assert caches[0][0].shape == (1, N_HEADS, 6, D_MODEL // N_HEADS)


def test_decode_logits_match_full_forward_f32(lm):
    """Eager-level: seed a ring from prefill, decode the next tokens,
    compare every step's logits against one full-context forward."""
    import jax.numpy as jnp

    prompt = _prompt(5)
    seq = list(prompt)
    # continue the sequence greedily for 6 steps to build a reference
    full = _full_logits(lm, seq)
    nxt = int(full[-1].argmax())
    _pl, caches = lm.prefill_forward(
        nd.array(np.asarray(seq, np.float32)[None]))
    S = 16
    ring = []
    for k, v in caches:
        kpad = jnp.zeros((1, N_HEADS, S, D_MODEL // N_HEADS), k.dtype)
        ring.append((kpad.at[:, :, :len(seq)].set(k),
                     jnp.zeros_like(kpad).at[:, :, :len(seq)].set(v)))
    for _step in range(6):
        seq.append(nxt)
        pos = jnp.full((1,), len(seq) - 1, jnp.int32)
        logits_nd, ring = lm.decode_forward(
            jnp.asarray([nxt], jnp.int32), ring, pos)
        got = np.asarray(logits_nd._data)[0]
        ref = _full_logits(lm, seq)[-1]
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)
        nxt = int(got.argmax())
        assert nxt == int(ref.argmax())


def test_engine_greedy_decode_matches_full_forward(lm, eng):
    """Engine-level (jitted): greedy generation equals full-context
    greedy re-forward, token for token."""
    prompt = _prompt(5, seed=3)
    slot, tok = eng.admit(prompt)
    toks = [tok]
    for _ in range(6):
        toks.append(eng.decode_step()[slot])
    eng.evict(slot, "length")
    seq = list(prompt)
    ref = []
    for _ in range(7):
        nxt = int(_full_logits(lm, seq)[-1].argmax())
        ref.append(nxt)
        seq.append(nxt)
    assert toks == ref


def test_engine_decode_matches_bf16_mixed(lm):
    """bf16_mixed engine: decode-step logits track the SAME policy's
    prefill (== full-context forward under that policy) to bf16
    rounding; cache dtype follows the policy compute dtype."""
    e = generate.GenerationEngine(
        lm, slots=2, cache_len=16, buckets=[16],
        dtype_policy="bf16_mixed",
        sampling=generate.SamplingConfig(greedy=True))
    assert e.cache_dtype == np.dtype("bfloat16")
    assert e.dtype_policy_tag == "bf16_mixed"
    prompt = _prompt(5, seed=4)
    slot, tok = e.admit(prompt)
    seq = list(prompt) + [tok]
    for _ in range(4):
        step_toks = e.decode_step()
        got = e.last_logits[slot]
        # reference: prefill of the full sequence so far on the OTHER
        # lane — prefill is exactly the full-context forward under the
        # same policy/params (head stays f32 per the norm/head rules)
        ref_slot, _rt = e.admit(np.asarray(seq, np.int32)[:16])
        ref = e.last_logits[0]
        e.evict(ref_slot, "length")
        np.testing.assert_allclose(got, ref, atol=0.12, rtol=0.05)
        assert int(got.argmax()) == int(ref.argmax())
        seq.append(step_toks[slot])


# ---------------------------------------------------------------------------
# sampling / PRNG discipline
# ---------------------------------------------------------------------------

def test_greedy_deterministic_and_sampling_reproducible(lm):
    e = generate.GenerationEngine(
        lm, slots=2, cache_len=16, buckets=[8],
        sampling=generate.SamplingConfig(greedy=False, top_k=8,
                                         temperature=0.9))
    prompt = _prompt(4, seed=5)

    def run():
        slot, tok = e.admit(prompt)
        out = [tok]
        for _ in range(5):
            out.append(e.decode_step()[slot])
        e.evict(slot, "length")
        return out

    mx.random.seed(7)
    a = run()
    mx.random.seed(7)
    b = run()
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
# engine admission / ring
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
    assert slot == slots[1], "evicted lane must be reused"
    for s in slots:
        eng.evict(s, "length")
    assert eng.free_slots() == eng.slots


def test_engine_prompt_too_long_and_occupancy(eng):
    with pytest.raises(MXNetError, match="prefill bucket"):
        eng.admit(np.zeros(MAX_LEN + 1, np.int32))
    occ = eng.occupancy()
    assert occ["active_slots"] == 0 and occ["cache_tokens"] == 0
    slot, _ = eng.admit(_prompt(6))
    occ = eng.occupancy()
    assert occ["active_slots"] == 1
    assert occ["cache_tokens"] == 6
    assert 0 < occ["occupancy"] <= 1
    eng.evict(slot, "length")


def test_ring_wraparound_past_cache_len(lm):
    """cache_len < max_len: generation slides the attention window
    through the ring without shape churn or failure."""
    e = generate.GenerationEngine(
        lm, slots=1, cache_len=8, buckets=[8],
        sampling=generate.SamplingConfig(greedy=True))
    slot, tok = e.admit(_prompt(6, seed=6))
    produced = [tok]
    # decode well past the ring (6 prompt + 10 > 8) up to max_len
    while not e.at_capacity(slot):
        produced.append(e.decode_step()[slot])
    # one token per position 6..23, plus the final step's sample
    # (produced at capacity, never fed back)
    assert len(produced) == MAX_LEN - 6 + 1
    assert all(0 <= t < VOCAB for t in produced)
    e.evict(slot, "length")


# ---------------------------------------------------------------------------
# KV-cache sharding layout + tp-meshed engine
# ---------------------------------------------------------------------------

def test_kv_cache_layout_rule():
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel import layout as playout

    mesh = parallel.resolve_mesh("dp=2,fsdp=2,tp=2")
    shape = (N_LAYERS, 4, 2, 16, 16)   # (L, slots, H, S, dh)
    res = playout.get_layout("fsdp_tp").resolve(
        [("cache_k", shape), ("cache_v", shape)], mesh)
    assert res.rule("cache_k") == "kv_cache"
    spec = res.spec("cache_k")
    # slots over the data axes, heads over tp, ring/d_head unsharded
    assert tuple(spec) == (None, ("dp", "fsdp"), "tp")
    res2 = playout.get_layout("fsdp").resolve(
        [("cache_k", shape)], parallel.resolve_mesh("fsdp=2"))
    assert res2.rule("cache_k") == "kv_cache"


def test_engine_tp_mesh_matches_single_device(lm, eng):
    """tp serving composes with the PR 9 mesh: a dp=2,tp=2 engine
    produces the same greedy tokens as the single-device engine."""
    e = generate.GenerationEngine(
        lm, slots=2, cache_len=16, buckets=[8], mesh="dp=2,tp=2",
        sampling=generate.SamplingConfig(greedy=True))
    assert e.layout_name == "fsdp_tp"
    assert e.mesh_shape == {"dp": 2, "tp": 2}
    prompt = _prompt(5, seed=3)
    slot, tok = e.admit(prompt)
    toks = [tok]
    for _ in range(4):
        toks.append(e.decode_step()[slot])
    e.evict(slot, "length")
    ref_slot, ref_tok = eng.admit(prompt)
    ref = [ref_tok]
    for _ in range(4):
        ref.append(eng.decode_step()[ref_slot])
    eng.evict(ref_slot, "length")
    assert toks == ref


# ---------------------------------------------------------------------------
# the weights the ring engine holds (ISSUE 29; both engines side by side
# in tests/test_paged_decode.py)
# ---------------------------------------------------------------------------

def _tokens(e, prompt, n):
    slot, tok = e.admit(prompt)
    out = [tok] + [e.decode_step()[slot] for _ in range(n)]
    e.evict(slot, "length")
    return out


def test_ring_engine_weights_are_a_snapshot_in_the_compute_dtype():
    """The engine holds what the parameters were when it was built, cast
    once: a later change to the network's float32 masters reaches no
    dispatch, and the held weights take less than the masters."""
    from mxnet_tpu import tracing

    mx.random.seed(1)
    net = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
                        n_layers=N_LAYERS, max_len=MAX_LEN)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 4), np.float32)))
    e = generate.GenerationEngine(
        net, slots=2, cache_len=16, buckets=[8], dtype_policy="bf16_mixed",
        sampling=generate.SamplingConfig(greedy=True))
    span = [r for r in tracing.records() if r["name"] == "engine.weights"][-1]
    masters = sum(int(np.prod(p.shape)) * 4
                  for p in net.collect_params().values())
    assert e.param_bytes == span["args"]["held_bytes"] < masters
    assert span["args"]["cast_bytes"] == masters - e.param_bytes > 0
    prompt = _prompt(5, seed=9)
    before = _tokens(e, prompt, 4)
    for p in net.collect_params().values():
        p.set_data(nd.array(np.zeros(p.shape, np.float32)))
    assert _tokens(e, prompt, 4) == before
    assert len(set(before)) > 1, "a model of zeros would pass as well"


def test_ring_engine_mesh_prefill_same_on_held_and_master_weights(lm):
    """dp=2,tp=2 under ``bf16_mixed``: a prefill on the held (cast)
    weights gives bit for bit what the same program gives on the
    float32 masters placed under the same shardings."""
    import jax
    import jax.numpy as jnp

    e = generate.GenerationEngine(
        lm, slots=2, cache_len=16, buckets=[8], mesh="dp=2,tp=2",
        dtype_policy="bf16_mixed",
        sampling=generate.SamplingConfig(greedy=True))
    assert {str(a.dtype) for a in e._params} == {"bfloat16", "float32"}
    masters = tuple(jax.device_put(p.data()._data, a.sharding)
                    for p, a in zip(lm.collect_params().values(), e._params))
    tokens = np.zeros((1, 8), np.int32)
    tokens[0, :5] = _prompt(5, seed=3)

    def prefill(weights):
        tok, logits, ck, _cv = e._jit_prefill(
            weights, jnp.copy(e._cache_k), jnp.copy(e._cache_v), tokens,
            np.int32(5), np.int32(1), jax.random.PRNGKey(0))
        return int(tok[0]), np.asarray(logits), \
            np.asarray(ck.astype(np.float32))

    got, want = prefill(e._params), prefill(masters)
    assert got[0] == want[0]
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    assert np.abs(got[2]).max() > 0


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
    e = generate.GenerationEngine(
        lm, slots=3, cache_len=16, buckets=[8],
        sampling=generate.SamplingConfig(greedy=True))
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
