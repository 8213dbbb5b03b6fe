"""The sparse GQA/RoPE decoder of the zoo and block-diffusion decoding on
the paged engine (ISSUE 28), against the benchmark's plain reference
(``benchmark/lib/reference/sdar_moe.py``) at a small size on the CPU,
float32 policy, seeded random weights:

(a) the package model's full forward against the reference's;
(b) chunked prefill, then denoise and commit passes through the paged
    cache, against the reference's generation loop over full forwards:
    the same tokens, fixed at the same passes, and every denoise pass's
    logits at the masked positions;
(c) the share test: the expert layer told to hold experts [0, 4) and
    [4, 8) gives two parts that add up to the uncut layer's output;
(d) a planted fault each (a commit pass that writes nothing, a denoise
    pass that writes the pool, an expert's part left out) fails (b).

Sizes: hidden 64, 2 layers, 4 query / 2 key-value heads of 16, 8 experts
top-2 of width 32, vocabulary 512, block length 4 (the configuration
file's ``rehearsal`` block).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import generate, nd, telemetry, tracing
from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM
from mxnet_tpu.parallel.moe import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import programs  # noqa: E402
from benchmark.drivers import serve_blockgen  # noqa: E402
from benchmark.lib import manifest, weights  # noqa: E402

BL = 4


@pytest.fixture(scope="module")
def cfg():
    return manifest.config(manifest.manifest(), "sdar-30b-a3b-chat",
                           rehearse=True)


@pytest.fixture(scope="module")
def fam(cfg):
    return weights.family(cfg)


@pytest.fixture(scope="module")
def arrays(cfg, fam):
    """The benchmark's weights, the blocks' matrices four times as large
    (exact in bfloat16): at a hidden size of 64 a standard deviation of
    0.02 leaves the layers so weak that every masked position yields the
    same token."""
    made = weights.make_params(cfg, 28)
    return [a * 4 if name.startswith("h") and kind == "matrix" else a
            for a, (name, _shape, kind) in zip(made, fam.param_specs(cfg))]


@pytest.fixture(scope="module")
def net(cfg, fam, arrays):
    model = programs.program(cfg).build_net(cfg)
    programs.set_weights(model, fam.param_specs(cfg), arrays)
    return model


def make_engine(net, steps, slots=2):
    return generate.PagedGenerationEngine(
        net, slots=slots, cache_len=64, page_size=8, prefill_chunk=8,
        spec_k=0, prefix_share=True, dtype_policy="f32",
        denoise_steps=steps, sampling=generate.SamplingConfig(greedy=True))


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def test_model_forward_matches_the_reference(cfg, fam, arrays, net):
    toks = np.random.default_rng(0).integers(0, 512, (2, 23))
    with jax.default_matmul_precision("highest"):
        got = net(nd.array(toks.astype(np.float32))).asnumpy()
    want = np.asarray(fam.forward(cfg, arrays, toks.astype(np.int32)))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_config_names_the_cache_shape(net):
    c = net.config
    assert (c["n_kv_heads"], c["d_head"], c["block_length"]) == (2, 16, 4)
    eng = make_engine(net, 2)
    # (pages * page_size, layers, key/value heads * head size): not
    # n_heads * (d_model // n_heads)
    assert eng.pool_shape == (eng.num_pages * 8, 2, 2 * 16)
    # a layer count that tiles badly (6) folds the heads into it
    six = MoEDecoderLM(64, 256, 6, 4, 4, 128, 4, 2, 16, block_length=4,
                       mask_token_id=63)
    six.initialize(mx.init.Zero())
    eng6 = generate.PagedGenerationEngine(
        six, slots=1, cache_len=16, page_size=8, prefill_chunk=8, spec_k=0,
        dtype_policy="f32", denoise_steps=2)
    assert eng6.pool_shape == (eng6.num_pages * 8, 6 * 4, 128)
    assert eng.dispatch_shapes() == [(1, 8), (2, BL)]


def run_engine(eng, prompts, n_new, stagger=0):
    """Drive the engine by hand: admit ``prompts`` (the second after
    ``stagger`` passes of the first, so that the two slots are on
    different passes of one program), decode until each has ``n_new``
    tokens.  A call launches a pass and returns what the oldest pass
    still unread gave (``passes_ahead`` stay queued), with ``last_pass``
    and ``last_logits`` of that one.  Returns per prompt (tokens,
    fixed_at, [per pass: (start, pass, masked, logits of the block)])."""
    got = {}
    slots = {}
    T = eng._denoise_steps

    def admit(i):
        slot, tok = eng.admit(prompts[i])
        assert tok is None         # the last chunk yields no token
        slots[slot] = i
        got[i] = ([], [], [])

    admit(0)
    steps = 0
    assert eng.last_pass is None
    while any(len(got[i][0]) < n_new for i in got) or len(got) < len(prompts):
        if len(got) < len(prompts) and steps >= stagger:
            admit(len(got))
        last = eng.last_pass
        out = eng.decode_step()
        assert set(out) <= set(slots)
        assert (eng.last_pass is last) == (steps < eng.passes_ahead)
        if eng.last_pass is not last:      # this call read a pass
            ran, logits = eng.last_pass, eng.last_logits
            for s, i in slots.items():
                if not ran["on"][s]:
                    continue           # admitted after it was launched
                number = int(ran["pass"][s])
                if number <= T and len(got[i][0]) < n_new:
                    got[i][2].append((int(ran["start"][s]), number,
                                      ran["masked"][s], logits[s]))
                assert (number == T + 1) == isinstance(
                    out[s], generate.BlockTokens)
                got[i][0].extend(out[s])
                got[i][1].extend(getattr(out[s], "fixed_at", []))
        else:
            assert all(v == [] for v in out.values())
        steps += 1
        assert steps < 400
    for s in list(slots):
        eng.evict(s, "test")
    return [(got[i][0][:n_new], got[i][1][:n_new], got[i][2])
            for i in range(len(prompts))]


def agrees(cfg, fam, arrays, served, prompt, n_new, steps, tol=2e-4):
    """Whether one request's served tokens, passes of fixing and every
    denoise pass's logits at the masked positions are the reference's."""
    tokens, fixed_at, passes = served
    want_t, want_f, want_p = fam.generate(cfg, arrays, prompt, n_new, steps)
    if tokens != want_t or fixed_at != want_f:
        return False
    if len(passes) < len(want_p):
        return False
    for (start, t, masked, logits), ref in zip(passes, want_p):
        if (start, t, list(masked)) != (ref["start"], ref["pass"],
                                        ref["masked"]):
            return False
        a, b = logits[masked], ref["logits"][np.asarray(ref["masked"])]
        if a.size and np.abs(a - b).max() > tol * np.abs(b).max():
            return False
    return True


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("n_prompt", [16, 21, 11])    # n mod 4 = 0, 1, 3
def test_paged_block_decoding_matches_the_reference(
        cfg, fam, arrays, net, steps, n_prompt):
    """Chunked prefill (chunks of 8, so two or three a prompt) and
    block-diffusion decoding through the paged cache, two slots on
    different passes in one program, 10 new tokens (not a multiple of
    4)."""
    eng = make_engine(net, steps)
    prompts = [prompt_of(n_prompt, 100 + n_prompt), prompt_of(9, 7)]
    with jax.default_matmul_precision("highest"):
        served = run_engine(eng, prompts, 10, stagger=1)
        for s, p in zip(served, prompts):
            assert agrees(cfg, fam, arrays, s, p, 10, steps)
    # the second slot was admitted a pass late: with T > 1 its passes
    # interleave with the first one's
    assert len(served[1][0]) == 10


def test_a_prompt_shorter_than_a_block_needs_no_prefill(cfg, fam, arrays,
                                                        net):
    eng = make_engine(net, 2)
    prompt = prompt_of(3, 5)
    with jax.default_matmul_precision("highest"):
        slot = eng.admit_incremental(prompt)
        assert eng.pending_prefill() == 0 and slot in eng.active_slots()
        eng.evict(slot, "test")
        served = run_engine(eng, [prompt], 6)[0]
        assert agrees(cfg, fam, arrays, served, prompt, 6, 2)


def test_mask_state_is_kept_not_read_from_the_token_id(cfg, fam, arrays,
                                                       net):
    """A prompt made of the mask token's own id decodes as any other."""
    eng = make_engine(net, 2)
    prompt = np.full(10, cfg["assumed"]["mask_token_id"], np.int32)
    with jax.default_matmul_precision("highest"):
        served = run_engine(eng, [prompt], 8)[0]
        assert agrees(cfg, fam, arrays, served, prompt, 8, 2)


def test_token_server_bursts_cuts_and_reports_passes(cfg, fam, arrays, net):
    """Through ``TokenServer.submit``: ticks that deliver nothing, bursts
    of up to four, ``max_new_tokens`` cutting a block's overshoot, the
    pass of fixing on the result, and the counters that tell passes from
    tokens."""
    tracing.reset()
    was_on = telemetry.enabled()
    telemetry.enable()
    eng = make_engine(net, 2)
    before = {k: c.value() for k, c in (
        ("denoise", telemetry.DECODE_DENOISE_PASSES),
        ("commit", telemetry.DECODE_COMMIT_PASSES),
        ("blocks", telemetry.DECODE_BLOCKS_COMMITTED),
        ("tokens", telemetry.DECODE_BLOCK_TOKENS))}
    # the last request ends on the cache's last position (50 + 14 = 64):
    # its passes run ahead of its bursts, and capacity is asked of the
    # blocks delivered, not of those launched
    prompts = [prompt_of(13, 1), prompt_of(18, 2), prompt_of(8, 3),
               prompt_of(50, 4)]
    want = [6, 9, 5, 14]
    stamps = [[] for _ in prompts]
    with jax.default_matmul_precision("highest"), \
            generate.TokenServer(eng, queue_depth=8, deadline_ms=0,
                                 max_new_tokens=16) as server:
        futs = [server.submit(p, max_new_tokens=w,
                              on_token=lambda t, k=k: stamps[k].append(t))
                for k, (p, w) in enumerate(zip(prompts, want))]
        results = [f.result(timeout=120) for f in futs]
        for r, p, w, st in zip(results, prompts, want, stamps):
            ref_t, ref_f, _ = fam.generate(cfg, arrays, p, w, 2)
            assert r["tokens"] == ref_t == st
            assert r["fixed_at"] == ref_f
            assert len(r["confidence"]) == w and max(r["confidence"]) < 0
            assert r["finish_reason"] == "length" and r["ttft_s"] > 0
    if not was_on:
        telemetry.disable()
    moved = {k: c.value() - before[k] for k, c in (
        ("denoise", telemetry.DECODE_DENOISE_PASSES),
        ("commit", telemetry.DECODE_COMMIT_PASSES),
        ("blocks", telemetry.DECODE_BLOCKS_COMMITTED),
        ("tokens", telemetry.DECODE_BLOCK_TOKENS))}
    # blocks: ceil((n mod 4 + wanted) / 4) a request
    blocks = sum(-(-(len(p) % BL + w) // BL) for p, w in zip(prompts, want))
    # passes are launched ahead of their results, and none past the
    # block that covers a request's `max_new_tokens`
    assert moved["blocks"] == moved["commit"] == blocks
    assert moved["denoise"] == 2 * blocks
    assert moved["tokens"] == sum(
        BL * -(-(len(p) % BL + w) // BL) - len(p) % BL
        for p, w in zip(prompts, want))
    spans = [r for r in tracing.records() if r["name"] == "engine.decode"]
    assert spans and all({"denoise", "commit", "emitted"} <= set(r["args"])
                         for r in spans)
    assert sum(r["args"]["emitted"] for r in spans) == moved["tokens"]
    assert sum(r["args"]["denoise"] + r["args"]["commit"]
               for r in spans) == 3 * blocks
    # the expert layer's token counts are of the pass a call read: every
    # call but those that had none to read yet (`passes_ahead` of them
    # each time the slots had stood empty).  2 slots x 4 rows x top-2
    # over 8 experts, two layers
    read = [r for r in spans if "expert_load_max" in r["args"]]
    assert eng.passes_ahead <= len(spans) - len(read) \
        <= eng.passes_ahead * len(prompts)
    assert all(r["args"]["expert_load_mean"] == 2.0 for r in read)
    assert all(r["args"]["expert_load_max"] >= 2 for r in read)
    pre = [r for r in tracing.records() if r["name"] == "engine.prefill"]
    assert pre and all(r["args"]["block"] == BL for r in pre)


def test_capacity_leaves_room_for_a_whole_block(net):
    eng = make_engine(net, 2)
    with pytest.raises(Exception, match="exceeds the paged cache"):
        eng.bucket_for(64)                       # no room for its block
    eng.bucket_for(63)
    slot, _ = eng.admit(prompt_of(55, 4))
    assert not eng.at_capacity(slot)             # block 52..55 open
    for _ in range(3):
        eng.decode_step()
    assert eng.position(slot) == 56 and not eng.at_capacity(slot)
    for _ in range(3):
        eng.decode_step()
    assert eng.position(slot) == 60 and not eng.at_capacity(slot)
    for _ in range(3):
        eng.decode_step()
    # the last block's commit is launched: no pass follows it, and the
    # slot is at capacity once that block has been read
    assert eng.position(slot) == 64 and not eng.at_capacity(slot)
    for _ in range(eng.passes_ahead):
        assert not eng.at_capacity(slot)
        eng.decode_step()
    assert eng.position(slot) == 64 and eng.at_capacity(slot)
    assert not eng._inflight


def test_engine_refuses_what_block_decoding_cannot_do(net):
    with pytest.raises(Exception, match="greedy"):
        generate.PagedGenerationEngine(
            net, slots=1, cache_len=32, page_size=8, prefill_chunk=8,
            spec_k=2, dtype_policy="f32")
    with pytest.raises(Exception, match="multiples"):
        generate.PagedGenerationEngine(
            net, slots=1, cache_len=32, page_size=8, prefill_chunk=6,
            spec_k=0, dtype_policy="f32")
    with pytest.raises(Exception, match="denoise_steps"):
        generate.PagedGenerationEngine(
            net, slots=1, cache_len=32, page_size=8, prefill_chunk=8,
            spec_k=0, dtype_policy="f32", denoise_steps=5)


# -- (c) the share test -------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer(cfg, fam, arrays):
    """The expert layer told to hold experts [0, 4) and [4, 8): each
    routes over all 8 and computes its own experts' part; the two parts
    add up to what the uncut reference gives for the whole layer, and
    both report the whole layer's token counts."""
    specs = fam.param_specs(cfg)
    at = {name: i for i, (name, _s, _k) in enumerate(specs)}
    wr, wg, wu, wd = (arrays[at["h0_" + n]].astype(jnp.float32) for n in (
        "router_weight", "experts_gate_weight", "experts_up_weight",
        "experts_down_weight"))
    x = jax.random.normal(jax.random.key(3), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _margin = fam.experts(cfg, x, wr, wg, wu, wd)
        parts = []
        for first in (0, 4):
            held = slice(first * 32, (first + 4) * 32)
            out, counts = routed_experts(
                x, wr.T, wg[:, held], wu[:, held], wd[held], 2, 32,
                first=first)
            parts.append(np.asarray(out))
            assert int(counts.sum()) == 24 * 2 and counts.shape == (8,)
        ref_parts = [np.asarray(fam.experts(
            cfg, x, wr, wg[:, f * 32:(f + 4) * 32],
            wu[:, f * 32:(f + 4) * 32], wd[f * 32:(f + 4) * 32],
            held=(f, 4))[0]) for f in (0, 4)]
    want = np.asarray(want)
    scale = np.abs(want).max()
    assert np.abs(parts[0] + parts[1] - want).max() <= 1e-5 * scale
    for mine, ref in zip(parts, ref_parts):
        assert np.abs(mine - ref).max() <= 1e-5 * scale
    # neither part is the whole: the cut really cuts
    assert np.abs(parts[0] - want).max() > 1e-2 * scale


def test_model_told_its_share_holds_only_those_experts():
    part = MoEDecoderLM(512, 64, 1, 4, 2, 16, 8, 2, 32, block_length=4,
                        mask_token_id=509, experts_held=(4, 4))
    shapes = {p.name.split("_", 1)[1]: p.shape
              for p in part.collect_params().values()}
    assert shapes["h0_experts_gate_weight"] == (64, 4 * 32)
    assert shapes["h0_experts_down_weight"] == (4 * 32, 64)
    assert shapes["h0_router_weight"] == (8, 64)       # routes over all


# -- (d) planted faults -------------------------------------------------------

@pytest.fixture
def net_less_one_expert(cfg, fam, arrays):
    """The same weights with the first expert of every layer adding
    nothing."""
    specs = fam.param_specs(cfg)
    model = programs.program(cfg).build_net(cfg)
    programs.set_weights(model, specs,
                         serve_blockgen.without_an_expert(cfg, arrays))
    return model


@pytest.mark.parametrize("fault", ["commit_unwritten", "denoise_written",
                                   "expert_left_out"])
def test_planted_faults_fail_the_comparison(cfg, fam, arrays, net,
                                            net_less_one_expert, fault):
    eng = make_engine(net_less_one_expert if fault == "expert_left_out"
                      else net, 2)
    if fault != "expert_left_out":
        serve_blockgen.plant_write_fault(eng, fault)
    prompts = [prompt_of(21, 121), prompt_of(9, 7)]
    with jax.default_matmul_precision("highest"):
        served = run_engine(eng, prompts, 10, stagger=1)
        assert not all(agrees(cfg, fam, arrays, s, p, 10, 2)
                       for s, p in zip(served, prompts))
