"""Paged KV-cache decode (ISSUE 16 tentpole): the page-pool engine's
correctness contract against the full re-forward, plus each serving
lever.

Tier-1 guards:
* paged greedy decode is TOKEN-IDENTICAL to greedy decoding by full
  re-forward (float32, no cache) on one device, and a dp=2,tp=2 mesh
  decodes the one-device engine's tokens (the pool resolves through
  the layout registry's `pool_k|v` rule);
* chunked prefill produces the same tokens and decode logits as a
  single-chunk (monolithic) prefill of the same prompt;
* speculative decoding emits exactly the non-speculative sequence —
  greedy and sampled (the position-keyed PRNG stream makes the
  accept/reject path consume the same keys either way);
* prefix sharing attaches registered pages with refcounts, parks
  refcount-0 pages in the retained LRU on eviction, re-attaches them,
  and reclaims them under pool pressure;
* admission raises the typed `Overloaded` reasons (``slots`` /
  ``pages``) and the TokenServer's end-to-end output (chunked +
  shared + speculative) matches the full re-forward's;
* the new bench-mode ledger metrics gate in the right direction;
* the pool is indexed on its leading dimension (ISSUE 27), and for a
  model that takes its caches as rows it holds one row a (layer, token)
  (ISSUE 32): both index operations of the one dispatch address the
  donated pool's dimension 0 with nothing pool-sized before them, the
  TPU compiler (no chip: a described v5e) copies no pool, builds no
  view of all layers' caches and expands no gather into a loop at
  OPT-1.3B's widths, mixed batches match the full re-forward, and a
  mesh resolves the pool under its own ``kv_pool`` rule.

Engine programs stay tiny (d_model 32, cache 24) for the tier-1
budget; every paged engine compiles at most three chunk signatures.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import generate, nd
from mxnet_tpu.generate import Overloaded

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from transformer_lm import TransformerLM  # noqa: E402

VOCAB, D_MODEL, N_HEADS, N_LAYERS, MAX_LEN = 48, 32, 2, 2, 24


def _small_lm():
    mx.random.seed(0)
    net = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL,
                        n_heads=N_HEADS, n_layers=N_LAYERS,
                        max_len=MAX_LEN)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 4), np.float32)))
    return net


@pytest.fixture(scope="module")
def lm():
    return _small_lm()


@pytest.fixture(scope="module")
def paged(lm):
    return generate.PagedGenerationEngine(
        lm, slots=3, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        sampling=generate.SamplingConfig(greedy=True))


def _prompt(n=5, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, n) \
        .astype(np.int32)


def test_cached_is_for_models_that_declare_their_caches(paged):
    """``cached`` reads per-layer caches off a model that declares them
    (``tests/test_hybrid_decoder.py``); a model of K and V layers alone
    is told so."""
    with pytest.raises(mx.MXNetError, match="layer_caches"):
        paged.cached([0])


def _step(eng):
    """One decode step launched and read at once, by hand: a call of
    ``decode_step`` hands over the tokens of a step launched a call
    before, ``drain`` reads what is still in flight."""
    out = eng.decode_step()
    for more in eng.drain():
        for slot, toks in more.items():
            out[slot] = out.get(slot, []) + toks
    return out


def _drain(eng, slot, steps):
    """``steps`` decode steps for one slot, launched ahead of their
    results as a server's are, then all read: the slot's tokens."""
    out = []
    for _ in range(steps):
        out.extend(eng.decode_step()[slot])
    for more in eng.drain():
        out.extend(more.get(slot, []))
    return out


def _greedy_reference(lm, prompt, n):
    """``n`` greedy tokens by full re-forward (``lm(tokens)``, argmax
    at the last position, float32): the reference independent of any
    cache.  Every forward runs at the one shape (1, MAX_LEN), zeros
    after the sequence: under the causal mask they reach no position
    before them."""
    seq = np.zeros(MAX_LEN, np.float32)
    seq[:len(prompt)] = prompt
    out = []
    for at in range(len(prompt), len(prompt) + n):
        logits = np.asarray(lm(nd.array(seq[None]))._data)[0]
        out.append(int(logits[at - 1].argmax()))
        if at < MAX_LEN:
            seq[at] = out[-1]
    return out


# ---------------------------------------------------------------------------
# paged == full re-forward on one device; meshed == one device
# ---------------------------------------------------------------------------

def test_paged_greedy_matches_full_forward(lm, paged):
    """The tentpole's correctness bar: same prompt, same greedy
    tokens, token for token — the page-table gather/scatter is
    semantically a linear cache of the sequence."""
    prompt = _prompt(9, seed=3)
    p_slot, p_tok = paged.admit(prompt)
    got = [p_tok] + _drain(paged, p_slot, 8)
    paged.evict(p_slot, "length")
    assert got == _greedy_reference(lm, prompt, 9)


@pytest.mark.parametrize("mesh", [None, "dp=2,tp=2"],
                         ids=["one_device", "dp2_tp2"])
def test_pages_gathered_whole_match_full_forward(lm, mesh):
    """Pages of 8 float32 rows are whole sublane tiles, so a layer is
    gathered page by page from the pool seen as (layers * pages, page,
    heads * d_head) (ISSUE 32): two slots side by side, prompts that
    end inside a page and idle rows on the trash page, decode the full
    re-forward's greedy tokens, on one device and under a mesh."""
    e = generate.PagedGenerationEngine(
        lm, slots=3, cache_len=MAX_LEN, page_size=8, prefill_chunk=8,
        mesh=mesh, sampling=generate.SamplingConfig(greedy=True))
    prompts = [_prompt(5, seed=61), _prompt(11, seed=62)]
    got = _side_by_side(e, prompts, 7)
    for prompt, toks in zip(prompts, got):
        assert toks == _greedy_reference(lm, prompt, len(toks))


def test_paged_mesh_matches_single_device(lm, paged):
    """dp=2,tp=2: the pool shards through the layout registry
    (slots/pages over data axes, heads over tp) and decodes the same
    greedy tokens as the single-device paged engine."""
    e = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=16, page_size=4,
        prefill_chunk=8, mesh="dp=2,tp=2",
        sampling=generate.SamplingConfig(greedy=True))
    assert e.layout_name == "fsdp_tp"
    assert e.mesh_shape == {"dp": 2, "tp": 2}
    prompt = _prompt(5, seed=3)
    slot, tok = e.admit(prompt)
    toks = [tok] + _drain(e, slot, 4)
    e.evict(slot, "length")
    p_slot, p_tok = paged.admit(prompt)
    ref = [p_tok] + _drain(paged, p_slot, 4)
    paged.evict(p_slot, "length")
    assert toks == ref


# ---------------------------------------------------------------------------
# chunked prefill == monolithic prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_monolithic(lm, paged):
    """A 10-token prompt prefilled in 3-token chunks produces the same
    first token, the same decode tokens, and the same decode-step
    logits as the fixture's single-chunk prefill."""
    chunked = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=MAX_LEN, page_size=4, prefill_chunk=3,
        sampling=generate.SamplingConfig(greedy=True))
    prompt = _prompt(10, seed=4)
    c_slot, c_tok = chunked.admit(prompt)
    m_slot, m_tok = paged.admit(prompt)  # chunk 8 < 10: still 2 chunks
    assert c_tok == m_tok
    c_toks, m_toks = [], []
    for _ in range(5):
        c_toks.extend(_step(chunked)[c_slot])
        m_toks.extend(_step(paged)[m_slot])
        np.testing.assert_allclose(chunked.last_logits[0],
                                   paged.last_logits[0],
                                   rtol=0, atol=2e-5)
    chunked.evict(c_slot, "length")
    paged.evict(m_slot, "length")
    assert c_toks == m_toks


# ---------------------------------------------------------------------------
# speculative decoding == plain decoding
# ---------------------------------------------------------------------------

def _gen_tokens(eng, prompt, n):
    slot, tok = eng.admit(prompt)
    out = [tok]
    while len(out) < n:
        out.extend(eng.decode_step()[slot])
    eng.evict(slot, "length")
    return out[:n]


def test_spec_greedy_matches_plain(lm):
    """n-gram drafts + one-shot verify emit exactly the sequential
    greedy tokens; a repetitive prompt guarantees drafts actually
    fire (accept-path coverage, not just the no-draft fallback)."""
    spec = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        spec_k=3, spec_ngram=2,
        sampling=generate.SamplingConfig(greedy=True))
    plain = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        spec_k=0, sampling=generate.SamplingConfig(greedy=True))
    prompt = np.tile(_prompt(3, seed=7), 3)[:8].astype(np.int32)
    a = _gen_tokens(spec, prompt, 15)
    b = _gen_tokens(plain, prompt, 15)
    assert a == b
    assert spec.spec_accept_rate() is not None, \
        "the repetitive prompt must have produced drafts"
    assert spec._spec_accepted > 0, \
        "at least one draft must verify (accept-path coverage)"


def test_spec_sampling_matches_plain_under_seed(lm):
    """Sampled decode: the verify step's position-keyed PRNG stream
    (fold_in(lane_key, pos)) makes speculative output bit-identical to
    the plain engine under the same mx.random.seed."""
    scfg = generate.SamplingConfig(greedy=False, top_k=8,
                                   temperature=0.9)
    spec = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        spec_k=3, spec_ngram=2, sampling=scfg)
    plain = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        spec_k=0, sampling=scfg)
    prompt = np.tile(_prompt(3, seed=7), 3)[:8].astype(np.int32)
    mx.random.seed(11)
    a = _gen_tokens(spec, prompt, 15)
    mx.random.seed(11)
    b = _gen_tokens(plain, prompt, 15)
    assert a == b
    assert all(0 <= t < VOCAB for t in a)


# ---------------------------------------------------------------------------
# prefix sharing: attach / refcount / retained LRU / reclaim
# ---------------------------------------------------------------------------

def test_prefix_attach_refcount_and_eviction(lm):
    e = generate.PagedGenerationEngine(
        lm, slots=3, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        prefix_share=True,
        sampling=generate.SamplingConfig(greedy=True))
    prompt = _prompt(9, seed=7)        # 2 full shareable pages (8 tok)
    s1, t1 = e.admit(prompt)
    assert e.last_prefix_hit_tokens == 0, "cold admit cannot hit"
    shared = [int(p) for p in e._page_table[s1][:2]]
    s2, t2 = e.admit(prompt)
    assert e.last_prefix_hit_tokens == 8
    assert t2 == t1, "shared-prefix admission must sample the same token"
    assert [int(p) for p in e._page_table[s2][:2]] == shared
    assert all(e._page_ref[p] == 2 for p in shared)
    # the two lanes must now decode identical greedy tokens
    steps = {s: [] for s in (s1, s2)}
    for _ in range(4):
        out = _step(e)
        for s in steps:
            steps[s].extend(out[s])
    assert steps[s1] == steps[s2] and len(steps[s1]) == 4
    # detach one user: refcount drops, pages stay mapped for the other
    e.evict(s2, "eos")
    assert all(e._page_ref[p] == 1 for p in shared)
    # detach the last user: refcount-0 registered pages park in the
    # retained LRU (still hittable), not the free list
    e.evict(s1, "eos")
    assert all(e._page_ref[p] == 0 for p in shared)
    assert set(shared) <= set(e._reclaim)
    assert e.occupancy()["prefix_cached_pages"] >= 2
    s3, _t3 = e.admit(prompt)
    assert e.last_prefix_hit_tokens == 8, "retained pages must re-attach"
    assert [int(p) for p in e._page_table[s3][:2]] == shared
    e.evict(s3, "eos")
    # pool pressure: admitting DISTINCT prompts until pages run out
    # must reclaim the retained pages (unregistering them) before
    # raising Overloaded("pages")
    held = []
    with pytest.raises(Overloaded) as ei:
        for i in range(e.slots + 1):
            held.append(e.admit(_prompt(9, seed=20 + i))[0])
    assert ei.value.reason in ("slots", "pages")
    assert not (set(shared) & set(e._reclaim)), \
        "pool pressure must reclaim retained prefix pages"
    for s in held:
        e.evict(s, "length")


def test_paged_overloaded_pages(lm):
    # one usable page against two slots: the second admission must
    # fail typed on pages (slot still free) and roll back cleanly
    e = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=4, page_size=4, prefill_chunk=4,
        num_pages=2, prefix_share=False,
        sampling=generate.SamplingConfig(greedy=True))
    s1, _ = e.admit(_prompt(3, seed=1))
    assert e.free_slots() == 1
    with pytest.raises(Overloaded) as ei:
        e.admit(_prompt(3, seed=2))
    assert ei.value.reason == "pages"
    assert len(e._free_pages) == 0, "failed admission must roll back"
    e.evict(s1, "length")
    assert len(e._free_pages) == 1


def test_paged_overloaded_slots(lm):
    e = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=4, page_size=4, prefill_chunk=4,
        num_pages=3, prefix_share=False,
        sampling=generate.SamplingConfig(greedy=True))
    s1, _ = e.admit(_prompt(3, seed=1))
    s2, _ = e.admit(_prompt(3, seed=2))
    with pytest.raises(Overloaded) as ei:
        e.admit(_prompt(3, seed=3))
    assert ei.value.reason == "slots"
    e.evict(s2, "eos")
    s3, _ = e.admit(_prompt(3, seed=4))
    assert s3 == s2, "evicted lane must be reused (LIFO)"
    for s in (s1, s3):
        e.evict(s, "length")


# ---------------------------------------------------------------------------
# TokenServer end to end: every lever on == full re-forward
# ---------------------------------------------------------------------------

def test_server_paged_levers_match_full_forward(lm):
    """The integration bar: a TokenServer with chunked prefill, prefix
    sharing, AND speculation serves the greedy tokens of the full
    re-forward, prompt for prompt."""
    paged_eng = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=MAX_LEN, page_size=4, prefill_chunk=3,
        spec_k=2, spec_ngram=2, prefix_share=True,
        sampling=generate.SamplingConfig(greedy=True))
    prompts = [_prompt(9, seed=8), _prompt(5, seed=9),
               _prompt(9, seed=8)]   # the repeat exercises the hit path
    ref = [_greedy_reference(lm, p, 6) for p in prompts]
    got = []
    srv = generate.TokenServer(paged_eng, max_new_tokens=6)
    try:
        for p in prompts:
            got.append(srv.generate(p, max_new_tokens=6,
                                    timeout=60).tokens)
    finally:
        srv.close()
    assert got == ref
    assert paged_eng.prefix_hit_rate() is not None
    assert paged_eng.prefix_hit_rate() > 0, \
        "the repeated prompt must hit the prefix cache"


# ---------------------------------------------------------------------------
# bench-mode metrics gate in the right direction
# ---------------------------------------------------------------------------

def test_perf_gate_directions_for_paged_metrics():
    import perf_gate

    assert perf_gate.higher_is_better(
        "lm_decode_tokens_per_sec_per_user", "tokens/sec/user")
    assert perf_gate.higher_is_better(
        "lm_decode_prefix_share_tokens_per_sec", "tokens/sec")
    assert perf_gate.higher_is_better(
        "lm_decode_prefix_hit_rate", "ratio")
    assert perf_gate.higher_is_better(
        "lm_decode_spec_accepted_per_step", "tokens/step")
    assert not perf_gate.higher_is_better(
        "lm_decode_ttft_interference_p99_ms", "ms")


# ---------------------------------------------------------------------------
# the token-major pool: structure of the one dispatch (ISSUE 27)
# ---------------------------------------------------------------------------

def _dispatch_args(eng, shape):
    """The engine's own zero arguments for one of its dispatch shapes."""
    prefill, decode, verify = eng.dispatch_shapes()
    return eng._dispatch_args(
        {"prefill": prefill, "decode": decode, "verify": verify}[shape])


@pytest.mark.parametrize("page_size", [4, 8], ids=["by_row", "by_page"])
@pytest.mark.parametrize("shape", ["decode", "prefill", "verify"])
def test_pool_is_indexed_on_its_leading_dimension(lm, shape, page_size):
    """Both index operations of the dispatch take the donated pool
    itself — no transpose or copy of it first — and address its
    dimension 0: a gather a layer of whole rows (of whole pages, from
    the pool seen page by page, where a page is a whole number of
    sublane tiles: 8 rows of float32), one scatter of the chunk's rows
    of every layer; nothing else in the program has the pool's size."""
    import jax

    eng = generate.PagedGenerationEngine(
        lm, slots=3, cache_len=MAX_LEN, page_size=page_size,
        prefill_chunk=8, spec_k=2,
        sampling=generate.SamplingConfig(greedy=True))
    # one row a (layer, token): TransformerLM's config says cache_rows
    assert eng.pool_shape == (N_LAYERS * eng.num_pages * page_size,
                              D_MODEL)
    args = _dispatch_args(eng, shape)
    closed = jax.make_jaxpr(eng._jit_chunk)(*args)
    (call,) = closed.jaxpr.eqns          # the jitted chunk_fn itself
    body = call.params["jaxpr"].jaxpr
    n_params = len(eng._params)
    pools = list(body.invars[n_params:n_params + 2])
    pool_size = int(np.prod(eng.pool_shape))
    by_page = page_size == 8
    # the pool seen page by page: the same bytes under the TPU's tiling
    views = [e for e in body.eqns if e.primitive.name == "reshape"
             and any(e.invars[0] is p for p in pools)]
    assert len(views) == (2 * N_LAYERS if by_page else 0)
    for e in views:
        assert e.outvars[0].aval.shape == (
            N_LAYERS * eng.num_pages, page_size, D_MODEL)
    sources = pools + [e.outvars[0] for e in views]

    gathers = [e for e in body.eqns if e.primitive.name == "gather"
               and any(e.invars[0] is p for p in sources)]
    scatters = [e for e in body.eqns if e.primitive.name == "scatter"
                and any(e.invars[0] is p for p in pools)]
    assert len(gathers) == 2 * N_LAYERS and len(scatters) == 2, \
        (gathers, scatters)
    nb, nc = args[4].shape
    for e in gathers:
        dn = e.params["dimension_numbers"]
        assert tuple(dn.start_index_map) == (0,)
        assert tuple(dn.collapsed_slice_dims) == (0,)
        assert tuple(e.params["slice_sizes"]) == (
            (1, page_size, D_MODEL) if by_page else (1, D_MODEL))
        # a layer's rows of every slot, as the model takes them
        assert int(np.prod(e.outvars[0].aval.shape)) == \
            nb * eng.cache_len * D_MODEL
    for e in scatters:
        dn = e.params["dimension_numbers"]
        assert tuple(dn.scatter_dims_to_operand_dims) == (0,)
        assert tuple(dn.inserted_window_dims) == (0,)
        # a row a (layer, chunk position)
        assert e.invars[2].aval.shape == (N_LAYERS * nb * nc, D_MODEL)
        # padded positions collide on the trash page
        assert not e.params["unique_indices"]
    # the pools reach nothing but their gathers and their scatter (no
    # transpose or copy of a pool comes before either), and only the
    # views' and the scatters' results have the pool's size
    for e in body.eqns:
        if any(e is x for x in views + gathers + scatters):
            continue
        assert not any(v is p for v in e.invars for p in sources), e
        for v in e.outvars:
            assert int(np.prod(v.aval.shape)) < pool_size, e


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) TPU v5e chip to compile for; the
    TPU's compiler is loaded here and nowhere at import."""
    mp = pytest.MonkeyPatch()
    for k, v in (("TPU_LOG_DIR", "disabled"), ("TPU_SKIP_MDS_QUERY", "1"),
                 ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                 ("TPU_WORKER_HOSTNAMES", "localhost")):
        if k not in os.environ:
            mp.setenv(k, v)
    try:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # noqa: BLE001 - no compiler, no test
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def wide_engine():
    """The serving cell's engine at OPT-1.3B's widths (d_model 2048, 32
    heads, page 16, 8 slots of 1024 positions, 513 pages, bf16 cache),
    cut to 6 layers and a small FFN and vocabulary: the pool keeps its
    real rows, and at 0.2 GB is past what the compiler would move into
    the chip's fast memory whole (as it does a pool of 2 layers, which
    the cell's 24 are as far from)."""
    net = TransformerLM(vocab_size=256, d_model=2048, n_heads=32,
                        n_layers=6, d_ff=256, max_len=1024)
    net.initialize(mx.init.Zero())
    return generate.PagedGenerationEngine(
        net, slots=8, cache_len=1024, page_size=16, num_pages=513,
        prefill_chunk=32, spec_k=0, dtype_policy="bf16_mixed",
        sampling=generate.SamplingConfig(greedy=True))


@pytest.fixture(scope="module")
def wide_moe_engine():
    """The block-diffusion serving cell's engine (ISSUE 28) at
    SDAR-30B-A3B's widths and the cell's sizes: d_model 2048, 32 query
    and 4 key/value heads of 128, its 6 layers (the pool's rows depend
    on them), experts of width 768, blocks of 4, 32 slots of 1024
    positions, page 16, 2049 pages, prefill chunks of 256, bf16 weights
    and cache; cut to 8 experts (top 2) and a small vocabulary."""
    from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM

    net = MoEDecoderLM(
        vocab_size=256, d_model=2048, n_layers=6, n_heads=32, n_kv_heads=4,
        d_head=128, n_experts=8, top_k=2, d_expert=768, block_length=4,
        mask_token_id=255, max_len=1024, dtype="bfloat16")
    net.initialize(mx.init.Zero())
    return generate.PagedGenerationEngine(
        net, slots=32, cache_len=1024, page_size=16, num_pages=2049,
        prefill_chunk=256, spec_k=0, dtype_policy="bf16_mixed",
        denoise_steps=2, sampling=generate.SamplingConfig(greedy=True))


@pytest.fixture(scope="module")
def wide_hybrid_engine():
    """The hybrid serving cell's engine (ISSUE 33) at Ling-3.0-flash's
    widths and the cell's sizes: d_model 2560, 32 heads of 128, a latent
    of 512 + 64, 32 slots of 9216 positions, page 16, 18433 pages,
    prefill chunks of 512, bf16 weights and cache; cut to one KDA layer
    (per-slot state) with a dense feed-forward and one MLA layer (latent
    pages) with an expert layer of 8 experts of which 2 are held, and a
    small vocabulary."""
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    net = HybridDecoderLM(
        vocab_size=256, d_model=2560, mixers=["kda", "mla"],
        ffns=["dense", "moe"], n_heads=32, d_k=128, d_v=128, conv_kernel=4,
        kda_lower_bound=-5, d_nope=128, d_rope=64, d_latent=512, d_ff=256,
        n_experts=8, top_k=2, d_expert=768, n_group=4, topk_group=2,
        routed_scaling=2.5, max_len=9216, experts_held=(0, 2),
        dtype="bfloat16")
    net.initialize(mx.init.Zero())
    return generate.PagedGenerationEngine(
        net, slots=32, cache_len=9216, page_size=16, num_pages=18433,
        prefill_chunk=512, spec_k=0, prefix_share=False,
        dtype_policy="bf16_mixed",
        sampling=generate.SamplingConfig(greedy=True))


# the optimized HLO of the two accepted serving cells' programs at the
# fixtures' sizes, as commit 686993b (the parent of ISSUE 33, which
# changed the cache protocol) compiled them under this JAX: sha256 of
# the text with the tables of source lines and every `metadata={...}`
# taken out.  A change that means to alter neither program leaves them;
# one that means to re-bases them from a tree whose cells were measured:
# the two of the expert model are ISSUE 36's (its expert layers are the
# grouped kernels), as measured by PR 36's chip runs, hashed since ISSUE
# 37 with each kernel's module printed without source locations
# (`_kernel_sha256`: the serialized module names the line of every frame
# above the kernel's call, so the hash as PR 36 took it changed with any
# line added to `generate.py`; PR 36's tree gives these two under the
# new rule and its own two under the old).
HLO_JAX = "0.9.0"
HLO_SHA256 = {
    ("opt", "decode"):
        "b17b1c0056fb5bef84bb147d42be94eb4bb1d74b145483c06df8c18307abcaa7",
    ("opt", "prefill"):
        "78c914d33623a7f9f7a783b7521c7a1c5273ab4a0688605444e5d80505690a46",
    ("moe", "decode"):
        "227c00586e8ac6c03765d93f7acedd1bc477d82a645c949f961c12b880adaed6",
    ("moe", "prefill"):
        "cc91289480c4fb1550e9a9007b3f253f910d511be2901770787240ed2f3e0b79",
}


def _kernel_sha256(body):
    """sha256 of a Pallas kernel's module (the base64 ``body`` of its
    ``tpu_custom_call``) printed without source locations: the
    serialized module carries the file, function and line of every
    frame that led to the kernel's call (``generate.py``, the model,
    ``parallel/moe.py``), so as it stands it changes with any line
    added above one of them."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True    # stable_mosaic.*
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        asm = module.operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()


def _program_sha256(text):
    import hashlib
    import re

    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"([^"]+)"',
                  lambda m: '"body":"%s"' % _kernel_sha256(m.group(1)), text)
    lines = text.split("\n")
    first = next(i for i, line in enumerate(lines)
                 if line.rstrip().endswith("{") and "(" in line)
    return hashlib.sha256("\n".join(lines[first:]).encode()).hexdigest()


_COMPILED = {}      # (engine, shape) -> what the tests below share


def _compile_for(chip, eng, shape):
    """The dispatch of ``shape`` compiled for the described chip (nothing
    runs; a compile for a described chip is written to the persistent
    cache but cannot be read back without one, so the cache is off).
    Compiled once an engine and shape for the tests of this module."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if (id(eng), shape) in _COMPILED:
        return _COMPILED[id(eng), shape]

    def struct(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=chip)

    args = jax.tree_util.tree_map(struct, eng._dispatch_args(shape))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with _time_limit(300):
            compiled = eng._jit_chunk.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    _COMPILED[id(eng), shape] = compiled
    return compiled


# the programs' temporaries at those sizes (compiled.memory_analysis()):
# the gathered views of 32 x 1024 rows and their relayouts (decode), one
# slot's view and a chunk's expert activations (prefill)
MOE_TEMP_BYTES = {"decode": 1.4e9, "prefill": 0.5e9}
# the OPT cell's (ISSUE 32): no view of all layers' caches is built, so
# at the cell's 24 layers the decode program's are 0.03 GB (2.4 GB with
# the view; a quarter of either at the fixture's 6 layers)
OPT_TEMP_BYTES = {"decode": 0.5e9, "prefill": 0.5e9}


class _time_limit:
    """Fail, rather than hang the suite, when the compile for the
    described chip takes longer than ``seconds``."""

    def __init__(self, seconds):
        self.seconds = seconds

    def __enter__(self):
        import signal

        def late(_sig, _frame):
            raise TimeoutError("no compile in %d s" % self.seconds)

        self.was = signal.signal(signal.SIGALRM, late)
        signal.alarm(self.seconds)

    def __exit__(self, *exc):
        import signal

        signal.alarm(0)
        signal.signal(signal.SIGALRM, self.was)


@pytest.mark.parametrize("shape", ["decode", "prefill"])
@pytest.mark.parametrize("model", ["opt", "moe", "hybrid"])
def test_tpu_program_copies_no_pool(v5e_chip, request, model, shape):
    """What the chip's compiler makes of the dispatch (optimized HLO for
    a described v5e, nothing runs): the pool keeps a row-major layout
    with its rows outermost, and besides the parameter, the in-place
    scatter and the result no operation has the pool's size: no copy,
    no loop that gathers page by page, no buffer of zeros.  No weight
    enters as a float32 master to be cast in the program.  The
    temporaries stay under a stated size.  For the OPT cell's model,
    whose pool holds a row a (layer, token), nothing in the entry is as
    large as a view of all layers' caches either: a layer's rows are
    gathered and attended, and the next layer's after them."""
    import re

    import jax

    if model == "hybrid":      # latent pages and per-slot state (ISSUE 33)
        return _hybrid_program_copies_nothing(
            v5e_chip, request.getfixturevalue("wide_hybrid_engine"), shape)
    eng = request.getfixturevalue(
        "wide_engine" if model == "opt" else "wide_moe_engine")
    shapes = dict(zip(("prefill", "decode"), eng.dispatch_shapes()))
    compiled = _compile_for(v5e_chip, eng, shapes[shape])
    text = compiled.as_text()
    # the cache protocol of ISSUE 33 (a model may declare its layers'
    # caches) left these two models' programs as they were
    if jax.__version__ == HLO_JAX:
        assert _program_sha256(text) == HLO_SHA256[model, shape]
    pool = "bf16[%s]" % ",".join(str(d) for d in eng.pool_shape)
    assert " while(" not in text, "a gather or scatter became a loop"
    entry = text[text.index("\nENTRY"):]
    ops = re.findall(r"= %s(\{[^ ]*\})? ([\w\-]+)\(" % re.escape(pool),
                     entry)
    assert sorted(op for _layout, op in ops) == \
        ["fusion", "fusion", "parameter", "parameter"], ops
    major_first = "{" + ",".join(
        str(d) for d in reversed(range(len(eng.pool_shape))))
    for layout, _op in ops:
        assert layout.startswith(major_first), "rows are not outermost"
    scatter_fusions = re.findall(
        r"= %s\S* fusion\(.*op_name=\"jit\(chunk_fn\)/cache.write/scatter" %
        re.escape(pool), entry)
    assert len(scatter_fusions) == 2
    assert text.count("may-alias") + text.count("must-alias") >= 2, \
        "the donated pools are not aliased to the results"
    # the weights enter in the dtypes the policy's rules give them (ISSUE
    # 29): float32 only where a rule keeps it, and nothing of a weight
    # matrix's shape is converted to bfloat16 inside the program
    policy = eng._dtype_policy
    entered = {int(i): dt for dt, i in re.findall(
        r"= (\w+)\[[\d,]*\]\S* parameter\((\d+)\)", entry)}
    matrices = set()
    for i, (name, a) in enumerate(zip(eng._param_names, eng._params)):
        want = policy.param_cast_dtype(name, tuple(a.shape))
        assert entered[i] == {"bfloat16": "bf16", "float32": "f32"}[
            str(want)], (name, entered[i])
        if entered[i] == "f32":
            assert policy.rule_name(name, tuple(a.shape)), name
        elif a.ndim > 1:
            matrices.add(tuple(a.shape))
    rows = int(np.prod(shapes[shape]))
    matrices.discard((rows, eng.model_config["d_model"]))  # activations'
    assert len(matrices) >= 4
    for m in matrices:
        assert not re.findall(r"= bf16\[%s\]\S* convert\("
                              % ",".join(str(d) for d in m), text), m
    temp = compiled.memory_analysis().temp_size_in_bytes
    if model == "opt":
        assert eng.pool_shape == (6 * 513 * 16, 2048)
        assert temp < OPT_TEMP_BYTES[shape], temp
        # all layers' caches of the dispatch's slots: the view the
        # program used to build, re-tile twice and slice
        view = shapes[shape][0] * eng.cache_len * 6 * 2048
        large = []
        for line in entry.splitlines():
            m = re.match(r"\s*(?:ROOT )?\S+ = (.*?) ([\w\-]+)\(", line)
            # (the entry's result is a tuple that names the pools; a
            # bitcast, the pool seen page by page, moves nothing)
            if m and m.group(2) not in ("tuple", "bitcast") and any(
                    np.prod([int(d) for d in dims.split(",")]) >= view
                    for dims in re.findall(r"\[([\d,]+)\]", m.group(1))):
                large.append(m.group(2))
        assert sorted(large) == ["fusion", "fusion", "parameter",
                                 "parameter"], large
    if model == "moe":
        assert eng.pool_shape == (2049 * 16, 6 * 4, 128)
        assert temp < MOE_TEMP_BYTES[shape], temp
        # no expert matrix is copied to be multiplied
        assert not re.findall(r"= bf16\[2048,6144\]\S* copy\(", entry)
        assert not re.findall(r"= bf16\[6144,2048\]\S* copy\(", entry)


@pytest.mark.parametrize("shape", ["decode", "prefill"])
@pytest.mark.parametrize("model", ["moe", "hybrid"])
def test_tpu_program_reads_the_experts_in_place(v5e_chip, request, model,
                                                shape):
    """The expert layers of the two expert models' step and chunk as the
    chip's compiler sees them (ISSUE 36; optimized HLO for a described
    v5e, nothing runs): every expert layer is the two grouped kernels
    (`tpu_custom_call`: gate and up with the activation, then down), and
    no `copy` or `transpose` touches anything of an expert matrix's
    size: the kernels take the ``(d_model, held x width)`` matrices as
    the engine holds them and find an expert by block index."""
    import re

    eng = request.getfixturevalue(
        "wide_moe_engine" if model == "moe" else "wide_hybrid_engine")
    cfg = eng.model_config
    layers = 6 if model == "moe" else 1
    _first, held = cfg.get("experts_held", (0, cfg["n_experts"]))
    D, F = cfg["d_model"], cfg["d_expert"]
    shapes = dict(zip(("prefill", "decode"), eng.dispatch_shapes()))
    text = _compile_for(v5e_chip, eng, shapes[shape]).as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 2 * layers, len(calls)
    expert = held * F * D
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = (\w+\[[\d,]*\])\S* "
                     r"(copy|transpose|copy-start)\(", line)
        if m:
            dims = re.findall(r"\d+", m.group(1).split("[")[1])
            assert np.prod([int(d) for d in dims]) != expert, line
    # the matrices enter as they are held: two dimensions each
    entry = text[text.index("\nENTRY"):]
    for dims in ("%d,%d" % (D, held * F), "%d,%d" % (held * F, D)):
        assert re.findall(r"= bf16\[%s\]\S* parameter\(" % dims, entry)


def _hybrid_program_copies_nothing(v5e_chip, wide_hybrid_engine, shape):
    """The hybrid model's two programs for a described v5e (ISSUE 33):
    the one pool of latent rows (576 values in 640 lanes) keeps its rows
    outermost and is a parameter and the in-place scatter, nothing else;
    each layer's recurrent state ``S`` (32 slots x 32 x 128 x 128
    float32) is a parameter and the fusion that makes the new one, never
    copied in HBM (the decode program brings it to fast memory, `S(1)`,
    which is no copy of it); pool and state are all aliased to the
    results.  The decode program attends in the latent space: nothing
    has the size of the slots' cached rows expanded to 32 heads, and no
    gather or scatter became a loop; the chunk's attends the slot's rows
    a block at a time (ISSUE 38), its one loop besides the delta rule's
    scan.  (The convolution's tails, 2.4 MB, are re-tiled; they are not
    asserted on.)"""
    import re

    eng = wide_hybrid_engine
    assert eng.pool_shape == (18433 * 16, 640)
    assert [tuple(a.shape) for a in eng._state] == [
        (32, 32, 128, 128), (32, 3, 3 * 32 * 128)]
    shapes = dict(zip(("prefill", "decode"), eng.dispatch_shapes()))
    compiled = _compile_for(v5e_chip, eng, shapes[shape])
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    pool_ops = re.findall(r"= bf16\[294928,640\](\{[^ ]*\})? ([\w\-]+)\(",
                          entry)
    assert sorted(op for _l, op in pool_ops) == ["fusion", "parameter"]
    assert all(layout.startswith("{1,0") for layout, _op in pool_ops)
    assert re.findall(r"= bf16\[294928,640\]\S* fusion\(.*"
                      r"op_name=\"jit\(chunk_fn\)/cache.write/scatter", entry)
    state_ops = [op for op in re.findall(
        r"= f32\[32,32,128,128\]\S* ([\w\-]+)\(", entry)
        if op not in ("get-tuple-element", "bitcast")]
    assert "parameter" in state_ops and "copy" not in state_ops
    # an asynchronous copy of it moves it between HBM and fast memory:
    # of its two ends exactly one is in `S(1)`
    for ends in re.findall(r"= \((f32\[32,32,128,128\]\S*), "
                           r"(f32\[32,32,128,128\]\S*), \S+ copy-start\(",
                           entry):
        assert sum("S(1)" in end for end in ends) == 1, ends
    # the pool and the two state arrays, donated in place
    assert text.count("may-alias") + text.count("must-alias") >= 3
    if shape == "decode":
        assert " while(" not in text, "a gather or scatter became a loop"
        # 32 slots x 9216 rows x 32 heads x (128 | 128): what expanding
        # the cache would build
        expanded = 32 * 9216 * 32 * 128
        for dims in re.findall(r"= \w+\[([\d,]+)\]", entry):
            assert np.prod([int(d) for d in dims.split(",")]) < expanded
        assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    else:
        # the chunkwise delta rule's scan over 8 sub-chunks and the MLA
        # layer's blocks of cached rows up to `start` (ISSUE 38: a chunk
        # attends in blocks, a step does not), and no other: nothing in
        # the chunk's program has the size of its float32 scores over
        # all the 9216 rows a slot holds
        assert text.count(" while(") == 2
        whole = 32 * 512 * 9216
        for dims in re.findall(r"= f32\[([\d,]+)\]", text):
            assert np.prod([int(d) for d in dims.split(",")]) < whole


# ---------------------------------------------------------------------------
# the weights an engine holds (ISSUE 29): cast once, when it is built
# ---------------------------------------------------------------------------

def _build(net, **kw):
    """A small engine over ``net``."""
    return generate.PagedGenerationEngine(
        net, slots=3, cache_len=MAX_LEN, page_size=4, prefill_chunk=8,
        sampling=generate.SamplingConfig(greedy=True), **kw)


def _moe_lm(block_length=1, **kw):
    """The zoo's decoder, small: it takes head-split views."""
    from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM

    net = MoEDecoderLM(64, 32, 2, 4, 2, 16, 4, 2, 16,
                       block_length=block_length, mask_token_id=63,
                       max_len=32, **kw)
    net.initialize(mx.init.Normal(0.02))
    return net


def _weights_span(build):
    """``build()``'s engine and the ``engine.weights`` span it left in
    the ring."""
    from mxnet_tpu import tracing

    before = {r["span_id"] for r in tracing.records()}
    eng = build()
    spans = [r for r in tracing.records() if r["name"] == "engine.weights"
             and r["span_id"] not in before]
    assert len(spans) == 1, spans
    return eng, spans[0]["args"]


def _pointers(arrays):
    return [a.unsafe_buffer_pointer() for a in arrays]


def _on_device(net):
    """``net`` with every parameter a committed device array, as a
    caller who made or loaded the weights on the device hands them
    over."""
    import jax

    from mxnet_tpu.ndarray import NDArray

    for p in net.collect_params().values():
        p.set_data(NDArray(jax.device_put(p.data()._data,
                                          jax.devices()[0])))
    return net


@pytest.fixture(scope="module")
def lm_on_device():
    return _on_device(_small_lm())


def test_engine_holds_weights_in_the_rules_dtypes(lm_on_device):
    """Under ``bf16_mixed`` with float32 parameters every held array has
    the dtype the policy's rules give that parameter (norms and the head
    stay float32), the span says what was cast, and the network keeps
    its float32 masters: the engine's copy is a snapshot."""
    from mxnet_tpu import dtype_policy

    lm = lm_on_device
    policy = dtype_policy.get_policy("bf16_mixed")
    eng, span = _weights_span(lambda: _build(lm,
                                             dtype_policy="bf16_mixed"))
    params = list(lm.collect_params().values())
    assert [p.name for p in params] == eng._param_names
    want = [policy.param_cast_dtype(p.name, tuple(p.shape)) for p in params]
    assert [np.dtype(a.dtype) for a in eng._params] == want
    assert {str(d) for d in want} == {"bfloat16", "float32"}
    assert np.dtype(eng._params[-1].dtype) == np.float32, "the head's rule"
    assert all(str(p.data()._data.dtype) == "float32" for p in params)
    cast = [a for a, p in zip(eng._params, params)
            if a.dtype != p.data()._data.dtype]
    assert set(span) == {"held_bytes", "cast_bytes", "aliased"}
    assert span["cast_bytes"] == sum(a.nbytes for a in cast) > 0
    assert span["aliased"] == len(params) - len(cast)
    assert span["held_bytes"] == eng.param_bytes == \
        sum(a.nbytes for a in eng._params)
    # the parameters the rules keep are the network's own buffers
    kept = [(a, p.data()._data) for a, p in zip(eng._params, params)
            if a.dtype == p.data()._data.dtype]
    assert _pointers(a for a, _m in kept) == _pointers(m for _a, m in kept)


def test_engine_without_a_policy_casts_nothing(lm_on_device):
    lm = lm_on_device
    eng, span = _weights_span(lambda: _build(lm, dtype_policy="f32"))
    masters = [p.data()._data for p in lm.collect_params().values()]
    assert _pointers(eng._params) == _pointers(masters)
    assert span["cast_bytes"] == 0 and span["aliased"] == len(masters)
    assert span["held_bytes"] == eng.param_bytes == \
        sum(m.nbytes for m in masters)


def test_weights_handed_over_at_their_targets_are_the_same_buffers():
    """A model stored in bfloat16 with float32 norms and head (the
    block-diffusion cell's, small) is held as the very buffers it has:
    no second copy of the weights."""
    net = _on_device(_moe_lm(block_length=4, dtype="bfloat16"))
    eng, span = _weights_span(lambda: generate.PagedGenerationEngine(
        net, slots=2, cache_len=32, page_size=8, prefill_chunk=8, spec_k=0,
        dtype_policy="bf16_mixed", denoise_steps=2))
    masters = [p.data()._data for p in net.collect_params().values()]
    assert {str(m.dtype) for m in masters} == {"bfloat16", "float32"}
    assert _pointers(eng._params) == _pointers(masters)
    assert span == {"held_bytes": sum(m.nbytes for m in masters),
                    "cast_bytes": 0, "aliased": len(masters)}


def test_held_weights_keep_their_sharding(lm):
    """Under a mesh the cast copy lies where the layout put the master."""
    from jax.sharding import NamedSharding

    from mxnet_tpu import parallel

    e = _build(lm, mesh="dp=2,tp=2", dtype_policy="bf16_mixed")
    params = list(lm.collect_params().values())
    res = parallel.layout.get_layout(e.layout_name).resolve(
        [(p.name, tuple(p.shape)) for p in params], e._mesh)
    sharded = 0
    for p, a in zip(params, e._params):
        want = NamedSharding(e._mesh, res.spec(p.name))
        assert a.sharding.is_equivalent_to(want, a.ndim), p.name
        sharded += any(ax is not None for ax in res.spec(p.name))
    assert sharded, "no parameter of the layout is sharded"
    assert {str(a.dtype) for a in e._params} == {"bfloat16", "float32"}


@pytest.mark.parametrize("model", ["rows", "views", "views_blocks"])
def test_spans_name_the_attention_form_and_the_pool(lm, model,
                                                    monkeypatch):
    """The counter that says the mechanism engaged (ISSUE 32): every
    ``engine.decode`` / ``engine.prefill`` span carries ``attn``, the
    form the launched program attends in by the engine's rule for that
    model and dispatch shape, and the ``engine.pool`` span written once
    when the engine is built carries the pool's shape."""
    from mxnet_tpu import tracing
    from mxnet_tpu.ops import attention_rows

    # two heads: a decode step (2 query rows a slot) and a verify step
    # (6) read the rows as they lie, a prefill chunk of 8 (16) does not
    monkeypatch.setattr(attention_rows, "BLOCK_DIAGONAL_MAX_QUERY_ROWS", 8)
    before = {r["span_id"] for r in tracing.records()}
    if model == "rows":
        eng = _build(lm, spec_k=2)
        want = {"engine.prefill": "heads", "engine.decode": "rows"}
        shape = (N_LAYERS * eng.num_pages * 4, D_MODEL)
        assert [eng._attends_in(c) for _b, c in eng.dispatch_shapes()] \
            == ["heads", "rows", "rows"]
    else:
        blocks = 4 if model == "views_blocks" else 1
        eng = generate.PagedGenerationEngine(
            _moe_lm(blocks), slots=2, cache_len=32, page_size=8,
            prefill_chunk=8, spec_k=0, denoise_steps=2 if blocks > 1
            else None)
        want = {"engine.prefill": "heads", "engine.decode": "heads"}
        shape = (eng.num_pages * 8, 2, 2 * 16)
    # two prefill chunks of 8 (whole blocks only, under block decoding)
    slot, _tok = eng.admit(_prompt(11 if model == "rows" else 19, seed=7))
    for _ in range(3):
        eng.decode_step()
    eng.evict(slot, "length")
    mine = [r for r in tracing.records() if r["span_id"] not in before]
    for name, form in want.items():
        spans = [r for r in mine if r["name"] == name]
        assert len(spans) >= 2, name
        assert [r["args"]["attn"] for r in spans] == [form] * len(spans)
    (pool,) = [r for r in mine if r["name"] == "engine.pool"]
    assert tuple(pool["args"]["shape"]) == eng.pool_shape == shape
    assert pool["args"]["cache_rows"] == (model == "rows")
    assert pool["args"]["bytes"] == 2 * int(np.prod(shape)) * 4


def _weight_converts(jitted, args, n_params):
    """The ``convert_element_type`` equations of ``jitted``'s program
    whose operand is one of its first ``n_params`` arguments."""
    import jax

    closed = jax.make_jaxpr(jitted)(*args)
    (call,) = closed.jaxpr.eqns
    body = call.params["jaxpr"].jaxpr
    weights = body.invars[:n_params]
    return [e for e in body.eqns if e.primitive.name == "convert_element_type"
            and any(v is w for v in e.invars for w in weights)]


def _paged_dispatch_args(eng, shape, params):
    """A dispatch with live page tables, tokens and write rows, on
    copies of the pools (the dispatch donates them)."""
    import jax.numpy as jnp

    rs = np.random.RandomState(11)
    nb, nc = dict(zip(("prefill", "decode"), eng.dispatch_shapes()))[shape]
    table = 1 + np.arange(nb * eng.pages_per_slot, dtype=np.int32).reshape(
        (nb, eng.pages_per_slot))
    start = rs.randint(1, 8, nb).astype(np.int32)
    pos = start[:, None] + np.arange(nc, dtype=np.int32)
    wpage = np.take_along_axis(table, pos // eng.page_size, 1).reshape(-1)
    woff = (pos % eng.page_size).reshape(-1).astype(np.int32)
    pool = rs.standard_normal(eng.pool_shape).astype(np.float32)
    return eng._jit_chunk, (
        params, jnp.asarray(pool, eng.cache_dtype),
        jnp.asarray(pool[::-1], eng.cache_dtype), table,
        rs.randint(0, VOCAB, (nb, nc)).astype(np.int32), start, wpage, woff,
        np.zeros((nb, 2), np.uint32))


@pytest.mark.parametrize("shape", ["prefill", "decode"])
def test_dispatch_casts_no_weight_and_computes_the_same(lm, shape):
    """The program traced on the held weights has no ``convert`` of a
    parameter; called with the float32 masters it is the old program,
    which casts every one the rules do not keep, and the two give
    bitwise equal logits, tokens and caches: the rounding to bfloat16
    moved from every program to the constructor, and nothing else."""
    import jax

    eng = _build(lm, dtype_policy="bf16_mixed")
    masters = tuple(jax.device_put(p.data()._data)
                    for p in lm.collect_params().values())
    n = len(masters)
    jitted, held_args = _paged_dispatch_args(eng, shape, eng._params)
    _jitted, master_args = _paged_dispatch_args(eng, shape, masters)
    assert _weight_converts(jitted, held_args, n) == []
    n_cast = sum(str(a.dtype) == "bfloat16" for a in eng._params)
    assert len(_weight_converts(jitted, master_args, n)) == n_cast > 0
    got, want = jitted(*held_args), jitted(*master_args)
    flat_got, tree_got = jax.tree_util.tree_flatten(got)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree_got == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g.astype(np.float32)),
                                      np.asarray(w.astype(np.float32)))
    assert float(np.abs(np.asarray(got[1])).max()) > 0, "logits all zero"


# ---------------------------------------------------------------------------
# mixed batches == full re-forward, and the pool's own layout rule under
# a mesh
# ---------------------------------------------------------------------------

def _side_by_side(eng, prompts, steps):
    """Admit every prompt, decode ``steps`` steps with all of them
    active, evict: the tokens of each."""
    slots = [eng.admit(p) for p in prompts]
    got = [[tok] for _s, tok in slots]
    for out in [eng.decode_step() for _ in range(steps)] + eng.drain():
        for g, (s, _t) in zip(got, slots):
            g.extend(out[s])
    for s, _t in slots:
        eng.evict(s, "length")
    assert all(len(g) == steps + 1 for g in got)
    return got


def _mixed_inside_page(eng):
    """Three prompts that end inside a page (lengths 5, 7, 10 on pages
    of 4), decoding side by side."""
    prompts = [_prompt(5, seed=31), _prompt(7, seed=32),
               _prompt(10, seed=33)]
    return prompts, _side_by_side(eng, prompts, 6)


def _mixed_evict_reuse(eng):
    """A slot is evicted mid-flight, a step launched for it still
    unread, and the next admission takes its slot and its pages while
    the neighbour keeps decoding: the step in flight gives the slot's
    next occupant nothing and the neighbour its token."""
    a, b, c = _prompt(9, seed=41), _prompt(6, seed=42), _prompt(11, seed=43)
    (sa, ta), (sb, tb) = eng.admit(a), eng.admit(b)
    got_a, got_b = [ta], [tb]
    for _ in range(3):
        out = eng.decode_step()
        got_a.extend(out[sa])
        got_b.extend(out[sb])
    assert len(eng._inflight) == eng.steps_ahead
    assert len(got_a) == len(got_b) == 1 + 3 - eng.steps_ahead
    pages_a = set(int(p) for p in eng._page_table[sa] if p)
    eng.evict(sa, "eos")
    sc, tc = eng.admit(c)
    assert sc == sa, "LIFO slot reuse"
    assert pages_a & set(int(p) for p in eng._page_table[sc]), \
        "the next admission must take the evicted slot's pages"
    got_c = [tc]
    for out in [eng.decode_step() for _ in range(4)] + eng.drain():
        got_b.extend(out[sb])
        got_c.extend(out[sc])
    assert (len(got_b), len(got_c)) == (1 + 3 + 4, 1 + 4)
    eng.evict(sb, "length")
    eng.evict(sc, "length")
    return [a, b, c], [got_a, got_b, got_c]


def _mixed_trash_collisions(eng):
    """One active slot of three (the idle slots' rows of every decode
    step collide on the trash page) after a prompt of 3 tokens in a
    chunk of 8 (five padded positions collide there too); the trash
    page's rows must never reach a live slot's view."""
    prompts = [_prompt(3, seed=51)]
    return prompts, _side_by_side(eng, prompts, 9)


@pytest.mark.parametrize("scenario", [
    _mixed_inside_page, _mixed_evict_reuse, _mixed_trash_collisions],
    ids=["ends_inside_page", "evict_and_reuse", "trash_collisions"])
def test_mixed_batch_matches_full_forward(lm, paged, scenario):
    prompts, got = scenario(paged)
    assert paged.pages_in_use() == 0
    for prompt, toks in zip(prompts, got):
        assert toks == _greedy_reference(lm, prompt, len(toks))


@pytest.mark.parametrize("mesh,spec", [
    ("tp=2", (None, "tp")),
    ("dp=2,tp=2", ("dp", "tp")),
    ("fsdp=2,tp=2", ("fsdp", "tp")),
    ("dp=2,fsdp=2", (("dp", "fsdp"),))])
def test_pool_layout_rule_under_mesh(lm, paged, mesh, spec):
    """The pool of rows (rank 2: a row a (layer, token)) resolves under
    its own ``kv_pool`` rule — rows over the data axes, heads over tp —
    and a meshed engine decodes the one-device engine's greedy
    tokens."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import parallel

    e = generate.PagedGenerationEngine(
        lm, slots=3, cache_len=16, page_size=4, prefill_chunk=8,
        mesh=mesh, sampling=generate.SamplingConfig(greedy=True))
    res = parallel.layout.get_layout(e.layout_name).resolve(
        [("pool_k", e.pool_shape), ("pool_v", e.pool_shape)], e._mesh)
    assert res.rule("pool_k") == res.rule("pool_v") == "kv_pool"
    assert e.pool_shape == (N_LAYERS * 52, D_MODEL)
    assert not res.fallbacks, "104 rows and 32 lanes divide by 2 and 4"
    assert res.spec("pool_k") == P(*spec)
    assert e._pool_k.sharding.spec == e._pool_v.sharding.spec == P(*spec)
    prompts = [_prompt(5, seed=3), _prompt(7, seed=5)]
    assert _side_by_side(e, prompts, 4) == _side_by_side(paged, prompts, 4)


@pytest.mark.parametrize("fault,problem", [
    (None, None), ("page_size", "no whole number of pages"),
    ("prefill_chunk", "token block width")])
def test_prewarm_check_reads_the_token_major_rows(lm, tmp_path, fault,
                                                  problem):
    """``tools/prewarm.py --check`` judges a stored paged signature by
    the pool's leaves, here rows of one (layer, token) each: a healthy
    store passes, a row whose extras disagree with its own recorded
    shapes is named."""
    import prewarm
    from mxnet_tpu import aot

    store = aot.AOTStore(str(tmp_path / "store"))
    e = generate.PagedGenerationEngine(
        lm, slots=2, cache_len=16, page_size=4, prefill_chunk=8,
        aot=store, sampling=generate.SamplingConfig(greedy=True))
    assert [i["status"] for i in e.prewarm()] == ["compiled", "compiled"]
    rows, problems = store.manifest_entries()
    assert problems == [] and len(rows) == 2
    for row in rows:
        assert row["label"] == "generate:paged_chunk"
        assert row["pool_layout"] == "L%dxtokens%dxHD%d" % (
            N_LAYERS, e.num_pages * e.page_size, D_MODEL)
        assert row["pool_layout"] in row["spec"]
        if fault is not None:
            row[fault] = 5
    found = [m for row in rows for m in prewarm._check_paged_row(row)]
    if problem is None:
        assert found == []
    else:
        assert found and all(problem in m for m in found), found


# ---------------------------------------------------------------------------
# decode steps launched ahead of their results (ISSUE 34)
# ---------------------------------------------------------------------------

def _ahead_engine(net, depth, greedy=True, **kw):
    """An engine that leaves ``depth`` steps unread a call: 0 reads
    every step before the next is launched (the read-then-launch engine
    the others are held to), 1 is the class's own, 3 the block path's."""
    kw.setdefault("slots", 3)
    kw.setdefault("prefill_chunk", 8)
    eng = generate.PagedGenerationEngine(
        net, cache_len=32, page_size=4, spec_k=0,
        sampling=generate.SamplingConfig(greedy=True) if greedy else
        generate.SamplingConfig(greedy=False, top_k=8, temperature=0.9),
        **kw)
    eng.steps_ahead = depth
    return eng


def _staggered(eng, prompts, want):
    """Prompts admitted one every second call (so that slots join a
    program the others are already in, a first token fed on the device
    beside tokens of steps in flight), each bounded by its ``want``
    tokens as a server's requests are, decoded until every launch is
    read: per prompt its tokens, and the calls it took."""
    mx.random.seed(5)
    slots, got, calls = {}, [[] for _ in prompts], 0
    while len(slots) < len(prompts) or eng._inflight or any(
            len(got[i]) < want[i] for i in slots.values()):
        if len(slots) < len(prompts) and calls % 2 == 0:
            i = len(slots)
            slot = eng.admit_incremental(prompts[i], max_new=want[i])
            while eng.pending_prefill():
                assert eng.prefill_step() in (None, (slot, None))
            slots[slot] = i
        for slot, toks in eng.decode_step().items():
            got[slots[slot]].extend(toks)
        calls += 1
        assert calls < 200
    for slot in slots:
        eng.evict(slot, "length")
    return got, calls


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
@pytest.mark.parametrize("model", ["rows", "views"])
def test_launched_ahead_yields_the_tokens_of_read_then_launch(lm, model,
                                                              greedy):
    """One step or three left unread a call give, token for token, what
    reading every step before the next launch gives: greedy and sampled
    (keys are folded in by position), for a model that takes rows and
    one that takes views, three requests of different lengths joining
    and leaving a running batch.  No step is launched past a request's
    last token, whatever the depth."""
    from mxnet_tpu import telemetry

    net = lm if model == "rows" else _moe_lm(1)
    prompts = [_prompt(9, seed=71), _prompt(3, seed=72), _prompt(14, seed=73)]
    want = [7, 1, 5]
    telemetry.enable()
    try:
        wasted = telemetry.DECODE_STEPS_WASTED.value(reason="length")
        ref, ref_calls = _staggered(_ahead_engine(net, 0, greedy), prompts,
                                    want)
        assert [len(t) for t in ref] == want
        for depth in (1, 3):
            eng = _ahead_engine(net, depth, greedy)
            got, calls = _staggered(eng, prompts, want)
            assert got == ref, depth
            assert calls >= ref_calls
        assert telemetry.DECODE_STEPS_WASTED.value(reason="length") == wasted
    finally:
        telemetry.disable()
    if greedy and model == "rows":
        for prompt, toks in zip(prompts, ref):
            assert toks == _greedy_reference(lm, prompt, len(toks))


def test_first_token_reaches_the_host_with_the_step_after_it(lm):
    """A prompt's last chunk hands no token over: ``prefill_step`` says
    ``(slot, None)``, the slot's first step takes the token on the
    device, and the call that reads that step returns both; positions
    are by the launches made, ``at_capacity`` by those read."""
    eng = _ahead_engine(lm, 1, slots=1)
    prompt = _prompt(11, seed=74)
    want = _greedy_reference(lm, prompt, 4)
    slot = eng.admit_incremental(prompt, max_new=4)
    assert eng.prefill_step() is None
    assert eng.prefill_step() == (slot, None)
    assert eng.position(slot) == 11 and eng.active_slots() == [slot]
    assert eng.decode_step() == {slot: []}
    assert eng.position(slot) == 12 and eng._read_pos[slot] == 11
    assert eng.decode_step() == {slot: want[:2]}
    assert eng.decode_step() == {slot: want[2:3]}
    # the request's last step is launched: a call now only reads
    assert eng.position(slot) == 14 and eng._drained[slot]
    assert eng.decode_step() == {slot: want[3:]}
    assert eng.position(slot) == eng._read_pos[slot] == 14
    assert eng.decode_step() == {slot: []} and not eng._inflight
    # a request of one token: its chunk's alone, no step at all
    eng.evict(slot, "length")
    slot = eng.admit_incremental(prompt, max_new=1)
    while eng.pending_prefill():
        eng.prefill_step()
    assert eng.decode_step() == {slot: want[:1]}
    assert eng.position(slot) == 11 and not eng._inflight
    eng.evict(slot, "length")


@pytest.mark.parametrize("how", ["eos", "cancelled", "evicted"])
def test_slot_left_with_steps_in_flight_stays_silent_and_clean(lm, how):
    """A request that ends by what only the data says (its EOS id, a
    cancellation) or is evicted by hand has a step launched for it and
    not read: that step is counted as wasted, gives nobody a token, and
    the slot's next occupant decodes the full re-forward's tokens."""
    from mxnet_tpu import telemetry

    prompt, after = _prompt(7, seed=75), _prompt(10, seed=76)
    full = _greedy_reference(lm, prompt, 8)
    telemetry.enable()
    try:
        if how == "evicted":
            eng = _ahead_engine(lm, 1, slots=1)
            slot, tok = eng.admit(prompt)
            got = [tok] + [t for _ in range(3)
                           for t in eng.decode_step()[slot]]
            assert got == full[:3] and len(eng._inflight) == 1
            eng.evict(slot, "test")
            assert telemetry.DECODE_STEPS_WASTED.value(reason="test") == 1
            slot, tok = eng.admit(after)
            # the call that reads the dead step gives the newcomer nothing
            assert eng.decode_step() == {slot: []}
            got = [tok] + [t for _ in range(3)
                           for t in eng.decode_step()[slot]]
            assert got == _greedy_reference(lm, after, 4)
            return
        eos = full[3] if how == "eos" else None
        assert eos is None or eos not in full[:3]
        eng = generate.PagedGenerationEngine(
            lm, slots=1, cache_len=32, page_size=4, prefill_chunk=8,
            spec_k=0, sampling=generate.SamplingConfig(greedy=True,
                                                       eos_id=eos))
        seen = []
        with generate.TokenServer(eng, max_new_tokens=8) as srv:
            if how == "eos":
                out = srv.generate(prompt, timeout=60)
                assert out.finish_reason == "eos"
                assert out.tokens == full[:4]
                reason = "eos"
            else:
                fut = srv.submit(prompt, on_token=lambda t: (
                    seen.append(t), len(seen) == 3 and fut.cancel()))
                with pytest.raises(generate.Cancelled):
                    fut.result(60)
                reason = "cancelled"
            nxt = srv.generate(after, max_new_tokens=5, timeout=60)
        clean = _greedy_reference(lm, after, 5)
        if eos in clean:
            clean = clean[:clean.index(eos) + 1]
        assert nxt.tokens == clean
        assert seen in ([], full[:3])
        assert telemetry.DECODE_STEPS_WASTED.value(reason=reason) == \
            eng.steps_ahead
        assert eng.free_slots() == 1 and eng.pages_in_use() == 0
    finally:
        telemetry.reset()
        telemetry.disable()
