"""LM generation engine: paged KV-cache decode with chunked prefill
and continuous-batching token serving.

The training half of the LM stack (``examples/transformer_lm.py`` +
``ShardedTrainer``) ships tokens *into* the model; production LM
traffic is autoregressive decode *out* of it, and a naive decode
re-runs the full context every token — O(T) work per token where a KV
cache pays O(1).  This module is the inference half, built the way the
TPU path rewards (fixed-shape compiled executables, PAPERS.md "full
compilation" line):

* **The KV cache is a page pool, donated device state** —
  :class:`PagedGenerationEngine` holds one fixed-shape pool per K and
  V, indexed on its leading dimension (one row a (layer, token),
  ``(layers * pages * page_size, heads * d_head)``, for a model that
  takes its caches as rows; else token-major, ``(pages * page_size,
  layers, heads * d_head)``),
  donated into every dispatch so it updates in place; host-side page
  tables map each decode slot's positions onto pool pages, so
  admission and eviction flip host state and never the compiled
  program.  The pool's dtype follows the ``dtype_policy=`` compute
  dtype (bf16 under ``bf16_mixed``), and with a mesh the pool shards
  by the ``kv_pool`` spec rule of the layouts (``heads * d_head`` over
  tp, rows over the data axes where they divide them — tp serving
  composes with the training mesh).
* **One compiled dispatch, three shapes** — a prefill chunk
  ``(1, prefill_chunk)``, a decode step ``(slots, 1)`` (``(slots,
  block_length)`` under block-diffusion decoding) and, with n-gram
  speculation on, a verify step ``(slots, spec_k + 1)``, each a
  distinct AOT manifest row ``tools/prewarm.py`` can warm.  Long
  prompts stream in fixed-size chunks interleaved with decode steps, so
  admission never freezes active slots; the chunk that completes a
  prompt samples its first token (the TTFT token).
* **What pages buy** — prefix sharing (a shared system prompt prefills
  ONCE; new requests attach to its pages refcounted, copy-on-write by
  page alignment) and n-gram self-speculative decoding (draft K tokens
  from a suffix match over the sequence's own history, verify all of
  them in ONE fixed-shape dispatch; exact-match acceptance over the
  position-keyed sampler keeps spec output bit-identical to
  non-speculative sampling).
* **Sampling under the PRNG discipline** — greedy / top-k / top-p
  fused into the compiled step; sampling keys come from
  ``mxnet_tpu.random.next_key()``, so ``mx.random.seed(n)`` makes a
  generation stream reproducible end to end (greedy consumes no keys).
* **Continuous-batching token serving** — :class:`TokenServer` drives
  the engine from a bounded admission queue with the SAME typed error
  taxonomy as ``serving_async`` (:class:`Overloaded` at admission,
  :class:`DeadlineExceeded` tagged ``stage="prefill"`` vs
  ``stage="decode"``, burn-rate shedding over the TTFT histogram,
  drained ``close()``), so the HTTP front end maps decode failures to
  429/504 exactly like predict failures.

The model protocol: a served net implements ``chunk_forward(tokens,
caches, start)`` — C positions a sequence against a linear view of the
cached ones; one entry point covers prefill chunks, decode and the
verify step (``examples/transformer_lm.py``,
``gluon.model_zoo.language.MoEDecoderLM``) — and a ``config`` dict with
``vocab_size`` / ``d_model`` / ``n_heads`` / ``n_layers`` / ``max_len``
(``n_kv_heads`` / ``d_head`` where they are not the defaults;
``block_length`` and ``mask_token_id`` for block-diffusion decoding;
``cache_rows`` where it takes a layer's cache as rows, ``(B, S, heads *
d_head)``, and not as a head-split view, ``(B, heads, S, d_head)``).  A
model whose layers do not all cache K and V a head
(``gluon.model_zoo.language.HybridDecoderLM``) says what each keeps in
``layer_caches``: paged rows of a width, in one pool, arrays a slot
(recurrent state), or a window: rows a slot in a ring beside the pool,
the last positions of a sliding-window layer and no page; its
``chunk_forward`` takes a fourth argument, the positions of each row
that count.
Benchmarks: ``tools/bench_decode.py`` (tokens/s/user, TTFT p50/p99,
the KV-cache-vs-reforward ratio, plus the prefix-share /
chunked-prefill / speculative modes); docs: ``docs/lm_serving.md``.
"""
from __future__ import annotations

import collections
import logging
import threading
import time
import weakref

import numpy as np

from . import config as _config
from . import events as _events
from . import telemetry as _telemetry
from . import tracing as _tracing
from .base import MXNetError
from .serving_async import (Cancelled, DeadlineExceeded, Overloaded,
                            ReplicaFailed, ServingError, ServingFuture,
                            BurnRateShedder)

__all__ = ["SamplingConfig", "PagedGenerationEngine", "TokenServer",
           "GenerationResult",
           "sample_logits", "ServingError", "Overloaded",
           "DeadlineExceeded", "Cancelled"]

_logger = logging.getLogger("mxnet_tpu.generate")

_UNSET = object()

# live TokenServers (weak), feeding the /statusz decode subsystem
# (slot occupancy, TTFT burn rate) and the /healthz readiness
# contract — a decode process stops being ready the moment a drained
# close() starts.  The lock serializes explicit add/discard/iterate
# across threads (see serving_async._live_predictors).
_live_servers = weakref.WeakSet()
_live_lock = threading.Lock()


def _live_snapshot():
    with _live_lock:
        return list(_live_servers)


def _decode_statusz():
    out = {"servers": []}
    for s in _live_snapshot():
        st = s.stats()
        st["occupancy"] = s._engine.occupancy()
        st["pool_shape"] = list(s._engine.pool_shape)
        if s._shedder is not None:
            st["ttft_burn_rate"] = round(s._shedder.burn, 4)
        out["servers"].append(st)
    return out


def _decode_ready():
    servers = _live_snapshot()
    if not servers:
        return True
    return any(not s._closed and s._running for s in servers)


_telemetry.register_status_provider("decode", _decode_statusz)
_telemetry.register_readiness("decode", _decode_ready)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class SamplingConfig:
    """Declared sampling recipe, fused into the compiled decode step.

    ``greedy=True`` (default) takes the argmax and consumes no PRNG
    keys.  Otherwise sampling is categorical over the
    temperature-scaled logits, optionally restricted to the ``top_k``
    highest logits and/or the smallest set of tokens whose cumulative
    probability reaches ``top_p`` (nucleus).  ``eos_id`` is the token
    that finishes a sequence (eviction reason ``eos``); None means
    sequences only finish by length/deadline."""

    def __init__(self, greedy=True, temperature=1.0, top_k=None,
                 top_p=None, eos_id=None):
        self.greedy = bool(greedy)
        self.temperature = float(temperature)
        if self.temperature <= 0:
            raise MXNetError("temperature must be > 0, got %r"
                             % (temperature,))
        self.top_k = int(top_k) if top_k is not None else None
        if self.top_k is not None and self.top_k < 1:
            raise MXNetError("top_k must be >= 1, got %r" % (top_k,))
        self.top_p = float(top_p) if top_p is not None else None
        if self.top_p is not None and not 0 < self.top_p <= 1:
            raise MXNetError("top_p must be in (0, 1], got %r" % (top_p,))
        self.eos_id = int(eos_id) if eos_id is not None else None

    @property
    def tag(self):
        """Compact recipe tag (AOT manifest rows, BENCH records)."""
        if self.greedy:
            return "greedy"
        parts = ["sample"]
        if self.temperature != 1.0:
            parts.append("t%g" % self.temperature)
        if self.top_k:
            parts.append("k%d" % self.top_k)
        if self.top_p:
            parts.append("p%g" % self.top_p)
        return "_".join(parts)

    def __repr__(self):
        return "SamplingConfig(%s, eos_id=%r)" % (self.tag, self.eos_id)


def sample_logits(logits, key, cfg):
    """In-graph token selection over (B, V) f32 logits -> (B,) int32.

    Pure and jit-traceable; every slot samples independently from one
    key (``jax.random.categorical`` splits per row)."""
    import jax
    import jax.numpy as jnp

    if cfg.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if cfg.temperature != 1.0:
        logits = logits / cfg.temperature
    neg = jnp.asarray(-jnp.inf, logits.dtype)
    if cfg.top_k:
        k = min(cfg.top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if cfg.top_p is not None and cfg.top_p < 1.0:
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep a token while the mass BEFORE it is under top_p (the
        # first token always survives)
        kept = (cum - probs) < cfg.top_p
        min_kept = jnp.min(
            jnp.where(kept, sorted_logits, jnp.inf), axis=-1,
            keepdims=True)
        logits = jnp.where(logits < min_kept, neg, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the weights an engine holds
# ---------------------------------------------------------------------------

def _cast_weights(names, tree, policy):
    """Every parameter of ``tree`` in the dtype ``policy``'s rules give
    it (``param_cast_dtype``); one already there, one that is not
    floating and all of them without a policy are the arrays handed in."""
    if policy is None:
        return tree
    return tuple(policy.cast_compute(n, a) for n, a in zip(names, tree))


def _hold_weights(names, placed, policy, draft=0):
    """The tuple an engine holds and hands to every dispatch: the
    parameters ``placed`` (on their device, under their sharding) as
    the dispatch itself casts them (:func:`_cast_weights`), cast here
    once, on the device, each keeping its sharding.  Serving never
    updates a weight, so the cast has one result for the engine's life;
    a program handed arrays at their targets traces with no ``convert``
    on a weight, and reads no float32 master.  A parameter already at
    its target is held as the very buffer it is: nothing is copied.
    Commits the ``engine.weights`` span: ``held_bytes``, ``cast_bytes``
    (the bytes of the copies made), ``aliased`` (the parameters kept
    as handed over) and, for a model whose last ``draft`` parameters
    are a draft block's, ``draft_bytes``."""
    import jax

    with _tracing.begin("engine.weights") as sp:
        held = _cast_weights(names, placed, policy)
        jax.block_until_ready(held)
        kept = [h is a for h, a in zip(held, placed)]
        sp.set(held_bytes=sum(int(h.nbytes) for h in held),
               cast_bytes=sum(int(h.nbytes) for h, k in zip(held, kept)
                              if not k),
               aliased=sum(kept))
        if draft:
            sp.set(draft_bytes=sum(int(h.nbytes) for h in held[-draft:]))
    return held


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _ngram_draft(history, ngram, k):
    """Draft up to ``k`` continuation tokens by suffix match: find the
    most recent earlier occurrence of the last ``ngram`` tokens of
    ``history`` and propose the tokens that followed it.  Pure host
    work, O(len * ngram) worst case; returns [] when the sequence has
    never repeated its suffix (the verify step then degrades to a plain
    one-token decode)."""
    n = len(history)
    if k <= 0 or ngram <= 0 or n < ngram + 1:
        return []
    pat = history[-ngram:]
    for e in range(n - 2, ngram - 2, -1):
        if history[e - ngram + 1: e + 1] == pat:
            return list(history[e + 1: e + 1 + k])
    return []


def _prefix_page_hashes(token_ids, page_size, limit, ahead=0):
    """Chained content hashes of the first ``limit`` FULL prompt pages:
    ``h_i = sha1(h_{i-1} || tokens of page i)``.  The chain makes a
    page's identity depend on everything before it, so two prompts
    share page i only when they agree on all of pages 0..i — exactly
    the prefix property page attachment needs.  With ``ahead`` a page's
    hash also covers that many tokens after it: what a draft block
    caches of a position depends on the token that follows."""
    import hashlib

    hashes = []
    prev = b""
    for i in range(limit):
        block = token_ids[i * page_size:(i + 1) * page_size + ahead]
        h = hashlib.sha1(prev + block.tobytes()).hexdigest()
        hashes.append(h)
        prev = h.encode()
    return hashes


class BlockTokens(list):
    """The tokens a committed block emits, with ``fixed_at``, the
    denoise pass (1..T) at which each was fixed, and ``confidence``,
    the log-probability the model gave it in that pass."""

    def __init__(self, tokens=(), fixed_at=(), confidence=()):
        super().__init__(tokens)
        self.fixed_at = list(fixed_at)
        self.confidence = list(confidence)


class _OpenBlocks:
    """The schedule of every slot's open block under block-diffusion
    decoding, on the host, one row a slot.  What a pass does to a block
    follows from counts alone (``T`` denoise passes that fix
    ``ceil(masked at block start / T)`` positions each, then a commit),
    so the host knows every slot's next pass without a result of the
    last one: the denoise passes launched, the positions still masked
    after them, the masked positions at the block's start, and for a
    block no pass has run yet (``fresh``) the tokens the prompt gave
    it.  The block itself (its tokens, which positions are masked, the
    pass that fixed each and with what confidence) is state the program
    carries on the device from pass to pass."""

    def __init__(self, slots, length):
        self.tokens = np.zeros((slots, length), np.int32)
        self.fresh = np.zeros(slots, bool)
        self.given = np.zeros(slots, np.int32)
        self.passes = np.zeros(slots, np.int32)
        self.left = np.zeros(slots, np.int32)
        self.masked_at_start = np.zeros(slots, np.int32)

    def open(self, slot, given=()):
        """A fresh block in ``slot``: ``given`` tokens lead, the rest
        is masked."""
        g, length = len(given), self.tokens.shape[1]
        self.tokens[slot] = 0
        self.tokens[slot, :g] = given
        self.fresh[slot] = True
        self.given[slot], self.passes[slot] = g, 0
        self.left[slot] = self.masked_at_start[slot] = length - g


class PagedGenerationEngine:
    """Paged/block KV-cache generation over a chunk-protocol model.

    Device state is what the model's protocol declares, donated through
    every dispatch: for a model of attention layers alone one
    fixed-shape page pool per K/V, in one of two forms; for a model that
    says a layer at a time what it caches (``layer_caches``), one pool
    of paged rows, arrays of per-slot state and rings of per-slot rows
    side by side (below).
    A model whose ``config`` says ``cache_rows`` takes each layer's
    cache as rows, and the pool is two-dimensional, ``(layers * pages *
    page_size, heads * d_head)``: row ``layer * tokens + page *
    page_size + offset`` holds one position's K (or V) of one layer,
    every head side by side.  A dispatch gathers a layer's rows at a
    time, ``(slots, cache_len, heads * d_head)``, and the model attends
    them as they lie (``ops.attention_rows``): nothing the size of all
    layers' caches is ever built, sliced or re-tiled.  Any other model
    gets the token-major pool, ``(pages * page_size, layers, heads *
    d_head)``: row ``page * page_size + offset`` holds one position's
    K (or V) of every layer and head, gathered whole into a view that
    is split by layer and head for the model.  Either way both index
    operations of a dispatch address dimension 0, so XLA gathers whole
    rows and scatters the chunk's rows in place on the donated buffer;
    no dispatch copies the pool.  Each decode slot maps its positions
    onto pool pages through a host-side page table (page 0 is a
    write-through "trash" page absorbing padded/invalid positions, so
    shapes never change).
    One compiled ``chunk`` function covers all three dispatch shapes:

    * **prefill chunk** ``(1, prefill_chunk)`` — prompts stream in
      fixed-size chunks (:meth:`prefill_step`, one chunk per call) so a
      long admission interleaves with decode steps instead of stalling
      them;
    * **decode** ``(slots, 1)`` — every active slot advances one token.
      The token a step samples is the next step's input ON THE DEVICE
      (a prompt's first token, of its last chunk, too), and positions,
      write pages and budgets advance by counts, so :meth:`decode_step`
      launches a step before it reads the one before: ``steps_ahead``
      steps stay queued and the host's delivery, admission and launches
      run beside the device's work, not between two of its programs.
      What only the data says (an EOS id, a cancellation) is found when
      the token is read; the steps launched for that slot meanwhile are
      thrown away (``mxnet_tpu_decode_steps_wasted_total``).
      With a model whose ``config`` gives a ``block_length`` B > 1,
      ``(slots, B)`` — **block-diffusion decoding**: every active slot
      runs one pass of its open block (``denoise_steps`` T denoise
      passes, whose K/V rows go to the trash page, then one commit pass
      that writes them to the slot's pages and emits the block).  The
      open blocks live on the device and a pass hands them to the next,
      so :meth:`decode_step` launches a pass before it reads the
      results of the ones before: ``passes_ahead`` passes stay queued,
      the device does not wait for the host between two passes, and a
      block's tokens arrive that many calls after its commit was
      launched;
    * **verify** ``(slots, spec_k + 1)`` — with n-gram speculation on,
      each step carries the current token plus up to ``spec_k`` drafted
      tokens and verifies them all at once (drafts come of the host's
      history, so this step is read before the next is launched).
      Acceptance is exact-match
      against the position-keyed sampler (each position's key is
      ``fold_in(lane_key, position)``), so accepted output is
      bit-identical to what non-speculative sampling would have
      produced — distribution preservation by construction.  A model
      that declares a draft block (``config["draft_layers"]`` = 1, a
      multi-token-prediction module beside the trunk) drafts for this
      step itself at ``spec_k`` = 1, **self-drafting**: the verify
      program runs the trunk on ``[current, draft]``, samples both
      rows, runs the model's ``draft_forward`` on both, each fed the
      token the trunk chose after it, and hands back the block's
      choices; the one at the last accepted row is the next step's
      draft.  The chunk program runs the block over the prompt too (row
      ``i`` fed prompt token ``i + 1``, the last row of the last chunk
      the first token the same program chose) and yields the first
      draft.  The block's rows live in the one pool of rows beside the
      trunk's and are rolled back with them: rows at and after the new
      position are masked by ``start`` and overwritten by the next
      step.  :meth:`drafted` gives a slot's drafts.

    **Layers that keep other things.**  ``config["layer_caches"]`` has
    one entry a layer.  ``{"rows": width}``: one row a token of that
    many values (a latent that all heads share), paged like K and V but
    in ONE pool and no second one (one width a model), ``(layers that
    keep rows * pages * page_size, width padded to whole tiles of 128
    lanes)``; the model is handed a layer's gathered rows as they lie
    and returns the chunk's.  ``{"state": [(shape, dtype), ...]}``:
    arrays a SLOT (a recurrent layer's state), held as ``(slots,) +
    shape`` in a tuple of their own, handed to every dispatch donated
    like the pools and taken back from it; :meth:`cached` reads a
    slot's caches back for a check.  A ``(slots, 1)`` decode step hands
    the model every slot's row and takes every row back, the model
    leaving alone the rows of slots no one is in (``valid`` 0); a
    ``(1, C)`` prefill chunk reads
    and writes its own slot's row only, and the chunk that starts a
    sequence reads it as zeros, so admission costs no dispatch and
    eviction clears nothing.  State cannot be cut at a page boundary,
    so a model with any is not offered prefix attachment (asked for, it
    is turned off with a warning and no page is registered), and
    ``spec_k > 0`` (whoever drafts) or a ``block_length`` over 1 raise:
    a rejected draft or a re-run block would have to be rolled back out
    of it.  A model that keeps rows alone is offered prefix attachment;
    under self-drafting a page's hash covers one token more, the one the
    draft block's row of the page's last position was fed.
    ``{"window": (positions, width)}``: a sliding-window layer, which
    attends the last ``positions`` positions (itself included) and so
    keeps rows **a slot in a ring**, ``(slots, rows, width padded to
    whole tiles of 128 lanes)`` beside the pool, ``rows`` =
    ``ops.attention_rows.ring_rows(positions, spec_k)`` (``positions +
    spec_k`` up to whole sublane tiles), and no page: what it costs does
    not grow with a slot's capacity.  Position ``p`` lies in row ``p mod
    rows``; positions are never stored, the model computes them from
    ``start``.  The rings follow the state arrays in the tuple every
    dispatch is handed donated.  A dispatch hands the model the ring of
    each of its slots as the dispatch before left it, the model attends
    it by position beside the chunk's own rows under the band and
    returns the chunk's rows like a layer of paged rows, and the engine
    puts those that count in their places (``ring_write``: a ``(1, C)``
    prefill chunk longer than the ring leaves its last rows; a
    ``(slots, C)`` step scatters its ``C`` rows a slot in place).  The
    chunk that starts a sequence masks whatever the ring held (no
    position is below 0), so admission and eviction cost no dispatch.
    A rejected draft's row lies at or above the next ``start``: by
    position it reads as ``rows`` earlier, which no query's band
    reaches, and the next step overwrites it, as the pool's rows are;
    so speculation (the model's own drafts or n-gram drafts) stays on,
    and nothing is copied to roll back.  A prefix's pages do not hold
    what a ring kept of it, so prefix attachment is turned off with a
    warning for a model with any window layer; block-diffusion decoding
    over one raises.  :meth:`cached` returns a window layer's rows in
    position order with the first position they hold.

    **Prefix sharing** is page-aligned copy-on-write: full prompt pages
    are content-hashed (chained, so identity implies identical prefix)
    and registered after prefill; a later admission attaches to matching
    pages refcounted and prefills only the tail.  Shared pages are never
    written again (a slot's writes start at its first un-shared
    position), so sharing needs no device-side copy; pages whose
    refcount drops to zero stay cached (LRU) until pool pressure
    reclaims them.

    **The weights** are a snapshot taken when the engine is built, held
    in the dtype the policy computes in: each parameter is cast once, on
    the device and under its sharding, to what the policy's rules give
    it (bfloat16 under ``bf16_mixed``, float32 where a rule keeps it),
    so no dispatch reads a float32 master or casts a weight
    (``param_bytes``; the ``engine.weights`` span says what was cast).
    A parameter handed over at its target is held as the buffer it is,
    never copied.  A caller who keeps float32 masters in the network
    pays for both, the masters and the engine's copy (1.5 x the
    masters); hand the weights over in the compute dtype to hold them
    once.

    Single-consumer: one thread drives the engine (TokenServer's loop,
    or a bench loop).  Admission control, deadlines, and futures live
    in :class:`TokenServer`.
    """

    # block-diffusion decoding: the passes launched and not yet read
    # that a call leaves queued.  One keeps the device busy while the
    # host delivers, admits and launches; three hold about 70 ms of work
    # at SDAR-30B-A3B's six layers, which is what the serving machine's
    # host has been seen to stand still for now and then (100-120 ms,
    # PERF.md section 2).  Every one delays a burst by a pass.
    passes_ahead = 3
    # token-at-a-time decoding: the same for its steps.  One hides the
    # host's 3.5 ms a tick behind a step of 6.7 ms (OPT-1.3B) or 19 ms
    # (Ling-3.0-flash); each further one holds a finished request's slot
    # and every token a tick longer (PERF.md section 6, PR 34)
    steps_ahead = 1

    def __init__(self, net, slots=None, cache_len=None, page_size=None,
                 num_pages=None, prefill_chunk=None, spec_k=None,
                 spec_ngram=None, prefix_share=None, mesh=None,
                 layout=None, dtype_policy=None, aot=None, aot_spec=None,
                 sampling=None, device=None, denoise_steps=None):
        import jax
        import jax.numpy as jnp

        from . import aot as _aot
        from . import dtype_policy as _dtp
        from . import autograd
        from . import parallel
        from .gluon import block as block_mod
        from .ndarray.ndarray import NDArray

        for attr in ("chunk_forward", "config"):
            if not hasattr(net, attr):
                raise MXNetError(
                    "PagedGenerationEngine needs a model implementing "
                    "the chunk protocol (chunk_forward / config — see "
                    "examples/transformer_lm.py); %s lacks %r"
                    % (type(net).__name__, attr))
        cfg = dict(net.config)
        for k in ("vocab_size", "d_model", "n_heads", "n_layers",
                  "max_len"):
            if k not in cfg:
                raise MXNetError("model config lacks %r (decode "
                                 "protocol)" % k)
        self.model_config = cfg
        if slots is None:
            slots = _config.get("MXNET_DECODE_SLOTS")
        self._slots = int(slots)
        if self._slots < 1:
            raise MXNetError("slots must be >= 1, got %r" % (slots,))
        if cache_len is None:
            cache_len = min(_config.get("MXNET_DECODE_CACHE_LEN"),
                            cfg["max_len"])
        cache_len = int(min(cache_len, cfg["max_len"]))
        if page_size is None:
            page_size = _config.get("MXNET_DECODE_PAGE_SIZE")
        self._page_size = int(page_size)
        if self._page_size < 1:
            raise MXNetError("page_size must be >= 1, got %r"
                             % (page_size,))
        self._pages_per_slot = -(-cache_len // self._page_size)
        self._capacity = self._pages_per_slot * self._page_size
        if num_pages is None:
            num_pages = _config.get("MXNET_DECODE_PAGES")
        if not num_pages:
            # safe floor: every slot can always back its full capacity
            # (+1 trash page), so decode-time allocation cannot starve
            num_pages = self._slots * self._pages_per_slot + 1
        self._num_pages = int(num_pages)
        if self._num_pages < self._pages_per_slot + 1:
            raise MXNetError(
                "num_pages=%d cannot back even one slot (%d pages per "
                "slot + the trash page)" % (self._num_pages,
                                            self._pages_per_slot))
        if prefill_chunk is None:
            prefill_chunk = _config.get("MXNET_DECODE_PREFILL_CHUNK")
        self._chunk = max(1, int(prefill_chunk))
        if spec_k is None:
            spec_k = _config.get("MXNET_DECODE_SPEC_K")
        self._spec_k = max(0, int(spec_k))
        if spec_ngram is None:
            spec_ngram = _config.get("MXNET_DECODE_SPEC_NGRAM")
        self._spec_ngram = max(1, int(spec_ngram))
        if prefix_share is None:
            prefix_share = _config.get("MXNET_DECODE_PREFIX_SHARE")
        self._prefix_share = bool(prefix_share)
        self.sampling = sampling if sampling is not None \
            else SamplingConfig()
        # block-diffusion decoding: the model's mask is block-causal
        # with this block length (1 = causal, a token a pass)
        self._block = Bl = int(cfg.get("block_length", 1))
        if Bl > 1:
            if self._spec_k or not self.sampling.greedy:
                raise MXNetError(
                    "block-diffusion decoding (block_length=%d) is greedy "
                    "and takes no n-gram speculation" % Bl)
            if self._page_size % Bl or self._chunk % Bl:
                raise MXNetError(
                    "page_size (%d) and prefill_chunk (%d) must be "
                    "multiples of the model's block_length (%d)"
                    % (self._page_size, self._chunk, Bl))
            if cfg.get("mask_token_id") is None:
                raise MXNetError("model config lacks 'mask_token_id' "
                                 "(block-diffusion decoding)")
            self._denoise_steps = int(denoise_steps) if denoise_steps \
                else Bl
            if not 1 <= self._denoise_steps <= Bl:
                raise MXNetError(
                    "denoise_steps must be in [1, block_length=%d], got %r"
                    % (Bl, denoise_steps))
        elif denoise_steps:
            raise MXNetError("denoise_steps needs a model with "
                             "block_length > 1")

        probe = NDArray(jnp.zeros(
            (1, min(8, cfg["max_len"])), jnp.float32))
        with autograd.pause():
            block_mod._abstract_eval_forward(net, [probe])
        self._net = net
        params = list(net.collect_params().values())
        self._param_names = [p.name for p in params]
        dt_policy = _dtp.resolve_policy(dtype_policy)
        self._dtype_policy = dt_policy
        _dtp.note_policy(dt_policy, "generate")
        self._cache_dtype = np.dtype(dt_policy.compute_dtype) \
            if dt_policy is not None else np.dtype(np.float32)

        self._mesh = parallel.resolve_mesh(mesh)
        # key/value heads and the head size are the model's to say
        # (grouped-query attention; a head size that is not d_model /
        # n_heads)
        L = cfg["n_layers"]
        H = int(cfg.get("n_kv_heads", cfg["n_heads"]))
        dh = int(cfg.get("d_head", cfg["d_model"] // cfg["n_heads"]))
        n_tokens = self._num_pages * self._page_size
        # what the model's protocol declares picks the pool's rows
        self._cache_rows = bool(cfg.get("cache_rows"))
        # a model whose layers do not all cache K and V a head says, a
        # layer, what it caches: {"rows": width}, paged rows of that
        # many values a token in ONE pool (no second one), or
        # {"state": [(shape, dtype), ...]}, arrays a slot (a dtype of
        # None is the cache's)
        declared = cfg.get("layer_caches")
        self._declared = declared is not None
        # a model with a draft block (``draft_layers``; its entries of
        # ``layer_caches`` follow the trunk's) drafts for the verify
        # step itself at ``spec_k`` 1: the block runs in the chunk and
        # verify programs, its rows live in the pool beside the
        # trunk's.  At any other ``spec_k`` the block is left alone and
        # its rows have no place in the pool
        n_draft = int(cfg.get("draft_layers", 0)) if self._declared else 0
        self._self_draft = bool(n_draft) and self._spec_k == 1
        if self._declared:
            if len(declared) != L + n_draft or any(
                    set(kind) not in ({"rows"}, {"rows", "attended"},
                                      {"state"}, {"window"})
                    or kind.get("attended", "whole") != "whole"
                    for kind in declared) or any(
                    "rows" not in kind for kind in declared[L:]):
                raise MXNetError(
                    "model config's layer_caches must give each of the "
                    "%d layers (and then each of the %d draft blocks, "
                    "rows) {'rows': width} (with 'attended': 'whole' "
                    "where every dispatch multiplies all the rows a slot "
                    "holds), {'state': [(shape, dtype), ...]} or "
                    "{'window': (positions, width)}, got %r"
                    % (L, n_draft, declared))
            if not self._self_draft:
                declared = declared[:L]
        self._layer_caches = declared
        self._state_layers = state_layers = [
            li for li, kind in enumerate(declared or ()) if "state" in kind]
        self._window_layers = window_layers = [
            li for li, kind in enumerate(declared or ()) if "window" in kind]
        if self._declared:
            if self._mesh is not None:
                raise MXNetError(
                    "a model that declares its layers' caches is served "
                    "on one device: the layouts have no rule yet for "
                    "pools of latent rows, per-slot state or rings")
            if window_layers:
                # a ring holds a sequence's last rows and nothing a
                # prefix's pages could stand for; a rejected draft's row
                # is rolled back by position, so speculation stays
                if Bl > 1:
                    raise MXNetError(
                        "block-diffusion decoding runs a block several "
                        "times before it commits it; a model with "
                        "windowed layers (rings a slot, layers %s) "
                        "cannot" % window_layers)
                if self._prefix_share:
                    _logger.warning(
                        "prefix sharing is not offered to a model with "
                        "windowed layers (layers %s): a prefix's pages do "
                        "not hold what those layers' rings kept of it; "
                        "every prompt prefills whole", window_layers)
                    self._prefix_share = False
            if state_layers:
                # state cannot be cut at a page boundary, copied by
                # attaching pages or rolled back past a rejected draft
                if self._spec_k:
                    raise MXNetError(
                        "spec_k=%d: speculation needs to roll a rejected "
                        "draft back, which per-slot recurrent state "
                        "(layers %s) cannot; build the engine with "
                        "spec_k=0" % (self._spec_k, state_layers))
                if Bl > 1:
                    raise MXNetError(
                        "block-diffusion decoding runs a block several "
                        "times; a model with per-slot recurrent state "
                        "cannot")
                if self._prefix_share:
                    _logger.warning(
                        "prefix sharing is not offered to a model with "
                        "per-slot recurrent state (layers %s): a prefix's "
                        "pages do not hold what those layers kept of it; "
                        "every prompt prefills whole", state_layers)
                    self._prefix_share = False
            # ONE pool of rows, of one width (a second width waits for
            # a model that has one): row `k * tokens + page * page_size
            # + offset` holds one position of the k-th layer that keeps
            # rows, gathered a layer at a time like a model's that
            # takes rows.  A row is whole tiles of 128 lanes, the
            # model's values first and zeros after them: the TPU pads a
            # row to that in memory whatever its shape says, and given
            # a minor dimension that is no multiple of 128 (a latent
            # row's 576) its runtime lays the TOKENS out minor-most and
            # the program copies the pool twice a dispatch to index it.
            # The model is handed the rows as they lie, zeros included
            row_layers = [li for li, kind in enumerate(declared)
                          if "rows" in kind]
            self._row_layers = row_layers
            widths = {int(declared[li]["rows"]) for li in row_layers}
            if len(widths) > 1:
                raise MXNetError(
                    "layer_caches declares rows of several widths (%s); "
                    "the engine keeps one pool of one width"
                    % sorted(widths))
            pool_shape = (len(row_layers) * n_tokens,
                          -(-max(widths, default=0) // 128) * 128)
            state_specs = [
                ((self._slots,) + tuple(int(d) for d in shape),
                 np.dtype(dt) if dt is not None else self._cache_dtype)
                for li in state_layers
                for shape, dt in declared[li]["state"]]
            # a windowed layer's rows, a ring a slot beside the pool:
            # (slots, rows, lanes), position p in row p mod rows, lanes
            # as the pool's (whole tiles of 128).  The rings follow the
            # state arrays in the one tuple every dispatch is handed
            # donated
            from .ops.attention_rows import ring_rows

            self._ring_rows = [
                ring_rows(declared[li]["window"][0], self._spec_k,
                          self._cache_dtype.itemsize)
                for li in window_layers]
            ring_specs = [
                ((self._slots, rows,
                  -(-int(declared[li]["window"][1]) // 128) * 128),
                 self._cache_dtype)
                for li, rows in zip(window_layers, self._ring_rows)]
        elif self._cache_rows:
            # one row a (layer, token): the layer is part of the row
            # index, so a layer's cache is a gather of whole rows,
            # (slots * cache_len, heads * d_head), with nothing to slice
            # or re-tile out of it, and the model's attention reads the
            # rows as they lie (ops.attention_rows).  Both index
            # operations address dimension 0 and heads * d_head is the
            # one minor dimension (the one `tp` shards under a mesh)
            row = (H * dh,)
            pool_shape = (L * n_tokens,) + row
        else:
            # token-major: the dimension the page table addresses leads
            # and a token's (layers, heads * d_head) trail as one
            # contiguous row.  Heads and d_head are kept as ONE dimension
            # because the TPU runtime lays an array out by its shape:
            # with a d_head under 128 lanes minor-most it would make the
            # tokens the minor-most dimension instead and copy the pool
            # to index it.  For the same reason the row's second-minor
            # dimension has to tile without padding (1, 2, 4 or a
            # multiple of 8 sublanes): with 6 layers of 4 x 128 the
            # runtime puts the layers outermost and the program copies
            # the pool twice a dispatch to index it.  Such a row folds
            # the heads into the layers (one chip; under a mesh
            # heads * d_head stays the dimension that `tp` shards).
            def tiles(n):
                return n in (1, 2, 4) or n % 8 == 0

            row = (L, H * dh)
            if self._mesh is None and not tiles(L):
                row = (L * H, dh) if tiles(L * H) and dh % 128 == 0 \
                    else (L * H * dh,)
            pool_shape = (n_tokens,) + row
        if self._mesh is not None:
            from jax.sharding import NamedSharding

            layout_obj = parallel.layout.resolve_layout(layout,
                                                        self._mesh)
            self.layout_name = layout_obj.name
            res = layout_obj.resolve(
                [(p.name, tuple(p.shape)) for p in params], self._mesh)
            placed = tuple(
                jax.device_put(p.data()._data,
                               NamedSharding(self._mesh, res.spec(p.name)))
                for p in params)
            pres = layout_obj.resolve(
                [("pool_k", pool_shape), ("pool_v", pool_shape)],
                self._mesh)
            self._pool_sharding = NamedSharding(self._mesh,
                                                pres.spec("pool_k"))
        else:
            self.layout_name = None
            dev = device if device is not None else jax.devices()[0]
            placed = tuple(
                jax.device_put(p.data()._data, dev) for p in params)
            self._pool_sharding = dev
        self._params = _hold_weights(
            self._param_names, placed, dt_policy,
            draft=int(cfg.get("draft_params", 0)))
        # what every dispatch is handed donated: the K and the V pool
        # or, for a model that declares its layers' caches, its one pool
        # of rows (no second one) and the arrays of per-slot state
        self._pool_v, self._state = None, ()
        with _tracing.begin("engine.pool") as sp:
            def zeros(shape, dtype):
                return jax.device_put(jnp.zeros(shape, dtype),
                                      self._pool_sharding)

            self._pool_k = zeros(pool_shape, self._cache_dtype)
            if self._declared:
                self._state = tuple(zeros(sh, dt)
                                    for sh, dt in state_specs + ring_specs)
                n_state = len(state_specs)
                state_bytes = sum(int(a.nbytes)
                                  for a in self._state[:n_state])
                window_bytes = sum(int(a.nbytes)
                                   for a in self._state[n_state:])
                sp.set(shape=list(pool_shape), cache_rows=True,
                       bytes=int(self._pool_k.nbytes) + state_bytes
                       + window_bytes,
                       latent_rows_bytes=int(self._pool_k.nbytes),
                       state_bytes=state_bytes)
                if window_layers:
                    sp.set(window_rows_bytes=window_bytes,
                           window_rows=list(self._ring_rows))
                if self._self_draft:
                    sp.set(draft_rows_bytes=int(self._pool_k.nbytes)
                           * n_draft // len(row_layers))
            else:
                self._pool_v = zeros(pool_shape, self._cache_dtype)
                sp.set(shape=list(pool_shape),
                       cache_rows=self._cache_rows,
                       bytes=2 * int(self._pool_k.nbytes))

        # host control plane: page tables + slot state + the prefix map
        P = self._pages_per_slot
        self._page_table = np.zeros((self._slots, P), np.int32)
        self._pos = np.zeros(self._slots, np.int32)
        self._active = np.zeros(self._slots, bool)
        self._cur_tok = np.zeros(self._slots, np.int32)
        self._free = collections.deque(range(self._slots))
        self._lane_keys = np.zeros((self._slots, 2), np.uint32)
        self._free_pages = collections.deque(range(1, self._num_pages))
        self._page_ref = np.zeros(self._num_pages, np.int32)
        self._prefix_map = {}                 # chain hash -> page id
        self._page_hash = {}                  # page id -> chain hash
        self._reclaim = collections.OrderedDict()  # refcnt-0 LRU
        self._pending = collections.OrderedDict()  # slot -> prefill st
        self._history = {}                    # slot -> prompt+emitted
        self.last_prefix_hit_tokens = 0
        self._prefix_hit_tokens = 0
        self._prefix_lookup_tokens = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_steps = 0
        # self-drafting: the draft block's choice for every slot's next
        # position, and a slot's drafts so far, one an emitted token
        # (the block's choice at the row that token was chosen at)
        self._draft_tok = np.zeros(self._slots, np.int32)
        self._drafted = {}
        # speculation: a prompt's first token (and first draft) stay on
        # the device when its last chunk is launched, so that the tick's
        # verify step is queued behind the chunk and both are waited for
        # once; `_spec_firsts` holds them (slot, sampled, drafts, row)
        # until that step's read-back, and a slot in it is given no row
        # of a verify step: its current token is not on the host yet
        self._spec_firsts = []
        self._chunks_run = 0
        # block-diffusion: the schedule of every slot's open block on
        # the host, the blocks themselves on the device (tokens, which
        # positions are masked, the pass that fixed each, the confidence
        # it was fixed with), handed from pass to pass
        self._blocks = _OpenBlocks(self._slots, Bl) if Bl > 1 else None
        # the launches (passes; steps of token-at-a-time decoding) not
        # yet read; a slot's count of occupants, by which a launch for
        # the last one is told from the present one's
        self._inflight = collections.deque()
        self._serial = np.zeros(self._slots, np.int64)
        self.last_pass = None
        # tokens a slot's request still wants of launches not yet made
        # (admit_incremental's `max_new`), and whether its last one is
        # made: nothing is launched for it after that
        self._budget = np.zeros(self._slots, np.int64)
        self._drained = np.zeros(self._slots, bool)
        # a slot's position by the launches READ (`_pos` is by those
        # made, up to `passes_ahead` or `steps_ahead` further on)
        self._read_pos = np.zeros(self._slots, np.int32)
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            on_device = NamedSharding(self._mesh, PartitionSpec())
        else:
            on_device = self._pool_sharding
        if Bl > 1:
            self._block_state = tuple(
                jax.device_put(np.zeros((self._slots, Bl), dt), on_device)
                for dt in (np.int32, bool, np.int32, np.float32))
        # token-at-a-time decoding without speculation feeds a step's
        # tokens to the next on the device: `_feed` is the last step's
        # `sampled`, with the first token of every prompt completed
        # since put in its slot's row; `_firsts` are those first tokens,
        # still on the device, for the next launch's record to carry to
        # the host.  (Speculation drafts from the tokens read, so its
        # step is read before the next is launched.)
        self._feeds = Bl == 1 and not self._spec_k
        if self._feeds:
            self._feed = jax.device_put(
                np.zeros((self._slots, 1), np.int32), on_device)
            self._firsts = []
            self._feed_first = jax.jit(
                lambda feed, sampled, at, slot:
                feed.at[slot, 0].set(sampled[0, at]))
            # a row no step is launched for feeds id 0, as it always did
            self._feed_on = jax.jit(
                lambda feed, on: jnp.where(on[:, None], feed, 0))

        gluon_params = params
        scfg = self.sampling
        S = self._capacity
        cache_dtype = self._cache_dtype
        page = self._page_size
        cache_rows = self._cache_rows
        mask_id = int(cfg["mask_token_id"]) if Bl > 1 else None

        def _traced(fn, params_):
            with _dtp.scope(dt_policy), \
                    block_mod.swapped_params(
                        gluon_params, _cast_weights(
                            self._param_names, params_, dt_policy)):
                return fn()

        def _cast_logits(arr):
            if dt_policy is not None:
                return dt_policy.cast_output(arr)
            return arr

        # a page of whole sublane tiles (8 rows of 32 bits: 16 of
        # bfloat16) lets the pool of rows be seen as (layers * pages,
        # page_size, H*dh) at no cost, the TPU's tiled layout of both
        # being the same bytes, and a layer gathered page by page: a
        # fifth of the time of the same bytes gathered row by row
        # (PERF.md, PR 32).  Any other page size would make that view a
        # copy of the pool, and gathers rows
        n_pages = self._num_pages
        by_page = page % (8 * max(1, 4 // cache_dtype.itemsize)) == 0

        def layer_rows(pool, li, page_table, rows):
            """Layer ``li``'s cached rows of every slot, (B, S, H*dh).
            Every gather takes its own view of the pool: with one view
            shared by a pool's 24 gathers the chip's compiler schedules
            the same operations a tenth slower (7.44 against 6.70 ms a
            decode program; PERF.md, PR 32)."""
            with jax.named_scope("cache.gather"):
                if not by_page:
                    return pool[li * n_tokens + rows]
                return pool.reshape((L * n_pages, page, H * dh))[
                    li * n_pages + page_table].reshape(
                        rows.shape + (H * dh,))

        def read_declared(pool, state, page_table, rows, lanes):
            """What every layer of a model that declares its caches
            kept: a layer's rows of every slot of the dispatch, (B, S,
            the pool's lanes), gathered like a layer of the pool of
            rows; a layer's state, each array's rows ``slot_ids`` of it
            (the arrays themselves where the dispatch is over all
            slots), zeros for a slot whose sequence starts here; a
            windowed layer's ring, the same rows of it as they lie (a
            sequence that starts here masks them all by position)."""
            slot_ids, fresh, _valid = lanes
            over_all = rows.shape[0] == self._slots
            caches, at = [], 0
            with jax.named_scope("cache.gather"):
                for li, kind in enumerate(declared):
                    if "window" in kind:
                        ring = state[n_state + window_layers.index(li)]
                        caches.append(ring if over_all else ring[slot_ids])
                        continue
                    if "rows" in kind:
                        k = row_layers.index(li)
                        if by_page:
                            got = pool.reshape(
                                (len(row_layers) * n_pages, page, -1))[
                                k * n_pages + page_table].reshape(
                                    rows.shape + (-1,))
                        else:
                            got = pool[k * n_tokens + rows]
                        caches.append(got)
                        continue
                    mine = []
                    for _spec in kind["state"]:
                        a = state[at] if over_all else state[at][slot_ids]
                        mine.append(jnp.where(
                            fresh.reshape((-1,) + (1,) * (a.ndim - 1)),
                            jnp.zeros((), a.dtype), a))
                        at += 1
                    caches.append(tuple(mine))
            return caches

        def write_declared(pool, state, kept, wpage, woff, lanes, start):
            """The chunk's rows scattered to the pool, in place, the
            state of the dispatch's slots put back, and a windowed
            layer's rows that count put in its ring by their positions
            (``ops.attention_rows.ring_write``)."""
            from .ops.attention_rows import ring_write

            slot_ids, valid = lanes[0], lanes[2]
            over_all = slot_ids.shape[0] == self._slots
            state, at = list(state), 0
            with jax.named_scope("cache.write"):
                if row_layers:
                    vals = jnp.stack([kept[li] for li in row_layers])
                    wrow = (jnp.arange(len(row_layers),
                                       dtype=jnp.int32)[:, None]
                            * n_tokens + (wpage * page + woff)[None, :]
                            ).reshape(-1)
                    vals = vals.astype(cache_dtype).reshape(
                        (-1, vals.shape[-1]))
                    pool = pool.at[wrow].set(jnp.pad(vals, (
                        (0, 0), (0, pool.shape[1] - vals.shape[1]))))
                for li in state_layers:
                    for new in kept[li]:
                        new = new.astype(state[at].dtype)
                        state[at] = new if over_all \
                            else state[at].at[slot_ids].set(new)
                        at += 1
                for at, li in enumerate(window_layers, n_state):
                    ring = state[at]
                    new = jnp.pad(kept[li], ((0, 0), (0, 0), (
                        0, ring.shape[2] - kept[li].shape[2])))
                    state[at] = ring_write(ring, new, start, valid) \
                        if over_all else ring.at[slot_ids].set(ring_write(
                            ring[slot_ids], new, start, valid))
            return pool, tuple(state)

        def chunk_fn(params_, pool_k, pool_v, page_table, tokens, start,
                     wpage, woff, lane_keys, block=None, lanes=None,
                     state=(), draft=None):
            """The one paged dispatch: gather the pool rows of each
            slot's pages into each layer's linear cache (a view
            (B, H, S, dh) a layer of the token-major pool; for a model
            that takes rows, (B, S, H*dh), a layer at a time), run the
            model's chunk_forward, sample EVERY chunk position with its
            position-derived key, and scatter the chunk's K/V rows back
            to the pool at token ``wpage * page_size + woff`` — trash
            page 0 absorbs padded positions.  pool_k/pool_v as
            ``pool_shape``; tokens (B, C); page_table (B, P);
            wpage/woff flat (B*C,).  The fifth result is a dict of what
            only some models give: ``expert_load`` (L, E) from an expert
            layer, ``block`` and ``masked`` from a pass of
            block-diffusion decoding.

            ``block`` (a pass of block-diffusion decoding; a prefill
            chunk gives none) is the open blocks as the last pass left
            them, (tokens, masked, fixed_at, confidence), each (B, C),
            and of this pass per slot: ``fresh``, whether its block
            opens here (``tokens`` then holds what the prompt gave it,
            ``given`` how many, the rest is masked), ``take``, how many
            masked positions the pass fixes (0: a commit pass, or no
            one in the slot) and ``number``, which of the block's
            passes it is.  The masked positions are fed the mask token;
            the ``take`` most confident of them (ties to the lower
            position) are fixed at their best token.  ``extras["block"]``
            is the blocks after the pass, ``extras["masked"]`` what was
            masked in it.

            For a model that declares its layers' caches ``pool_k`` is
            its one pool of rows, ``pool_v`` nothing, and ``state`` the
            tuple of its per-slot state arrays, donated like the pools
            and handed back as ``extras["state"]``;
            ``lanes`` = (``slot_ids`` (B,) the slot of each row of the
            dispatch, ``fresh`` (B,) whether its sequence starts here
            (its state reads as zeros: admission costs no dispatch of
            its own), ``valid`` (B,) how many of its C positions count:
            0 for a slot no one is in, whose state the model leaves as
            it was).

            ``draft`` (self-drafting) = (``follow`` (B, C) the token
            that follows each row, ``own`` (B, C) where that token is
            the one this program samples at the row): the model's draft
            block runs on the trunk's states and those tokens, its rows
            go to the pool with the trunk's, and ``extras["draft"]``
            (B, C) is its greedy choice a row, the draft of the position
            two on.

            The engine's own parts of the program are traced under
            ``jax.named_scope`` s, ``cache.gather``, ``cache.write`` and
            ``sample``, beside those the model opens, so that a device
            trace says which part took the time
            (``profiler.device_table``)."""
            Bc, C = tokens.shape
            if block is not None:
                with jax.named_scope("sample"):
                    (b_tok, b_mask, b_at, b_conf), fresh, given, take, \
                        number = block
                    opens = fresh[:, None]
                    b_tok = jnp.where(opens, tokens, b_tok)
                    b_mask = jnp.where(
                        opens, jnp.arange(C)[None, :] >= given[:, None],
                        b_mask)
                    b_at = jnp.where(opens, 0, b_at)
                    b_conf = jnp.where(opens, 0.0, b_conf)
                    tokens = jnp.where(b_mask, mask_id, b_tok)

            # pool token of every cache position: (B, P) pages -> (B, S)
            with jax.named_scope("cache.gather"):
                rows = (page_table[:, :, None] * page
                        + jnp.arange(page, dtype=jnp.int32)).reshape(
                            (Bc, S))

            def pick(logits):
                """A token a position: the best one, or one drawn with
                the position's own key."""
                with jax.named_scope("sample"):
                    if scfg.greedy:
                        return jnp.argmax(logits, axis=-1).astype(
                            jnp.int32)
                    pos_ids = start[:, None] + jnp.arange(
                        C, dtype=jnp.int32)
                    keys = jax.vmap(jax.vmap(jax.random.fold_in))(
                        jnp.broadcast_to(lane_keys[:, None, :],
                                         (Bc, C, 2)), pos_ids)
                    return jax.vmap(jax.vmap(
                        lambda lg, kk: sample_logits(
                            lg[None, :], kk, scfg)[0]))(logits, keys)

            def run():
                if lanes is not None:
                    caches = read_declared(pool_k, state, page_table,
                                           rows, lanes)
                    res = net.chunk_forward(tokens, caches, start, lanes[2])
                    logits, kept, extras = res[0]._data, list(res[1]), \
                        dict(res[2])
                    hidden = extras.pop("hidden", None)
                    if draft is not None:
                        # the draft block, fed the tokens this very
                        # program chose where the host could not know
                        # them
                        follow, own = draft
                        extras["sampled"] = sampled = pick(
                            _cast_logits(logits))
                        dres = net.draft_forward(
                            hidden, jnp.where(own, sampled, follow),
                            caches[L], start, lanes[2])
                        with jax.named_scope("sample"):
                            extras["draft"] = jnp.argmax(
                                dres[0]._data, axis=-1).astype(jnp.int32)
                        kept.append(dres[1])
                        if "expert_load" in dres[2]:
                            extras["expert_load"] = jnp.concatenate([
                                extras["expert_load"],
                                dres[2]["expert_load"]])
                    return logits, kept, extras
                if cache_rows:
                    caches = [(layer_rows(pool_k, li, page_table, rows),
                               layer_rows(pool_v, li, page_table, rows))
                              for li in range(L)]
                else:
                    def view(pool):  # (B, S, L, H*dh) -> (L, B, H, S, dh)
                        return pool[rows].reshape(
                            (Bc, S, L, H, dh)).transpose(2, 0, 3, 1, 4)

                    with jax.named_scope("cache.gather"):
                        gk, gv = view(pool_k), view(pool_v)
                        caches = [(gk[li], gv[li]) for li in range(L)]
                res = net.chunk_forward(tokens, caches, start)
                # a model may hand back a third item: arrays about the
                # forward itself (an expert layer's token counts)
                return res[0]._data, res[1], \
                    dict(res[2]) if len(res) > 2 else {}

            logits, chunk_caches, extras = _traced(run, params_)
            logits = _cast_logits(logits)              # (B, C, V) f32
            sampled = extras.pop("sampled", None)
            if sampled is None:
                sampled = pick(logits)
            if block is not None:
                with jax.named_scope("sample"):
                    # the confidence of each position's best token: its
                    # log-probability under the softmax, in float32
                    lg = logits.astype(jnp.float32)
                    conf = jnp.max(lg, axis=-1) - \
                        jax.scipy.special.logsumexp(lg, axis=-1)
                    # of each row's masked positions the `take` most
                    # confident are fixed; a stable sort leaves ties to
                    # the lower position
                    order = jnp.argsort(jnp.where(b_mask, -conf, jnp.inf),
                                        axis=1, stable=True)
                    rank = jnp.argsort(order, axis=1)
                    fix = b_mask & (rank < take[:, None])
                    extras["masked"] = b_mask
                    extras["block"] = (
                        jnp.where(fix, sampled, b_tok), b_mask & ~fix,
                        jnp.where(fix, number[:, None], b_at),
                        jnp.where(fix, conf, b_conf))
            if lanes is not None:
                pool_k, extras["state"] = write_declared(
                    pool_k, state, chunk_caches, wpage, woff, lanes, start)
                return sampled, logits, pool_k, pool_v, extras
            with jax.named_scope("cache.write"):
                k_new = jnp.stack([k for k, _v in chunk_caches])
                v_new = jnp.stack([v for _k, v in chunk_caches])
                # leading-dimension scatter, in place on the donated
                # pool; padded positions collide on the trash page, so
                # the rows are not unique
                if cache_rows:
                    # (L, B, C, H*dh): one pool row a (layer, chunk
                    # position)
                    kvals = k_new.astype(cache_dtype).reshape((-1,) + row)
                    vvals = v_new.astype(cache_dtype).reshape((-1,) + row)
                    wrow = (jnp.arange(L, dtype=jnp.int32)[:, None]
                            * n_tokens
                            + (wpage * page + woff)[None, :]).reshape(-1)
                else:
                    # (L, B, H, C, dh): one pool row a chunk position
                    kvals = k_new.astype(cache_dtype).transpose(
                        1, 3, 0, 2, 4).reshape((Bc * C,) + row)
                    vvals = v_new.astype(cache_dtype).transpose(
                        1, 3, 0, 2, 4).reshape((Bc * C,) + row)
                    wrow = wpage * page + woff
                pool_k = pool_k.at[wrow].set(kvals)
                pool_v = pool_v.at[wrow].set(vvals)
            return sampled, logits, pool_k, pool_v, extras

        self._jit_chunk = jax.jit(chunk_fn, donate_argnums=(1, 2, 11))
        # the spec and the fingerprint name the pool's layout: an
        # executable stored for another one is never loaded
        if self._declared:       # one pool of rows, state a slot
            pool_tag = "rows%dx%d:state%s" % (
                pool_shape + ("+".join("x".join(str(d) for d in sh)
                                       for sh, _dt in state_specs),))
            if ring_specs:
                pool_tag += ":rings" + "+".join(
                    "x".join(str(d) for d in sh) for sh, _dt in ring_specs)
        elif self._cache_rows:   # one row a (layer, token)
            pool_tag = "L%dxtokens%dxHD%d" % (L, n_tokens, H * dh)
        elif row == (L, H * dh):  # token-major, a token's layers a row
            pool_tag = "tokens%dxL%dxHD%d" % pool_shape
        else:                     # token-major, heads folded into layers
            pool_tag = "tokens" + "x".join(str(d) for d in pool_shape)
        self._aot_spec = aot_spec or (
            "lm_decode_paged:slots%dxpages%dxpg%d:%s"
            % (self._slots, self._num_pages, page, pool_tag))
        store = _aot.resolve_aot(aot)
        if store is not None:
            dtag = _dtp.policy_tag(dt_policy)
            fp = ("dtype=%s;sampling=%s;page=%d;chunk=%d;spec=%d;pool=%s"
                  % (dtag, scfg.tag, page, self._chunk, self._spec_k,
                     pool_tag))
            mext = {"dtype_policy": dtag, "sampling": scfg.tag,
                    "page_size": page, "prefill_chunk": self._chunk,
                    "spec_k": self._spec_k,
                    "pool_layout": pool_tag}
            self._jit_chunk = _aot.AOTFunction(
                self._jit_chunk, "generate:paged_chunk", store,
                fingerprint_extra=fp, manifest_kind="generate",
                manifest_spec=self._aot_spec, manifest_extra=mext)
        self._H, self._dh, self._L = H, dh, L

    # -- introspection ---------------------------------------------------

    @property
    def slots(self):
        return self._slots

    @property
    def cache_len(self):
        """Positions one slot can hold (pages_per_slot x page_size)."""
        return self._capacity

    @property
    def page_size(self):
        return self._page_size

    @property
    def num_pages(self):
        """Pool pages including the reserved trash page 0."""
        return self._num_pages

    @property
    def pages_per_slot(self):
        return self._pages_per_slot

    @property
    def pool_shape(self):
        """Shape of each of the K and V pools on the device (for a
        model that declares its layers' caches: of its one pool of
        rows)."""
        return tuple(self._pool_k.shape)

    @property
    def param_bytes(self):
        """Bytes of the weights the engine holds, as it holds them."""
        return sum(int(a.nbytes) for a in self._params)

    @property
    def prefill_chunk(self):
        return self._chunk

    @property
    def spec_k(self):
        return self._spec_k

    @property
    def dtype_policy_tag(self):
        from . import dtype_policy as _dtp

        return _dtp.policy_tag(self._dtype_policy)

    @property
    def cache_dtype(self):
        return self._cache_dtype

    @property
    def mesh_shape(self):
        from . import parallel

        return parallel.mesh_shape(self._mesh)

    def active_slots(self):
        return [int(i) for i in np.nonzero(self._active)[0]]

    def free_slots(self):
        return len(self._free)

    def pending_prefill(self):
        """Slots admitted but still streaming prefill chunks."""
        return len(self._pending)

    def position(self, slot):
        return int(self._pos[slot])

    def cached(self, slots):
        """What the caches hold of the sequences in ``slots``, read on
        the host (a check's reading, never the serving loop's: the
        whole pool of rows and every state array cross to the host,
        once, and nothing is compiled): for a model that declares its
        layers' caches, a slot ``{"position": the positions cached,
        "tokens": the ids at them, "layers": a layer its rows
        (position, width), the tuple of its state arrays or, for a
        windowed layer, ``{"first": p, "rows": (position - p, width)}``,
        the rows its ring holds in position order from the first
        position it still holds whole (a ring of R rows the last ``R -
        spec_k``: a rejected draft's row may lie where the one before
        them lay)}``, float32;
        under self-drafting the draft block's rows are the last layer's,
        ``"drafts"`` is :meth:`drafted` and ``"next_token"`` the id after
        the cached ones, which that block's last row was fed.
        The caches are as far as the launches MADE have brought them,
        so the ids of the launches still in flight are read too (and
        stay in flight: :meth:`decode_step` hands them out as it would
        have).  For the engine's one thread, between two dispatches."""
        if not self._declared:
            raise MXNetError("cached() reads the caches of a model that "
                             "declares them (config['layer_caches'])")
        flight = self._tokens_in_flight()
        pool = np.asarray(self._pool_k)
        state = [np.asarray(a) for a in self._state]
        n_tokens = self._num_pages * self._page_size
        out = []
        for slot in slots:
            st = self._pending.get(slot)
            n = int(st["filled"] if st is not None else self._pos[slot])
            at = np.arange(n)
            tok = self._page_table[slot, at // self._page_size] \
                * self._page_size + at % self._page_size
            layers, rows_seen, state_seen = [], 0, 0
            for li, kind in enumerate(self._layer_caches):
                if "window" in kind:    # (the rings follow the state)
                    ring = state[len(state) - len(self._window_layers)
                                 + self._window_layers.index(li)]
                    rows = ring.shape[1]
                    first = max(0, n - (rows - self._spec_k))
                    layers.append({"first": first, "rows": ring[
                        slot, np.arange(first, n) % rows,
                        :int(kind["window"][1])].astype(np.float32)})
                    continue
                if "rows" in kind:
                    layers.append(pool[rows_seen * n_tokens + tok][
                        :, :int(kind["rows"])].astype(np.float32))
                    rows_seen += 1
                else:
                    mine = state[state_seen:state_seen
                                 + len(kind["state"])]
                    state_seen += len(mine)
                    layers.append(tuple(a[slot].astype(np.float32)
                                        for a in mine))
            ids = self._history[slot] + flight.get(int(slot), [])
            out.append({"position": n, "tokens": ids[:n],
                        "layers": layers})
            if self._self_draft:
                # (the draft block's row of the last cached position was
                # fed the id after it)
                out[-1]["drafts"] = self.drafted(slot)
                out[-1]["next_token"] = ids[n] if len(ids) > n else None
        return out

    def drafted(self, slot):
        """Under self-drafting, the draft block's choices for ``slot``'s
        sequence so far, one an emitted token: entry ``j`` is what the
        block chose, at the row the ``j``-th emitted token was chosen
        at, for the token after it (the next step's draft where that row
        was the step's last accepted one).  None for an engine that does
        not draft from its model."""
        if not self._self_draft:
            return None
        return list(self._drafted.get(slot, ()))

    def _tokens_in_flight(self):
        """``{slot: ids}`` that the launches not yet read give their
        present occupants, in launch order, read without taking a launch
        off the queue: with ``_history`` they are the ids that ``_pos``
        counts."""
        out = {}
        for rec in self._inflight:
            mine = (rec["serial"] == self._serial) & self._active
            if self._block > 1:
                toks = np.asarray(rec["read"][0])
                for b in np.nonzero(mine & rec["on"] & rec["commit"])[0]:
                    out.setdefault(int(b), []).extend(
                        toks[b, rec["given"][b]:].tolist())
                continue
            for slot, first, at in rec["firsts"]:
                if mine[slot]:
                    out.setdefault(slot, []).append(
                        int(np.asarray(first)[0, at]))
            if rec["sampled"] is not None:
                toks = np.asarray(rec["sampled"])
                for b in np.nonzero(mine & rec["on"])[0]:
                    out.setdefault(int(b), []).append(int(toks[b, 0]))
        return out

    @property
    def last_logits(self):
        """The logits of the last launch READ, on the host: of the step
        (the pass) a :meth:`decode_step` handed over, or of a chunk whose
        token was read (:meth:`admit`; a speculating or block-decoding
        engine's :meth:`prefill_step`)."""
        out = getattr(self, "_last_logits", None)
        return None if out is None else np.asarray(out)

    def pages_in_use(self):
        """Distinct pool pages referenced by live slots (trash page and
        retained-but-unreferenced prefix pages excluded)."""
        live = np.unique(self._page_table)
        return int((live != 0).sum())

    def prefix_hit_rate(self):
        """Fraction of shareable prompt tokens served from the prefix
        cache (None before any lookup)."""
        if not self._prefix_lookup_tokens:
            return None
        return self._prefix_hit_tokens / self._prefix_lookup_tokens

    def spec_accept_rate(self):
        """Fraction of drafted tokens accepted by verify steps (None
        before any draft)."""
        if not self._spec_drafted:
            return None
        return self._spec_accepted / self._spec_drafted

    def spec_accepted_per_step(self):
        """Mean drafted-and-accepted tokens per verify step that
        carried at least one draft (each such step emits 1 + this)."""
        if not self._spec_steps:
            return None
        return self._spec_accepted / self._spec_steps

    def occupancy(self):
        active = int(self._active.sum()) + len(self._pending)
        tokens = int(np.minimum(self._pos[self._active],
                                self._capacity).sum()) \
            if self._active.any() else 0
        cap = self._slots * self._capacity
        out = {"active_slots": active, "slots": self._slots,
               "cache_tokens": tokens, "cache_capacity": cap,
               "occupancy": tokens / cap if cap else 0.0,
               "pages_in_use": self.pages_in_use(),
               "pages_total": self._num_pages - 1,
               "page_size": self._page_size,
               "prefix_cached_pages": len(self._prefix_map),
               "pending_prefill": len(self._pending)}
        hr = self.prefix_hit_rate()
        if hr is not None:
            out["prefix_hit_rate"] = round(hr, 4)
        ar = self.spec_accept_rate()
        if ar is not None:
            out["spec_accept_rate"] = round(ar, 4)
            out["spec_accepted_per_step"] = round(
                self.spec_accepted_per_step(), 4)
        return out

    def _note_occupancy(self):
        occ = self.occupancy()
        _telemetry.DECODE_ACTIVE_SLOTS.set(occ["active_slots"])
        _telemetry.DECODE_CACHE_TOKENS.set(occ["cache_tokens"])
        _telemetry.DECODE_PAGES_IN_USE.set(occ["pages_in_use"])

    def bucket_for(self, length):
        """Admissibility check: raises when ``length`` exceeds a slot's
        page capacity, else returns the chunk-padded prefill length
        (advisory; prefix hits shorten the actual work)."""
        limit = min(self._capacity, self.model_config["max_len"])
        Bl = self._block
        if Bl > 1:
            # the prompt's tail opens a block that must fit whole
            limit = Bl * (limit // Bl) - 1
        if length > limit:
            raise MXNetError(
                "prompt length %d exceeds the paged cache capacity %d "
                "(%d pages x %d positions; shorten the prompt or build "
                "the engine with a longer cache)"
                % (length, limit, self._pages_per_slot, self._page_size))
        return self._chunk * (-(-length // self._chunk))

    def at_capacity(self, slot):
        """No room for the slot's next token (under block-diffusion
        decoding: for the next whole block after those it has
        emitted)."""
        return self._read_pos[slot] + self._block - 1 >= min(
            self._capacity, self.model_config["max_len"])

    # -- page bookkeeping ------------------------------------------------

    def _take_page(self):
        """A free page, reclaiming the LRU retained prefix page when
        the free list is dry (reclaim unregisters it)."""
        if self._free_pages:
            return self._free_pages.popleft()
        if self._reclaim:
            pg, h = self._reclaim.popitem(last=False)
            del self._prefix_map[h]
            del self._page_hash[pg]
            return int(pg)
        return None

    def _release_slot_pages(self, slot):
        row = self._page_table[slot]
        for i in range(self._pages_per_slot):
            pg = int(row[i])
            if pg == 0:
                continue
            self._page_ref[pg] -= 1
            if self._page_ref[pg] <= 0:
                h = self._page_hash.get(pg)
                if h is not None:
                    # registered prefix page: retained (LRU) until
                    # pool pressure reclaims it — a follow-up request
                    # with the same prompt still hits
                    self._reclaim[pg] = h
                    self._reclaim.move_to_end(pg)
                else:
                    self._free_pages.appendleft(pg)
        row[:] = 0

    def _register_prefix(self, slot, token_ids, n):
        """After a prompt fully prefilled: register its full pages in
        the prefix map (first writer wins; an attached page is already
        registered under the same chain hash)."""
        limit = min((n - 1) // self._page_size, self._pages_per_slot)
        if limit <= 0:
            return
        row = self._page_table[slot]
        for i, h in enumerate(_prefix_page_hashes(
                token_ids, self._page_size, limit, int(self._self_draft))):
            if h in self._prefix_map:
                continue
            pg = int(row[i])
            self._prefix_map[h] = pg
            self._page_hash[pg] = h

    # -- lifecycle of one sequence ---------------------------------------

    def admit_incremental(self, token_ids, max_new=None):
        """Claim a slot for ``token_ids``: attach any shared prefix
        pages, allocate the remainder of the slot's pages upfront (so
        decode can never starve mid-flight), and queue the un-shared
        prompt tail for chunked prefill.  Returns the slot; the first
        token arrives from the :meth:`prefill_step` that completes the
        prompt.  ``max_new`` is the most tokens the caller will take:
        decode steps (and block-diffusion decoding's passes) are
        launched ahead of their results, and none past the token (the
        block) that covers them.
        Raises :class:`Overloaded` (``slots`` / ``pages``)."""
        token_ids = np.asarray(token_ids).astype(np.int32).reshape(-1)
        n = token_ids.size
        if n < 1:
            raise MXNetError("admit needs at least one prompt token")
        self.bucket_for(n)
        if not self._free:
            raise Overloaded("slots", "all %d decode slots busy"
                             % self._slots)
        # prefix attach: longest chain of already-registered full
        # prompt pages (never the page holding token n-1 — the tail
        # must prefill so the first token's logits exist)
        attached = []
        if self._prefix_share:
            limit = min((n - 1) // self._page_size,
                        self._pages_per_slot)
            # (a draft block's row of a position depends on the token
            # after it: a page is then the same only with that one too)
            hashes = _prefix_page_hashes(token_ids, self._page_size,
                                         limit, int(self._self_draft))
            for h in hashes:
                pg = self._prefix_map.get(h)
                if pg is None:
                    break
                attached.append((h, pg))
            self._prefix_lookup_tokens += limit * self._page_size
            self._prefix_hit_tokens += len(attached) * self._page_size
            _telemetry.DECODE_PREFIX_LOOKUP_TOKENS.inc(
                limit * self._page_size)
            _telemetry.DECODE_PREFIX_HIT_TOKENS.inc(
                len(attached) * self._page_size)
        self.last_prefix_hit_tokens = len(attached) * self._page_size
        fresh = []
        for _ in range(self._pages_per_slot - len(attached)):
            pg = self._take_page()
            if pg is None:
                for p in fresh:
                    self._free_pages.appendleft(p)
                raise Overloaded(
                    "pages", "page pool exhausted (%d/%d in use)"
                    % (self.pages_in_use(), self._num_pages - 1))
            fresh.append(pg)
        slot = self._free.popleft()
        self._budget[slot] = np.iinfo(np.int64).max if max_new is None \
            else int(max_new)
        self._drained[slot] = False
        row = self._page_table[slot]
        for i, (_h, pg) in enumerate(attached):
            if self._page_ref[pg] == 0:
                self._reclaim.pop(pg, None)
            self._page_ref[pg] += 1
            row[i] = pg
        for j, pg in enumerate(fresh):
            self._page_ref[pg] += 1
            row[len(attached) + j] = pg
        start = len(attached) * self._page_size
        # block-diffusion: whole blocks of the prompt are prefilled; its
        # tail opens the first block as given positions
        Bl = self._block
        target = n if Bl == 1 else Bl * (n // Bl)
        self._history[slot] = token_ids.tolist()
        if start < target:
            self._pending[slot] = {"tokens": token_ids, "filled": start,
                                   "n": target}
        else:
            self._open_first_block(slot, token_ids, target)
        if self.sampling.greedy:
            self._lane_keys[slot] = 0
        else:
            from . import random as _random

            self._lane_keys[slot] = np.asarray(_random.next_key(),
                                               np.uint32)
        return slot

    def prefill_step(self, slot=None):
        """Run ONE prefill chunk (round-robin across pending slots, or
        the given ``slot``).  Returns ``(slot, None)`` when that chunk
        completed its prompt, else None: the prompt's first token stays
        on the device, where the slot's first decode step takes it, and
        comes to the host in :meth:`decode_step`'s result (under
        block-diffusion decoding: of the first block's commit).  With
        speculation the next verify step's read-back brings it (that
        step is launched without the slot: its drafts need the token on
        the host); :meth:`admit` by hand reads it at once.  The
        TokenServer calls
        this once per loop tick, interleaving long prefills with decode
        steps; the round-robin keeps a short prompt's TTFT from hiding
        behind a long prompt admitted just before it."""
        return self._prefill_chunk(
            slot, read=not self._feeds and not self._spec_k)

    def _prefill_chunk(self, slot, read):
        """:meth:`prefill_step`; with ``read`` a prompt's first token is
        waited for and returned, wherever else it goes."""
        import jax

        if not self._pending:
            return None
        if slot is None:
            slot = next(iter(self._pending))
            self._pending.move_to_end(slot)
        st = self._pending[slot]
        toks, filled, n = st["tokens"], st["filled"], st["n"]
        count = min(self._chunk, n - filled)
        final = filled + count >= n
        with _tracing.begin("engine.prefill", args={
                "slot": int(slot), "filled": int(filled),
                "count": int(count), "final": final,
                "block": self._block,
                "attn": self._attends_in(self._chunk),
                "cache_rows_attended": self._cache_rows_attended(
                    self._chunk, filled),
                "cache_rows_held": self._cache_rows_held()}) as step:
            chunk = np.zeros((1, self._chunk), np.int32)
            chunk[0, :count] = toks[filled:filled + count]
            wpage = np.zeros(self._chunk, np.int32)
            woff = np.zeros(self._chunk, np.int32)
            row = self._page_table[slot]
            for j in range(count):
                p = filled + j
                wpage[j] = row[p // self._page_size]
                woff[j] = p % self._page_size
            lanes = None
            if self._declared:
                # the slot's own row of every state array, read as zeros
                # by the chunk that starts its sequence
                fresh = filled == 0
                lanes = (np.asarray([slot], np.int32), np.asarray([fresh]),
                         np.asarray([count], np.int32))
                if self._state_layers:
                    step.set(state_slots=1)
                    if fresh:
                        _telemetry.DECODE_STATE_RESETS.inc()
            draft = None
            if self._self_draft:
                # the draft block runs over the prompt too: row i is fed
                # prompt token i + 1, the last row of the last chunk the
                # first token this program chooses
                follow = np.zeros((1, self._chunk), np.int32)
                own = np.zeros((1, self._chunk), bool)
                ahead = toks[filled + 1:filled + 1 + count]
                follow[0, :len(ahead)] = ahead
                own[0, count - 1] = final
                draft = (follow, own)
                step.set(drafted=int(final))
            with _tracing.begin("engine.prefill:launch"):
                sampled, logits, extras = self._dispatch(
                    self._page_table[slot:slot + 1].copy(), chunk,
                    np.asarray([filled], np.int32), wpage, woff,
                    self._lane_keys[slot:slot + 1].copy(), lanes=lanes,
                    draft=draft)
            if read:    # (`last_logits` are of a launch that was read)
                self._last_logits = logits
            self._chunks_run += 1
            _telemetry.DECODE_PREFILL_CHUNKS.inc()
            if not final:
                st["filled"] = filled + count
                return None
            del self._pending[slot]
            if self._block > 1:
                # no token comes of a prompt's last chunk: its tail
                # opens the first block, which decode_step denoises
                self._open_first_block(slot, toks, n)
                return slot, None
            # the first token is one of the request's `max_new`; a step
            # is launched for the slot if it wants more and has room
            self._pos[slot] = self._read_pos[slot] = n
            self._budget[slot] -= 1
            self._drained[slot] = self._budget[slot] <= 0 or n >= min(
                self._capacity, self.model_config["max_len"])
            self._active[slot] = True
            tok = None
            if self._feeds:
                self._feed = self._feed_first(
                    self._feed, sampled, np.int32(count - 1),
                    np.int32(slot))
                if not read:
                    self._firsts.append((int(slot), sampled, count - 1))
                    step.set(fed=1)
            if read:
                with _tracing.begin("engine.prefill:readback"):
                    # one wait for the small arrays, not one each
                    got, chose = jax.device_get(
                        (sampled, extras.get("draft")))
                    tok = int(got[0, count - 1])
                    if self._self_draft:
                        self._draft_tok[slot] = chose[0, count - 1]
                        self._drafted[slot] = [int(chose[0, count - 1])]
                self._cur_tok[slot] = tok
                self._history[slot].append(tok)
            elif self._spec_k:
                self._spec_firsts.append(
                    (int(slot), sampled, extras.get("draft"), count - 1))
            if self._prefix_share:
                self._register_prefix(slot, toks, n)
            self._note_occupancy()
        return slot, tok

    def _open_first_block(self, slot, token_ids, prefilled):
        """Block-diffusion: the prompt's whole blocks are in the cache;
        its last ``n mod block_length`` tokens open the first block as
        given positions, the rest of it masked."""
        self._pos[slot] = self._read_pos[slot] = prefilled
        self._blocks.open(slot, token_ids[prefilled:])
        self._active[slot] = True
        if self._prefix_share:
            self._register_prefix(slot, token_ids, token_ids.size)
        self._note_occupancy()

    def admit(self, token_ids, max_new=None):
        """Synchronous admission, for a caller that drives the engine
        by hand: claim a slot, run every prefill chunk back to back and
        read what the last one gave.  Returns ``(slot, first_token)``
        (under block-diffusion decoding ``(slot, None)``)."""
        sl = self.admit_incremental(token_ids, max_new=max_new)
        while sl in self._pending:
            res = self._prefill_chunk(sl, read=True)
            if res is not None:
                return res
        return sl, None

    def decode_step(self):
        """One fixed-shape step for every active slot: launch it, then
        read the oldest launch still unread, leaving ``steps_ahead``
        queued (``passes_ahead`` passes under block-diffusion decoding,
        :meth:`_decode_blocks`).  Returns ``{slot: [tokens...]}`` of the
        launch READ: one token a slot it stepped, led by the prompt's
        first token where the slot's prompt completed just before that
        launch; ``[]`` for a slot that launch was not for (its occupant
        came since), and for every active slot when nothing is read yet
        (by hand: the first ``steps_ahead`` calls).  No step is
        launched for a slot whose request has all the tokens it wants
        (``admit_incremental``'s ``max_new``) or no room under way;
        with nothing to launch a call reads one launch, and
        :meth:`drain` reads them all.  ``last_logits`` are of the step
        read.  With speculation a call launches AND reads its own step:
        up to ``spec_k + 1`` tokens a slot (drafted tokens that
        verified, plus the one token sampling always yields), and for a
        slot whose prompt completed since the last call its first token
        alone, read with the step (the slot has no row in it).  Rejected
        drafts leave K/V at positions >= the new ``pos``; those entries
        are masked by ``start`` and overwritten as decode advances."""
        if not self._active.any():
            self._inflight.clear()    # no one is left to read them for
            return {}
        if self._block > 1:
            return self._decode_blocks()
        if self._spec_k:
            return self._decode_verify()
        B = self._slots
        cap = min(self._capacity, self.model_config["max_len"])
        active = [int(b) for b in np.nonzero(self._active)[0]]
        on = self._active & ~self._drained
        stepped = int(on.sum())
        # the step's phases as always-kept spans (docs/observability.md
        # "Spans of the hot loops"); DECODE_STEP_SECONDS reads the span
        with _tracing.begin("engine.decode", args={
                "slots": stepped, "live": int(self._pos[on].sum()),
                "fed": stepped, "attn": self._attends_in(1),
                "cache_rows_attended": self._cache_rows_attended(
                    1, int(self._pos[on].max(initial=0))),
                "cache_rows_held": self._cache_rows_held()}) as step:
            logits, extras = None, {}
            if stepped:
                with _tracing.begin("engine.decode:prep"):
                    pos = np.where(on, self._pos, 0).astype(np.int32)
                    wpage = np.where(on, self._page_table[
                        np.arange(B), np.minimum(
                            pos // self._page_size,
                            self._pages_per_slot - 1)], 0).astype(np.int32)
                    woff = np.where(on, pos % self._page_size,
                                    0).astype(np.int32)
                    key = self._lane_keys.copy()
                    table = self._page_table.copy()
                    lanes = None
                    if self._declared:
                        # every slot's row of the state; only those a
                        # step is launched for count a position, the
                        # others stay as they are
                        lanes = (np.arange(B, dtype=np.int32),
                                 np.zeros(B, bool), on.astype(np.int32))
                        if self._state_layers:
                            step.set(state_slots=stepped)
                with _tracing.begin("engine.decode:launch"):
                    tokens = self._feed if on.all() \
                        else self._feed_on(self._feed, on)
                    self._feed, logits, extras = self._dispatch(
                        table, tokens, pos, wpage, woff, key, lanes=lanes)
                # positions and budgets move on by counts, the step unread
                self._pos[on] += 1
                self._budget[on] -= 1
                self._drained |= on & ((self._budget <= 0)
                                       | (self._pos >= cap))
            if stepped or self._firsts:
                # (first tokens alone where their requests want no more)
                self._inflight.append({
                    "on": on, "serial": self._serial.copy(),
                    "sampled": self._feed if stepped else None,
                    "logits": logits, "load": extras.get("expert_load"),
                    "firsts": self._firsts})
                self._firsts = []
            ahead = self.steps_ahead if stepped else 0
            if len(self._inflight) > ahead:
                out = self._read_step(step)
            else:
                out = {b: [] for b in active}
            step.set(unread=len(self._inflight))
        _telemetry.DECODE_STEP_SECONDS.observe(step.dur)
        return out

    def _read_step(self, step):
        """Take the oldest step still unread off the queue and wait for
        it: ``{slot: [tokens]}``, for every active slot the tokens that
        the step (and the first tokens it carries) gave its present
        occupant."""
        import jax

        read = self._inflight.popleft()
        with _tracing.begin("engine.decode:readback"):
            # one wait for the small arrays, not one each
            sampled, load, firsts = jax.device_get((
                read["sampled"], read["load"],
                [first for _slot, first, _at in read["firsts"]]))
        with _tracing.begin("engine.decode:post"):
            # a slot evicted since the launch has another serial
            mine = (read["serial"] == self._serial) & self._active
            out = {int(b): [] for b in np.nonzero(self._active)[0]}
            for (slot, _first, at), got in zip(read["firsts"], firsts):
                if mine[slot]:
                    out[slot].append(int(got[0, at]))
            for b in np.nonzero(mine & read["on"])[0]:
                out[int(b)].append(int(sampled[b, 0]))
                self._read_pos[b] += 1
            for b, toks in out.items():
                self._history[b].extend(toks)
            if read["logits"] is not None:
                self._last_logits = read["logits"]
            if load is not None:
                self._note_expert_load(step, load)
            _telemetry.DECODE_TOKENS.inc(sum(len(t) for t in out.values()))
            _telemetry.DECODE_BATCH_TOKENS.observe(int(read["on"].sum()))
            self._note_occupancy()
        return out

    def drain(self):
        """Read every launch still in flight, launching nothing: a list,
        oldest first, of what :meth:`decode_step` would have returned
        for each.  For a caller that drives the engine by hand and wants
        the tokens of its last calls."""
        outs = []
        while self._inflight:
            with _tracing.begin("engine.drain") as step:
                outs.append(self._read_pass(step) if self._block > 1
                            else self._read_step(step))
        return outs

    def _decode_verify(self):
        """The step under speculation, ``(slots, spec_k + 1)``: the
        current token and the drafts of every active slot, launched and
        read in one call.  The drafts come of the host's n-gram history
        or, for a model with a draft block at ``spec_k`` 1, of the model
        itself: the program that verifies a draft also runs the draft
        block on both rows, each fed the token the trunk chose after
        it, and hands back the block's choices; the one at the last
        accepted row is the next step's draft.  Rows at and after the
        new position, the trunk's and the draft block's, are overwritten
        by the next step."""
        import jax

        B, K = self._slots, self._spec_k
        cap = min(self._capacity, self.model_config["max_len"])
        # the slots whose current token is on the host; one whose prompt
        # completed since the last call is given no row: its first token
        # comes with this step's read-back
        firsts, self._spec_firsts = self._spec_firsts, []
        waiting = {f[0] for f in firsts}
        active = [int(b) for b in np.nonzero(self._active)[0]
                  if b not in waiting]
        C = K + 1
        source = "model" if self._self_draft else "ngram"
        with _tracing.begin("engine.decode", args={
                "slots": len(active),
                "live": int(self._pos[active].sum()), "fed": 0,
                "attn": self._attends_in(C), "draft": source,
                "cache_rows_attended": self._cache_rows_attended(
                    C, int(self._pos[active].max(initial=0))),
                "cache_rows_held": self._cache_rows_held()}) as step:
            sampled = chose = load = None
            with _tracing.begin("engine.decode:prep"):
                tokens = np.zeros((B, C), np.int32)
                drafts = {}
                for b in active:
                    tokens[b, 0] = self._cur_tok[b]
                    room = cap - 1 - int(self._pos[b])
                    if room <= 0:
                        d = []
                    elif self._self_draft:
                        d = [int(self._draft_tok[b])]
                    else:
                        d = _ngram_draft(self._history[b],
                                         self._spec_ngram, min(K, room))
                    drafts[b] = d
                    tokens[b, 1:1 + len(d)] = d
                wpage = np.zeros(B * C, np.int32)
                woff = np.zeros(B * C, np.int32)
                for b in active:
                    for j in range(len(drafts[b]) + 1):
                        p = int(self._pos[b]) + j
                        wpage[b * C + j] = \
                            self._page_table[b, p // self._page_size]
                        woff[b * C + j] = p % self._page_size
                key = self._lane_keys.copy()
                table = self._page_table.copy()
                pos = self._pos.astype(np.int32).copy()
                lanes = draft = None
                if self._declared:
                    # (rows only: a model with per-slot state is refused
                    # speculation when the engine is built)
                    valid = np.zeros(B, np.int32)
                    for b in active:
                        valid[b] = len(drafts[b]) + 1
                    lanes = (np.arange(B, dtype=np.int32),
                             np.zeros(B, bool), valid)
                if self._self_draft:
                    # every row is followed by the token chosen at it
                    draft = (np.zeros((B, C), np.int32),
                             np.ones((B, C), bool))
            if active:
                with _tracing.begin("engine.decode:launch"):
                    sampled, self._last_logits, extras = self._dispatch(
                        table, tokens, pos, wpage, woff, key, lanes=lanes,
                        draft=draft)
                    chose = extras.get("draft")
                    load = extras.get("expert_load")
            with _tracing.begin("engine.decode:readback"):
                # one wait for the small arrays, not one each
                sampled, chose, load, first = jax.device_get((
                    sampled, chose, load,
                    [(got, d) for _slot, got, d, _at in firsts]))
            with _tracing.begin("engine.decode:post"):
                out = {}
                emitted_total = drafted = accepted = 0
                for (b, _got, _d, at), (got, d) in zip(firsts, first):
                    # (a slot let go since its last chunk is not in
                    # `firsts` any more: `evict`)
                    tok = int(got[0, at])
                    self._cur_tok[b] = tok
                    self._history[b].append(tok)
                    if self._self_draft:
                        self._draft_tok[b] = d[0, at]
                        self._drafted[b] = [int(d[0, at])]
                    out[b] = [tok]
                for b in active:
                    d = drafts[b]
                    acc = 0
                    while acc < len(d) and d[acc] == sampled[b, acc]:
                        acc += 1
                    emitted = [int(t) for t in sampled[b, :acc + 1]]
                    if d:
                        drafted += len(d)
                        accepted += acc
                        self._spec_steps += 1
                    if self._self_draft:
                        self._draft_tok[b] = chose[b, acc]
                        self._drafted[b].extend(
                            int(t) for t in chose[b, :acc + 1])
                    out[b] = emitted
                    emitted_total += len(emitted)
                    self._cur_tok[b] = emitted[-1]
                    self._pos[b] += len(emitted)
                    self._read_pos[b] = self._pos[b]
                    self._history[b].extend(emitted)
                self._spec_drafted += drafted
                self._spec_accepted += accepted
                _telemetry.DECODE_SPEC_DRAFTED.inc(drafted, source=source)
                _telemetry.DECODE_SPEC_ACCEPTED.inc(accepted, source=source)
                _telemetry.DECODE_TOKENS.inc(emitted_total + len(firsts))
                _telemetry.DECODE_BATCH_TOKENS.observe(len(active))
                if load is not None:
                    self._note_expert_load(step, load)
                self._note_occupancy()
            step.set(unread=0, drafted=drafted, accepted=accepted,
                     emitted=emitted_total)
            if firsts:
                step.set(firsts=len(firsts))
        _telemetry.DECODE_STEP_SECONDS.observe(step.dur)
        return out

    def _decode_blocks(self):
        """Launch one pass of every active slot's open block, all in the
        one ``(slots, block_length)`` program whatever pass each slot is
        on, then read what the oldest pass still unread gave.
        A **denoise pass** feeds the block with its masked positions as
        the mask token against the committed cache, and of the masked
        positions fixes the ``ceil(masked at block start / T)`` most
        confident (ties to the lower position) at their best token; its
        K/V rows go to the trash page.  After ``T`` of them nothing is
        masked, and a **commit pass** runs the clean block, writes its
        K/V to the slot's pages, and emits the block.  The blocks stay
        on the device and which pass a slot is on follows from counts
        alone, so a pass is queued behind the ones before it without the
        host having read them: a call leaves ``passes_ahead`` passes
        unread, and launches none for a slot whose request's last block
        (``admit_incremental``'s ``max_new``, the cache's end) has its
        commit launched.  Returns, for the pass read, ``{slot: []}``
        for a slot whose block stayed open and a :class:`BlockTokens`
        for one that committed; a slot whose occupant left meanwhile is
        not in it, and a call with no pass to read yet returns
        ``{slot: []}`` for every active slot.  ``last_pass`` and
        ``last_logits`` are of the pass read."""
        Bl, T = self._block, self._denoise_steps
        cap = min(self._capacity, self.model_config["max_len"])
        active = [int(b) for b in np.nonzero(self._active)[0]]
        with _tracing.begin("engine.decode", args={
                "slots": len(active),
                "live": int(self._pos[active].sum()),
                "attn": self._attends_in(Bl)}) as step:
            blk, on = self._blocks, self._active & ~self._drained
            if on.any():
                with _tracing.begin("engine.decode:prep"):
                    # a slot whose T denoise passes are done commits:
                    # its rows go to its pages, every other row to the
                    # trash
                    commit = on & (blk.passes >= T)
                    run = on & ~commit
                    take = np.where(run, np.minimum(
                        -(-blk.masked_at_start // T), blk.left), 0)
                    # a slot no pass is launched for reads as a block of
                    # zeros with nothing masked
                    fresh = blk.fresh | ~on
                    given = np.where(on, blk.given, Bl).astype(np.int32)
                    tokens = blk.tokens * (fresh & on)[:, None]
                    number = blk.passes + 1
                    p = self._pos[:, None] + np.arange(Bl)
                    wpage = np.where(commit[:, None], np.take_along_axis(
                        self._page_table, np.minimum(
                            p // self._page_size, self._pages_per_slot - 1),
                        axis=1), 0).astype(np.int32).reshape(-1)
                    woff = np.where(commit[:, None], p % self._page_size,
                                    0).astype(np.int32).reshape(-1)
                    key = self._lane_keys.copy()
                    table = self._page_table.copy()
                    pos = self._pos.astype(np.int32).copy()
                    launched = {
                        "on": on, "commit": commit, "start": pos,
                        "pass": number, "given": blk.given.copy(),
                        "serial": self._serial.copy()}
                    # the schedule moves on by counts, the pass unread
                    blk.fresh[on] = False
                    blk.left -= take
                    blk.passes[run] += 1
                    for b in np.nonzero(commit)[0]:
                        self._budget[b] -= Bl - blk.given[b]
                        self._pos[b] += Bl
                        self._drained[b] = self._budget[b] <= 0 \
                            or self._pos[b] + Bl > cap
                        blk.open(b)
                    step.set(denoise=int(run.sum()),
                             commit=int(commit.sum()))
                    _telemetry.DECODE_DENOISE_PASSES.inc(int(run.sum()))
                    _telemetry.DECODE_COMMIT_PASSES.inc(int(commit.sum()))
                    _telemetry.DECODE_BATCH_TOKENS.observe(len(active))
                with _tracing.begin("engine.decode:launch"):
                    _sampled, logits, extras = self._dispatch(
                        table, tokens, pos, wpage, woff, key,
                        block=(self._block_state, fresh, given,
                               take.astype(np.int32), number))
                    self._block_state = now = extras["block"]
                    launched["logits"] = logits
                    launched["read"] = (now[0], now[2], now[3],
                                        extras["masked"],
                                        extras.get("expert_load"))
                    self._inflight.append(launched)
            else:
                step.set(denoise=0, commit=0)
            # with nothing launched (every request's last commit is
            # under way) the passes in flight are read one a call
            ahead = self.passes_ahead if on.any() else 0
            if len(self._inflight) <= ahead:
                step.set(emitted=0)
                out = {b: [] for b in active}
            else:
                out = self._read_pass(step)
        _telemetry.DECODE_STEP_SECONDS.observe(step.dur)
        return out

    def _read_pass(self, step):
        """Take the oldest pass still unread off the queue and wait for
        it (:meth:`_decode_blocks` says what it returns)."""
        import jax

        read = self._inflight.popleft()
        with _tracing.begin("engine.decode:readback"):
            # one wait for the small arrays, not one each
            toks, at, conf, masked, load = jax.device_get(read["read"])
        with _tracing.begin("engine.decode:post"):
            # a slot evicted since the launch has another serial
            mine = read["on"] & (read["serial"] == self._serial) \
                & self._active
            out = {int(b): [] for b in np.nonzero(mine)[0]}
            emitted_total = 0
            for b in np.nonzero(mine & read["commit"])[0]:
                g = read["given"][b]
                out[int(b)] = emitted = BlockTokens(
                    toks[b, g:].tolist(), at[b, g:].tolist(),
                    conf[b, g:].tolist())
                emitted_total += len(emitted)
                self._history[int(b)].extend(emitted)
                self._read_pos[b] = read["start"][b] + self._block
            self._last_logits = read["logits"]
            self.last_pass = {
                "on": read["on"], "start": read["start"],
                "pass": read["pass"], "masked": masked}
            step.set(emitted=emitted_total)
            if load is not None:
                self._note_expert_load(step, load)
            _telemetry.DECODE_BLOCKS_COMMITTED.inc(
                int((mine & read["commit"]).sum()))
            _telemetry.DECODE_BLOCK_TOKENS.inc(emitted_total)
            _telemetry.DECODE_TOKENS.inc(emitted_total)
            self._note_occupancy()
        return out

    def _dispatch(self, page_table, tokens, start, wpage, woff, keys,
                  block=None, lanes=None, draft=None):
        """Launch the one program on the engine's donated pools (and,
        for a model that declares its layers' caches, its per-slot
        state), keep what it hands back in their place, and return
        ``(sampled, logits, extras)``."""
        more = (block,) if block is not None else ()
        if self._declared:
            more = (block, lanes, self._state)
            if draft is not None:
                more += (draft,)
        sampled, logits, self._pool_k, self._pool_v, extras = \
            self._jit_chunk(self._params, self._pool_k, self._pool_v,
                            page_table, tokens, start, wpage, woff, keys,
                            *more)
        self._state = extras.pop("state", ())
        return sampled, logits, extras

    def _note_expert_load(self, step, load):
        """An expert model's routing in the program a step read, on its
        span: ``load`` (expert layers, experts) counts the rows routed
        to each expert of the whole layer (padded and idle rows too).
        ``expert_rows_all`` is every chosen (row, expert) pair,
        ``expert_rows_held`` those that fell on the experts held here
        (a model that says ``experts_held``; all of them otherwise),
        ``experts_held_touched`` the (layer, held expert) pairs at least
        one row fell on: the experts whose weights the step needed.
        ``expert_rows_multiplied`` is the rows the held experts
        multiplied, each one's rows padded up to whole row tiles
        (``parallel.moe.routed_experts``), ``expert_rows_dense`` what
        every held expert multiplying every row would be."""
        from .parallel.moe import expert_row_tile, expert_rows_multiplied

        first, held = self.model_config.get("experts_held",
                                            (0, load.shape[1]))
        mine = load[:, first:first + held]
        pairs = int(load[0].sum())                  # rows x top_k a layer
        step.set(expert_load_max=int(load.max()),
                 expert_load_mean=float(load.mean()),
                 expert_rows_held=int(mine.sum()),
                 expert_rows_all=int(load.sum()),
                 experts_held_touched=int((mine > 0).sum()),
                 expert_rows_multiplied=expert_rows_multiplied(
                     mine, expert_row_tile(pairs, load.shape[1])),
                 expert_rows_dense=pairs // self.model_config["top_k"]
                 * held * load.shape[0])

    def evict(self, slot, reason):
        """Free ``slot`` (mid-prefill pendings included): drop its
        refcounts, return private pages to the free list, park
        refcnt-0 prefix pages in the retained LRU."""
        pending = slot in self._pending
        if not pending and not self._active[slot]:
            return
        # what was launched for its occupant and is not read yet is
        # thrown away when it is (its serial has moved on by then)
        wasted = sum(1 for rec in self._inflight if rec["on"][slot]
                     and rec["serial"][slot] == self._serial[slot])
        if wasted:
            _telemetry.DECODE_STEPS_WASTED.inc(wasted, reason=reason)
        if self._feeds:
            self._firsts = [f for f in self._firsts if f[0] != slot]
        self._spec_firsts = [f for f in self._spec_firsts if f[0] != slot]
        self._pending.pop(slot, None)
        self._history.pop(slot, None)
        self._drafted.pop(slot, None)
        self._active[slot] = False
        self._pos[slot] = 0
        self._serial[slot] += 1
        self._release_slot_pages(slot)
        # LIFO reuse: the same request sequence lands on the same
        # slots run after run
        self._free.appendleft(int(slot))
        _telemetry.DECODE_EVICTIONS.inc(reason=reason)
        self._note_occupancy()

    def prewarm(self):
        """Compile — or AOT-load — the three chunk-family signatures
        (prefill chunk, decode step, and the verify step when
        speculation is on) without executing.  Each signature is its
        own manifest row under ``kind=generate``."""
        from . import aot as _aot

        if not isinstance(self._jit_chunk, _aot.AOTFunction):
            return [{"label": "generate", "status": "disabled"}]
        return [self._jit_chunk.prewarm(*self._dispatch_args(shape))
                for shape in self.dispatch_shapes()]

    def dispatch_shapes(self):
        """The ``(rows, chunk)`` token shapes the one dispatch is
        compiled for: a prefill chunk, a decode step, and the verify
        step when speculation is on."""
        shapes = [(1, self._chunk), (self._slots, self._block)]
        if self._spec_k > 0:
            shapes.append((self._slots, self._spec_k + 1))
        return shapes

    def _attends_in(self, chunk):
        """The form the program of a dispatch of ``chunk`` positions a
        slot attends in (the ``attn`` of the step spans): ``"rows"``
        where the model reads its cache rows as they lie, ``"heads"``
        where it splits them by head.  A model that takes rows attends
        through ``ops.attention_rows``, whose rule goes by the query
        rows a slot; a head-split view is attended head by head; a
        model that declares its layers' caches reads its rows as they
        lie in every shape."""
        if self._declared:      # handed rows as they lie, nothing else
            return "rows"
        if not self._cache_rows:
            return "heads"
        from .ops.attention_rows import attends_in

        return attends_in(chunk, self.model_config["n_heads"])

    def _cache_rows_attended(self, chunk, written):
        """Cached rows a slot that the program of a dispatch of
        ``chunk`` positions a slot multiplies, summed over its layers
        that cache rows, when the longest of its sequences has written
        ``written`` (the ``cache_rows_attended`` of ``engine.prefill``
        and ``engine.decode`` beside ``cache_rows_held``): a model that
        declares its layers' caches attends its rows itself, by
        ``ops.attention_rows``'s rule on the dispatch's shape, a chunk
        in whole blocks up to ``written``, unless the layer says
        ``"attended": "whole"``; a windowed layer multiplies its ring's
        rows; every other attention multiplies all it is handed and
        masks."""
        if not self._declared:
            return self._L * self._capacity
        from .ops.attention_rows import attended_cache_rows

        return sum(
            attended_cache_rows(chunk, written, self._capacity,
                                self._layer_caches[li].get("attended"))
            for li in self._row_layers) + sum(self._ring_rows)

    def _cache_rows_held(self):
        """What :meth:`_cache_rows_attended` would count were every
        layer that caches rows paged and multiplied at a slot's
        capacity."""
        if not self._declared:
            return self._L * self._capacity
        return (len(self._row_layers) + len(self._window_layers)) \
            * self._capacity

    def _dispatch_args(self, shape):
        """Arguments of the dispatch at one token shape, all zeros
        beside the engine's own parameters and pools: what a compile
        that runs nothing needs."""
        nb, nc = shape
        # a token-at-a-time step is fed the last one's result, an array
        # on the device, and is compiled (and stored) for that
        fed = self._feeds and shape == (self._slots, 1)
        args = (self._params, self._pool_k, self._pool_v,
                np.zeros((nb, self._pages_per_slot), np.int32),
                self._feed if fed else np.zeros((nb, nc), np.int32),
                np.zeros(nb, np.int32),
                np.zeros(nb * nc, np.int32), np.zeros(nb * nc, np.int32),
                np.zeros((nb, 2), np.uint32))
        if self._block > 1 and shape == (self._slots, self._block):
            # a pass of block-diffusion decoding: the open blocks too
            args += ((self._block_state, np.zeros(nb, bool),
                      np.zeros(nb, np.int32), np.zeros(nb, np.int32),
                      np.zeros(nb, np.int32)),)
        if self._declared:
            args += (None, (np.zeros(nb, np.int32), np.zeros(nb, bool),
                            np.zeros(nb, np.int32)), self._state)
            if self._self_draft:
                args += ((np.zeros((nb, nc), np.int32),
                          np.zeros((nb, nc), bool)),)
        return args


# ---------------------------------------------------------------------------
# continuous-batching token serving
# ---------------------------------------------------------------------------

class GenerationResult(dict):
    """Resolution payload of one generation request: ``tokens`` (ids,
    prompt excluded), ``finish_reason`` (``eos`` / ``length``),
    ``ttft_s`` (submit -> first token); under block-diffusion decoding
    also ``fixed_at``, the denoise pass (1..T) of its block at which
    each token was fixed, and ``confidence``, the log-probability the
    model gave the token in that pass; under self-drafting ``drafts``,
    what the model's draft block chose to follow each token
    (:meth:`PagedGenerationEngine.drafted`)."""

    @property
    def tokens(self):
        return self["tokens"]

    @property
    def finish_reason(self):
        return self["finish_reason"]

    @property
    def ttft_s(self):
        return self["ttft_s"]


class _GenRequest:
    __slots__ = ("tokens", "future", "deadline", "t_submit", "max_new",
                 "out", "slot", "ttft", "span", "t_pickup", "prefix_hit",
                 "on_token", "fixed_at", "confidence")

    def __init__(self, tokens, deadline, max_new, span=None,
                 on_token=None):
        self.tokens = tokens
        self.future = None
        self.deadline = deadline
        self.t_submit = time.monotonic()
        self.max_new = max_new
        self.out = []
        self.slot = None
        self.ttft = None
        self.span = span           # detached root span (tracing on)
        self.t_pickup = None       # queue -> prefill pickup time
        self.prefix_hit = None     # prompt tokens served by prefix pages
        self.on_token = on_token   # streaming observer (gateway SSE)
        self.fixed_at = []         # block-diffusion: pass of each token
        self.confidence = []       # ... and its log-probability there


class TokenServer:
    """Continuous-batching token front end over one
    :class:`PagedGenerationEngine`.

    ``submit`` admits a prompt through a bounded queue and returns a
    :class:`ServingFuture` resolving to a :class:`GenerationResult`.
    A background loop admits queued prompts into free decode slots,
    runs one prefill chunk and one decode step of every active slot a
    tick, and evicts on EOS, deadline, length cap, or cancellation.
    Of the engine it calls ``bucket_for`` (is the prompt admissible),
    ``free_slots``, ``admit_incremental`` (claim a slot and its pages),
    ``prefill_step`` (one chunk; ``(slot, first_token or None)`` when
    it ends a prompt), ``decode_step`` (``{slot: [tokens]}``, a list a
    slot in every mode, of a step launched a call or more before: the
    first token of a request comes this way too), ``at_capacity``,
    ``evict`` and ``occupancy``, and reads
    ``sampling.eos_id``, ``last_prefix_hit_tokens`` and ``pool_shape``
    (for ``/statusz``).  The typed
    degradation contract is the serving_async taxonomy applied
    per-token:

    * admission: :class:`Overloaded` — ``queue`` (queue full), ``slo``
      (TTFT burn-rate shedding), ``shutdown``; cooperative
      backpressure via ``block=True``.
    * deadlines: :class:`DeadlineExceeded` with ``stage="prefill"``
      (expired waiting or during prefill) or ``stage="decode"``
      (expired mid-generation; the partial tokens are dropped and the
      slot evicted with reason ``deadline``).
    * shutdown: ``close(drain=True)`` stops admission, lets active
      sequences finish (bounded), and fails the rest
      :class:`Cancelled`.
    """

    def __init__(self, engine, queue_depth=None, deadline_ms=None,
                 max_new_tokens=None, slo_ms=None, shed_error_budget=0.1,
                 shed_burn_threshold=2.0, shed_window_s=30.0,
                 shed_hist=None):
        self._engine = engine
        if queue_depth is None:
            queue_depth = _config.get("MXNET_DECODE_QUEUE")
        self._depth = int(queue_depth)
        if self._depth < 1:
            raise MXNetError("queue_depth must be >= 1, got %r"
                             % (queue_depth,))
        if deadline_ms is None:
            deadline_ms = _config.get("MXNET_DECODE_DEADLINE_MS")
        self._deadline_s = float(deadline_ms) / 1e3 if deadline_ms \
            else None
        if max_new_tokens is None:
            max_new_tokens = _config.get("MXNET_DECODE_MAX_NEW")
        self._max_new = int(max_new_tokens)
        self._shedder = None
        if slo_ms:
            # burn-rate shedding over TIME-TO-FIRST-TOKEN: the latency
            # a decode tier's clients feel first (serving_async sheds
            # over whole-request latency; per-token serving degrades at
            # admission before queues melt)
            self._shedder = BurnRateShedder(
                float(slo_ms) / 1e3, error_budget=shed_error_budget,
                burn_threshold=shed_burn_threshold, window_s=shed_window_s,
                hist=shed_hist if shed_hist is not None
                else _telemetry.DECODE_TTFT_SECONDS)
        self._cond = threading.Condition()
        self._queue = collections.deque()
        self._by_slot = {}
        self._running = True
        self._closed = False
        self._worker = threading.Thread(target=self._loop,
                                        name="decode-server", daemon=True)
        self._worker.start()
        with _live_lock:
            _live_servers.add(self)

    # -- admission -------------------------------------------------------

    def _admission_error_locked(self, deadline, now):
        if self._closed or not self._running:
            return Overloaded("shutdown")
        if self._shedder is not None and self._shedder.shedding:
            return Overloaded("slo", "TTFT burn rate %.2fx"
                              % self._shedder.burn)
        if deadline is not None and now >= deadline:
            return DeadlineExceeded("prefill", "expired before admission")
        if len(self._queue) >= self._depth:
            return Overloaded("queue", "depth %d" % self._depth)
        return None

    def submit(self, token_ids, deadline_ms=_UNSET, max_new_tokens=None,
               block=False, timeout=None, on_token=None):
        """Admit one prompt; returns its :class:`ServingFuture`.

        Non-blocking by default (typed :class:`Overloaded` on a full
        queue); ``block=True`` waits up to ``timeout`` seconds for
        queue space (``slo``/``shutdown`` still raise immediately).
        ``deadline_ms`` overrides the server default; None/0 = no
        deadline.  ``max_new_tokens`` caps generation for this request
        (finish_reason ``length``).  ``on_token`` is called from the
        decode loop with each generated token id as it is sampled
        (streaming consumers, e.g. the gateway's SSE path); a raising
        observer is detached, never the decode loop's problem."""
        token_ids = np.asarray(token_ids).astype(np.int32).reshape(-1)
        if token_ids.size < 1:
            raise MXNetError("submit needs at least one prompt token")
        self._engine.bucket_for(token_ids.size)  # fail-fast: too long
        now = time.monotonic()
        if deadline_ms is _UNSET:
            deadline_s = self._deadline_s
        else:
            deadline_s = float(deadline_ms) / 1e3 if deadline_ms else None
        deadline = now + deadline_s if deadline_s is not None else None
        max_new = int(max_new_tokens) if max_new_tokens else self._max_new
        wait_until = now + timeout if timeout is not None else None
        span = _tracing.begin("decode.request", activate=False,
                              args={"prompt_tokens": int(token_ids.size)}) \
            if _tracing.enabled() else None

        def _rejected(err):
            """Typed admission failure: count it, close the span, and
            file the request's ONE wide event."""
            if isinstance(err, Overloaded):
                _telemetry.SERVING_SHED.inc(reason=err.reason)
                outcome = {"outcome": "shed", "reason": err.reason}
            else:
                _telemetry.SERVING_DEADLINE_EXCEEDED.inc(stage="prefill")
                outcome = {"outcome": "deadline", "stage": "prefill"}
            if span is not None:
                span.set(error=type(err).__name__).end(error=True)
            if _events.enabled():
                _events.emit("token_request",
                             span_id=span.span_id if span is not None
                             else None,
                             prompt_tokens=int(token_ids.size), **outcome)

        with self._cond:
            while True:
                err = self._admission_error_locked(deadline,
                                                   time.monotonic())
                if err is None:
                    break
                blockable = isinstance(err, Overloaded) and \
                    err.reason == "queue"
                if not block or not blockable:
                    _rejected(err)
                    raise err
                remaining = None
                if wait_until is not None:
                    remaining = wait_until - time.monotonic()
                    if remaining <= 0:
                        _rejected(err)
                        raise err
                self._cond.wait(remaining if remaining is not None
                                else 0.1)
            req = _GenRequest(token_ids, deadline, max_new, span=span,
                              on_token=on_token)
            req.future = ServingFuture(owner=self, req=req)
            self._queue.append(req)
            _telemetry.DECODE_QUEUE_DEPTH.set(len(self._queue))
            self._cond.notify_all()
        return req.future

    def generate(self, token_ids, timeout=None, **kwargs):
        """Blocking convenience: ``submit`` (backpressure-admitting) +
        ``result``."""
        t_end = time.monotonic() + timeout if timeout is not None \
            else None
        fut = self.submit(token_ids, block=True, timeout=timeout,
                          **kwargs)
        remaining = None
        if t_end is not None:
            remaining = max(0.0, t_end - time.monotonic())
        return fut.result(remaining)

    def _cancel(self, req):
        """ServingFuture.cancel hook: dequeue a waiting request, or
        flag an active one for eviction at the next loop tick."""
        with self._cond:
            resolved = req.future._resolve(
                exc=Cancelled("request cancelled"))
            if resolved:
                self._emit_event(req, outcome="evicted",
                                 reason="cancelled",
                                 evicted=req.slot is not None)
            if resolved and req.slot is None and req in self._queue:
                self._queue.remove(req)
                _telemetry.DECODE_QUEUE_DEPTH.set(len(self._queue))
            self._cond.notify_all()
            return resolved

    # -- the decode loop -------------------------------------------------

    def _emit_event(self, req, evicted=False, **kw):
        """The request's ONE wide event, filed at resolution (callers
        guard on the future's first-writer-wins _resolve, so a
        deadline racing a finish files exactly one).  Stage split:
        ``queue`` (submit -> prefill pickup), ``prefill`` (pickup ->
        first token; sampling is fused into the compiled dispatch),
        ``decode`` (first token -> resolution)."""
        if req.span is not None:
            err = kw.get("outcome", "ok") != "ok"
            req.span.set(tokens=len(req.out), **{k: v
                         for k, v in kw.items() if v is not None})
            req.span.end(error=err)
        if not _events.enabled():
            return
        now = time.monotonic()
        stages = {}
        if req.t_pickup is not None:
            stages["queue"] = req.t_pickup - req.t_submit
            if req.ttft is not None:
                stages["prefill"] = \
                    (req.t_submit + req.ttft) - req.t_pickup
                stages["decode"] = now - (req.t_submit + req.ttft)
            else:
                # picked up but no first token: the time went into the
                # (failed/expired) prefill dispatch — error-path
                # events are always kept, their split must add up too
                stages["prefill"] = now - req.t_pickup
        else:
            stages["queue"] = now - req.t_submit
        _events.emit(
            "token_request", dur_s=now - req.t_submit, stages_s=stages,
            tokens=len(req.out), prompt_tokens=int(req.tokens.size),
            ttft_s=req.ttft, slot=req.slot,
            prefix_hit_tokens=req.prefix_hit,
            evicted=True if evicted else None,
            span_id=req.span.span_id if req.span is not None else None,
            **kw)

    def _finish(self, req, reason):
        _telemetry.DECODE_REQUESTS_FINISHED.inc(reason=reason)
        result = GenerationResult(tokens=list(req.out),
                                  finish_reason=reason, ttft_s=req.ttft)
        if req.fixed_at:
            result["fixed_at"] = list(req.fixed_at)
            result["confidence"] = list(req.confidence)
        drafts = self._engine.drafted(req.slot) \
            if req.slot is not None else None
        if drafts is not None:
            result["drafts"] = drafts[:len(req.out)]
        if req.future._resolve(result=result):
            self._emit_event(req, outcome="ok", reason=reason)

    def _fail(self, req, exc, stage=None):
        if isinstance(exc, DeadlineExceeded):
            _telemetry.SERVING_DEADLINE_EXCEEDED.inc(stage=exc.stage)
        if not req.future._resolve(exc=exc):
            return
        if isinstance(exc, DeadlineExceeded):
            self._emit_event(req, outcome="deadline", stage=exc.stage,
                             evicted=req.slot is not None)
        elif isinstance(exc, Overloaded):
            self._emit_event(req, outcome="shed", reason=exc.reason)
        elif isinstance(exc, Cancelled):
            self._emit_event(req, outcome="evicted", reason="cancelled",
                             evicted=req.slot is not None)
        else:
            self._emit_event(req, outcome="error",
                             error_kind=type(exc).__name__)

    def _admit_locked_pop(self):
        """Pop the next admissible queued request (dropping expired
        ones, typed) — caller holds the lock."""
        now = time.monotonic()
        while self._queue:
            req = self._queue.popleft()
            _telemetry.DECODE_QUEUE_DEPTH.set(len(self._queue))
            self._cond.notify_all()    # queue space freed: wake any
                                       # block=True submitter
            if req.future.done():      # cancelled while queued
                continue
            if req.deadline is not None and now >= req.deadline:
                self._fail(req, DeadlineExceeded(
                    "prefill", "expired waiting for a decode slot"))
                continue
            return req
        return None

    def _sweep_queue(self):
        """Expire queued deadlines even while every slot is busy — a
        request must not discover its deadline only when a slot frees."""
        now = time.monotonic()
        with self._cond:
            expired = [r for r in self._queue
                       if r.deadline is not None and now >= r.deadline
                       and not r.future.done()]
            if not expired and not any(r.future.done()
                                       for r in self._queue):
                return
            self._queue = collections.deque(
                r for r in self._queue
                if r not in expired and not r.future.done())
            _telemetry.DECODE_QUEUE_DEPTH.set(len(self._queue))
            self._cond.notify_all()
        for req in expired:
            self._fail(req, DeadlineExceeded(
                "prefill", "expired waiting for a decode slot"))

    def _admissions(self):
        """Move queued requests into free slots; returns how many."""
        eng = self._engine
        admitted = 0
        while eng.free_slots() > 0:
            with self._cond:
                req = self._admit_locked_pop()
            if req is None:
                break
            t_pick = time.monotonic()
            req.t_pickup = t_pick
            ex = {"trace_id": _tracing.TRACE_ID,
                  "span_id": req.span.span_id} \
                if req.span is not None else None
            _telemetry.DECODE_QUEUE_WAIT_SECONDS.observe(
                t_pick - req.t_submit, exemplar=ex)
            # claim the slot + pages only; chunks run one per loop
            # tick (the TTFT clock keeps running until the chunk
            # that completes the prompt samples the first token)
            try:
                slot = eng.admit_incremental(req.tokens,
                                             max_new=req.max_new)
            except ServingError as e:
                self._fail(req, e)
                continue
            except Exception as e:
                self._fail(req, ReplicaFailed(
                    "prefill admission failed: %s" % (e,), cause=e))
                continue
            req.slot = slot
            req.prefix_hit = eng.last_prefix_hit_tokens or None
            with self._cond:
                self._by_slot[slot] = req
            admitted += 1
        return admitted

    def _deliver(self, req, slot, tok, last=True, fixed=None):
        """Append one generated token and apply the finish/evict
        rules.  Returns False when the request left its slot.  ``last``
        is false for all but the last token of a burst (speculation, a
        committed block): the engine has advanced past the whole burst,
        so the cache's capacity is asked after only at its end.
        ``fixed`` is a block-diffusion token's (pass, confidence)."""
        eng = self._engine
        if req.future.done():                      # cancelled mid-run
            self._release(slot)
            eng.evict(slot, "cancelled")
            return False
        now = time.monotonic()
        if req.deadline is not None and now >= req.deadline:
            stage = "decode" if req.out else "prefill"
            self._fail(req, DeadlineExceeded(
                stage, "deadline hit after %d token(s)" % len(req.out)))
            self._release(slot)
            eng.evict(slot, "deadline")
            return False
        req.out.append(tok)
        if fixed is not None:
            req.fixed_at.append(fixed[0])
            req.confidence.append(fixed[1])
        if req.ttft is None:
            # a prompt's last chunk hands no token over: the first one
            # comes of a decode step, and is stamped as it is read
            req.ttft = now - req.t_submit
            _telemetry.DECODE_TTFT_SECONDS.observe(
                req.ttft, exemplar={"trace_id": _tracing.TRACE_ID,
                                    "span_id": req.span.span_id}
                if req.span is not None else None)
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception:
                _logger.exception("on_token observer failed; detaching")
                req.on_token = None
        eos = self._engine.sampling.eos_id
        if eos is not None and tok == eos:
            self._finish(req, "eos")
            self._release(slot)
            eng.evict(slot, "eos")
            return False
        if len(req.out) >= req.max_new or \
                (last and eng.at_capacity(slot)):
            self._finish(req, "length")
            self._release(slot)
            eng.evict(slot, "length")
            return False
        return True

    def _release(self, slot):
        with self._cond:
            self._by_slot.pop(slot, None)
            self._cond.notify_all()

    def _prefill_tick(self):
        """One chunked-prefill step: evict cancelled/expired
        mid-prefill requests first — no point streaming chunks for a
        dead request — then run ONE chunk; when it completes a prompt,
        the sampled first token starts the request's delivery (TTFT
        observed here)."""
        eng = self._engine
        with self._cond:
            stale = [(s, r) for s, r in self._by_slot.items()
                     if r.ttft is None and
                     (r.future.done() or
                      (r.deadline is not None
                       and time.monotonic() >= r.deadline))]
        for slot, req in stale:
            if req.future.done():          # cancelled while prefilling
                reason = "cancelled"
            else:
                reason = "deadline"
                self._fail(req, DeadlineExceeded(
                    "prefill", "deadline hit mid-prefill"))
            self._release(slot)
            eng.evict(slot, reason)
        res = eng.prefill_step()
        if res is None:
            return
        slot, tok = res
        with self._cond:
            req = self._by_slot.get(slot)
        if req is None:
            eng.evict(slot, "cancelled")
            return
        if tok is None:
            # the first token stays on the device for the slot's first
            # step and is delivered, and the TTFT stamped, when a decode
            # step hands it over (block-diffusion: the first block is
            # now open)
            return
        req.ttft = time.monotonic() - req.t_submit
        ex = {"trace_id": _tracing.TRACE_ID,
              "span_id": req.span.span_id} \
            if req.span is not None else None
        _telemetry.DECODE_TTFT_SECONDS.observe(req.ttft, exemplar=ex)
        with _tracing.begin("serve.deliver", args={"tokens": 1}):
            self._deliver(req, slot, tok)

    def _loop(self):
        # one span tree a tick, kept whether or not tracing is on
        # (docs/observability.md "Spans of the hot loops")
        while True:
            with self._cond:
                if self._running and not self._queue and not self._by_slot:
                    with _tracing.begin("serve.idle"):
                        while self._running and not self._queue \
                                and not self._by_slot:
                            self._cond.wait(0.02)
                if not self._running:
                    return
                active, queued = len(self._by_slot), len(self._queue)
            try:
                with _tracing.begin("serve.tick", cpu=True, args={
                        "slots": active, "queue": queued}):
                    self._tick()
            except Exception as e:
                # a broken engine (failed dispatch after donation) can
                # serve nobody: fail everything typed and stop
                _logger.exception("decode loop failed; shutting down")
                with self._cond:
                    self._closed = True
                    self._running = False
                    victims = list(self._by_slot.values()) \
                        + list(self._queue)
                    self._by_slot.clear()
                    self._queue.clear()
                    _telemetry.DECODE_QUEUE_DEPTH.set(0)
                for req in victims:
                    self._fail(req, ReplicaFailed(
                        "decode loop failed: %s" % (e,), cause=e))
                return

    def _tick(self):
        with _tracing.begin("serve.admit") as sp:
            self._sweep_queue()
            sp.set(admitted=self._admissions())
        self._prefill_tick()
        toks = self._engine.decode_step()
        if toks:
            with _tracing.begin("serve.deliver", args={
                    "tokens": sum(len(t) for t in toks.values())}):
                self._deliver_step(toks)
        if self._shedder is not None:
            self._shedder.update()

    def _deliver_step(self, toks):
        for slot, burst in toks.items():
            with self._cond:
                req = self._by_slot.get(slot)
            if req is None:
                self._engine.evict(slot, "cancelled")
                continue
            # a step may emit several tokens a slot (verified drafts; a
            # committed block, or none while a block is open);
            # _deliver's finish rules apply per token, so the overshoot
            # past eos/max_new is truncated here
            fixed = list(zip(burst.fixed_at, burst.confidence)) \
                if isinstance(burst, BlockTokens) else None
            for i, t in enumerate(burst):
                if not self._deliver(
                        req, slot, t, last=i == len(burst) - 1,
                        fixed=fixed[i] if fixed else None):
                    break

    # -- lifecycle -------------------------------------------------------

    def close(self, drain=True, timeout=None):
        """Stop admission; with ``drain`` (default) let active
        sequences finish (bounded by ``timeout`` seconds, else a
        30 s no-progress guard), then fail the remainder
        :class:`Cancelled`.  Idempotent."""
        deadline = time.monotonic() + timeout if timeout is not None \
            else None
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if drain:
            last_busy = None
            last_progress = time.monotonic()
            while True:
                with self._cond:
                    busy = len(self._queue) + len(self._by_slot)
                    if not busy or not self._running:
                        break
                now = time.monotonic()
                if last_busy is None or busy < last_busy:
                    last_busy, last_progress = busy, now
                elif now - last_progress > 30.0:
                    _logger.warning(
                        "close(): no drain progress in 30s with %d "
                        "request(s) live; cancelling the remainder",
                        busy)
                    break
                if deadline is not None and now >= deadline:
                    break
                time.sleep(0.005)
        with self._cond:
            self._running = False
            self._cond.notify_all()
        # join BEFORE touching engine state: the worker may be
        # mid-iteration, and engine.evict/admit are single-consumer —
        # evicting concurrently would double-free a KV lane
        self._worker.join(timeout=5.0)
        worker_gone = not self._worker.is_alive()
        with self._cond:
            victims = list(self._by_slot.values()) + list(self._queue)
            self._by_slot.clear()
            self._queue.clear()
            _telemetry.DECODE_QUEUE_DEPTH.set(0)
            self._cond.notify_all()
        for req in victims:
            if not req.future.done():
                if req.future._resolve(exc=Cancelled(
                        "token server shut down before completion")):
                    self._emit_event(req, outcome="evicted",
                                     reason="drain",
                                     evicted=req.slot is not None)
            if req.slot is not None and worker_gone:
                # a worker stuck in a device call could still race the
                # lane; leave it active then (the engine is unusable
                # anyway) rather than double-free it
                self._engine.evict(req.slot, "drain")
        # readiness: 503 while close() drains, then this server stops
        # counting (see AsyncPredictor.close)
        with _live_lock:
            _live_servers.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def stats(self):
        with self._cond:
            return {
                "queue_depth": len(self._queue),
                "active": len(self._by_slot),
                "free_slots": self._engine.free_slots(),
                "shedding": (self._shedder.shedding
                             if self._shedder else False),
                "closed": self._closed,
            }
