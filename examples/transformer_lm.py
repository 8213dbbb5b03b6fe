"""Decoder-only transformer language model (Gluon HybridBlock).

The LLM-shaped workload the parallel stack has been waiting for
(ROADMAP "New workload"): where bench.py exercises conv/BN hot paths,
this model is embeddings + causal attention + FFN matmuls — the profile
that makes the dp × fsdp × tp mesh earn its keep.  Parameter names are
chosen to match the ``fsdp_tp`` spec-rule layout
(mxnet_tpu/parallel/layout.py): ``proj_q/proj_k/proj_v`` and ``ffn_up``
are column-parallel over tp, ``attn_out``/``ffn_down`` row-parallel,
``embed``/``head`` split over fsdp × tp — resolve the layout against
``lm.collect_params()`` and every parameter matches exactly one rule
(asserted by tests/test_sharding_layouts.py).

Train it sharded::

    from mxnet_tpu import parallel, gluon
    lm = TransformerLM(vocab_size=32000, d_model=512, n_heads=8,
                       n_layers=8)
    lm.initialize(mx.init.Xavier())
    trainer = parallel.ShardedTrainer(
        lm, lm_loss, mesh="dp=2,fsdp=2,tp=2", layout="fsdp_tp",
        optimizer="adam")

``tools/bench_lm.py`` wraps exactly that into a BENCH-JSON benchmark
(tokens/s + MFU).  Eager/traced execution only (the attention math uses
concrete shapes) — like the other examples, not the symbolic Module
path.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402

__all__ = ["TransformerLM", "DecoderBlock", "lm_loss_fn"]


class DecoderBlock(gluon.HybridBlock):
    """Pre-norm decoder block: LN -> causal MHA -> residual -> LN ->
    FFN -> residual."""

    def __init__(self, d_model, n_heads, d_ff, **kwargs):
        super().__init__(**kwargs)
        if d_model % n_heads:
            raise ValueError("d_model (%d) must divide by n_heads (%d)"
                             % (d_model, n_heads))
        self._n_heads = n_heads
        self._d_head = d_model // n_heads
        with self.name_scope():
            self.ln1 = nn.LayerNorm(prefix="ln1_")
            self.proj_q = nn.Dense(d_model, flatten=False, use_bias=False,
                                   prefix="proj_q_")
            self.proj_k = nn.Dense(d_model, flatten=False, use_bias=False,
                                   prefix="proj_k_")
            self.proj_v = nn.Dense(d_model, flatten=False, use_bias=False,
                                   prefix="proj_v_")
            self.attn_out = nn.Dense(d_model, flatten=False,
                                     use_bias=False, prefix="attn_out_")
            self.ln2 = nn.LayerNorm(prefix="ln2_")
            self.ffn_up = nn.Dense(d_ff, flatten=False, activation="relu",
                                   prefix="ffn_up_")
            self.ffn_down = nn.Dense(d_model, flatten=False,
                                     prefix="ffn_down_")

    def _split_heads(self, a):  # (B, T, D) -> (B*H, T, dh)
        B, T, _D = a.shape
        H, dh = self._n_heads, self._d_head
        return a.reshape((B, T, H, dh)).transpose(
            (0, 2, 1, 3)).reshape((B * H, T, dh))

    def _merge_heads(self, a, B, T):  # (B*H, T, dh) -> (B, T, D)
        H, dh = self._n_heads, self._d_head
        return a.reshape((B, H, T, dh)).transpose(
            (0, 2, 1, 3)).reshape((B, T, H * dh))

    def _attend(self, F, x):
        """Causal MHA over the full sequence (the train path and the
        full re-forward the serving tests hold ``forward_chunk`` to)."""
        import jax

        B, T, _D = x.shape
        dh = self._d_head
        with jax.named_scope("attn.proj"):
            q = self._split_heads(self.proj_q(x))
            k = self._split_heads(self.proj_k(x))
            v = self._split_heads(self.proj_v(x))
        with jax.named_scope("attn.core"):
            scores = F.batch_dot(q, k, transpose_b=True) * (dh ** -0.5)
            pos = F.arange(T)
            causal = F.broadcast_greater_equal(pos.reshape((T, 1)),
                                               pos.reshape((1, T)))
            scores = F.where(causal.reshape((1, T, T)), scores,
                             F.ones_like(scores) * -1e30)
            att = F.softmax(scores, axis=-1)
            out = F.batch_dot(att, v)  # (B*H, T, dh)
        with jax.named_scope("attn.proj"):
            return self.attn_out(self._merge_heads(out, B, T))

    def hybrid_forward(self, F, x):
        import jax

        with jax.named_scope("attn.proj"):
            h = self.ln1(x)
        x = x + self._attend(F, h)
        with jax.named_scope("ffn"):
            return x + self.ffn_down(self.ffn_up(self.ln2(x)))

    def forward_chunk(self, F, x, k_rows, v_rows, start):
        """One block's C-position chunk forward against its layer's
        cached rows — the shared attention shape behind chunked prefill,
        paged decode (C=1), and the speculative verify step (C=K+1).

        ``x`` is the (B, C, D) chunk input NDArray; ``k_rows`` /
        ``v_rows`` are RAW jax arrays (B, S, D), one row a cached
        position with every head side by side, as the engine's page
        pool holds them (this chunk's K/V is NOT in them); ``start``
        raw (B,) int32: a chunk query attends the cached positions
        below its sequence's start and the chunk's own up to itself
        (``ops.attention_rows.chunk_attention_rows``, which reads the
        rows as they lie).  Returns ``(x_out, k_chunk, v_chunk)`` with
        the chunk K/V as raw (B, C, D) rows for the caller to write
        into its pool.  The projection/LN/FFN submodules are the SAME
        children the train path runs, so chunk logits track the
        full-context forward.  The parts are traced under the named
        scopes ``attn.proj``, ``attn.core`` (opened by
        ``chunk_attention_rows``) and ``ffn``, which a device trace
        reads the time of (``mxnet_tpu.profiler.device_table``)."""
        import jax

        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.ops.attention_rows import chunk_attention_rows

        with jax.named_scope("attn.proj"):
            h = self.ln1(x)
            k_c, v_c = self.proj_k(h)._data, self.proj_v(h)._data
            q = self.proj_q(h)._data
        out = chunk_attention_rows(q, k_c, v_c, k_rows, v_rows, start,
                                   self._n_heads)
        with jax.named_scope("attn.proj"):
            x = x + self.attn_out(NDArray(out))
        with jax.named_scope("ffn"):
            return (x + self.ffn_down(self.ffn_up(self.ln2(x))),
                    k_c, v_c)


class TransformerLM(gluon.HybridBlock):
    """Token + learned-position embeddings, ``n_layers`` decoder blocks,
    final LayerNorm, untied LM head.  Input (batch, seq) token ids ->
    (batch, seq, vocab) logits."""

    def __init__(self, vocab_size, d_model=256, n_heads=4, n_layers=2,
                 d_ff=None, max_len=512, **kwargs):
        super().__init__(**kwargs)
        d_ff = d_ff or 4 * d_model
        self._cfg = dict(vocab_size=vocab_size, d_model=d_model,
                         n_heads=n_heads, n_layers=n_layers, d_ff=d_ff,
                         max_len=max_len)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, d_model,
                                      prefix="embed_")
            self.pos_embed = nn.Embedding(max_len, d_model,
                                          prefix="pos_embed_")
            self._blocks = []
            for i in range(n_layers):
                blk = DecoderBlock(d_model, n_heads, d_ff,
                                   prefix="h%d_" % i)
                self.register_child(blk, "h%d" % i)
                self._blocks.append(blk)
            self.ln_f = nn.LayerNorm(prefix="ln_f_")
            self.head = nn.Dense(vocab_size, flatten=False,
                                 use_bias=False, prefix="head_")

    @property
    def config(self):
        return dict(self._cfg)

    def flops_per_token(self, seq_len=None):
        """Train FLOPs/token: the standard 6N dense term plus — when
        ``seq_len`` is given — the quadratic attention term
        ``12 * n_layers * d_model * seq_len`` (fwd+bwd QK^T and att·V
        matmuls), the PaLM-appendix accounting the MFU gauge
        cross-checks."""
        c = self._cfg
        n_params = (c["vocab_size"] * c["d_model"] * 2          # embed+head
                    + c["max_len"] * c["d_model"]
                    + c["n_layers"] * (4 * c["d_model"] ** 2
                                       + 2 * c["d_model"] * c["d_ff"]))
        flops = 6 * n_params
        if seq_len:
            flops += 12 * c["n_layers"] * c["d_model"] * int(seq_len)
        return flops

    def hybrid_forward(self, F, tokens):
        B, T = tokens.shape
        if T > self._cfg["max_len"]:
            raise ValueError("sequence length %d > max_len %d"
                             % (T, self._cfg["max_len"]))
        import jax

        with jax.named_scope("embed"):
            pos = F.arange(T)
            x = F.broadcast_add(self.embed(tokens),
                                self.pos_embed(pos).reshape(
                                    (1, T, self._cfg["d_model"])))
        for blk in self._blocks:
            x = blk(x)
        with jax.named_scope("head"):
            return self.head(self.ln_f(x))

    # -- generation protocol (mxnet_tpu/generate.py) ---------------------
    #
    # chunk_forward is the cache-aware inference form of hybrid_forward:
    # any model exposing it (plus .config with vocab_size / d_model /
    # n_heads / n_layers / max_len) plugs into
    # generate.PagedGenerationEngine, which hands it K/V rows.  It is
    # called under the gluon trace machinery with parameters swapped
    # in, exactly like serving.Predictor.from_block's traced forward.

    def chunk_forward(self, tokens, caches, start):
        """C positions per sequence against a linear KV cache — the one
        attention shape behind chunked prefill (B=1, C=chunk), paged
        decode (C=1), and speculative verify (C=K+1).

        ``tokens`` raw (B, C) int32 — the tokens occupying positions
        ``start_b .. start_b+C-1`` of each sequence; ``caches`` one
        ``(k, v)`` pair of raw (B, S, D) jax arrays per layer, one row
        a position with every head side by side, holding the already
        cached positions 0..start_b-1 (a layer's gathered pages in the
        paged engine);
        ``start`` raw (B,) int32.
        Position j of the chunk attends cache positions ``s < start_b``
        plus chunk positions ``j' <= j`` — exactly the causal window the
        full forward gives it.  Returns ``(logits NDArray (B, C, V),
        chunk_caches)`` where ``chunk_caches`` is one ``(k, v)`` pair of
        raw (B, C, D) rows per layer for the caller to write back
        (positions past a sequence's real length just produce values the
        caller routes to its trash page)."""
        import jax
        import jax.numpy as jnp

        from mxnet_tpu import ndarray as F
        from mxnet_tpu.ndarray import NDArray

        B, C = tokens.shape
        D = self._cfg["d_model"]
        start = start.astype(jnp.int32)
        with jax.named_scope("embed"):
            pos_ids = jnp.clip(
                start[:, None] + jnp.arange(C, dtype=jnp.int32),
                0, self._cfg["max_len"] - 1)                # (B, C)
            x = self.embed(NDArray(tokens)) + self.pos_embed(
                NDArray(pos_ids)).reshape((B, C, D))
        chunk_caches = []
        for blk, (k_rows, v_rows) in zip(self._blocks, caches):
            x, k_c, v_c = blk.forward_chunk(F, x, k_rows, v_rows, start)
            chunk_caches.append((k_c, v_c))
        with jax.named_scope("head"):
            return self.head(self.ln_f(x)), chunk_caches


def lm_loss_fn(vocab_size):
    """Next-token softmax-CE adapter for ShardedTrainer: flattens
    (B, T, V) logits against (B, T) label ids."""
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss(logits, labels):
        B, T, V = logits.shape
        return ce(logits.reshape((B * T, V)), labels.reshape((B * T,)))

    return loss


if __name__ == "__main__":
    # tiny smoke run: one eager forward + one sharded train step
    import numpy as np

    from mxnet_tpu import nd, parallel

    lm = TransformerLM(vocab_size=128, d_model=64, n_heads=4, n_layers=2,
                       max_len=64)
    lm.initialize(mx.init.Xavier())
    rng = np.random.RandomState(0)
    tokens = nd.array(rng.randint(0, 128, (4, 32)).astype(np.float32))
    labels = nd.array(rng.randint(0, 128, (4, 32)).astype(np.float32))
    logits = lm(tokens)
    print("logits:", logits.shape)
    trainer = parallel.ShardedTrainer(
        lm, lm_loss_fn(128), mesh=None, optimizer="adam",
        optimizer_params={"learning_rate": 1e-3})
    for i in range(3):
        print("step %d loss %.4f" % (i, float(trainer.step([tokens],
                                                           labels))))
