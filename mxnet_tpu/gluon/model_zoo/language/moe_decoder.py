"""A sparse decoder-only language model: RMSNorm, grouped-query
attention with per-head QK-norm and rotary positions, and a gated-SiLU
top-k expert layer in every block (the Qwen3-MoE / ``sdar_moe`` block).

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

The attention mask is **block-causal** with the model's
``block_length`` B: position i attends j iff ``j // B <= i // B``
(bidirectional inside a block, causal across blocks).  B = 1 is the
causal mask; B > 1 is what generation by diffusion over blocks needs
(``docs/lm_serving.md``, "Block-diffusion decoding").

The model speaks the chunk protocol of ``mxnet_tpu.generate``
(``chunk_forward`` / ``config``), with the key/value head count and the
head size in ``config`` (``n_kv_heads``, ``d_head``: the head size is
its own number here, not ``d_model / n_heads``).  The expert layer is
``parallel.moe.routed_experts``: told which experts it holds
(``experts_held``), it routes over all of them and computes its own
experts' part.  Parameters are registered in one flat list, block by
block; every matrix is stored ``(out, in)`` as ``nn.Dense`` stores it,
and the experts side by side as ``(d_model, experts * d_expert)`` twice
and ``(experts * d_expert, d_model)``, the shapes from which the
grouped product reads an expert in place (``d_expert`` whole lane
tiles: a column block of the one, a row block of the other).  ``dtype`` is the type the embedding and the
matrices are *stored* in (``"bfloat16"`` to serve from half the
memory); norm weights and the output head are always float32, which is
where ``dtype_policy``'s ``bf16_mixed`` keeps them.
"""
from __future__ import annotations

from ...block import HybridBlock

__all__ = ["MoEDecoderLM"]


def _rms(x, gamma, eps):
    """RMSNorm over the last axis, in float32."""
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * lax.rsqrt(var + eps) * gamma.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half rotary positions over the whole head.  x
    (B, C, H, dh) float32, pos (B, C) int32."""
    import jax.numpy as jnp

    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, :, None] * inv         # (B, C, dh/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


class MoEDecoderLM(HybridBlock):
    """Token ids (batch, seq) -> logits (batch, seq, vocab).

    ``experts_held`` = ``(first, count)`` makes this instance hold only
    those experts of every layer (the chip's share of an
    expert-parallel deployment): the router keeps its full width, the
    layer's output is the held experts' part.  Default: all of them.
    """

    def __init__(self, vocab_size, d_model, n_layers, n_heads, n_kv_heads,
                 d_head, n_experts, top_k, d_expert, block_length=1,
                 mask_token_id=None, max_len=32768, rope_theta=1e6,
                 rms_eps=1e-6, norm_topk=True, experts_held=None,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if n_heads % n_kv_heads:
            raise ValueError("n_heads (%d) must divide by n_kv_heads (%d)"
                             % (n_heads, n_kv_heads))
        first, held = experts_held if experts_held is not None \
            else (0, n_experts)
        if not (0 <= first and held >= 1 and first + held <= n_experts):
            raise ValueError("experts_held %r outside [0, %d)"
                             % (experts_held, n_experts))
        self._cfg = dict(
            vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads, d_head=d_head, n_layers=n_layers,
            n_experts=n_experts, top_k=top_k, d_expert=d_expert,
            block_length=int(block_length), mask_token_id=mask_token_id,
            max_len=max_len)
        self._theta, self._eps = float(rope_theta), float(rms_eps)
        self._norm_topk, self._first = bool(norm_topk), int(first)
        D, dh, F = d_model, d_head, d_expert

        def get(name, shape, stored=dtype):
            return self.params.get(name, shape=shape, dtype=stored)

        with self.name_scope():
            self._embed = get("embed_weight", (vocab_size, D))
            self._layers = []
            for i in range(n_layers):
                h = "h%d_" % i
                self._layers.append([
                    get(h + "attn_norm_gamma", (D,), "float32"),
                    get(h + "proj_q_weight", (n_heads * dh, D)),
                    get(h + "proj_k_weight", (n_kv_heads * dh, D)),
                    get(h + "proj_v_weight", (n_kv_heads * dh, D)),
                    get(h + "q_norm_gamma", (dh,), "float32"),
                    get(h + "k_norm_gamma", (dh,), "float32"),
                    get(h + "attn_out_weight", (D, n_heads * dh)),
                    get(h + "moe_norm_gamma", (D,), "float32"),
                    get(h + "router_weight", (n_experts, D)),
                    get(h + "experts_gate_weight", (D, held * F)),
                    get(h + "experts_up_weight", (D, held * F)),
                    get(h + "experts_down_weight", (held * F, D))])
            self._final = get("final_norm_gamma", (D,), "float32")
            self._head = get("head_weight", (vocab_size, D), "float32")

    @property
    def config(self):
        return dict(self._cfg)

    # -- the one forward ---------------------------------------------------

    def _block(self, x, p, pos, k_cache, v_cache, cache_mask, chunk_mask):
        """One block over C positions a sequence.  ``x`` (B, C, D) raw;
        ``k_cache``/``v_cache`` (B, Hkv, S, dh) raw or None;
        ``cache_mask`` (B, 1, 1, S); ``chunk_mask`` (C, C).  Returns
        (x_out, k_chunk, v_chunk (B, Hkv, C, dh), counts (E,)).  Its
        parts are traced under the named scopes ``attn.proj``,
        ``attn.core`` and the expert layer's own (``experts.route``,
        ``experts.ffn``), which ``profiler.device_table`` reads."""
        import jax
        import jax.numpy as jnp

        from ....parallel.moe import routed_experts

        (g1, wq, wk, wv, gq, gk, wo, g2, wr, wg, wu, wd) = \
            [q.data()._data for q in p]
        c = self._cfg
        B, C, D = x.shape
        Hq, Hkv, dh = c["n_heads"], c["n_kv_heads"], c["d_head"]
        G = Hq // Hkv
        act = wq.dtype                    # compute follows the weight
        f32 = jnp.float32

        def mm(a, w):                     # a (..., in) x w (out, in)
            return jnp.dot(a.astype(w.dtype), w.T)

        with jax.named_scope("attn.proj"):
            n = _rms(x, g1, self._eps)
            q = _rms(mm(n, wq).reshape((B, C, Hq, dh)), gq, self._eps)
            k = _rms(mm(n, wk).reshape((B, C, Hkv, dh)), gk, self._eps)
            v = mm(n, wv).reshape((B, C, Hkv, dh))
            q = _rope(q, pos, self._theta).astype(act)
            k = _rope(k, pos, self._theta).astype(act)
            # a key/value head serves G query heads: fold them into the
            # query rows, so the cache is read once and never repeated
            qg = q.reshape((B, C, Hkv, G, dh)).transpose(0, 2, 3, 1, 4) \
                .reshape((B, Hkv, G * C, dh))
            k_c, v_c = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        with jax.named_scope("attn.core"):
            scale = dh ** -0.5
            neg = jnp.asarray(-1e30, f32)
            s = jnp.einsum("bhqd,bhsd->bhqs", qg, k_c,
                           preferred_element_type=f32) * scale
            s = jnp.where(jnp.tile(chunk_mask, (G, 1))[None, None], s, neg)
            vals = v_c
            if k_cache is not None:
                sc = jnp.einsum("bhqd,bhsd->bhqs", qg, k_cache.astype(act),
                                preferred_element_type=f32) * scale
                s = jnp.concatenate([jnp.where(cache_mask, sc, neg), s], -1)
                vals = jnp.concatenate([v_cache.astype(act), v_c], 2)
            att = jax.nn.softmax(s, axis=-1).astype(act)
            o = jnp.einsum("bhqs,bhsd->bhqd", att, vals)
        with jax.named_scope("attn.proj"):
            o = o.reshape((B, Hkv, G, C, dh)).transpose(0, 3, 1, 2, 4) \
                .reshape((B, C, Hq * dh))
            x = x + mm(o, wo).astype(x.dtype)
        with jax.named_scope("experts.route"):
            m = _rms(x, g2, self._eps).astype(act).reshape((B * C, D))
        y, counts = routed_experts(
            m, wr.T, wg, wu, wd, c["top_k"], c["d_expert"],
            first=self._first, norm_topk=self._norm_topk)
        return x + y.reshape((B, C, D)).astype(x.dtype), k_c, v_c, counts

    def _run(self, tokens, caches, start):
        """tokens (B, C) int; caches a list of (k, v) raw (B, Hkv, S,
        dh) or None; start (B,) int32.  Returns (logits raw (B, C, V),
        [(k, v) raw (B, Hkv, C, dh)], expert load (L, E) int32)."""
        import jax
        import jax.numpy as jnp

        c = self._cfg
        B, C = tokens.shape
        Bl = c["block_length"]
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)
        with jax.named_scope("embed"):
            x = jnp.take(self._embed.data()._data, tokens, axis=0)
        # within the chunk: a chunk starts on a block boundary
        blk = jnp.arange(C, dtype=jnp.int32) // Bl
        chunk_mask = blk[:, None] >= blk[None, :]
        cache_mask = None
        if caches is not None:
            S = caches[0][0].shape[2]
            cache_mask = (jnp.arange(S, dtype=jnp.int32)[None, :]
                          < start[:, None]).reshape((B, 1, 1, S))
        new, loads = [], []
        for li, p in enumerate(self._layers):
            kc, vc = caches[li] if caches is not None else (None, None)
            x, k_c, v_c, counts = self._block(x, p, pos, kc, vc,
                                              cache_mask, chunk_mask)
            new.append((k_c, v_c))
            loads.append(counts)
        with jax.named_scope("head"):
            head = self._head.data()._data
            h = _rms(x, self._final.data()._data,
                     self._eps).astype(head.dtype)
            logits = jnp.dot(h, head.T)
        return logits, new, jnp.stack(loads)

    def hybrid_forward(self, F, tokens, **_registered):
        import jax.numpy as jnp

        from ....ndarray import NDArray

        ids = tokens._data.astype(jnp.int32)
        logits, _kv, _load = self._run(
            ids, None, jnp.zeros((ids.shape[0],), jnp.int32))
        return NDArray(logits)

    def chunk_forward(self, tokens, caches, start):
        """C positions a sequence against a linear K/V cache view (the
        chunk protocol of ``generate.PagedGenerationEngine``): ``tokens``
        raw (B, C) int32 at positions ``start_b ..``, where ``start_b``
        is a multiple of the block length; ``caches`` one ``(k, v)`` of
        raw (B, n_kv_heads, S, d_head) a layer, holding positions
        ``< start_b``.  A chunk position attends the cache and the chunk
        positions of its own and earlier blocks.  Returns ``(logits
        NDArray (B, C, V), [(k, v) raw (B, n_kv_heads, C, d_head)],
        {"expert_load": (layers, experts) int32})``."""
        import jax.numpy as jnp

        from ....ndarray import NDArray

        logits, new, load = self._run(tokens.astype(jnp.int32), caches,
                                      start.astype(jnp.int32))
        return NDArray(logits), new, {"expert_load": load}
