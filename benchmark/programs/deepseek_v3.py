"""The system under test for the ``deepseek_v3`` family: the package's
``gluon.model_zoo.language.HybridDecoderLM`` with every mixer an MLA
layer with a query latent under YaRN and no output gate, the
configuration's share of the routed experts, and its
multi-token-prediction module as the model's draft block; matrices
stored in the configuration's ``weights_dtype``; the benchmark's weights
are put in afterwards."""


def build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    held = cfg["layers_held"]
    net = HybridDecoderLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        mixers=["mla"] * len(held),
        ffns=["dense" if l < cfg["first_k_dense_replace"] else "moe"
              for l in held],
        n_heads=cfg["num_attention_heads"],
        q_latent=cfg["q_lora_rank"], d_latent=cfg["kv_lora_rank"],
        d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
        d_v_mla=cfg["v_head_dim"], mla_gate=False,
        rope_scaling=cfg["rope_scaling"], d_ff=cfg["intermediate_size"],
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        max_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        experts_held=(cfg["experts_first"], cfg["n_routed_experts"]),
        draft_layers=cfg["num_nextn_predict_layers"],
        dtype=cfg["weights_dtype"])
    net.initialize(mx.init.Zero())
    return net
