"""The system under test for the ``bailing_hybrid`` family: the package's
``gluon.model_zoo.language.HybridDecoderLM`` at the configuration's
sizes, holding the configuration's share of the routed experts, its
matrices stored in the configuration's ``weights_dtype``; the benchmark's
weights are put in afterwards."""


def build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    held = cfg["layers_held"]
    net = HybridDecoderLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        mixers=["mla" if (l + 1) % cfg["layer_group_size"] == 0 else "kda"
                for l in held],
        ffns=["dense" if l < cfg["first_k_dense_replace"] else "moe"
              for l in held],
        n_heads=cfg["num_attention_heads"], d_k=cfg["head_dim"],
        d_v=cfg["head_dim"], conv_kernel=cfg["short_conv_kernel_size"],
        kda_lower_bound=cfg["kda_lower_bound"],
        d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
        d_latent=cfg["kv_lora_rank"], d_ff=cfg["intermediate_size"],
        n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        max_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        dtype=cfg["weights_dtype"])
    net.initialize(mx.init.Zero())
    return net
