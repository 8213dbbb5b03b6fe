"""The system under test for the ``sdar_moe`` family: the package's
``gluon.model_zoo.language.MoEDecoderLM`` at the configuration's sizes,
holding every expert, its matrices stored in the configuration's
``weights_dtype``; the benchmark's weights are put in afterwards."""


def build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM

    net = MoEDecoderLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        block_length=cfg["assumed"]["block_length"],
        mask_token_id=cfg["assumed"]["mask_token_id"],
        max_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        norm_topk=cfg["norm_topk_prob"], dtype=cfg["weights_dtype"])
    net.initialize(mx.init.Zero())
    return net
