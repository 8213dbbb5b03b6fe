"""The system under test for the ``exaone_moe`` family: the package's
``gluon.model_zoo.language.HybridDecoderLM`` with grouped-query
attention layers under QK-norm, windowed (``"swa"``, rotary positions)
where the configuration's ``sliding_windows`` gives the held layer a
window and full (``"gqa"``, no positions) elsewhere, the
configuration's share of the routed experts behind a sigmoid router of
one group, and its multi-token-prediction module as the model's draft
block; matrices stored in the configuration's ``weights_dtype``; the
benchmark's weights are put in afterwards."""


def build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    held = cfg["layers_held"]
    if cfg["num_nextn_predict_layers"] and (
            cfg["mtp_sliding_windows"][0] or cfg["sliding_windows"][held[-1]]):
        raise SystemExit("the draft block is of the kind of the trunk's "
                         "last layer held, and both must be full attention")
    net = HybridDecoderLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        mixers=["swa" if cfg["sliding_windows"][l] else "gqa" for l in held],
        ffns=["moe" if cfg["mlp_layer_types"][l] == "sparse" else "dense"
              for l in held],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        window=cfg["sliding_window"], qk_norm=True, rotary=("swa",),
        d_ff=cfg["intermediate_size"],
        n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        max_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        rms_eps=cfg["rms_norm_eps"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        draft_layers=cfg["num_nextn_predict_layers"],
        dtype=cfg["weights_dtype"])
    net.initialize(mx.init.Zero())
    return net
