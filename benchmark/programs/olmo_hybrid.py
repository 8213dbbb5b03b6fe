"""The system under test for the ``olmo_hybrid`` family: the package's
``gluon.model_zoo.language.HybridDecoderLM`` with Gated DeltaNet
(``"gdn"``, ``beta`` in [0, 2] where ``linear_allow_neg_eigval``) and
full multi-head attention (``"gqa"``, QK-norm over the whole
projection, no positions) in the configuration's ``layer_types``,
OLMo 2's post-norm blocks and dense MLPs; matrices stored in the
configuration's ``weights_dtype``; the benchmark's weights are put in
afterwards."""


def build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    H = cfg["num_attention_heads"]
    if not (cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"]
            == H):
        raise SystemExit("the program's GDN layers have as many key and "
                         "value heads as the attention layers")
    if cfg["rope_parameters"].get("rope_theta") is not None:
        raise SystemExit("the full layers are built without positions")
    held = cfg["layers_held"]
    net = HybridDecoderLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        mixers=["gdn" if cfg["layer_types"][l] == "linear_attention"
                else "gqa" for l in held],
        ffns=["dense"] * len(held), n_heads=H,
        d_k=cfg["linear_key_head_dim"], d_v=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        neg_eigval=cfg["linear_allow_neg_eigval"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg["hidden_size"] // H, qk_norm="whole", rotary=(),
        d_ff=cfg["intermediate_size"], post_norm=True,
        max_len=cfg["max_position_embeddings"], rms_eps=cfg["rms_norm_eps"],
        dtype=cfg["weights_dtype"])
    net.initialize(mx.init.Zero())
    return net
