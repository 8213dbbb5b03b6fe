"""The system under test for the ``opt`` family: the repo's only LM,
``examples/transformer_lm.TransformerLM``, at the configuration's sizes,
with the benchmark's weights put in."""
import os
import sys

from benchmark.lib.manifest import ROOT


def _lm_module():
    path = os.path.join(ROOT, "examples")
    if path not in sys.path:
        sys.path.insert(0, path)
    import transformer_lm

    return transformer_lm


def build_net(cfg):
    import mxnet_tpu as mx

    net = _lm_module().TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["ffn_dim"],
        max_len=cfg["max_position_embeddings"])
    net.initialize(mx.init.Zero())
    return net


def loss_fn(cfg):
    return _lm_module().lm_loss_fn(cfg["vocab_size"])
