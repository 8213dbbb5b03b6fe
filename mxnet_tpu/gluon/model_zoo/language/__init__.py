"""Language models of the zoo (decoder-only; served by
``mxnet_tpu.generate.PagedGenerationEngine`` through the chunk
protocol)."""
from .moe_decoder import MoEDecoderLM  # noqa: F401
from .hybrid_decoder import HybridDecoderLM  # noqa: F401
