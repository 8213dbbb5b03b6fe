"""TPU-native parallelism (mesh/pjit/shard_map + ICI collectives).

This package is the TPU-first replacement for the reference's entire
distribution stack (SURVEY §2.3): kvstore allreduce -> sharding-induced
psum; ps-lite multi-host -> jax.distributed; plus new capabilities the
reference lacked (tensor parallelism, ring-attention sequence parallelism,
microbatched pipeline parallelism).
"""
from .mesh import (make_mesh, local_mesh, init_distributed, MeshConfig,  # noqa: F401
                   bootstrap_distributed, distributed_env,
                   DistributedUnavailable, UNAVAILABLE_SIGNATURES,
                   shard_map, parse_mesh, resolve_mesh, require_axes,
                   mesh_shape, MESH_AXES, DATA_AXES)
from .layout import (SpecRule, Layout, register_layout, get_layout,  # noqa: F401
                     list_layouts, resolve_layout, default_layout_for)
from .train import ShardedTrainer  # noqa: F401
from .ring_attention import (ring_attention, ring_attention_sharded,  # noqa: F401
                             local_attention)
from .pipeline import pipeline_forward, gpipe_loss  # noqa: F401
from .ulysses import ulysses_attention, ulysses_attention_sharded  # noqa: F401
from .moe import moe_ffn, moe_ffn_sharded, routed_experts  # noqa: F401
