"""Hot-op kernels (Pallas on TPU, interpreter fallback elsewhere).

The reference has no compute ops at all (SURVEY.md §2 — it is a pure
communication runtime); this package is the rebuild's tpu-native ops
library, supplying the kernels the flagship workloads sit on. Kernels are
written with ``jax.experimental.pallas`` against the TPU backend and run
in interpreter mode on CPU so the whole suite is testable without chips.
"""

from .attention import (
    blockwise_attention,
    dense_attention,
    flash_attention,
    flash_attention_with_lse,
    flash_block_defaults,
    flash_chunk_bwd,
    merge_attention_chunks,
    set_flash_block_defaults,
)
from .autotune import tune_flash_blocks
from .decode_attention import flash_decode_attention
from .eva_attention import eva_attention, eva_summaries
from .ring_collectives import (
    ring_allgather,
    ring_allgather_sharded,
    ring_allreduce,
    ring_allreduce_sharded,
)

__all__ = [
    "dense_attention",
    "blockwise_attention",
    "flash_attention",
    "flash_attention_with_lse",
    "flash_block_defaults",
    "flash_decode_attention",
    "eva_attention",
    "eva_summaries",
    "set_flash_block_defaults",
    "tune_flash_blocks",
    "flash_chunk_bwd",
    "merge_attention_chunks",
    "ring_allgather",
    "ring_allgather_sharded",
    "ring_allreduce",
    "ring_allreduce_sharded",
]
