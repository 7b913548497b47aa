"""Pallas TPU kernels for the hot ops (flash prefill + decode attention)."""

from llm_consensus_tpu.ops.pallas.decode_attention import (
    decode_attention,
    decode_flash_supported,
    decode_sweep_plan,
)
from llm_consensus_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_supported,
)

__all__ = [
    "decode_attention",
    "decode_flash_supported",
    "decode_sweep_plan",
    "flash_attention",
    "flash_supported",
]
