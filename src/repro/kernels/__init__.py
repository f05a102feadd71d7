"""Pallas TPU kernels for KPynq (compiled on the TPU, interpreted on the
CPU: see ``repro.platform.pallas_interpret``)."""
from .ops import build_group_block_mask, compact_indices, grouped_assign

__all__ = ["build_group_block_mask", "compact_indices", "grouped_assign"]
