"""Pallas TPU kernels for KPynq (compiled on the TPU, interpreted on the
CPU: see ``repro.platform.pallas_interpret``)."""
from .flash_attention import flash_attention
from .ssd_intra import ssd_intra
from .ops import (build_block_mask, build_group_block_mask,
                  centroid_update, compact_indices, filtered_assign,
                  filtered_assign_auto, grouped_assign, pairwise_sq_dists)

__all__ = ["pairwise_sq_dists", "filtered_assign", "centroid_update",
           "build_block_mask", "build_group_block_mask", "compact_indices",
           "filtered_assign_auto", "grouped_assign", "flash_attention",
           "ssd_intra"]
