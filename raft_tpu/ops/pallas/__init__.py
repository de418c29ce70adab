"""Pallas TPU kernels for the hot ops.

These are the TPU analogs of the reference's hand-written CUDA kernels
(``matrix/detail/select_radix.cuh``, ``select_warpsort.cuh``, and the
tiled contraction engine ``linalg/detail/contractions.cuh``): where XLA's
stock lowering leaves performance on the table, the op is expressed as an
explicit grid over VMEM-resident blocks.

Dispatch is centralized in :mod:`.gate` and decided by the platform
alone: Mosaic on a TPU, ``interpret=True`` elsewhere so the same code
paths are exercised by the CPU test mesh (SURVEY.md §4's
LocalCUDACluster analog).
"""

from .gate import interpret, on_tpu
from .select_k import select_k_pallas
from .fused_l2_topk import fused_shortlist

__all__ = ["select_k_pallas", "fused_shortlist", "interpret", "on_tpu"]
