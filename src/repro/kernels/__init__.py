"""Pallas TPU kernels for the W8A8 serving hot spots.  Each compiles
with Mosaic when lowered for a TPU and runs in the Pallas interpreter
on every other platform (kernels/platform.py); the paged-attention
kernel keeps its K/V pools in HBM and copies pages into VMEM.  See
DESIGN.md §6."""
from repro.kernels.ops import (
    int8_matmul_requant, linear_rqt_kernel, quant_flash_attention, requant,
)
