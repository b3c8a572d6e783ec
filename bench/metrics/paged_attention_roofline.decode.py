"""The paged-attention kernel's share of its roofline (decode)."""
from harness.measure import kernel_roofline


def read(run):
    return kernel_roofline(run, "paged_attention")
