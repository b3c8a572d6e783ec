"""Whole-step share of the int8 peak in the decode cells."""
from harness.measure import step_mfu as read  # noqa: F401
