"""Idle ms per unified step on chip 0 between step programs with the
host in `repro.serving/unified`: the step's argument transfers, any
program run to prepare them, and the launch."""
from harness.host_idle import per_step_ms


def read(run):
    return per_step_ms(run, "enqueue")
