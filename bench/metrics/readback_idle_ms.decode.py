"""Idle ms per unified step on chip 0 between step programs with the
host in `phase/harvest` outside `phase/advance`: from the step
program's end until its token vector reaches the host."""
from harness.host_idle import per_step_ms


def read(run):
    return per_step_ms(run, "readback")
