"""Idle ms per unified step on chip 0 between step programs with the
host in a `phase/*` span other than `phase/harvest`: the bookkeeping
after the tokens arrive (`phase/advance`), admission, chunk planning."""
from harness.host_idle import per_step_ms


def read(run):
    return per_step_ms(run, "host")
