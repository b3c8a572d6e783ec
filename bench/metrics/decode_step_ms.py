"""Mean device time of a width-1 (decode-only) unified step."""
from harness.measure import mean_step_ms


def read(run):
    return mean_step_ms(run, wide=False)
