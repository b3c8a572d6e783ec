"""Mean device time of a unified step that carries a prefill chunk
(width C), in a cell whose steps are mostly width 1."""
from harness.measure import mean_step_ms


def read(run):
    return mean_step_ms(run, wide=True)
