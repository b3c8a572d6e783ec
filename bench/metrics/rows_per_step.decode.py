"""Mean number of rows (decode rows and chunk rows) the engine put into
one unified step of the window: the scheduler's batch occupancy, read
from the engine's host state as each step is dispatched."""


def read(run):
    steps = run.window.dispatches
    if not steps:
        return None
    return sum(len(run.rows(d)) for d in steps) / len(steps)
