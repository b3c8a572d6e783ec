"""% of the unified steps' device time spent in steps that carry a
prefill chunk: what the width-1 decode path does not account for."""


def read(run):
    steps = run.step_device_s()
    total = sum(s for _, s in steps or ())
    if not total:
        return None
    return 100.0 * sum(s for w, s in steps if w > 1) / total
