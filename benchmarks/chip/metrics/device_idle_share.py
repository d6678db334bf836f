"""Share of the traced window in which no operation ran on the device
(averaged over the chips): 1 - union of the op intervals / window."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100 * (1 - run.trace.busy_s() / run.trace.window_s)
