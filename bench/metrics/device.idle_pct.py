"""Share of the traced window in which no operation ran on the device:
1 - (union of the operations' intervals / window), averaged over chips."""


def read(run):
    if run.trace is None or run.trace.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns / run.trace.window_ns)
