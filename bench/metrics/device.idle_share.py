"""Share of the traced slice in which no operation ran on the device,
in percent: 1 - busy / window, from the same reduction that gives the
result line's ``busy_s`` and ``window_s``."""


def read(run):
    if run.trace is None or run.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns / run.trace.window_ns)
