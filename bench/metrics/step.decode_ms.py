"""Device time of one decode-step program execution, in ms: the mean
duration of the decode module's executions in the traced slice."""

from bench.program import DECODE_MODULE


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times(DECODE_MODULE)
    return sum(times) / len(times) * 1e-6 if times else None
