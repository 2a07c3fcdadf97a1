"""Device time of the decode step under the program's
``attention/select`` scope, in ms a step: query quantisation, the fused
filter kernel, the Eq. 3 thresholds and the tier top-B
(``bench/program_trace.py``). None when no op of the step carries it."""

from bench import program_trace


def read(run):
    trace = program_trace.of_run(run)
    return trace.scope_ms("attention/select") if trace else None
