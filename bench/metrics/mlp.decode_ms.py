"""Device time of the decode step under the program's ``mlp`` scope,
in ms a step: the gated MLP of every layer (``bench/program_trace.py``).
None when no op of the step carries it."""

from bench import program_trace


def read(run):
    trace = program_trace.of_run(run)
    return trace.scope_ms("mlp") if trace else None
