"""Device time of the decode step under the program's ``attention``
scope, in ms a step, the scopes nested in it included: QKV projection,
page writes, selection, gather and output projection of every layer
(``bench/program_trace.py``). None when no op of the step carries it."""

from bench import program_trace


def read(run):
    trace = program_trace.of_run(run)
    return trace.scope_ms("attention") if trace else None
