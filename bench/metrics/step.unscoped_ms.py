"""Device time of the decode step under no program scope, in ms a step:
XLA's layout copies (which carry no name path), norms, residual adds,
the embedding and the layer loop's carry (``bench/program_trace.py``).
On a program without scopes it is the whole step's op time."""

from bench import program_trace


def read(run):
    trace = program_trace.of_run(run)
    return trace.scope_ms(program_trace.UNSCOPED) if trace else None
