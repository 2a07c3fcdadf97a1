"""Device time of the decode step under the program's
``attention/page_write`` scope, in ms a step: the K/V scatter into the
page pool and the int16 filter-plane refresh of the touched pages
(``bench/program_trace.py``). None when no op of the step carries it."""

from bench import program_trace


def read(run):
    trace = program_trace.of_run(run)
    return trace.scope_ms("attention/page_write") if trace else None
