"""Host time of one engine tick, in ms: the mean over the traced
slice's ``serve.tick`` spans of each one's duration less the
``serve.decode.sync`` and ``serve.prefill.sync`` spans inside it, where
the host waits for the chip (``bench/program_trace.py``). None when the
program writes no ``serve.tick`` span."""

from bench import program_trace

_WAITS = ("serve.decode.sync", "serve.prefill.sync")


def read(run):
    trace = program_trace.of_run(run)
    if trace is None:
        return None
    ticks = [(s, e) for s, e, n in trace.spans if n == "serve.tick"]
    if not ticks:
        return None
    waits = [(s, e) for s, e, n in trace.spans if n in _WAITS]
    host = [(e - s) - sum(we - ws for ws, we in waits if s <= ws and we <= e)
            for s, e in ticks]
    return sum(host) / len(host) * 1e-6
