"""Wall time of one prefill chunk, in ms: the window's
``EngineMetrics.prefill_time`` over its ``prefill_dispatches``.
``prefill_time`` adds up the engine's ``serve.prefill`` spans, each read
from building its first chunk's inputs to the device finishing its last
(after ``serve.prefill.sync``), over the whole window: a traced slice
often admits no new turn, the window always does. None when the window
dispatched no prefill chunk."""


def read(run):
    chunks = run.counters.get("prefill_dispatches")
    if not chunks:
        return None
    return 1e3 * run.counters["prefill_time"] / chunks
