"""Share of the window the scheduler spent in prefill phases:
``EngineMetrics.prefill_time`` (host clock around each prefill dispatch,
synchronised with the device) over the window, in percent."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * run.counters["prefill_time"] / run.window_s
