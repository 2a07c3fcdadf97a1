"""Share of the window's prompt tokens that the prefix cache served:
``EngineMetrics.prefill_tokens_skipped`` over skipped plus prefilled
prompt tokens, in percent. None when no prompt token was admitted."""


def read(run):
    c = run.counters
    total = c["prefill_tokens"] + c["prefill_tokens_skipped"]
    if not total:
        return None
    return 100.0 * c["prefill_tokens_skipped"] / total
