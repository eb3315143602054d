"""KV pool: share of prompt tokens in the window served from cached
prefix blocks, from the engine's prefill counters, in %."""


def read(run):
    total = run.counters["prefill_tokens_total"]
    if total <= 0:
        return None
    return 100.0 * (1.0 - run.counters["prefill_tokens_computed"] / total)
