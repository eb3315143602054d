"""Scheduler: share of the engine steps' host time (the ``bench.tick``
annotations of the traced seconds, as ``device.idle_share``) in which the
chip sat idle inside a ``serve.admit`` span: pool reservation, table
uploads, the eager KV scatter and the first token's read, in %."""
from bench import span_clock


def read(run):
    split = span_clock.idle_split(run)
    if split is None or split["total"] <= 0:
        return None
    return 100.0 * split["admit"] / split["total"]
