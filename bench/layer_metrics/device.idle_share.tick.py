"""Scheduler: share of the engine steps' host time (the ``bench.tick``
annotations of the traced seconds, as ``device.idle_share``) in which the
chip sat idle inside ``serve.tick`` but outside ``serve.admit`` and
``serve.sample``: scheduling, input upload and the decode's launch, in %.
With the admit and sample parts it adds up to ``device.idle_share``."""
from bench import span_clock


def read(run):
    split = span_clock.idle_split(run)
    if split is None or split["total"] <= 0:
        return None
    return 100.0 * split["tick"] / split["total"]
