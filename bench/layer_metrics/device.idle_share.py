"""Device: share of the engine steps' host time (the benchmark's
``bench.tick`` annotation around each step) in which no operation ran
on the chip, from the profiler trace of the window's last seconds, in %."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    v = trace_reduce.idle_share(run.trace["ops"], run.trace["ticks"])
    return None if v is None else 100.0 * v
