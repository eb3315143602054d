"""Scheduler: median wait from a request's due time to the start of the
engine step that admitted it, over the requests due in the window."""
import numpy as np


def read(run):
    w = run.window
    waits = [r.admit_tick - r.due for r in w.recs
             if r.admit_tick is not None and w.w0 <= r.due < w.w1]
    return float(np.median(waits)) * 1e3 if waits else None
