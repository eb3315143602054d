"""Scheduler: p95 over the requests due in the window of the engine's own
queue wait, from ``Request.submit_s`` to ``Request.admit_s`` (when its
admission began; engine clock); a request still queued at the window's
end counts its wait so far."""
import numpy as np


def read(run):
    w = run.window
    due = [r for r in w.recs if w.w0 <= r.due < w.w1 and not r.refused]
    if not due or not hasattr(due[0].req, "admit_s"):
        return None          # a program that does not stamp admissions
    waits = []
    for r in due:
        # the engine clock's zero: harness.drive stamps arrival_s = due - t0
        end = w.w1 - (r.due - r.req.arrival_s)
        admit = r.req.admit_s
        waits.append((end if admit is None or admit > end else admit)
                     - r.req.submit_s)
    return float(np.percentile(waits, 95)) * 1e3
