"""The engine's spans on the profiler trace's clock.

A run's spans (``run.spans``) are stamped with ``time.perf_counter``; the
device's operations (``run.trace["ops"]``) with the profiler's clock, in
ns.  Each traced step has both: its window record's ``ts`` (perf_counter,
read just before its ``bench.tick`` annotation opened) and that
annotation's start in ``run.trace["ticks"]``.  The median of their
differences maps one clock onto the other (the same pairing
``paged_attention_roofline`` uses: the traced ticks are the window's
last ones).

``idle_split`` then divides the device's idle share of the traced steps
(``trace_reduce.idle_share`` over the ``bench.tick`` annotations) into
the engine phases the host was in: admission, sampling, and the rest of
the step.
"""
from __future__ import annotations

import statistics

from bench import trace_reduce


def offset_ns(run) -> float | None:
    """Profiler clock (ns) minus perf_counter (ns), or None untraced."""
    tr = run.trace
    if tr is None or not tr["ticks"]:
        return None
    ticks = run.window.ticks[-len(tr["ticks"]):]
    if len(ticks) != len(tr["ticks"]):
        return None
    return statistics.median(h[1] - t["ts"] * 1e9
                             for t, h in zip(ticks, tr["ticks"]))


def span_intervals(run, name: str, off: float) -> list:
    """The spans called ``name`` as (name, start_ns, end_ns) intervals on
    the profiler's clock."""
    return [(name, s["start"] * 1e9 + off,
             (s["start"] + s["dur"]) * 1e9 + off)
            for s in run.spans if s["name"] == name]


def intersect(a: list, b: list) -> list:
    """[start, end) pieces common to two sorted lists of disjoint
    [start, end) pairs."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ns(ops, pieces: list) -> float:
    """Device-idle ns inside disjoint [start, end) ``pieces``."""
    total = sum(e - s for s, e in pieces)
    if total <= 0:
        return 0.0
    return total * trace_reduce.idle_share(ops, [("", s, e)
                                                 for s, e in pieces])


def idle_split(run) -> dict | None:
    """{"admit", "sample", "tick"}: device-idle ns inside ``serve.admit``
    spans, inside ``serve.sample`` spans, and inside ``serve.tick`` but
    outside both, each within the traced steps; and "total": the summed
    ``bench.tick`` ns they are shares of.  None where the trace or the
    engine's ``serve.sample`` spans are missing."""
    off = offset_ns(run)
    if off is None:
        return None
    tr = run.trace
    lo, hi = tr["lo"], tr["hi"]
    steps = trace_reduce.union(tr["ticks"], lo, hi)

    def pieces(*names):
        spans = [iv for n in names for iv in span_intervals(run, n, off)]
        return intersect(trace_reduce.union(spans, lo, hi), steps)

    sample = pieces("serve.sample")
    if not sample:
        return None
    admit, tick = pieces("serve.admit"), pieces("serve.tick")
    ops = tr["ops"]
    inner = idle_ns(ops, intersect(pieces("serve.admit", "serve.sample"),
                                   tick))
    return {"admit": idle_ns(ops, admit), "sample": idle_ns(ops, sample),
            "tick": idle_ns(ops, tick) - inner,
            "total": sum(e - s for _, s, e in tr["ticks"])}
