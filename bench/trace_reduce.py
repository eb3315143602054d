"""Reduce a profiler trace to device busy time, idle share and kernel time.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain interval lists; everything else works on those lists, so the
tests can build a trace by hand.  An interval is ``(name, start_ns,
end_ns)``; device operations and host annotations share the profiler's
clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"            # the device plane's line of executed ops
HOST_PREFIX = "bench."          # the benchmark's own host annotations


def read_xplane(path: str) -> dict:
    """{"devices": {plane: [op intervals]}, "host": [host intervals]}."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda x: x[1])
        elif plane.name.startswith("/host:CPU"):
            # the thread that runs the benchmark's loop: the one line that
            # carries its annotations
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.duration_ns > 0]
                if any(n.startswith(HOST_PREFIX) for n, _, _ in evs):
                    host += evs
    return {"devices": devices, "host": sorted(host, key=lambda x: x[1])}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) spans of ``intervals`` clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                   if e > lo and s < hi)
    out: list = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(ops, lo, hi))


def idle_share(ops, windows) -> float | None:
    """1 - (device busy inside ``windows``) / (their total length).
    ``windows``: host intervals, e.g. the annotation around each step."""
    total = sum(e - s for _, s, e in windows)
    if total <= 0:
        return None
    merged = union(ops, min(s for _, s, _ in windows),
                   max(e for _, _, e in windows))
    starts = [s for s, _ in merged]
    busy = 0.0
    for _, lo, hi in windows:
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(merged) and merged[i][0] < hi:
            busy += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
            i += 1
    return 1.0 - busy / total


def kernel_ns(ops, pattern: str, lo: float, hi: float) -> float:
    """Device time of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(min(e, hi) - max(s, lo) for n, s, e in ops
               if rx.search(n) and e > lo and s < hi)


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def leaves(ops) -> list:
    """The ops that contain no other op (a loop's body ops, not the loop)."""
    out, stack = [], []
    for op in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0][2] <= op[1]:
            prev, inner = stack.pop()
            if not inner:
                out.append(prev)
        if stack:
            stack[-1][1] = True
        stack.append([op, False])
    out += [op for op, inner in stack if not inner]
    return out


def top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    """[[name, seconds], ...] of the innermost ops that took most device
    time, by short name."""
    tot: dict = defaultdict(float)
    for name, s, e in leaves(ops):
        if e > lo and s < hi:
            tot[short_name(name)] += min(e, hi) - max(s, lo)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def _innermost(host, starts, t: float) -> str:
    """Name of the innermost host interval open at ``t``.  Intervals of
    one thread nest, so the latest-started one that covers ``t`` is it."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, e = host[i]
        if e > t:
            return name
        i -= 1
    return "(no host span)"


def idle_gaps(ops, host, lo: float, hi: float, n: int = 10) -> list:
    """[[host activity, seconds], ...]: the device's idle time in [lo, hi),
    each gap credited to what the host thread was inside at its middle,
    summed by that name.  ``host``: one thread's intervals, by start."""
    busy = union(ops, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    starts = [s for _, s, _ in host]
    tot: dict = defaultdict(float)
    for s, e in gaps:
        tot[_innermost(host, starts, (s + e) / 2)] += e - s
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]
