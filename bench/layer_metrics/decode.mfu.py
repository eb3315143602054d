"""Decode step: required FLOPs of the tokens decoded in the window (all
projections and the head, and attention over each token's context, from
host state around each step), over the summed ``serve.decode`` span
time, over the chip's bf16 peak, in %."""
from bench import flops


def read(run):
    w, m = run.window, run.model
    work = sum(flops.decode_flops(m, k) for t in w.ticks
               if w.w0 <= t["ts"] < w.w1 for k in t["decode_keys"])
    secs = sum(s["dur"] for s in run.spans
               if s["name"] == "serve.decode" and w.w0 <= s["start"] < w.w1)
    if secs <= 0 or work <= 0:
        return None
    return 100.0 * work / secs / run.peaks["bf16_flops_per_s"]
