"""Prefill: model FLOPs of the prompt tokens computed in the window (full
prompts and suffixes after a cached prefix, each with attention over its
context), over the summed ``serve.prefill`` and ``serve.chunk_prefill``
span time, over the chip's bf16 peak, in %."""
from bench import flops


def read(run):
    w, m = run.window, run.model
    work = secs = 0.0
    for s in run.spans:
        if not w.w0 <= s["start"] < w.w1:
            continue
        if s["name"] == "serve.prefill":
            work += flops.prefill_flops(m, s["args"]["plen"])
        elif s["name"] == "serve.chunk_prefill":
            a = s["args"]
            work += flops.prefill_flops(m, a["suffix"], a["shared"])
        else:
            continue
        secs += s["dur"]
    if secs <= 0:
        return None
    return 100.0 * work / secs / run.peaks["bf16_flops_per_s"]
