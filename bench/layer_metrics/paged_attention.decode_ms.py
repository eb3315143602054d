"""Paged-attention kernel: device time of its decode form (one query
token per request) in the traced seconds, over the ``serve.decode`` spans
that start there: the kernel's ms per decode step, all layers."""
import re

from bench import span_clock, trace_reduce

# the decode form's custom call is named after its jitted wrapper,
# kernels/paged_attention/ops.py paged_attention_op_decode
KERNEL = r"^%paged_attention_op_decode"


def read(run):
    off = span_clock.offset_ns(run)
    if off is None:
        return None
    tr = run.trace
    lo, hi = tr["lo"], tr["hi"]
    # the union of the form's events: an event nested in another of the
    # same name counts once
    rx = re.compile(KERNEL)
    ns = trace_reduce.busy_ns([op for op in tr["ops"] if rx.search(op[0])],
                              lo, hi)
    steps = sum(1 for _, s, _ in span_clock.span_intervals(
        run, "serve.decode", off) if lo <= s < hi)
    if ns <= 0 or not steps:
        return None
    return ns / 1e6 / steps
