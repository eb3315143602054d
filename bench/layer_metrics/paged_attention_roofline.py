"""Paged-attention kernel: the least time the chip could take for the
kernel's calls in the traced seconds (the larger of their FLOPs over the
bf16 peak and their bytes over HBM bandwidth), over the kernel's device
time there, in %.

Bytes are the algorithm's: each live request's visible keys and values
read once per KV head, plus its queries and outputs; FLOPs are QK^T and
PV over each query's causal context.  Calls are counted from host state
around each traced step: every live slot's decode, and each suffix
prefill after a cached prefix (its ``serve.chunk_prefill`` span)."""
from bench import flops, trace_reduce

# the kernel's custom call is named after its jitted wrapper,
# kernels/paged_attention/ops.py paged_attention_op
KERNEL = r"^%paged_attention_op"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    ns = trace_reduce.kernel_ns(tr["ops"], KERNEL, tr["lo"], tr["hi"])
    ticks = run.window.ticks[-len(tr["ticks"]):]
    if ns <= 0 or not ticks:
        return None
    m = run.model
    fl = by = 0
    for t in ticks:
        for k in t["decode_keys"]:
            f, b = flops.paged_attention_call(m, 1, k - 1)
            fl, by = fl + f, by + b
    lo, hi = ticks[0]["ts"], ticks[-1]["te"]
    for s in run.spans:
        if s["name"] == "serve.chunk_prefill" and lo <= s["start"] < hi:
            a = s["args"]
            f, b = flops.paged_attention_call(m, a["suffix"], a["shared"])
            fl, by = fl + f, by + b
    p = run.peaks
    least = max(fl / p["bf16_flops_per_s"], by / p["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
