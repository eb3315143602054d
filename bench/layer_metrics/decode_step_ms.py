"""Decode step: mean ``serve.decode`` span in the window (the span ends
after the step's logits are ready)."""


def read(run):
    w = run.window
    d = [s["dur"] for s in run.spans
         if s["name"] == "serve.decode" and w.w0 <= s["start"] < w.w1]
    return 1e3 * sum(d) / len(d) if d else None
