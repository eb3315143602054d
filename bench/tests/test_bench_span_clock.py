"""Engine spans mapped onto a hand-built profiler trace, and the readers
built on them."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, span_clock, trace_reduce

OFF = 7_000_000_000          # profiler ns - perf_counter ns
TS = [10.0, 10.002, 10.004]  # the traced steps' window stamps (perf s)
JITTER = [0, 500, 0]         # ns from a stamp to its annotation's start


def p(t):                    # perf_counter s -> profiler ns
    return t * 1e9 + OFF


def us(t0, a, b):            # [a, b) us after perf time t0, profiler ns
    return p(t0) + a * 1e3, p(t0) + b * 1e3


def make_run(sample=True, kernel="%paged_attention_op_decode.2"):
    ticks = [("bench.tick", p(t) + j, us(t, 0, 1000)[1])
             for t, j in zip(TS, JITTER)]
    ops, spans = [], []

    def span(name, t0, a, b):
        spans.append({"name": name, "start": t0 + a * 1e-6,
                      "dur": (b - a) * 1e-6, "args": {}})

    for k, t in enumerate(TS):
        span("serve.tick", t, 0, 1000)
        if k < 2:            # the last step admits nothing
            span("serve.admit", t, 0, 400)
            span("serve.prefill", t, 50, 350)
            ops.append(("%fusion.9 = prefill", *us(t, 100, 350)))
        span("serve.decode", t, 450, 850)
        ops.append((f"{kernel} = bf16[] custom-call()", *us(t, 470, 800)))
        ops.append(("%fusion.1 = bf16[] fusion()", *us(t, 800, 840)))
        if sample:
            span("serve.sample", t, 850, 1000)
        ops.append(("%argmax.3 = s32[] reduce()", *us(t, 860, 870)))
    # two untraced steps, then the traced ones
    window = SimpleNamespace(ticks=[{"ts": t, "te": t + 1e-3}
                                    for t in [8.0, 9.0] + TS],
                             recs=[], w0=0.0, w1=20.0)
    trace = {"lo": ticks[0][1], "hi": ticks[-1][2], "ticks": ticks,
             "ops": sorted(ops, key=lambda x: x[1])}
    return SimpleNamespace(window=window, spans=spans, trace=trace)


def test_offset_is_the_median_stamp_to_annotation_gap():
    assert span_clock.offset_ns(make_run()) == pytest.approx(OFF)
    assert span_clock.offset_ns(SimpleNamespace(trace=None)) is None


def test_idle_split_adds_up_to_the_idle_share():
    run = make_run()
    split = span_clock.idle_split(run)
    ticks = run.trace["ticks"]
    assert split["total"] == pytest.approx(2999.5e3)
    # admission: 0-100 and 350-400 us idle (the middle step's annotation
    # opens 0.5 us late); sampling: 850-860 and 870-1000; the rest of
    # the step: 400-470 and 840-850, or 0-470 and 840-850 without admission
    assert split["admit"] == pytest.approx(150e3 + 149.5e3, abs=1)
    assert split["sample"] == pytest.approx(3 * 140e3, abs=1)
    assert split["tick"] == pytest.approx(80e3 + 80e3 + 480e3, abs=1)
    parts = split["admit"] + split["sample"] + split["tick"]
    assert parts / split["total"] == pytest.approx(
        trace_reduce.idle_share(run.trace["ops"], ticks), abs=1e-9)
    shares = [harness.load_reader(f"device.idle_share.{k}")(run)
              for k in ("admit", "sample", "tick")]
    whole = harness.load_reader("device.idle_share")(run)
    assert sum(shares) == pytest.approx(whole, abs=1e-6)


def test_readers_are_silent_on_a_program_without_the_spans():
    run = make_run(sample=False, kernel="%paged_attention_op.9")
    assert span_clock.idle_split(run) is None
    for k in ("admit", "sample", "tick"):
        assert harness.load_reader(f"device.idle_share.{k}")(run) is None
    assert harness.load_reader("paged_attention.decode_ms")(run) is None


def test_decode_kernel_ms_per_decode_step():
    # 330 us of the decode form in each of three steps
    run = make_run()
    read = harness.load_reader("paged_attention.decode_ms")
    assert read(run) == pytest.approx(0.33)
    # an event nested in another of the same name counts once
    name, s, e = next(op for op in run.trace["ops"]
                      if op[0].startswith("%paged_attention_op_decode"))
    run.trace["ops"] = sorted(run.trace["ops"] + [(name, s + 1e3, e - 1e3)],
                              key=lambda x: x[1])
    assert read(run) == pytest.approx(0.33)


def _rec(due, submit, admit, t0=100.0, refused=False):
    req = SimpleNamespace(arrival_s=due - t0, submit_s=submit, admit_s=admit)
    return SimpleNamespace(due=due, req=req, refused=refused)


def test_queue_wait_counts_the_engine_side_wait():
    w = SimpleNamespace(w0=110.0, w1=120.0, ticks=[], recs=[
        _rec(105.0, 5.0, 9.0),                  # due before the window
        _rec(111.0, 11.0, 11.5),
        _rec(112.0, 12.0, 12.25),
        _rec(118.0, 18.0, None),                # still queued: 2 s so far
        _rec(119.0, 19.0, 23.0),                # admitted after the end
        _rec(119.5, None, None, refused=True)])  # refused at submit
    waits = [500.0, 250.0, 2000.0, 1000.0]
    got = harness.load_reader("queue_wait_ms.p95")(SimpleNamespace(window=w))
    assert got == pytest.approx(float(np.percentile(waits, 95)))
    for r in w.recs:
        del r.req.admit_s                        # a program without stamps
    assert harness.load_reader("queue_wait_ms.p95")(
        SimpleNamespace(window=w)) is None
