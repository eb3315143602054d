"""The harness end to end at a tiny size on the CPU (the look for a chip
skipped): a sound run is correct and reports its metrics; the float8
control reads a gap above the limit; a closed loop never runs dry; a
traced run says why a trace reading is missing."""
import json

import pytest

import tiny
from bench import harness, trace_reduce, workload
from bench.weights import make_params

E2E = {"ttft_p95_ms", "itl_p95_ms", "output_tokens_per_s", "setup_s"}


def test_sound_run_is_correct_and_reports_end_to_end():
    r = tiny.run(trace=False, seed=2 ** 31 + 99)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == E2E
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"          # compared numbers come last
    json.dumps(r)


def test_traced_run_reports_layers_and_no_window_compiles():
    r = tiny.run(trace=True, seed=11)
    assert r["correct"], r["checks"]
    got = set(r["metrics"])
    # the CPU has no device trace and no memory stats: those readers
    # return nothing and their metrics are left out
    assert {"admit_wait_ms.p50", "prefill.mfu", "prefix_hit_share",
            "decode_step_ms", "decode.mfu", "window_compiles"} <= got
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert 0 < r["metrics"]["prefix_hit_share"]["value"] < 100


def test_control_reads_above_the_limit():
    # the float8 control in the program's place goes through the verdict
    r = tiny.run(trace=False, seed=4, control=True)
    c = r["checks"]
    assert not r["correct"], c
    assert c["program_logit_gap"]["value"] <= c["logit_gap"]["limit"]
    assert c["logit_gap"]["value"] > c["logit_gap"]["limit"]


def test_too_few_compared_tokens_is_not_correct():
    r = tiny.run(trace=False, seed=5, conf=tiny.conf(tokens=10 ** 6))
    c = r["checks"]
    assert c["logit_gap"]["value"] <= c["logit_gap"]["limit"]
    assert c["compared_tokens"]["value"] < c["compared_tokens"]["limit"]
    assert not r["correct"], c


# a closed loop whose whole request set the CPU engine serves in a
# fraction of a second
DRAINED = {"loop": "closed", "requests": 4, "callers": 4, "lead_in_s": 0.2,
           "levels": 4,
           "prompt": {"median": 10, "sigma": 0.6, "min": 4, "max": 16},
           "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


def test_drained_closed_loop_keeps_ticking():
    conf = tiny.conf()
    m = conf["model"]
    engine = harness.build_engine(conf, harness.model_config(conf),
                                  make_params(m, 7))
    supply = workload.rounds(DRAINED, 7, m["vocab_size"])
    specs = next(supply)
    harness.warm(engine, harness.warm_plan(engine, specs), m["vocab_size"])
    win = harness.drive(engine, specs, DRAINED, harness.TRACE_S + 2.0,
                        more=supply, record=True)
    n = DRAINED["requests"]
    first = [r for r in win.recs if r.spec.idx < n]
    assert len(first) == n and all(r.done is not None for r in first)
    traced = win.w1 - harness.TRACE_S
    assert max(r.done for r in first) < traced   # round 0 ran out early
    assert any(traced <= k["ts"] < win.w1 for k in win.ticks)
    assert any(n <= r.spec.idx < 2 * n for r in win.recs)   # round 1 sent
    assert all(not r.refused for r in win.recs)


@pytest.mark.parametrize("case,why", [
    ("no_file", "no .xplane.pb"),
    ("no_tick", "no bench.tick in the traced seconds"),
    ("no_device", "no TPU plane"),
])
def test_missing_trace_reading_says_why(case, why, tmp_path, monkeypatch):
    data = {"no_tick": {"devices": {"/device:TPU:0": [("op", 0, 5)]},
                        "host": [("serve.tick", 0, 10)]},
            "no_device": {"devices": {}, "host": [("bench.tick", 0, 10)]}}
    if case != "no_file":
        monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.pb")
        monkeypatch.setattr(trace_reduce, "read_xplane", lambda p: data[case])
    said = []
    got = harness._reduce_trace(tmp_path / "trace",
                                log=lambda *a, **k: said.append(a[0]))
    assert got is None
    assert len(said) == 1 and why in said[0]
