"""The harness end to end at a tiny size on the CPU (the look for a chip
skipped): a sound run is correct and reports its metrics; the float8
control reads a gap above the limit."""
import json

import tiny

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
