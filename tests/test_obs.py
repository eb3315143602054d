"""Observability invariants: span nesting/self-time accounting, spans
named in a jax.profiler trace, the time-attribution panel summing to
~1.0, Chrome-trace export round-trip, audit calibration math, and the
no-op tracer staying under 5% of a real 200-step serve_loop's
wall-clock."""
import glob
import json
import os
import time

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.reconfig import ReconfigCostModel
from repro.models import lm
from repro.obs import (NOP_TRACER, Tracer, TuningAudit, time_attribution,
                       write_audit_jsonl, write_chrome_trace)
from repro.obs.report import FRACTION_KEYS
from repro.serving import (DEFAULT_SERVING_SETTING, Request, ServingEngine,
                           serve_loop)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("starcoder2-3b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n, max_new, seed=0, plen=8):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (plen,))
                    .astype(np.int32),
                    max_new=max_new, arrival_s=0.0) for i in range(n)]


# --------------------------------------------------------------- span core
def test_span_nesting_self_time_and_ordering():
    tr = Tracer()
    with tr.span("serve.tick"):
        with tr.span("serve.admit", rid=0):
            with tr.span("serve.prefill"):
                time.sleep(0.004)
            time.sleep(0.002)
        with tr.span("serve.decode", batch=1):
            time.sleep(0.004)
    # children exit (and are appended) before their parents
    assert [e["name"] for e in tr.events] == [
        "serve.prefill", "serve.admit", "serve.decode", "serve.tick"]
    by = {e["name"]: e for e in tr.events}
    assert by["serve.tick"]["depth"] == 0
    assert by["serve.admit"]["depth"] == 1
    assert by["serve.prefill"]["depth"] == 2
    # a span's duration covers its children; self time excludes them
    admit = by["serve.admit"]
    assert admit["dur"] >= by["serve.prefill"]["dur"]
    assert admit["self"] == pytest.approx(
        admit["dur"] - by["serve.prefill"]["dur"], abs=1e-6)
    tick = by["serve.tick"]
    assert tick["self"] == pytest.approx(
        tick["dur"] - admit["dur"] - by["serve.decode"]["dur"], abs=1e-6)
    # ts is start time: parents start before their children
    assert tick["ts"] <= admit["ts"] <= by["serve.prefill"]["ts"]
    assert by["serve.admit"]["args"] == {"rid": 0}


def test_unregistered_span_name_rejected():
    tr = Tracer()
    with pytest.raises(AssertionError):
        tr.span("serve.not_a_registered_name")
    # ...but the disabled tracer never validates (it must do nothing)
    with NOP_TRACER.span("serve.not_a_registered_name"):
        pass
    assert NOP_TRACER.events == []


def test_noop_span_is_shared_and_records_nothing():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("serve.tick"), tr.span("serve.decode")
    assert s1 is s2            # one preallocated context manager, no allocs
    with s1:
        pass
    assert tr.events == [] and tr._stack == []


def test_spans_named_in_profiler_trace(tmp_path):
    """Under a jax.profiler trace an enabled tracer's spans appear in the
    host plane by name, nested as they were opened; the no-op tracer's
    spans leave nothing there."""
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("serve.tick"):
            with tr.span("serve.admit"):
                with tr.span("serve.prefill"):
                    time.sleep(0.002)
            with tr.span("serve.decode"):
                time.sleep(0.002)
        with NOP_TRACER.span("train.step"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    ev = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                ev.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    names = ("serve.tick", "serve.admit", "serve.prefill", "serve.decode")
    assert all(len(ev.get(n, ())) == 1 for n in names), sorted(ev)
    assert "train.step" not in ev
    (tick,), (admit,), (pre,), (dec,) = (ev[n] for n in names)

    def inside(a, b):
        return b[0] <= a[0] and a[1] <= b[1]

    assert inside(pre, admit) and inside(admit, tick) and inside(dec, tick)
    assert admit[1] <= dec[0]           # siblings in the order opened
    # the span list itself is what it was without a profiler
    assert [e["name"] for e in tr.events] == [
        "serve.prefill", "serve.admit", "serve.decode", "serve.tick"]


def test_tag_adds_args_to_innermost_open_span():
    tr = Tracer()
    with tr.span("serve.tick", queued=2):
        with tr.span("serve.admit"):
            tr.tag(shared=16)
        tr.tag(admitted=1)
    tr.tag(stray=1)                     # no span open: dropped
    by = {e["name"]: e["args"] for e in tr.events}
    assert by == {"serve.admit": {"shared": 16},
                  "serve.tick": {"queued": 2, "admitted": 1}}
    NOP_TRACER.tag(admitted=1)
    assert NOP_TRACER.events == []


def test_max_events_bounds_memory():
    tr = Tracer(max_events=3)
    for _ in range(10):
        with tr.span("serve.tick"):
            pass
    assert len(tr.events) == 3


# ------------------------------------------------------------- attribution
def test_attribution_fractions_sum_to_one():
    tr = Tracer()
    with tr.span("serve.tick"):
        with tr.span("serve.prefill"):
            time.sleep(0.005)
        with tr.span("serve.decode"):
            time.sleep(0.005)
    attr = time_attribution(tr, tr.now_s)
    assert attr["fractions_sum"] == pytest.approx(1.0, abs=1e-6)
    assert set(FRACTION_KEYS) <= set(attr["fractions"])
    assert attr["seconds"]["decode"] > 0 and attr["seconds"]["prefill"] > 0
    # idle time past the last span lands in "other", and the sum still holds
    attr2 = time_attribution(tr, tr.now_s + 0.05)
    assert attr2["fractions_sum"] == pytest.approx(1.0, abs=1e-6)
    assert attr2["seconds"]["other"] > attr["seconds"]["other"]


# ------------------------------------------------------------------ export
def test_chrome_trace_roundtrips(tmp_path):
    tr = Tracer()
    with tr.span("serve.tick"):
        with tr.span("serve.decode", batch=2):
            time.sleep(0.002)
    tr.instant("drift", z=3.1)
    path = tmp_path / "trace.json"
    n = write_chrome_trace(str(path), tr, process_name="test")
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == n
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        for k in ("ph", "ts", "dur", "name", "pid", "tid"):
            assert k in e, f"complete event missing {k}"
        assert e["ts"] >= 0 and e["dur"] >= 0      # microseconds
    assert [e["name"] for e in events if e["ph"] == "i"] == ["drift"]
    assert any(e["ph"] == "M" for e in events)     # process metadata


def test_audit_jsonl_roundtrips(tmp_path):
    audit = TuningAudit()
    audit.decision(window=0, phase="init", candidate={"a": 1},
                   incumbent={"a": 0}, switched=True, reason="init_sample")
    audit.reconfig(kinds=("II",), predicted_by_kind={"II": 2.0},
                   actual_s=1.0, actual_by_kind={"II": 1.0},
                   method="swap", setting={"a": 1})
    path = tmp_path / "audit.jsonl"
    n = write_audit_jsonl(str(path), audit)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == n == 2
    assert [r["type"] for r in lines] == ["decision", "reconfig"]
    assert lines[1]["predicted_s"] == 2.0


# ----------------------------------------------------- audit / calibration
def test_calibration_residuals():
    audit = TuningAudit()
    audit.reconfig(kinds=("II",), predicted_by_kind={"II": 0.5},
                   actual_s=1.0, actual_by_kind={"II": 1.0},
                   method="swap", setting={})
    cal = audit.calibration()
    assert cal["II"]["ratio_actual_over_predicted"] == pytest.approx(2.0)
    assert cal["II"]["mean_abs_log2_residual"] == pytest.approx(1.0)
    # a seed-based prediction is excluded from the warm ratio
    audit2 = TuningAudit()
    audit2.reconfig(kinds=("II",), predicted_by_kind={"II": 5.0},
                    actual_s=1.0, actual_by_kind={"II": 1.0},
                    method="swap", setting={}, seeded_kinds=("II",))
    audit2.reconfig(kinds=("II",), predicted_by_kind={"II": 1.0},
                    actual_s=1.1, actual_by_kind={"II": 1.1},
                    method="swap", setting={})
    cal2 = audit2.calibration()["II"]
    assert cal2["n"] == 2 and cal2["n_warm"] == 1
    assert cal2["ratio_warm"] == pytest.approx(1.1)
    assert cal2["ratio_actual_over_predicted"] == pytest.approx(2.1 / 6.0)


def test_cost_model_apportions_proportionally():
    """Mixed-kind observations split by the kinds' learned scale, not
    evenly — a warm II swap must not absorb half of a relayout's cost."""
    m = ReconfigCostModel()
    m.observe(("II",), 0.01)        # warm swaps: cheap
    m.observe(("I-b",), 0.40)       # relayouts: expensive
    shares = m.observe(("I-b", "II"), 0.50)
    assert shares["I-b"] > 10 * shares["II"]
    assert sum(shares.values()) == pytest.approx(0.50)
    est = m.estimate_by_kind(("I-b", "II"))
    assert est["I-b"] > est["II"]
    assert m.estimate(("I-b", "II")) == pytest.approx(sum(est.values()))


def test_cost_model_measured_breakdown_beats_backwards_prior():
    """All-mixed plans with a measured I-b portion converge to the truth
    even when the seeds have the kind ratio backwards (the serving case:
    seeds say II >> I-b, a warm engine is the opposite)."""
    m = ReconfigCostModel()          # seeds: II=2.0, I-b=0.02
    for _ in range(6):               # every plan mixed, relayout-dominated
        shares = m.observe(("I-b", "II"), 1.0, measured={"I-b": 0.95})
        assert shares["I-b"] == pytest.approx(0.95)
        assert shares["II"] == pytest.approx(0.05)
    est = m.estimate_by_kind(("I-b", "II"))
    assert est["I-b"] > 10 * est["II"]          # prior ratio corrected
    # without the measurement, the same stream reinforces the prior
    m2 = ReconfigCostModel()
    for _ in range(6):
        m2.observe(("I-b", "II"), 1.0)
    est2 = m2.estimate_by_kind(("I-b", "II"))
    assert est2["II"] > est2["I-b"]             # stuck backwards


def test_cost_model_scales_with_migration_volume():
    """Relayout cost is proportional to the state migrated: a model that
    only saw cheap light-load relayouts must still price a load-spike
    relayout at the spike's migration volume (the >2x miscalibration the
    bench panel exposed), while kinds/calls without scales keep the
    scalar decayed-average behaviour."""
    m = ReconfigCostModel()
    m.observe(("I-b",), 0.2, scales={"I-b": 4})      # light load: 4 blocks
    m.observe(("I-b",), 0.3, scales={"I-b": 6})
    light = m.estimate(("I-b",), scales={"I-b": 5})
    spike = m.estimate(("I-b",), scales={"I-b": 50})
    assert spike == pytest.approx(10 * light)
    assert spike == pytest.approx(50 * 0.05, rel=0.2)  # ~0.05 s/block
    # no scale provided -> scalar average (old behaviour, other callers)
    assert m.estimate(("I-b",)) == pytest.approx(m.avgs["I-b"])
    # kinds without any per-unit history ignore the scales argument
    assert m.estimate(("II",), scales={"II": 50}) == \
        pytest.approx(m.estimate(("II",)))


# ----------------------------------------------- no-op overhead on the loop
def test_noop_overhead_under_5pct(model):
    """The disabled tracer's per-span cost, times the number of spans a
    real ~200-step serve_loop opens, stays under 5% of that loop's
    wall-clock.  (Counting via an enabled run, then measuring the pure
    no-op cost, is deterministic where an A/B wall comparison is noise.)"""
    cfg, params = model
    setting = dict(DEFAULT_SERVING_SETTING, max_batch=2)
    engine = ServingEngine(params, cfg, setting, max_seq=48)
    serve_loop(engine, _requests(cfg, 2, 4))     # absorb compiles

    tr = Tracer()
    engine.set_tracer(tr)
    stats = serve_loop(engine, _requests(cfg, 12, 38, seed=1))
    engine.set_tracer(NOP_TRACER)
    n_ticks = sum(1 for e in tr.events if e["name"] == "serve.tick")
    assert n_ticks >= 200, f"microbench only ran {n_ticks} ticks"
    n_spans = len(tr.events)

    nop = Tracer(enabled=False)
    reps = 50_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with nop.span("serve.tick"):
            pass
    per_span = (time.perf_counter() - t0) / reps
    overhead = per_span * n_spans
    assert overhead < 0.05 * stats["wall_s"], \
        (f"no-op tracing would cost {overhead * 1e3:.2f}ms over "
         f"{n_spans} spans vs wall {stats['wall_s'] * 1e3:.0f}ms")
