"""Each traffic file: the same requests under one seed, other token ids
under another, and one schedule replayed for every seed; a closed loop's
later rounds, and an open loop's requests in the traced seconds."""
import hashlib
import itertools
import json

import numpy as np
import pytest

from bench import harness, workload

MIXES = sorted(p.stem for p in workload.TRAFFIC_DIR.glob("*.json"))
OPEN = [m for m in MIXES if workload.load_traffic(m)["loop"] == "open"]
BIG = 2 ** 31 + 12345          # seeds beyond 32 signed bits


def _key(specs):
    return [(s.due_s, s.session, s.max_new, s.prompt.tobytes())
            for s in specs]


@pytest.mark.parametrize("mix", MIXES)
def test_seed_decides_the_requests(mix):
    t = workload.load_traffic(mix)
    a = workload.generate(t, BIG, 1000)
    b = workload.generate(t, BIG, 1000)
    c = workload.generate(t, BIG + 1, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_replays_the_same_schedule(mix):
    t = workload.load_traffic(mix)
    a = workload.generate(t, 1, 1000)
    c = workload.generate(t, 2, 1000)
    shape = lambda ss: [(s.due_s, len(s.prompt), s.prefix_len, s.session,
                         s.max_new) for s in ss]
    assert shape(a) == shape(c)
    for s in a:
        assert s.prompt.dtype == np.int32 and 0 <= s.prompt.min()
        assert s.prompt.max() < 1000 and s.max_new >= 1
    if t["loop"] == "open":
        due = [s.due_s for s in a]
        assert due == sorted(due) and due[0] == 0.0
        # the gaps' mean is 1 / rate (exponential quantile points)
        assert due[-1] / (len(due) - 1) == pytest.approx(
            1 / t["rate_per_s"], rel=0.1)
    assert len(a) == t["requests"]


def test_sessions_share_their_prefix():
    t = workload.load_traffic("code_shared")
    specs = workload.generate(t, 5, 1000)
    by = {}
    for s in specs:
        head = s.prompt[:s.prefix_len].tobytes()
        assert by.setdefault(s.session, head) == head
    counts = np.bincount([s.session for s in specs])
    assert counts.max() > counts.min()            # Zipf: uneven sessions


def test_lognormal_levels_are_clipped_quantiles():
    d = {"median": 100, "sigma": 1.0, "min": 20, "max": 300}
    pts = workload.lognormal_levels(d, 5)
    assert pts == sorted(pts) and pts[2] == 100
    assert min(pts) >= 20 and max(pts) <= 300


# round 0 of each mix, seed 1, vocab 1000, pinned: sha256 over each spec
# in order, first 16 hex digits; a change here changes every cell's work
ROUND0 = {"code_batch": (96, "f0386b54c196d29e"),
          "code_shared": (48, "c10bed59aad2c854"),
          "chat": (24, "b2404173ca17766f")}


@pytest.mark.parametrize("mix", sorted(ROUND0))
def test_round0_is_unchanged(mix):
    specs = workload.generate(workload.load_traffic(mix), 1, 1000)
    h = hashlib.sha256()
    for s in specs:
        h.update(repr((s.idx, s.due_s, s.session, s.prefix_len,
                       s.max_new)).encode())
        h.update(s.prompt.tobytes())
    assert (len(specs), h.hexdigest()[:16]) == ROUND0[mix]


def test_closed_loop_rounds_repeat_the_shape_with_fresh_tokens():
    t = workload.load_traffic("code_batch")
    rounds = list(itertools.islice(workload.rounds(t, BIG, 1000), 4))
    shape = lambda ss: [(len(s.prompt), s.prefix_len, s.session, s.max_new,
                         s.due_s) for s in ss]
    assert _key(rounds[0]) == _key(workload.generate(t, BIG, 1000))
    prompts = {s.prompt.tobytes() for s in rounds[0]}
    for r in rounds[1:]:
        assert shape(r) == shape(rounds[0])
        assert not prompts & {s.prompt.tobytes() for s in r}
        prompts |= {s.prompt.tobytes() for s in r}
    idx = [s.idx for r in rounds for s in r]
    assert len(set(idx)) == len(idx)


@pytest.mark.parametrize("mix", OPEN)
def test_open_loop_has_a_request_in_the_traced_seconds(mix):
    # a faster program still ticks in the traced seconds: the schedule,
    # not the program, brings a request there
    t = workload.load_traffic(mix)
    run_s = json.loads((harness.ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    hi = t["lead_in_s"] + run_s
    assert len(list(workload.rounds(t, 1, 1000))) == 1
    assert any(hi - harness.TRACE_S <= s.due_s < hi
               for s in workload.generate(t, 1, 1000))
