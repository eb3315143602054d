"""Each traffic file: the same requests under one seed, other token ids
under another, and one schedule replayed for every seed."""
import numpy as np
import pytest

from bench import workload

MIXES = sorted(p.stem for p in workload.TRAFFIC_DIR.glob("*.json"))
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
