"""End-to-end arithmetic on a hand-built window: percentiles over all
requests, with requests still waiting at the window's end."""
import numpy as np
import pytest

from bench import harness
from bench.workload import Spec


class _Req:
    def __init__(self, n):
        self.tokens_out = [0] * n


def _rec(due, stamps, max_new, done=None):
    spec = Spec(idx=0, due_s=due, session=None, prefix_len=0,
                prompt=np.zeros(4, np.int32), max_new=max_new)
    r = harness.Rec(spec=spec, req=_Req(len(stamps)), due=due,
                    stamps=list(stamps), done=done)
    return r


def test_ttft_counts_requests_still_waiting():
    recs = [_rec(10.0 + i, [10.5 + i, 10.6 + i], 2, done=10.6 + i)
            for i in range(9)]
    recs.append(_rec(18.0, [], 4))            # due in the window, no token
    recs.append(_rec(5.0, [9.0, 11.0], 2, done=11.0))   # due before it
    win = harness.Window(recs=recs, w0=10.0, w1=20.0, ticks=[])
    e = harness.e2e(win)
    ttft = [0.5] * 9 + [2.0]                  # the waiting one: w1 - due
    assert e["n_ttft"] == 10
    assert e["ttft_p95_ms"] == pytest.approx(np.percentile(ttft, 95) * 1e3)


def test_gaps_and_tokens_inside_the_window():
    recs = [_rec(0.0, [9.0, 11.0, 12.0], 3, done=12.0),
            # in flight at the end: its open gap 21 -> w1 counts
            _rec(0.0, [19.0, 21.0 - 2.0], 10),
            _rec(0.0, [12.0, 22.0], 2, done=22.0)]   # 2nd token after w1
    win = harness.Window(recs=recs, w0=10.0, w1=20.0, ticks=[])
    e = harness.e2e(win)
    # tokens emitted in (w0, w1]: 11, 12, 19, 19, 12
    assert e["tokens"] == 5
    assert e["output_tokens_per_s"] == pytest.approx(0.5)
    # 9 -> 11, 11 -> 12, 19 -> 19, and the open gaps 19 -> 20, 12 -> 20
    gaps = [2.0, 1.0, 0.0, 1.0, 8.0]
    assert e["n_gaps"] == len(gaps)
    assert e["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95) * 1e3)


def test_sample_reaches_its_tokens_and_rows_hold_the_pack():
    from bench import check
    recs = [_rec(0.0, [1.0] * n, n, done=2.0) for n in (30, 5, 20, 12, 8)]
    for i, r in enumerate(recs):
        r.spec.prompt = np.zeros(40 + 10 * i, np.int32)
    pick = check.sample(recs, seed=7, pack=100, want=50)
    assert max(recs, key=check._size) in pick        # the longest is in it
    assert sum(len(r.req.tokens_out) for r in pick) >= 50
    rows = check.rows_of(pick, 100)
    assert sorted(map(id, pick)) == sorted(id(r) for row in rows for r in row)
    assert all(sum(check._size(r) for r in row) <= 100 for row in rows)
    assert len(rows) > 1                    # more than one row was needed
