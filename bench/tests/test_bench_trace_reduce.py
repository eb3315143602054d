"""The trace reduction on a hand-built trace."""
import pytest

from bench import trace_reduce as tr

# device ops (ns) as the trace names them: a loop holding a kernel call
# and a fusion, then two more ops; a gap inside the first step
K = "%paged_attention_op.3 = bf16[384,1,128] custom-call(...)"
OPS = [("%while.5 = (s32[], ...) while(...)", 100, 400), (K, 150, 300),
       ("%fusion.1 = bf16[16] fusion(...)", 300, 400),
       ("%fusion.2 = bf16[16] fusion(...)", 500, 600), (K, 900, 1000)]
# host: two steps, and a host op inside the first step's gap
HOST = [("bench.tick", 50, 650), ("PjitFunction(f)", 420, 480),
        ("bench.tick", 800, 1100)]


def test_union_merges_overlaps_and_clips():
    assert tr.union(OPS, 0, 2000) == [[100, 400], [500, 600], [900, 1000]]
    assert tr.union(OPS, 350, 950) == [[350, 400], [500, 600], [900, 950]]
    assert tr.busy_ns(OPS, 0, 2000) == 300 + 100 + 100


def test_idle_share_inside_the_steps():
    ticks = [h for h in HOST if h[0] == "bench.tick"]
    # steps last 600 + 300 ns; busy inside them 300 + 100 + 100
    assert tr.idle_share(OPS, ticks) == pytest.approx(1 - 500 / 900)
    assert tr.idle_share(OPS, []) is None


def test_kernel_time_by_name():
    assert tr.kernel_ns(OPS, r"^%paged_attention_op", 0, 2000) == 250
    assert tr.kernel_ns(OPS, r"^%paged_attention_op", 0, 950) == 150 + 50


def test_leaves_leave_out_the_loop():
    assert [tr.short_name(n) for n, _, _ in tr.leaves(OPS)] == [
        "%paged_attention_op.3", "%fusion.1", "%fusion.2",
        "%paged_attention_op.3"]


def test_top_ops_and_idle_gaps():
    top = tr.top_ops(OPS, 0, 2000)
    assert top[0] == ["%paged_attention_op.3", 250e-9]
    assert "%while.5" not in dict(top)
    gaps = dict(tr.idle_gaps(OPS, HOST, 50, 1100))
    # gaps 50-100 and 1000-1100 lie inside a step, 400-500 inside the host
    # op, and 600-900 has its middle (750) between the steps
    assert gaps["PjitFunction(f)"] == pytest.approx(100e-9)
    assert gaps["bench.tick"] == pytest.approx(150e-9)
    assert gaps["(no host span)"] == pytest.approx(300e-9)
