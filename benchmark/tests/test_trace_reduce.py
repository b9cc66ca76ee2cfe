"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps, on a trace recorded on the CPU (three queries,
each two runs of one jitted sort with a 20 ms host pause between them,
and 30 ms of client time between queries)."""

import os

import pytest

import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def prof():
    return TR.load(DATA)


def test_load_keeps_ops_and_bench_annotations(prof):
    assert prof.on_cpu
    (ops,) = prof.device_ops.values()
    assert sum(1 for name, _, _ in ops if name == "sort.0") == 6
    names = [h[0] for h in prof.host]
    assert names.count("bench.query.sort") == 3
    assert names.count("bench.window") == 1
    assert all(n.startswith("bench.") for n in names)


def test_busy_union_and_idle_share(prof):
    win = TR.find(prof.host, "bench.window")
    red = TR.reduce(prof, win[1], win[2])
    (ops,) = prof.device_ops.values()
    # An independent union: mark every nanosecond tick of 1 us.
    covered = set()
    for _, s, e in TR.clip(ops, win[1], win[2]):
        covered.update(range(int(s // 1000), int(-(-e // 1000))))
    assert red["busy_s"] == pytest.approx(len(covered) * 1e-6, rel=0.01)
    assert red["window_s"] == pytest.approx((win[2] - win[1]) / 1e9)
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    assert 0 < red["idle_share"] < 1


def test_top_ops(prof):
    win = TR.find(prof.host, "bench.window")
    red = TR.reduce(prof, win[1], win[2])
    assert red["device_ops"][0][0] == "sort.0"
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10


def test_gap_attribution(prof):
    win = TR.find(prof.host, "bench.window")
    red = TR.reduce(prof, win[1], win[2])
    gaps = dict(red["idle_gaps"])
    # The 3 x 30 ms between queries are the client's; the 3 x 20 ms
    # pauses inside a query are the query's. Together they are the idle
    # time of the window.
    assert gaps["client"] == pytest.approx(0.09, abs=0.01)
    assert gaps["bench.query.sort"] == pytest.approx(0.06, abs=0.01)
    idle = red["window_s"] - red["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_union_gaps_attribute_synthetic():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 35, 38)]
    assert TR.union(ops) == [(0, 20), (30, 40)]
    assert TR.gaps(TR.union(ops), -5, 50) == [(-5, 0), (20, 30), (40, 50)]
    host = [("outer", 18, 60), ("inner", 21, 29)]
    got = TR.attribute([(-5, 0), (20, 30), (40, 50)], host)
    assert got == {"client": 5, "inner": 8, "outer": 12}
    assert TR.innermost(host) == [("outer", 18, 21), ("inner", 21, 29),
                                  ("outer", 29, 60)]


def test_host_spans_are_added_and_window_span_dropped(prof):
    win = TR.find(prof.host, "bench.window")
    extra = [("plan", win[1], win[1] + 1)]
    red = TR.reduce(prof, win[1], win[2], extra)
    assert "bench.window" not in dict(red["idle_gaps"])
