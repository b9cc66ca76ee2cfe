"""The readers of the program's spans (``metrics/*`` over
``span_time.py``), on a synthetic window: what each reads, that the host
time blocked on the device is taken out of ``dispatch_ms``, and that a
program without the spans reports nothing."""

import pytest

import harness
from conftest import REPO

MS = 1e-3


def sent(spans):
    return harness.Sent(query=None, t0=0.0, t1=1.0, spans=spans)


def window(*queries):
    return harness.Window(lake=None, sent=list(queries), t0=0.0, t1=1.0,
                          setup_s=1.0, compiles=0, pool_before={},
                          pool_after={}, device_kind="cpu")


def read(name, w):
    return harness._module(REPO, "metrics", name, "test_metric_").read(w)


def q6_query(shift=0.0):
    """One Q6-shaped query's spans, times in ms from ``shift``."""
    def at(label, a, b):
        return (label, (shift + a) * MS, (shift + b) * MS)
    return sent([
        at("query", 0, 40),
        at("rewrite.index_rules", 1, 19),
        at("rewrite.index_lookup", 1, 3),
        at("rewrite.source_check", 3, 4),
        at("rewrite.source_check", 4, 12),
        at("skipping.probe", 12, 18),
        at("spmd.gate", 19, 20),
        at("exec.stage:Aggregate", 20, 34),
        at("spmd.gate", 20, 25),
        at("spmd.dispatch", 25, 34),
        at("spmd.prepare", 25, 29),
        at("spmd.launch", 29, 30),
        at("spmd.merge", 30, 34),
        at("device.sync:spmd.merge", 30, 32),
        at("device.sync:spmd.merge", 32, 33),
        at("result.fetch", 35, 39),
        at("device.sync:result", 35, 37),
    ])


def test_readers_on_a_q6_window():
    w = window(q6_query(), q6_query(100.0))
    assert read("source_check_ms.q6", w) == pytest.approx(11.0)
    assert read("sketch_probe_ms.q6", w) == pytest.approx(6.0)
    # gate 1 + 5 ms and dispatch 9 ms, less the 3 ms of device syncs
    # inside the dispatch; the result's sync is outside both.
    assert read("dispatch_ms.q6", w) == pytest.approx(12.0)
    assert read("device_syncs.q6", w) == pytest.approx(3.0)
    assert read("fetch_ms.q6", w) == pytest.approx(4.0)
    assert read("compile_ms.q3", w) == 0.0


def test_dispatch_ms_takes_out_overlapping_syncs_once():
    w = window(sent([
        ("spmd.gate", 0.0, 2 * MS),
        ("spmd.dispatch", 2 * MS, 10 * MS),
        ("device.sync:spmd.overflow", 3 * MS, 5 * MS),
        ("device.sync:spmd.merge", 4 * MS, 6 * MS),   # overlaps the first
        ("device.sync:result", 9 * MS, 12 * MS),      # half outside
        ("result.fetch", 10 * MS, 12 * MS),
    ]))
    assert read("dispatch_ms.q6", w) == pytest.approx(10.0 - 3.0 - 1.0)


def test_means_are_over_every_traced_query():
    one = sent([("result.fetch", 0.0, 4 * MS),
                ("xla.compile:jit(spmd_agg)", 1 * MS, 3 * MS),
                ("xla.compile:jit(add)", 2 * MS, 4 * MS)])
    two = sent([("result.fetch", 0.0, 2 * MS)])
    untraced = sent([])
    w = window(one, two, untraced)
    assert read("fetch_ms.q3", w) == pytest.approx(3.0)
    # Compile spans overlapping in time count once.
    assert read("compile_ms.q3", w) == pytest.approx(1.5)


def test_a_program_without_the_spans_reports_nothing():
    # The spans a program records without this instrumentation.
    older = window(sent([("query", 0.0, 0.04),
                         ("rewrite.index_rules", 0.001, 0.019),
                         ("exec.stage:Aggregate", 0.02, 0.034),
                         ("spmd.dispatch", 0.025, 0.034)]))
    for name in ("source_check_ms.q6", "sketch_probe_ms.q6",
                 "dispatch_ms.q6", "device_syncs.q6", "fetch_ms.q3",
                 "fetch_ms.q6", "compile_ms.q3"):
        assert read(name, older) is None, name
    assert read("fetch_ms.q6", window(sent([]))) is None
