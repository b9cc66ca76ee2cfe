"""The reader of the program's ``join.probe_table`` spans
(``metrics/probe_tables.py``), on a synthetic window: one count per
traced query, and nothing where every join searches or the program has no
such span."""

import harness
from conftest import REPO


def sent(spans):
    return harness.Sent(query=None, t0=0.0, t1=1.0, spans=spans)


def window(*queries):
    return harness.Window(lake=None, sent=list(queries), t0=0.0, t1=1.0,
                          setup_s=1.0, compiles=0, pool_before={},
                          pool_after={}, device_kind="cpu")


def read(w):
    return harness._module(REPO, "metrics", "probe_tables.q3",
                           "test_metric_").read(w)


def test_probe_tables_counts_direct_probes_per_query():
    q3 = [("query", 0.0, 7.0), ("spmd.prepare", 0.1, 0.2),
          ("join.probe_table", 0.15, 0.16)]
    assert read(window(sent(q3), sent(q3))) == 1.0


def test_probe_tables_reports_nothing_without_the_span():
    searched = sent([("query", 0.0, 7.0), ("spmd.prepare", 0.1, 0.2)])
    assert read(window(searched, searched)) is None
