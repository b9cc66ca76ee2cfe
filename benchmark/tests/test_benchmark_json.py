"""BENCHMARK.json alone, and merged with the sf1_build cell's entries as
the tests run it: every name once, every metric's cells and moved metric
there."""

import json
import os

import pytest

from conftest import BENCH, BUILD_ENTRIES, REPO, merged_benchmark

KINDS = ("end_to_end", "per_layer")


def benchmark_alone() -> dict:
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_entries_hold_only_keys_of_the_benchmark():
    assert set(json.load(open(BUILD_ENTRIES))) <= set(benchmark_alone())


@pytest.mark.parametrize("keys", [KINDS, ("configs",), ("workloads",)])
@pytest.mark.parametrize("load", [benchmark_alone, merged_benchmark])
def test_every_name_is_unique(keys, load):
    bench = load()
    names = [m["name"] for k in keys for m in bench[k]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("load", [benchmark_alone, merged_benchmark])
def test_metrics_name_cells_and_metrics_that_exist(load):
    """A metric's "workloads" names cells of the same file: BENCHMARK.json
    is refused whole where one names a cell it does not hold."""
    bench = load()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m["name"]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        # Each cell of a per-layer metric reports the metric it moves.
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]


@pytest.mark.parametrize("load", [benchmark_alone, merged_benchmark])
def test_every_cell_reports_set_up_another_metric_and_a_layer(load):
    bench = load()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])}
        layers = [m for m in bench["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layers, w["name"]


@pytest.mark.parametrize("load", [benchmark_alone, merged_benchmark])
def test_every_metric_has_a_reader(load):
    bench = load()
    for m in bench["end_to_end"] + bench["per_layer"]:
        files = {m["name"] + ".py", m["name"].split(".", 1)[0] + ".py"}
        assert any(os.path.exists(os.path.join(BENCH, "metrics", f))
                   for f in files), m["name"]
