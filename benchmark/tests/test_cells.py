"""The harness end to end on the CPU at a tiny scale: each cell is correct
against its plain reference, the control (the reference computed one
precision lower, put in the program's place) and each fault the cells can
have make ``correct`` false, a cell made of new files runs, and the
command refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

import harness

SEED = 2**31 + 101
SECONDS = 0.3
CELLS = ["sf1_q3_indexed", "sf1_q6_skipping"]
SUFFIX = {"sf1_q3_indexed": "q3", "sf1_q6_skipping": "q6"}
DEVICE_METRICS = ("device_ms_per_query.q3", "device_ms_per_query.q6",
                  "q3_roofline", "idle_share.q3", "idle_share.q6")


def run(root, cell, trace=False, fault=None):
    return harness.run_cell(root, cell, SEED, SECONDS, trace,
                            allow_cpu=True, fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_reports_its_metrics(tiny_root, cell):
    res = run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    want = {m["name"] for m in harness.load_cell(tiny_root, cell).end_to_end}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_no_device_metric_off_the_chip(tiny_root, cell):
    res = run(tiny_root, cell, trace=True)
    assert res["correct"], res["checks"]
    names = set(res["metrics"])
    assert names and not names & set(DEVICE_METRICS)
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert res["metrics"][f"window_compiles.{SUFFIX[cell]}"]["value"] == 0
    assert set(res["traced_end_to_end"]) == {"qps", "setup_s"}


def control(q, answer, lake):
    return q.module.reference(lake, q.spec, q.lits, np.float32)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(tiny_root, cell):
    res = run(tiny_root, cell, fault=control)
    assert not res["correct"]
    c = res["checks"]
    assert c["rel_err"]["value"] > c["rel_err"]["limit"] \
        or c["rows_wrong"]["value"] > 0


def altered(q, answer, lake):
    """One value of the answer changed where it is produced."""
    col = answer.column(0).to_pylist()
    col[0] = col[0] + 1
    return answer.set_column(0, answer.column_names[0],
                             pa.array(col, answer.schema.field(0).type))


def half_dropped(q, answer, lake):
    return answer.slice(0, answer.num_rows // 2)


class Stale:
    """Every query gets the first answer the program gave: state that is
    never updated."""

    def __init__(self):
        self.first = None

    def __call__(self, q, answer, lake):
        if self.first is None:
            self.first = answer
        return self.first


# A stale answer is a fault only where the answers differ: the Q3 cell
# sends one literal set, so its first answer is right for every query.
@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in ("altered", "half_dropped", "stale")
    if (c, f) != ("sf1_q3_indexed", "stale")])
def test_fault_in_the_answer_fails(tiny_root, cell, fault):
    hook = {"altered": altered, "half_dropped": half_dropped,
            "stale": Stale()}[fault]
    res = run(tiny_root, cell, fault=hook)
    assert not res["correct"], res["checks"]
    assert res["checks"]["rows_wrong"]["value"] > 0 or \
        res["checks"]["rel_err"]["value"] > res["checks"]["rel_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_rewrite_left_out_fails(tiny_root, cell, monkeypatch):
    """The answers stay right, but the queries no longer read the
    indexes the cell states."""
    import importlib
    AH = importlib.import_module("hyperspace_tpu.rules.apply_hyperspace")
    monkeypatch.setattr(AH, "apply_hyperspace",
                        lambda session, plan, ctx=None: plan)
    res = run(tiny_root, cell)
    assert not res["correct"]
    assert res["checks"]["index_misses"]["value"] == res["attempted"]


def test_new_cell_from_new_files_only(tiny_root):
    """A configuration, a traffic mix, an end-to-end metric and a per-layer
    metric added as new files, plus entries in BENCHMARK.json; no existing
    file changes."""
    bench_dir = os.path.join(tiny_root, "benchmark")
    before = {}
    for r, _, files in os.walk(bench_dir):
        for f in files:
            p = os.path.join(r, f)
            before[p] = open(p, "rb").read()

    def write(rel, text):
        with open(os.path.join(bench_dir, rel), "w") as fh:
            fh.write(text)

    config = json.load(open(os.path.join(
        bench_dir, "configs", "tpch_sf1_shipdate_skipping.json")))
    config["name"] = "tpch_tiny_plain"
    config["tables"] = {"lineitem": {"files": 2}}
    config["indexes"] = []
    write("configs/tpch_tiny_plain.json", json.dumps(config))
    write("traffic/q6_unindexed.json", json.dumps({
        "source": "test",
        "queries": [{"kind": "q6", "indexes": [], "literal_sets": [
            {"date": "1994-01-01", "discount": 0.06, "quantity": 24}]}],
        "limits": {"rows_wrong": 0, "rel_err": 1e-10, "index_misses": 0}}))
    write("metrics/answer_rows.py",
          "def read(w):\n"
          "    rows = [s.answer.num_rows for s in w.sent\n"
          "            if s.answer is not None]\n"
          "    return sum(rows) / len(rows) if rows else None\n")
    write("metrics/window_queries.py",
          "def read(w):\n"
          "    return float(len(w.sent))\n")

    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({
        "name": "tpch_tiny_plain", "source": "test",
        "file": "benchmark/configs/tpch_tiny_plain.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "tiny_plain", "config": "tpch_tiny_plain",
        "traffic": "q6_unindexed", "chips": 1, "why": "test"})
    bench["end_to_end"].append({
        "name": "window_queries", "unit": "queries", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": ["tiny_plain"]})
    bench["per_layer"].append({
        "name": "answer_rows.plain", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "session and rules",
        "moves": "qps", "workloads": ["tiny_plain"]})
    with open(path, "w") as fh:
        json.dump(bench, fh)

    res = run(tiny_root, "tiny_plain")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"qps", "setup_s", "window_queries"}
    assert res["metrics"]["window_queries"]["value"] == res["attempted"]
    traced = run(tiny_root, "tiny_plain", trace=True)
    assert traced["metrics"]["answer_rows.plain"]["value"] == 1
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_traffic_the_harness_does_not_implement_is_refused(tiny_root):
    """An open-loop or many-client mix needs code the harness lacks: it is
    refused, not run as one closed-loop client."""
    path = os.path.join(tiny_root, "benchmark", "traffic", "q6_power.json")
    traffic = json.load(open(path))
    traffic["clients"] = 2
    with open(path, "w") as fh:
        json.dump(traffic, fh)
    with pytest.raises(ValueError, match="clients"):
        harness.load_cell(tiny_root, "sf1_q6_skipping")


def test_command_refuses_without_a_tpu(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "sf1_q6_skipping",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
