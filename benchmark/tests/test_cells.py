"""The harness end to end on the CPU at a tiny scale: each cell is correct
against its plain reference, the control (for a query, the reference
computed one precision lower, put in the program's place; for an index
build, buckets by the key modulo the bucket count) and each fault the
cells can have make ``correct`` false, a cell made of new files runs, and
the command refuses to run without a TPU."""

import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import harness

SEED = 2**31 + 101
SECONDS = 0.3
CELLS = ["sf1_q3_indexed", "sf1_q6_skipping"]
BUILD = "sf1_build"
SUFFIX = {"sf1_q3_indexed": "q3", "sf1_q6_skipping": "q6",
          BUILD: "build"}
DEVICE_METRICS = ("device_ms_per_query.q3", "device_ms_per_query.q6",
                  "q3_roofline", "idle_share.q3", "idle_share.q6",
                  "device_ms_per_build", "build_roofline",
                  "idle_share.build")


def run(root, cell, trace=False, fault=None):
    return harness.run_cell(root, cell, SEED, SECONDS, trace,
                            allow_cpu=True, fault=fault)


@pytest.mark.parametrize("cell", CELLS + [BUILD])
def test_cell_is_correct_and_reports_its_metrics(tiny_root, cell):
    res = run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    want = {m["name"] for m in harness.load_cell(tiny_root, cell).end_to_end}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS + [BUILD])
def test_traced_run_reports_no_device_metric_off_the_chip(tiny_root, cell):
    res = run(tiny_root, cell, trace=True)
    assert res["correct"], res["checks"]
    names = set(res["metrics"])
    assert names and not names & set(DEVICE_METRICS)
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert res["metrics"][f"window_compiles.{SUFFIX[cell]}"]["value"] == 0
    want = {m["name"] for m in harness.load_cell(tiny_root, cell).end_to_end}
    assert set(res["traced_end_to_end"]) == want


def control(q, answer, lake):
    return q.module.reference(lake, q.spec, q.lits, np.float32)


@pytest.mark.parametrize("cell", CELLS + [BUILD])
def test_control_fails(tiny_root, cell):
    if cell == BUILD:
        (_, lifecycle), = harness.plan_ops(harness.load_cell(tiny_root, cell))
        with lifecycle.control():
            res = run(tiny_root, cell)
        assert not res["correct"]
        assert res["checks"]["rows_misplaced"]["value"] > 0
        return
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


def index_files(ctx):
    return ctx.hs.index_manager.get_index("li_idx").content.files


def rewrite(path, table):
    pq.write_table(table, path)


def row_dropped(cycle, step, ctx):
    if step == "create":
        path = index_files(ctx)[0]
        t = pq.read_table(path)
        rewrite(path, t.slice(1))


def bucket_halves(cycle, step, ctx):
    """Every bucket file keeps its first half of rows: half of the batch
    left out."""
    if step == "create":
        for path in index_files(ctx):
            t = pq.read_table(path)
            rewrite(path, t.slice(0, t.num_rows // 2))


def row_moved(cycle, step, ctx):
    """The first row of one bucket's file moves into the next bucket's
    file, where it keeps that file sorted."""
    if step == "create":
        src, dst = index_files(ctx)[:2]
        a, b = pq.read_table(src), pq.read_table(dst)
        rewrite(src, a.slice(1))
        rewrite(dst, pa.concat_tables([b, a.slice(0, 1)]).sort_by(
            "l_orderkey"))


def price_off_by_an_ulp(cycle, step, ctx):
    """One row's l_extendedprice a unit in the last place off, as a
    float64 that is not moved bit for bit reads."""
    if step == "create":
        path = index_files(ctx)[0]
        t = pq.read_table(path)
        price = t.column("l_extendedprice").to_numpy().copy()
        price[0] = np.nextafter(price[0], 0.0)
        rewrite(path, t.set_column(t.schema.get_field_index(
            "l_extendedprice"), "l_extendedprice", pa.array(price)))


def rows_swapped(cycle, step, ctx):
    """The first and last rows of one bucket's file change places."""
    if step == "create":
        path = index_files(ctx)[0]
        t = pq.read_table(path)
        n = t.num_rows
        assert t.column("l_orderkey")[0] != t.column("l_orderkey")[n - 1]
        rewrite(path, t.take([n - 1] + list(range(1, n - 1)) + [0]))


def vacuum_leaves(keep):
    """A vacuum that leaves ``keep`` files of each version directory (all
    of them where None: the step leaves its state unchanged)."""
    def op(self):
        for v in self.data_manager.get_all_version_ids():
            d = self.data_manager.get_path(v)
            names = sorted(os.listdir(d))
            for f in names[len(names) if keep is None else keep:]:
                os.remove(os.path.join(d, f))
    return op


# Each fault, and the one check it has to make fail; the others read 0.
@pytest.mark.parametrize("fault,check", [
    ("row_dropped", "rows_wrong"),
    ("bucket_halves", "rows_wrong"),
    ("price_off_by_an_ulp", "rows_wrong"),
    ("row_moved", "rows_misplaced"),
    ("rows_swapped", "unsorted_buckets"),
    ("file_left_by_vacuum", "leftover_files"),
    ("vacuum_unchanged", "leftover_files"),
])
def test_fault_in_the_index_fails(tiny_root, fault, check, monkeypatch):
    hook = None
    if fault in ("file_left_by_vacuum", "vacuum_unchanged"):
        from hyperspace_tpu.actions.lifecycle import VacuumAction
        monkeypatch.setattr(VacuumAction, "op", vacuum_leaves(
            1 if fault == "file_left_by_vacuum" else None))
    else:
        hook = {"row_dropped": row_dropped, "bucket_halves": bucket_halves,
                "price_off_by_an_ulp": price_off_by_an_ulp,
                "row_moved": row_moved, "rows_swapped": rows_swapped}[fault]
    res = run(tiny_root, BUILD, fault=hook)
    assert not res["correct"], res["checks"]
    values = {k: c["value"] for k, c in res["checks"].items()}
    assert values.pop(check) > 0, res["checks"]
    assert not any(values.values()), res["checks"]


def test_ops_window_ends_on_the_wall_clock(tiny_root, monkeypatch):
    """The harness's own time between steps counts toward an ops window,
    as it does toward a query window: a slow aside after each create ends
    the window in fewer cycles. Every cycle is still digested, and
    build_rows_per_s still divides the created rows by the summed step
    time, the asides left out."""
    seconds = 1.5
    windows = []
    real = harness.run_ops_window

    def spy(*args, **kwargs):
        windows.append(real(*args, **kwargs))
        return windows[-1]

    def slow_aside(cycle, step, ctx):
        if step == "create":
            time.sleep(seconds)

    monkeypatch.setattr(harness, "run_ops_window", spy)
    runs = [harness.run_cell(tiny_root, BUILD, SEED, seconds, False,
                             allow_cpu=True, fault=f)
            for f in (None, slow_aside)]
    (plain, _, _), (slowed, s0, s1) = windows
    assert len(slowed) == 1 < len(plain)
    for res, (cycles, t0, t1) in zip(runs, windows):
        assert res["correct"], res["checks"]
        assert res["attempted"] == len(cycles)
        assert t1 - t0 >= seconds
        assert all("digest" in c.record for c in cycles)
        rows = sum(c.record["source_rows"] for c in cycles)
        assert res["metrics"]["build_rows_per_s"]["value"] == \
            pytest.approx(rows / sum(c.step_s for c in cycles))
    assert sum(c.step_s for c in slowed) < seconds <= s1 - s0


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
    next(m for m in bench["end_to_end"]
         if m["name"] == "qps")["workloads"].append("tiny_plain")
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


@pytest.mark.parametrize("cell,traffic,key,match", [
    ("sf1_q6_skipping", "q6_power", "clients", "clients"),
    (BUILD, "li_idx_lifecycle", "queries", "not both"),
])
def test_traffic_the_harness_does_not_implement_is_refused(
        tiny_root, cell, traffic, key, match):
    """An open-loop or many-client mix, or one that mixes queries and ops,
    needs code the harness lacks: it is refused, not run as one
    closed-loop client."""
    path = os.path.join(tiny_root, "benchmark", "traffic", traffic + ".json")
    mix = json.load(open(path))
    mix[key] = json.load(open(os.path.join(
        tiny_root, "benchmark", "traffic", "q6_power.json")))["queries"] \
        if key == "queries" else 2
    with open(path, "w") as fh:
        json.dump(mix, fh)
    with pytest.raises(ValueError, match=match):
        harness.load_cell(tiny_root, cell)


def test_command_refuses_without_a_tpu(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "sf1_q6_skipping",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
