"""Tests of the benchmark's own code, on the CPU at a tiny scale:
``python -m pytest benchmark/tests``. They never report a device metric."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, REPO]

TINY_SCALE = 0.002  # 3,000 orders, about 12,000 lineitem rows

# The entries the index-build cell sf1_build takes in BENCHMARK.json: its
# configuration, its cell and its eight metrics. A metric's "workloads" may
# name only cells that BENCHMARK.json holds, so all of them wait here while
# the program alters float64 values on the TPU (PERF.md, section 7); the
# cell's files are in the benchmark, and on the CPU, where the program keeps
# the values, the tests run it from these.
BUILD_ENTRIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "sf1_build_entries.json")


def merged_benchmark() -> dict:
    """BENCHMARK.json with each sf1_build entry whose name it lacks, so
    that entries moved into it are not added twice."""
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for key, entries in json.load(open(BUILD_ENTRIES)).items():
        have = {e["name"] for e in bench[key]}
        bench[key].extend(e for e in entries if e["name"] not in have)
    return bench


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like root holding the merged BENCHMARK.json and a copy
    of the benchmark directory whose configurations run at TINY_SCALE."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(merged_benchmark()))
    for path in (root / "benchmark" / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        config["scale_factor"] = TINY_SCALE
        path.write_text(json.dumps(config))
    return str(root)


@pytest.fixture(autouse=True)
def fresh_sketch_cache():
    """The program caches sketch tables process-wide by (index name, log
    entry id), whatever the system path: a second run in one process with
    an index of the same name would read the first run's sketches and
    prune nothing. The benchmark runs one cell per process; these tests
    run many, so each starts with the cache empty."""
    from hyperspace_tpu.rules import data_skipping_rule
    data_skipping_rule._SKETCH_CACHE.clear()
    yield
