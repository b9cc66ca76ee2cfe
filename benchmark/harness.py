"""Run one benchmark cell once: the lake from the seed, the cell's indexes,
a warm-up of everything the window will send, the measured window, the
check against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix, query or op
kind, or metric is found by name under the benchmark's directory:

- ``configs/<config>.json``: the deployment (lake layout, indexes, session
  conf); ``BENCHMARK.json`` names the file;
- ``traffic/<traffic>.json``: the mix's ``source``, the limits of the
  check, and either ``queries`` (each with its literal sets and the
  indexes it must be rewritten onto) or ``ops`` (operations on the lake's
  indexes, such as an index's create, delete and vacuum). One client sends
  them in a closed loop, as TPC-H's power test does; a traffic file with
  any other key, or with both, is refused;
- ``queries/<kind>.py``: how to build a query of that kind through the
  DataFrame API, and its plain reference on the host;
- ``ops/<kind>.py``: how to run one cycle of an op of that kind
  (``run``), its steps timed by the harness and its own reading of the
  result between them, and the check of the window's cycles against its
  plain reference (``check``). ``ops/lifecycle.py`` creates, deletes and
  vacuums a covering index, and holds every cycle's files to the bucket
  layout ``bucket_reference.py`` computes from the lake;
- ``metrics/<name>.py`` (or ``metrics/<name up to its first dot>.py``): a
  reader that takes one metric, end-to-end or per-layer, from the
  window's record.

Set-up builds the configuration's indexes that no op names. Both kinds
of window run on the wall clock: a query cell's ends at the first
completion after ``--seconds``; an ops cell's at the first cycle that
completes after ``--seconds``, the harness's own reads between steps
included, or at the first cycle that fails.

A new cell therefore needs new files and entries in ``BENCHMARK.json``,
and no edit of a file that is already there.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.basename(HERE)

WINDOW_ANNOTATION = "bench.window"
TRAFFIC_KEYS = {"source", "queries", "ops", "limits"}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str


@dataclass
class Query:
    kind: str
    entry: int          # index of the query entry in the traffic file
    lits_index: int
    spec: dict
    lits: dict
    expect: frozenset
    module: object
    df: object = None

    @property
    def label(self) -> str:
        return f"{self.kind}#{self.entry}.{self.lits_index}"


@dataclass
class Sent:
    """One query of the window."""
    query: Query
    t0: float
    t1: float
    answer: object = None
    error: Optional[str] = None
    used: frozenset = frozenset()
    skipping: List[tuple] = field(default_factory=list)  # (kept, total)
    spans: List[tuple] = field(default_factory=list)     # (name, t0, t1)


@dataclass
class Cycle:
    """One cycle of an op in the window: its steps, each timed on the host
    clock, the harness's own work between them, and what the op's module
    keeps for its check."""
    op: dict
    module: object
    steps: List[tuple] = field(default_factory=list)    # (step, t0, t1)
    asides: List[tuple] = field(default_factory=list)   # (label, t0, t1)
    record: dict = field(default_factory=dict)
    error: Optional[str] = None

    @property
    def step_s(self) -> float:
        return sum(b - a for _, a, b in self.steps)


@dataclass
class Window:
    """What the metric readers read."""
    lake: object
    sent: List[Sent]
    t0: float           # the window's start and end, on perf_counter
    t1: float
    setup_s: float      # process start to the window's start
    compiles: int
    pool_before: dict
    pool_after: dict
    device_kind: str
    trace: Optional[dict] = None
    skipping_indexes: Dict[str, str] = field(default_factory=dict)
    cycles: List[Cycle] = field(default_factory=list)


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == work["config"])
    config = _load_json(os.path.join(root, conf_entry["file"]))
    traffic = _load_json(os.path.join(root, BENCH_DIR, "traffic",
                                      work["traffic"] + ".json"))
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic {work['traffic']!r} has keys the harness "
                         f"does not implement: {sorted(unknown)}")
    if ("queries" in traffic) == ("ops" in traffic):
        raise ValueError(f"traffic {work['traffic']!r} needs one of "
                         f"'queries' and 'ops', not both or neither")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=work["chips"], config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def _module(root: str, sub: str, name: str, prefix: str):
    path = os.path.join(root, BENCH_DIR, sub, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(root, BENCH_DIR, sub,
                            name.split(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _process_start_wall() -> float:
    """Wall-clock time at which this process started."""
    with open("/proc/self/stat") as fh:
        fields = fh.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as fh:
        uptime = float(fh.read().split()[0])
    return time.time() - uptime + int(fields[19]) / os.sysconf("SC_CLK_TCK")


def _session(cell: Cell, system_path: str, trace: bool):
    import hyperspace_tpu as hst

    conf = dict(cell.config.get("session_conf", {}))
    conf["hyperspace.eventLoggerClass"] = "event_sink.EventSink"
    conf["hyperspace.tpu.telemetry.trace.enabled"] = \
        "true" if trace else "false"
    if trace:
        conf["hyperspace.tpu.telemetry.trace.sampleRate"] = "1.0"
    return hst.Session(conf=conf, system_path=system_path)


def build_indexes(session, lake, indexes: List[dict]) -> None:
    from hyperspace_tpu.api import (BloomFilterSketch,
                                    DataSkippingIndexConfig, Hyperspace,
                                    IndexConfig, MinMaxSketch)

    hs = Hyperspace(session)
    for ix in indexes:
        df = session.read.parquet(lake.dirs[ix["table"]])
        if ix["kind"] == "covering":
            rows = lake.rows[ix["table"]]
            session.conf.set("hyperspace.index.numBuckets",
                             str(ix["num_buckets"]))
            session.conf.set("hyperspace.index.rowGroupSize", str(max(
                4096, rows // ix["num_buckets"]
                // ix["row_groups_per_bucket"])))
            cfg = IndexConfig(ix["name"], ix["indexed"], ix["included"])
        else:
            sketches = []
            for s in ix["sketches"]:
                if s["type"] == "MinMax":
                    sketches.append(MinMaxSketch(s["column"]))
                else:
                    sketches.append(BloomFilterSketch(
                        s["column"], fpp=s["fpp"],
                        expected_items=s["expected_items"]))
            cfg = DataSkippingIndexConfig(ix["name"], sketches)
        hs.create_index(df, cfg)


def plan_queries(cell: Cell, session, lake) -> List[Query]:
    out = []
    for entry, q in enumerate(cell.traffic["queries"]):
        mod = _module(cell.root, "queries", q["kind"], "bench_query_")
        for i, lits in enumerate(q["literal_sets"]):
            query = Query(kind=q["kind"], entry=entry, lits_index=i,
                          spec=q, lits=lits, expect=frozenset(q["indexes"]),
                          module=mod)
            query.df = mod.build(session, lake, q, lits)
            out.append(query)
    return out


def stream(queries: List[Query], seed: int):
    """The closed loop's sequence: cycles of every literal set, the kinds
    taken round-robin, each cycle in an order drawn from the seed. Every
    seed sends the same work per cycle."""
    rng = np.random.default_rng(seed)
    by_entry: Dict[int, List[Query]] = {}
    for q in queries:
        by_entry.setdefault(q.entry, []).append(q)
    entries = sorted(by_entry)
    width = max(len(v) for v in by_entry.values())
    while True:
        perms = {e: rng.permutation(len(by_entry[e])) for e in entries}
        kinds = rng.permutation(entries)
        for r in range(width):
            for e in kinds:
                if r < len(by_entry[e]):
                    yield by_entry[e][perms[e][r]]


def _usage(events) -> tuple:
    """Index names used, and (kept, total) files of each data-skipping
    rewrite, from one query's index-usage events."""
    used, skipping = set(), []
    for ev in events:
        if type(ev).__name__ != "HyperspaceIndexUsageEvent":
            continue
        used.update(ev.index_names)
        for tok in ev.plan_string.split("[")[1:]:
            if "files after skipping" in tok:
                kept, total = tok.split()[0].split("/")
                skipping.append((int(kept), int(total)))
    return frozenset(used), skipping


def _span_label(span) -> str:
    """A program span's name, with the plan node it ran where it has one
    (``exec.stage:Filter``)."""
    node = span.attrs.get("node")
    return f"{span.name}:{node}" if node else span.name


def _pool_stats() -> dict:
    from hyperspace_tpu.execution.buffer_pool import get_pool
    return dict(get_pool().stats())


def run_window(queries, seed: int, seconds: float, hs, trace: bool,
               lake=None, fault=None) -> tuple:
    """Send queries in a closed loop until ``seconds`` have passed; the
    window ends at the first completion after that. ``fault``, for the
    tests only, replaces each answer by ``fault(query, answer, lake)``."""
    from event_sink import EventSink

    annotate = None
    if trace:
        from jax.profiler import TraceAnnotation as annotate
    sent: List[Sent] = []
    seq = stream(queries, seed)
    t0 = time.perf_counter()
    while True:
        q = next(seq)
        n_ev = len(EventSink.events)
        ts = time.perf_counter()
        answer = error = None
        try:
            if annotate is not None:
                with annotate("bench.query." + q.kind):
                    answer = q.df.to_arrow()
            else:
                answer = q.df.to_arrow()
            if fault is not None:
                answer = fault(q, answer, lake)
        except Exception as e:  # a failed query counts, the loop goes on
            error = f"{type(e).__name__}: {e}"
        te = time.perf_counter()
        used, skipping = _usage(EventSink.events[n_ev:])
        s = Sent(query=q, t0=ts, t1=te, answer=answer, error=error,
                 used=used, skipping=skipping)
        if trace:
            tr = hs.last_trace()
            if tr is not None:
                s.spans = [(_span_label(sp), sp.start_perf, sp.end_perf)
                           for sp in tr.spans if sp.end_perf is not None
                           and sp.start_perf >= ts]
        sent.append(s)
        if te - t0 >= seconds:
            return sent, t0, te


@dataclass
class OpContext:
    """What an op's module drives and reads: the program's session and
    API, the lake, the configuration, and the harness's timer."""
    session: object
    hs: object
    lake: object
    config: dict
    annotate: object = None   # jax.profiler.TraceAnnotation in a traced run
    fault: object = None      # for the tests: fault(cycle, step, ctx)
    state: dict = field(default_factory=dict)  # the modules', per window

    def build(self, indexes: List[dict]) -> None:
        build_indexes(self.session, self.lake, indexes)

    def step(self, cycle: Cycle, name: str, fn) -> None:
        """Run one step of ``cycle`` and time it."""
        t0 = time.perf_counter()
        if self.annotate is not None:
            with self.annotate("bench.op." + name):
                fn()
        else:
            fn()
        cycle.steps.append((name, t0, time.perf_counter()))
        if self.fault is not None:
            self.fault(cycle, name, self)

    def aside(self, cycle: Cycle, label: str, fn) -> None:
        """Run the harness's own work between steps, outside their time;
        the traced run's breakdown names its idle time ``label``."""
        t0 = time.perf_counter()
        fn()
        cycle.asides.append((label, t0, time.perf_counter()))


def plan_ops(cell: Cell) -> List[tuple]:
    """(op, its module) for each op of the traffic."""
    return [(op, _module(cell.root, "ops", op["kind"], "bench_op_"))
            for op in cell.traffic["ops"]]


def run_cycle(ctx: OpContext, op: dict, module) -> Cycle:
    c = Cycle(op=op, module=module)
    try:
        module.run(ctx, c)
    except Exception as e:  # a failed cycle counts
        c.error = f"{type(e).__name__}: {e}"
    return c


def run_ops_window(ops: List[tuple], seed: int, seconds: float,
                   ctx: OpContext) -> tuple:
    """Run cycles of the ops in a closed loop, each round of them in an
    order drawn from the seed, until ``seconds`` have passed on the wall
    clock, the cycles' asides (the digests) included, as a query window's
    time includes the harness's own work; the window ends at the first
    cycle that completes after that, or at the first that fails, which
    leaves the index's state unknown. A rate over the cycles divides by
    their summed step time, which leaves the asides out."""
    rng = np.random.default_rng(seed)
    cycles: List[Cycle] = []
    t0 = time.perf_counter()
    while True:
        for i in rng.permutation(len(ops)):
            c = run_cycle(ctx, *ops[i])
            cycles.append(c)
            t1 = time.perf_counter()
            if c.error is not None or t1 - t0 >= seconds:
                return cycles, t0, t1


def check_ops(cell: Cell, lake, cycles: List[Cycle]) -> dict:
    """The numbers that decide ``correct`` in an ops cell, each with its
    limit: every op module's counts over its cycles of the window."""
    values: Dict[str, float] = {}
    for mod in {id(c.module): c.module for c in cycles}.values():
        mine = [c for c in cycles if c.module is mod]
        for k, v in mod.check(lake, cell.config, mine).items():
            values[k] = values.get(k, 0) + v
    limits = cell.traffic["limits"]
    return {k: {"value": values[k], "limit": limits[k]} for k in values}


def check(cell: Cell, lake, sent: List[Sent]) -> dict:
    """The numbers that decide ``correct``, each with its limit: every
    answer of the window held to the plain reference of its query,
    computed once per literal set."""
    from compare import compare

    limits = cell.traffic["limits"]
    rows_wrong, rel_err, misses = 0, 0.0, 0
    refs: Dict[str, object] = {}
    last: Dict[str, tuple] = {}
    for s in sent:
        q = s.query
        if s.used != q.expect:
            misses += 1
        if s.answer is None:
            continue
        if q.label not in refs:
            refs[q.label] = q.module.reference(lake, q.spec, q.lits)
        prev = last.get(q.label)
        if prev is not None and prev[0].equals(s.answer):
            got = prev[1]
        else:
            got = compare(s.answer, refs[q.label], q.module.ORDERED)
            last[q.label] = (s.answer, got)
        rows_wrong += got[0]
        rel_err = max(rel_err, got[1])
    values = {"rows_wrong": rows_wrong, "rel_err": rel_err,
              "index_misses": misses}
    return {k: {"value": values[k], "limit": limits[k]} for k in values}


def _trace_reduction(trace_dir: str, window_perf0: float,
                     host_spans: List[tuple]) -> Optional[dict]:
    import trace_reduce as TR

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    prof = TR.load(paths[0])
    win = TR.find(prof.host, WINDOW_ANNOTATION)
    if win is None:
        return None
    # The spans are on the host's perf_counter clock; the window
    # annotation was opened at window_perf0 on that clock.
    offset = win[1] - window_perf0 * 1e9
    spans = [(name, a * 1e9 + offset, b * 1e9 + offset)
             for (name, a, b) in host_spans]
    return TR.reduce(prof, win[1], win[2], spans)


def read_metrics(root: str, specs: List[dict], window: Window,
                 on_tpu: bool) -> dict:
    """Each metric of ``specs`` from its reader; one whose reader finds
    nothing to read is left out."""
    out = {}
    for m in specs:
        if m["source"] == "device_trace" and not on_tpu:
            continue  # no device metric from a CPU run
        value = _module(root, "metrics", m["name"],
                        "bench_metric_").read(window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _du(path: str) -> int:
    return sum(os.path.getsize(os.path.join(r, f))
               for r, _, files in os.walk(path) for f in files)


class _Phases:
    """Set-up phase times on standard error, for the record."""

    def __init__(self, t_start: float):
        self.t = t_start

    def done(self, name: str) -> None:
        now = time.time()
        print(f"phase {name} {now - self.t:.3f} s compiles "
              f"{_compile_count()}", file=sys.stderr, flush=True)
        self.t = now


def _compile_count() -> int:
    from hyperspace_tpu.execution import shapes
    return shapes.compile_count()


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False, fault=None) -> dict:
    """Run the cell once and return the result line's object.
    ``allow_cpu`` and ``fault`` are for the tests and the control: in a
    query cell ``fault`` replaces each answer (``run_window``), in an ops
    cell it runs after each step of the window (``OpContext.step``)."""
    t_start = _process_start_wall()
    cell = load_cell(root, name)
    for p in (HERE, os.path.dirname(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    import lake as L

    work = tempfile.mkdtemp(prefix="hsbench-")
    try:
        phases = _Phases(t_start)
        # The runtime takes seconds to find a TPU; the lake is written
        # meanwhile, in numpy and pyarrow, which release the GIL.
        with ThreadPoolExecutor(1) as pool:
            writing = pool.submit(L.write_lake, os.path.join(work, "lake"),
                                  cell.config, seed)
            devices = jax.devices()
            on_tpu = devices[0].platform == "tpu"
            if (not on_tpu and not allow_cpu) or len(devices) < cell.chips:
                raise NoAccelerator(
                    f"cell {name} needs {cell.chips} TPU chip(s); JAX "
                    f"found {len(devices)} {devices[0].platform} device(s)")
            if on_tpu:
                import peaks
                peaks.peak(devices[0].device_kind)  # an unknown chip fails
            from hyperspace_tpu.api import Hyperspace
            from hyperspace_tpu.execution import shapes

            from event_sink import EventSink
            lake = writing.result()
        phases.done("lake")
        session = _session(cell, os.path.join(work, "indexes"), trace)
        hs = Hyperspace(session)
        ops = plan_ops(cell) if "ops" in cell.traffic else []
        named = {op["index"] for op, _ in ops}
        build_indexes(session, lake, [ix for ix in cell.config["indexes"]
                                      if ix["name"] not in named])
        phases.done("indexes")
        queries: List[Query] = []
        if ops:
            ctx = OpContext(session=session, hs=hs, lake=lake,
                            config=cell.config)
            # Two whole cycles of every op, as every query runs twice.
            for _ in range(2):
                for op, mod in ops:
                    c = run_cycle(ctx, op, mod)
                    if c.error is not None:
                        raise RuntimeError(f"warm-up cycle: {c.error}")
            ctx.state.clear()
        else:
            session.enable_hyperspace()
            queries = plan_queries(cell, session, lake)
            # Warm every program, twice over: the first run of a query
            # reads the files and fills the buffer pool, the second takes
            # the pool's path, which runs programs of its own.
            for _ in range(2):
                for q in queries:
                    q.df.to_arrow()
        phases.done("warm-up")

        EventSink.events.clear()
        pool0, c0 = _pool_stats(), shapes.compile_count()
        trace_dir = os.path.join(work, "trace")
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # it would trace every call
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            win = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        wall0 = time.time()
        window_perf0 = time.perf_counter()
        if trace:
            win.__enter__()
        sent: List[Sent] = []
        cycles: List[Cycle] = []
        if ops:
            ctx.annotate = jax.profiler.TraceAnnotation if trace else None
            ctx.fault = fault
            cycles, t0, t1 = run_ops_window(ops, seed, seconds, ctx)
        else:
            sent, t0, t1 = run_window(queries, seed, seconds, hs, trace,
                                      lake, fault)
        if trace:
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
        compiles = shapes.compile_count() - c0
        pool1 = _pool_stats()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell.chips])
        del session, hs
        if ops:
            ctx.session = ctx.hs = None
        for q in queries:
            q.df = None

        window = Window(
            lake=lake, sent=sent, t0=t0, t1=t1, setup_s=wall0 - t_start,
            compiles=compiles, pool_before=pool0, pool_after=pool1,
            device_kind=devices[0].device_kind,
            skipping_indexes={ix["name"]: ix["table"]
                              for ix in cell.config["indexes"]
                              if ix["kind"] == "skipping"},
            cycles=cycles)
        if trace:
            window.trace = _trace_reduction(
                trace_dir, window_perf0,
                [sp for s in sent for sp in s.spans]
                + [a for c in cycles for a in c.asides])
        phases.done("window")
        if ops:
            _print_cycles(cycles, t1 - t0)
        else:
            lat = sorted(s.t1 - s.t0 for s in sent)
            print(f"window {len(sent)} queries in {t1 - t0:.3f} s, latency "
                  f"min {lat[0]:.4f} median {lat[len(lat) // 2]:.4f} max "
                  f"{lat[-1]:.4f} s", file=sys.stderr)
        print(f"disk bytes written {_du(work)}", file=sys.stderr)
        checks = check_ops(cell, lake, cycles) if ops else \
            check(cell, lake, sent)
        phases.done("check")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    done = cycles or sent
    failed = sum(1 for s in done if s.error is not None)
    correct = failed == 0 and bool(done) and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(done), "failed": failed,
              "metrics": read_metrics(
                  root, cell.per_layer if trace else cell.end_to_end,
                  window, on_tpu),
              "device": device}
    if trace:
        red = window.trace
        if on_tpu and red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        # The traced window's own end-to-end numbers, for the tracing
        # overhead; they are not among the result's metrics.
        result["traced_end_to_end"] = {
            k: v["value"] for k, v in read_metrics(
                root, cell.end_to_end, window, on_tpu).items()}
    if failed:
        result["errors"] = sorted({s.error for s in done if s.error})[:5]
    result["checks"] = checks
    return result


def _print_cycles(cycles: List[Cycle], wall_s: float) -> None:
    """An ops window's cycles on standard error, for the record."""
    steps: Dict[str, List[float]] = {}
    for c in cycles:
        for name, a, b in c.steps:
            steps.setdefault(name, []).append(b - a)
    aside = sum(b - a for c in cycles for _, a, b in c.asides)
    means = ", ".join(f"{n} {1000 * sum(v) / len(v):.1f}" for n, v in
                      steps.items())
    print(f"window {len(cycles)} cycles in {wall_s:.3f} s: steps "
          f"{sum(c.step_s for c in cycles):.3f} s, between steps "
          f"{aside:.3f} s; mean step ms: {means}; index bytes written "
          f"{sum(c.record.get('bytes', 0) for c in cycles)}",
          file=sys.stderr)
