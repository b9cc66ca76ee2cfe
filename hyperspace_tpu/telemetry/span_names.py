"""Frozen registry of trace span names.

Every ``trace.span(...)`` / ``trace.add_span(...)`` site in the package
must name its span with one of these constants — free-form strings are
rejected by the scripts/lint.py span-discipline gate, and every name
registered here must be referenced under tests/ (an unobserved span is
unverified observability, the same contract the event-taxonomy gate
enforces for telemetry/events.py).

Keep the vocabulary SMALL and stable: dashboards, the Chrome-trace
exporter, and the explain "Trace:" section all key on these strings.
Variable detail (node kinds, hit/miss, byte counts) rides in span
attributes, never in the name.
"""

from __future__ import annotations

# The per-query root span, opened by Session.execute (one per
# QueryContext; literal-sweep members nest under SERVING_SWEEP).
QUERY = "query"

# Plan normalization (push_filters + prune_columns) in Session.optimize.
PLAN_NORMALIZE = "plan.normalize"

# Cost-based join reordering (optimizer/join_order.reorder_joins).
JOIN_REORDER = "optimize.join_reorder"

# The hyperspace index-rewrite batch (rules/apply_hyperspace).
INDEX_REWRITE = "rewrite.index_rules"

# Result-cache key computation + probe (serving/result_cache).
CACHE_LOOKUP = "serving.cache_lookup"

# Program-bank lookup (serving/program_bank; attrs carry hit/miss) and
# the wrapper construction on a bank miss.
BANK_LOOKUP = "bank.lookup"
BANK_COMPILE = "bank.compile"

# One span per executed plan node (execution/executor._execute).
EXEC_STAGE = "exec.stage"

# One span per fused-region dispatch (execution/fusion.py): the whole
# filter/project/join-probe/aggregate region runs as ONE banked program;
# attrs carry ``fused_nodes`` (plan nodes collapsed) and output rows.
EXEC_FUSED = "exec.fused"

# Pooled multi-file read fan-out / prefetch stream (parallel/io.py),
# recorded on the consumer side of the r11 per-query io attribution.
IO_READ = "io.read"
IO_PREFETCH = "io.prefetch"

# SPMD mesh dispatch (execution/spmd) and the AOT compile of one mesh
# executable (parallel/sharding.MeshProgram).
SPMD_DISPATCH = "spmd.dispatch"
SPMD_COMPILE = "spmd.compile"

# The shared literal-sweep batch span (serving/frontend._run_batch);
# member queries' QUERY spans are its children.
SERVING_SWEEP = "serving.sweep"

# Streaming ingestion tier (streaming/). One INGEST_APPEND per staged
# batch (attrs carry rows + per-index prebuild counts), one
# INGEST_COMMIT per commit() publishing staged batches through the
# op-log protocol, one INGEST_COMPACT per compacted log.
INGEST_APPEND = "ingest.append"
INGEST_COMMIT = "ingest.commit"
INGEST_COMPACT = "ingest.compact"

# Group-commit publication wave (streaming/ingest.CommitCoordinator):
# one INGEST_WAVE per wave the leader publishes, wrapping its
# INGEST_COMMIT sub-waves (attrs carry batches, joined committers,
# sub-waves). One INGEST_SOURCE per productive continuous-source poll
# (streaming/sources.py; attrs carry appended batches / committed rows).
INGEST_WAVE = "ingest.wave"
INGEST_SOURCE = "ingest.source"

# Artifact store (artifacts/): one ARTIFACT_LOAD per lake probe (attrs
# carry hit/reason/nbytes), one ARTIFACT_EXPORT per serialize+publish,
# one ARTIFACT_WARMUP per boot preload pass (attrs carry loaded count
# and bytes).
ARTIFACT_LOAD = "artifact.load"
ARTIFACT_EXPORT = "artifact.export"
ARTIFACT_WARMUP = "artifact.warmup"

# Serving cluster (cluster/): one CLUSTER_FORWARD per routed submission
# shipped to its shard owner (attrs carry owner/hit/ok), one
# CLUSTER_BROADCAST per commit fan-out to the live peers, one
# CLUSTER_GATHER per host-TCP allgather round on the owned path.
CLUSTER_FORWARD = "cluster.forward"
CLUSTER_BROADCAST = "cluster.broadcast"
CLUSTER_GATHER = "cluster.gather"

# Inside the index rewrite (rules/): INDEX_LOOKUP is the index
# manager's read of the ACTIVE entries (op-log reads included);
# SOURCE_CHECK lists the relation's files, stats them and computes the
# signature compared with the index's recorded one; SKIPPING_PROBE loads
# (or hits) a data-skipping index's sketch table and evaluates the
# filter over it (attrs carry files and kept).
INDEX_LOOKUP = "rewrite.index_lookup"
SOURCE_CHECK = "rewrite.source_check"
SKIPPING_PROBE = "skipping.probe"

# The SPMD tier's host work (execution/spmd.py): SPMD_GATE is the
# per-query decision to go SPMD (parquet footer reads included); under
# SPMD_DISPATCH, SPMD_PREPARE materializes and shards the leaf,
# SPMD_LAUNCH looks up and enqueues the program, SPMD_MERGE builds the
# answer from its outputs.
SPMD_GATE = "spmd.gate"
SPMD_PREPARE = "spmd.prepare"
SPMD_LAUNCH = "spmd.launch"
SPMD_MERGE = "spmd.merge"

# The build of a broadcast join's direct-address position table
# (execution/spmd._prepare_broadcast), opened only when the side's keys
# are dense enough for the probe to skip the binary search (attrs carry
# keys and slots).
JOIN_PROBE_TABLE = "join.probe_table"

# One DEVICE_SYNC per blocking device->host round trip on the query
# path (attr ``node`` names the site); RESULT_FETCH is the answer's copy
# to the host and its Arrow build (DataFrame.to_arrow/collect/
# to_pandas), inside the query's root span.
DEVICE_SYNC = "device.sync"
RESULT_FETCH = "result.fetch"

# One XLA_COMPILE per backend compile or persistent-cache load inside a
# traced query (execution/shapes.py, recorded after the fact; attrs carry
# ``node`` = the jitted function's name, seconds, cached).
XLA_COMPILE = "xla.compile"

SPAN_NAMES = frozenset({
    QUERY, PLAN_NORMALIZE, JOIN_REORDER, INDEX_REWRITE, CACHE_LOOKUP,
    BANK_LOOKUP, BANK_COMPILE, EXEC_STAGE, EXEC_FUSED, IO_READ,
    IO_PREFETCH, SPMD_DISPATCH, SPMD_COMPILE, SERVING_SWEEP,
    INGEST_APPEND, INGEST_COMMIT, INGEST_COMPACT,
    INGEST_WAVE, INGEST_SOURCE,
    ARTIFACT_LOAD, ARTIFACT_EXPORT, ARTIFACT_WARMUP,
    CLUSTER_FORWARD, CLUSTER_BROADCAST, CLUSTER_GATHER,
    INDEX_LOOKUP, SOURCE_CHECK, SKIPPING_PROBE,
    SPMD_GATE, SPMD_PREPARE, SPMD_LAUNCH, SPMD_MERGE, JOIN_PROBE_TABLE,
    DEVICE_SYNC, RESULT_FETCH, XLA_COMPILE,
})
